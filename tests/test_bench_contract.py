"""bench.py driver contract (VERDICT r3 ask #2): bounded wall-clock and
a parseable JSON artifact no matter when the driver kills it.  Round 3's
failure mode was rc=124 with an empty tail."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench.py")


def _last_json(stdout):
    lines = [l for l in stdout.strip().splitlines() if l.startswith("{")]
    assert lines, f"no JSON lines in: {stdout[:500]!r}"
    return json.loads(lines[-1])


class TestBenchLMContract:
    """ISSUE-7 pin: the BENCH_LM record carries a ``trust`` verdict,
    per-leg ``compile_s``, and the remat-policy leg labels; every
    published number derives from blocked-p50 and a non-trusted (CPU)
    record is forced to ``vs_baseline: 0`` (PR 6's contract)."""

    @pytest.mark.slow
    def test_lm_record_contract(self, capsys):
        # slow tier (ISSUE-9 re-tier): the 5-leg A/B sweep is ~25s, the
        # single heaviest tier-1 test; the record-schema surface it pins
        # only changes when bench.py's LM leg does
        import importlib.util

        spec = importlib.util.spec_from_file_location("_t_bench", BENCH)
        bench = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bench)
        # compile probe off: the tier-1 pin covers the record contract;
        # the probe itself is pinned by the slow acceptance test below
        rec = bench.run_lm_bench(size="tiny", steps=2, batch=2, seq=16,
                                 vocab=64, compile_size="off")
        out = capsys.readouterr().out
        assert json.loads(out.strip().splitlines()[-1]) == rec  # strict
        assert rec["metric"] == "transformer_lm_tokens_per_sec_per_chip"
        assert "trust" in rec
        legs = rec["extra"]["legs"]
        # the A/B matrix: unrolled vs scan, remat-policy legs, flash off
        assert {"unrolled", "scan", "scan:nothing_saveable",
                "scan:dots_saveable", "scan:no_flash"} <= set(legs)
        for leg in legs.values():
            assert leg["compile_s"] > 0
            assert leg["sec_per_step_blocked"] > 0
            assert leg["trust"]
            # blocked-p50 is the one published basis
            assert leg["timing_audit"]["published"]["basis"] \
                == "step_blocked_s"
        assert rec["extra"]["scan_loss_matches_unrolled"] is True
        assert rec["extra"]["scan_compile_speedup"] > 0
        # this suite runs on CPU: the verdict must be honestly off-TPU
        # and the record cannot claim the baseline
        if rec["extra"]["platform"] != "tpu":
            assert rec["trust"] == "invalid:off_tpu"
            assert rec["vs_baseline"] == 0.0


@pytest.mark.slow
class TestScanCompileAcceptance:
    def test_medium_scan_compile_speedup(self):
        """ISSUE-7 acceptance: transformer_lm('medium') jit-compile wall
        time with scan_layers=True is >= 3x lower than unrolled on the
        same host (measured 21.9x on the dev box; 3x is the floor under
        CI noise).  Abstract-aval lowering only -- no params
        materialize -- and the compilation cache is disabled around the
        probe, so the ratio cannot be faked by a warm cache."""
        import importlib.util

        spec = importlib.util.spec_from_file_location("_t_bench2", BENCH)
        bench = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bench)
        probe = bench._lm_compile_probe("medium", 32000, 64, 1)
        assert probe["compile_speedup"] >= 3.0, probe
        assert probe["unrolled_compile_s"] > 0
        assert probe["scan_compile_s"] > 0
        assert probe["cache_disabled"] is True


@pytest.mark.slow
class TestBenchContract:
    def test_budget_bounds_hung_device(self):
        """A device that never answers (every child hangs) exits within the budget with
        a parseable record, never a bare timeout."""
        env = dict(os.environ)
        env.update(BENCH_FAKE_HANG="1", BENCH_TOTAL_BUDGET="60",
                   BENCH_NO_CPU_FALLBACK="1")
        t0 = time.time()
        proc = subprocess.run([sys.executable, BENCH], env=env,
                              capture_output=True, text=True, timeout=200)
        assert time.time() - t0 < 150
        rec = _last_json(proc.stdout)
        assert rec["vs_baseline"] == 0.0
        assert rec["extra"]["failures"], rec
        # the probe's outcome is recorded honestly (ISSUE 6): a hung
        # probe reads "timeout", never a silently killed run
        assert rec["probe_result"] == "timeout"
        assert rec["extra"]["probe_sec"] is not None
        assert rec["trust"].startswith("invalid")

    def test_hang_mid_sweep_salvages_completed_leg(self):
        """A child that completes one sweep leg then wedges (big-batch
        compile that hangs) must not lose the valid record: the
        parent salvages the last flushed leg from the killed child."""
        env = dict(os.environ)
        env.update(BENCH_FAKE_HANG_MID_SWEEP="1", BENCH_TOTAL_BUDGET="120",
                   BENCH_TIMEOUT="40", BENCH_RETRIES="1",
                   BENCH_NO_CPU_FALLBACK="1")
        proc = subprocess.run([sys.executable, BENCH], env=env,
                              capture_output=True, text=True, timeout=200)
        rec = _last_json(proc.stdout)
        assert rec["value"] == 1234.0, rec
        assert rec["vs_baseline"] == 0.5
        assert "salvaged" in rec["extra"], rec
        assert rec["probe_result"] == "tpu"

    def test_crash_mid_sweep_salvages_completed_leg(self):
        """A child that crashes (rc != 0) after a completed leg is
        salvaged too, with the crash annotated -- not reported as a
        clean full-sweep success."""
        env = dict(os.environ)
        env.update(BENCH_FAKE_CRASH_MID_SWEEP="1", BENCH_TOTAL_BUDGET="120",
                   BENCH_TIMEOUT="40", BENCH_RETRIES="1",
                   BENCH_NO_CPU_FALLBACK="1")
        proc = subprocess.run([sys.executable, BENCH], env=env,
                              capture_output=True, text=True, timeout=200)
        rec = _last_json(proc.stdout)
        assert rec["value"] == 1234.0, rec
        assert "rc=3" in rec["extra"]["salvaged"], rec

    def test_deviceless_probe_and_fallback_record(self):
        """ISSUE-6 acceptance: on a deviceless box the probe answers in
        seconds (not the old 240 s), the CPU fallback runs, and the
        emitted record is COMPLETE -- trust verdict, probe outcome,
        blocked timing and compilation-cache state all present."""
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run([sys.executable, BENCH], env=env,
                              capture_output=True, text=True, timeout=900)
        rec = _last_json(proc.stdout)
        assert rec["probe_result"] == "cpu"
        assert rec["extra"]["probe_sec"] <= 60       # "seconds, not 240 s"
        assert rec["trust"] == "invalid:off_tpu"     # honest CPU verdict
        assert rec["extra"]["probe"] == "cpu→cpu"
        assert rec["extra"]["sec_per_step_blocked"] > 0
        assert rec["extra"]["timing_audit"]["published"]["basis"] == \
            "step_blocked_s"
        assert rec["extra"]["compilation_cache"] is not None
        assert rec["vs_baseline"] == 0.0             # CPU can't claim MFU

    def test_kill_mid_probe_leaves_json(self):
        """SIGTERM at any moment (the driver's timeout) leaves the last
        printed line as a valid record and reaps the hung children."""
        env = dict(os.environ)
        env["BENCH_FAKE_HANG"] = "1"
        # unique tag inherited by the whole bench process tree
        # (_spawn_child copies os.environ), so the leak scan below cannot
        # match bench children of an UNRELATED concurrent run (e.g.
        # tools/perf_ab.py on the live chip)
        value = f"{os.getpid()}_{time.time_ns()}"
        env["BENCH_TEST_TOKEN"] = value
        token = f"BENCH_TEST_TOKEN={value}"
        proc = subprocess.Popen([sys.executable, BENCH], env=env,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, text=True)
        try:
            time.sleep(5)              # mid device-probe
            proc.send_signal(signal.SIGTERM)
            out, _ = proc.communicate(timeout=30)
            rec = _last_json(out)
            assert "incomplete" in rec["extra"]["error"]
            assert rec["vs_baseline"] == 0.0
            # the SIGTERM handler must have reaped the hung child group
            time.sleep(1)
            left = []
            for pid in os.listdir("/proc"):
                if not pid.isdigit() or int(pid) == proc.pid:
                    continue
                try:
                    with open(f"/proc/{pid}/environ", "rb") as f:
                        if token.encode() in f.read():
                            left.append(pid)
                except OSError:
                    continue
            assert not left, f"leaked bench children: {left}"
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.communicate()


class TestRecoveryEventContract:
    """ISSUE-8 pin: the ``kind: "recovery"`` telemetry event schema the
    RunSupervisor emits (docs/robustness.md) -- obs_report's Recovery
    section and any external consumer parse exactly these keys."""

    def test_recovery_event_schema(self):
        from bigdl_tpu.optim.recovery import (RECOVERY_CAUSES,
                                              RECOVERY_EVENT_KEYS,
                                              RunSupervisor)

        events = []

        class Sink:                    # minimal telemetry duck type
            def record(self, kind, **fields):
                events.append({"kind": kind, **fields})

        class Dummy:
            checkpoint_path = None
            sharded_checkpoint_path = None
            driver_state = {"neval": 7}

            def __init__(self, fail):
                self.fail = fail

            def optimize(self):
                if self.fail:
                    raise RuntimeError("preempted")

        sup = RunSupervisor(max_restarts=1, backoff_base_s=0.5,
                            telemetry=Sink(), sleep=lambda s: None)
        sup.run(lambda attempt: Dummy(fail=(attempt == 0)))
        assert len(events) == 1
        ev = events[0]
        assert ev["kind"] == "recovery"
        # the closed key set, all present even when unknown (None)
        assert set(RECOVERY_EVENT_KEYS) <= set(ev)
        assert ev["cause"] in RECOVERY_CAUSES
        assert ev["restart"] == 1
        assert ev["at_step"] == 7
        assert ev["backoff_s"] == 0.5
        assert ev["snapshot"] is None and ev["steps_replayed"] is None
        json.dumps(ev)                 # JSONL-ready

    def test_recovery_is_durable_kind(self):
        from bigdl_tpu.observability.telemetry import DURABLE_KINDS

        assert "recovery" in DURABLE_KINDS
