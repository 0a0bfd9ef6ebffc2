"""Trusted timing (ISSUE 6): BlockingStepTimer, TimingAuditor
triangulation + trust verdicts, the driver-loop blocking mode across
drivers, and the obs_report Profiling section schema.

The tier-1 acceptance pins live here: a deliberately async-dispatch-
mistimed synthetic record MUST be flagged ``suspect:async_dispatch``,
and the obs_report ``--format json`` profiling section schema is
pinned so downstream consumers can rely on it.
"""

import importlib.util
import json
import os
import shutil

import pytest

from bigdl_tpu.observability.profiling import (INVALID_IMPOSSIBLE,
                                               INVALID_OFF_TPU,
                                               SUSPECT_ASYNC_DISPATCH,
                                               TRUSTED, BlockingStepTimer,
                                               TimingAuditor, percentile)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE_MULTI = os.path.join(os.path.dirname(__file__), "fixtures",
                             "synthetic_multi.xplane.pb")


def _load_by_path(name, relpath):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------------------- #
# TimingAuditor: the trust verdicts
# --------------------------------------------------------------------------- #

#: a plausible honest v5e measurement: blocked 0.119 s/step at 3.04e12
#: flops -> MFU ~0.13 (the judge-verified r02 number), chained slightly
#: faster (RTT amortised), trace busy slightly below blocked
HONEST = dict(platform="tpu", step_blocked_s=0.119,
              flops_per_step=3.04e12, peak_flops=197e12,
              dispatch_s_per_step=0.112, device_busy_s_per_step=0.105)


class TestTimingAuditor:
    def test_honest_measurement_is_trusted(self):
        audit = TimingAuditor().audit(**HONEST)
        assert audit["trust"] == TRUSTED
        assert audit["published"]["basis"] == "step_blocked_s"
        assert audit["published"]["mfu"] == pytest.approx(0.1297, abs=1e-3)
        assert audit["estimates"]["mfu_blocked"] == \
            audit["published"]["mfu"]
        assert audit["checks"]          # the evidence trail is never empty

    def test_device_busier_than_published_step_is_suspect(self):
        # the async-dispatch failure shape: the host clocked 80 ms
        # "steps" (a plausible 19% MFU) while the trace shows the
        # device busy 105 ms per step -- impossible serially
        audit = TimingAuditor().audit(
            **{**HONEST, "step_blocked_s": 0.080,
               "dispatch_s_per_step": None})
        assert audit["trust"] == SUSPECT_ASYNC_DISPATCH
        assert any("device-busy" in c for c in audit["checks"])

    def test_chained_slower_than_blocked_is_suspect(self):
        # a serial dependency chain cannot be SLOWER than a truly
        # fenced step: blocked 0.05 vs chained 0.112 means the fence
        # leaked (round-3's below-compute-floor blocked times)
        audit = TimingAuditor().audit(
            **{**HONEST, "step_blocked_s": 0.05,
               "device_busy_s_per_step": None})
        assert audit["trust"] == SUSPECT_ASYNC_DISPATCH
        assert any("dispatch-loop" in c for c in audit["checks"])

    def test_off_tpu_is_invalid(self):
        audit = TimingAuditor().audit(**{**HONEST, "platform": "cpu"})
        assert audit["trust"] == INVALID_OFF_TPU

    def test_impossible_mfu_is_invalid(self):
        # r02's raw artifact: a "step time" implying 274% MFU
        audit = TimingAuditor().audit(
            **{**HONEST, "step_blocked_s": 0.119 / 21})
        assert audit["trust"] == INVALID_IMPOSSIBLE
        assert any("outside (0, 1]" in c for c in audit["checks"])

    def test_missing_blocked_timing_is_invalid(self):
        audit = TimingAuditor().audit(platform="tpu", step_blocked_s=None)
        assert audit["trust"] == INVALID_IMPOSSIBLE

    def test_tolerance_is_respected(self):
        # 5% over is inside the default 10% band; 15% over is not
        ok = TimingAuditor().audit(
            **{**HONEST, "device_busy_s_per_step": 0.119 * 1.05})
        bad = TimingAuditor().audit(
            **{**HONEST, "device_busy_s_per_step": 0.119 * 1.15})
        assert ok["trust"] == TRUSTED
        assert bad["trust"] == SUSPECT_ASYNC_DISPATCH

    def test_straggler_in_chained_window_does_not_flag_honest_run(self):
        # one straggler step inflates the chained MEAN past p50 * 1.1
        # while the published p50 (a median) is immune to it; the
        # cross-check compares mean-to-mean (step_blocked_mean_s), so
        # the honest run stays trusted instead of being rejected
        audit = TimingAuditor().audit(
            platform="tpu", step_blocked_s=0.10,
            step_blocked_mean_s=0.12,
            flops_per_step=3.04e12, peak_flops=197e12,
            dispatch_s_per_step=0.125)
        assert audit["trust"] == TRUSTED
        # without the mean, the same numbers would (conservatively)
        # flag: the fallback reference is the published p50
        audit2 = TimingAuditor().audit(
            platform="tpu", step_blocked_s=0.10,
            flops_per_step=3.04e12, peak_flops=197e12,
            dispatch_s_per_step=0.125)
        assert audit2["trust"] == SUSPECT_ASYNC_DISPATCH

    def test_no_cross_estimates_still_trusted_with_note(self):
        audit = TimingAuditor().audit(
            platform="tpu", step_blocked_s=0.119,
            flops_per_step=3.04e12, peak_flops=197e12)
        assert audit["trust"] == TRUSTED
        assert any("no independent estimate" in c for c in audit["checks"])


class TestAuditRecord:
    """``audit_record`` over a record dict, incl. the tier-1 acceptance
    pin: a deliberately async-dispatch-mistimed synthetic record flags
    suspect."""

    def _record(self, **extra):
        base = {
            "platform": "tpu", "batch": 128, "steps": 20,
            "sec_per_step_blocked": 0.119, "sec_per_step_chained": 0.112,
            "flops_per_step": 3.04e12, "peak_flops_assumed": 197e12,
            "trace_witness": {
                "wall_sec_per_step": 0.112,
                "device_plane": {"plane": "/device:TPU:0",
                                 "span_sec": 2.3,
                                 "busy_event_sec": 2.1}},
        }
        base.update(extra)
        return {"metric": "resnet50_train_imgs_per_sec_per_chip",
                "value": 128 / base["sec_per_step_blocked"],
                "unit": "images/sec", "extra": base}

    def test_honest_record_passes(self):
        audit = TimingAuditor().audit_record(self._record())
        assert audit["trust"] == TRUSTED

    def test_async_dispatch_mistimed_record_flags_suspect(self):
        # the acceptance pin: published step time (0.02 s) < the
        # trace's own device-busy time per step (2.1 s / 20 = 0.105 s)
        rec = self._record(sec_per_step_blocked=0.02,
                           sec_per_step_chained=0.02)
        audit = TimingAuditor().audit_record(rec)
        assert audit["trust"] == SUSPECT_ASYNC_DISPATCH

    def test_r02_style_impossible_record_is_invalid(self):
        rec = self._record(sec_per_step_blocked=0.0056,
                           sec_per_step_chained=0.0056,
                           trace_witness=None)
        audit = TimingAuditor().audit_record(rec)
        assert audit["trust"] == INVALID_IMPOSSIBLE

    def test_cpu_fallback_record_is_off_tpu(self):
        rec = self._record(platform="cpu")
        audit = TimingAuditor().audit_record(rec)
        assert audit["trust"] == INVALID_OFF_TPU

    def test_falls_back_to_sec_per_step(self):
        rec = self._record()
        rec["extra"]["sec_per_step"] = rec["extra"].pop(
            "sec_per_step_blocked")
        assert TimingAuditor().audit_record(rec)["trust"] == TRUSTED

    def test_cli_audits_a_record_file(self, tmp_path, capsys):
        from bigdl_tpu.observability import profiling
        path = tmp_path / "record.json"
        path.write_text(json.dumps(self._record(
            sec_per_step_blocked=0.02, sec_per_step_chained=0.02)))
        rc = profiling.main([str(path)])
        assert rc == 1                     # non-trusted -> nonzero exit
        out = json.loads(capsys.readouterr().out)
        assert out["trust"] == SUSPECT_ASYNC_DISPATCH


# --------------------------------------------------------------------------- #
# BlockingStepTimer
# --------------------------------------------------------------------------- #

class TestBlockingStepTimer:
    def test_fenced_samples(self):
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(a):
            return a * 2.0

        a = jnp.ones((8, 8))
        f(a)                               # compile outside the windows
        timer = BlockingStepTimer()
        for _ in range(5):
            a = timer.time_step(f, a)
        assert len(timer.samples) == 5
        assert all(s > 0 for s in timer.samples)
        assert timer.p50() <= timer.p90()
        summary = timer.summary()
        assert summary["steps"] == 5
        assert summary["step_blocked_s_p50"] == timer.p50()
        assert summary["total_s"] == pytest.approx(sum(timer.samples))

    def test_empty_summary_is_none(self):
        assert BlockingStepTimer().summary() is None
        assert BlockingStepTimer().p50() is None

    def test_percentile_matches_obs_report(self):
        obs = _load_by_path("_t_obs_report", "tools/obs_report.py")
        vals = sorted([0.4, 0.1, 0.9, 0.3, 0.7])
        for q in (0, 10, 50, 90, 99, 100):
            assert percentile(vals, q) == obs.percentile(vals, q)


# --------------------------------------------------------------------------- #
# Driver-loop blocking mode (the shared seam, exercised per driver)
# --------------------------------------------------------------------------- #

def _train(tmp, make_opt, steps=5, batch=16):
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim
    from bigdl_tpu.dataset import SampleToMiniBatch, array_dataset
    from bigdl_tpu.observability import StepTelemetry

    rng = np.random.default_rng(0)
    x = rng.standard_normal((batch * 8, 8)).astype("float32")
    y = rng.integers(0, 3, batch * 8).astype("int32")
    ds = array_dataset(x, y) >> SampleToMiniBatch(batch)
    model = (nn.Sequential().add(nn.Linear(8, 16)).add(nn.ReLU())
             .add(nn.Linear(16, 3)))
    tel = StepTelemetry(tmp, trace=False)
    opt = make_opt(model, ds)
    opt.set_end_when(optim.Trigger.max_iteration(steps))
    opt.set_telemetry(tel)
    opt.set_blocking_timing(True)
    opt.optimize()
    tel.close()
    with open(os.path.join(tmp, "telemetry.jsonl")) as f:
        return [json.loads(ln) for ln in f]


class TestDriverLoopBlocking:
    def _check_stream(self, events, n_steps):
        header = events[0]
        assert header["kind"] == "header"
        # the header itself carries the timing discipline
        assert header["timing"] == {"mode": "blocking",
                                    "trust_basis": "step_blocked_s"}
        steps = [e for e in events if e["kind"] == "step"]
        assert len(steps) == n_steps
        assert all(e.get("step_blocked_s", 0) > 0 for e in steps)
        audits = [e for e in events if e["kind"] == "timing_audit"]
        assert len(audits) == 1
        # hermetic CPU tests: the verdict must say so, loudly
        assert audits[0]["trust"] == INVALID_OFF_TPU
        assert audits[0]["published"]["basis"] == "step_blocked_s"

    def test_local_driver(self, tmp_path):
        import bigdl_tpu.nn as nn
        from bigdl_tpu import optim

        events = _train(str(tmp_path), lambda m, ds: optim.LocalOptimizer(
            m, ds, nn.CrossEntropyCriterion(),
            optim.SGD(learning_rate=0.05)))
        self._check_stream(events, 5)

    def test_distri_driver(self, tmp_path):
        import bigdl_tpu.nn as nn
        from bigdl_tpu import optim
        from bigdl_tpu.utils.engine import Engine

        Engine.init()
        events = _train(str(tmp_path), lambda m, ds: optim.DistriOptimizer(
            m, ds, nn.CrossEntropyCriterion(),
            optim.SGD(learning_rate=0.05)))
        self._check_stream(events, 5)

    def test_off_by_default(self, tmp_path):
        import numpy as np

        import bigdl_tpu.nn as nn
        from bigdl_tpu import optim
        from bigdl_tpu.dataset import SampleToMiniBatch, array_dataset
        from bigdl_tpu.observability import StepTelemetry

        rng = np.random.default_rng(0)
        x = rng.standard_normal((64, 8)).astype("float32")
        y = rng.integers(0, 3, 64).astype("int32")
        ds = array_dataset(x, y) >> SampleToMiniBatch(16)
        model = (nn.Sequential().add(nn.Linear(8, 16))
                 .add(nn.Linear(16, 3)))
        tel = StepTelemetry(str(tmp_path), trace=False)
        opt = optim.LocalOptimizer(model, ds, nn.CrossEntropyCriterion(),
                                   optim.SGD(learning_rate=0.05))
        opt.set_end_when(optim.Trigger.max_iteration(3))
        opt.set_telemetry(tel)
        opt.optimize()
        tel.close()
        with open(os.path.join(str(tmp_path), "telemetry.jsonl")) as f:
            events = [json.loads(ln) for ln in f]
        assert "timing" not in events[0]
        assert all("step_blocked_s" not in e for e in events
                   if e["kind"] == "step")
        assert not [e for e in events if e["kind"] == "timing_audit"]


# --------------------------------------------------------------------------- #
# obs_report Profiling section: schema pin (--format json) + text
# --------------------------------------------------------------------------- #

class TestObsReportProfiling:
    @pytest.fixture
    def run_dir(self, tmp_path):
        import bigdl_tpu.nn as nn
        from bigdl_tpu import optim

        _train(str(tmp_path), lambda m, ds: optim.LocalOptimizer(
            m, ds, nn.CrossEntropyCriterion(),
            optim.SGD(learning_rate=0.05)))
        os.makedirs(tmp_path / "xplane")
        shutil.copy(FIXTURE_MULTI, tmp_path / "xplane" / "h.xplane.pb")
        return str(tmp_path)

    def test_json_schema_pin(self, run_dir, capsys):
        """The machine-readable profiling-section contract: these keys
        may grow but must not move or vanish."""
        obs = _load_by_path("_t_obs_report2", "tools/obs_report.py")
        assert obs.main([run_dir, "--format", "json"]) == 0
        rep = json.loads(capsys.readouterr().out)   # strict JSON
        pf = rep["profiling"]
        assert pf["timing_mode"] == "blocking"
        assert pf["trust_basis"] == "step_blocked_s"
        assert pf["trust"] == INVALID_OFF_TPU
        assert pf["steps_timed"] == 5
        assert pf["step_blocked_s_p50"] > 0
        assert pf["step_blocked_s_p90"] >= pf["step_blocked_s_p50"]
        assert pf["published"]["basis"] == "step_blocked_s"
        assert isinstance(pf["checks"], list) and pf["checks"]
        da = pf["device_attribution"]
        assert set(da) >= {"plane", "span_sec", "busy_sec", "compute_sec",
                           "collective_sec", "idle_sec", "compute_fraction",
                           "collective_fraction", "idle_fraction", "ops"}
        assert da["collective_fraction"] == pytest.approx(0.35)
        assert all(o["flavor"] in ("compute", "collective")
                   for o in da["ops"])
        # the step block publishes MFU from the BLOCKED basis only
        assert rep["steps"]["mfu_basis"] == "step_blocked_s"
        assert rep["steps"]["step_blocked_s_p50"] == \
            pf["step_blocked_s_p50"]

    def test_text_renders_profiling(self, run_dir):
        obs = _load_by_path("_t_obs_report3", "tools/obs_report.py")
        text = obs.format_report(obs.build_report(run_dir))
        assert "profiling: timing mode blocking" in text
        assert "trust invalid:off_tpu" in text
        assert "device attribution" in text
        assert "collective 35.0%" in text
        assert "basis: blocking-fenced step time" in text

    def test_unfenced_run_says_so(self, tmp_path):
        """A run WITHOUT blocking timing must not pass its wall-clock
        MFU off as fenced: mfu_basis says wall_s and the text labels it
        not publishable."""
        import numpy as np

        import bigdl_tpu.nn as nn
        from bigdl_tpu import optim
        from bigdl_tpu.dataset import SampleToMiniBatch, array_dataset
        from bigdl_tpu.observability import StepTelemetry

        rng = np.random.default_rng(0)
        x = rng.standard_normal((64, 8)).astype("float32")
        y = rng.integers(0, 3, 64).astype("int32")
        ds = array_dataset(x, y) >> SampleToMiniBatch(16)
        model = (nn.Sequential().add(nn.Linear(8, 16))
                 .add(nn.Linear(16, 3)))
        tel = StepTelemetry(str(tmp_path), trace=False)
        opt = optim.LocalOptimizer(model, ds, nn.CrossEntropyCriterion(),
                                   optim.SGD(learning_rate=0.05))
        opt.set_end_when(optim.Trigger.max_iteration(3))
        opt.set_telemetry(tel)
        opt.optimize()
        tel.close()
        obs = _load_by_path("_t_obs_report4", "tools/obs_report.py")
        rep = obs.build_report(str(tmp_path))
        assert rep["steps"]["mfu_basis"] == "wall_s"
        assert "not publishable" in obs.format_report(rep)

