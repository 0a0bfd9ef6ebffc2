"""End-to-end smoke for the observability subsystem (ISSUE 1).

A 3-step LocalOptimizer fit with telemetry enabled must produce: a
JSONL event log with the documented schema (split data-wait vs device
timers, memory stats where available), a valid chrome-trace JSON of
host spans, and an obs_report summary merging both with an xplane
trace.  The recompile watchdog must fire exactly once when a static
argument changes mid-run, and TensorBoard scalars must agree with the
JSONL events they are derived from.
"""

import json
import logging
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu import optim
from bigdl_tpu.dataset import SampleToMiniBatch, array_dataset
from bigdl_tpu.observability import (MemoryWatchdog, RecompileWatchdog,
                                     SpanTracer, StepTelemetry, span)
from bigdl_tpu.utils.random_generator import RNG
from bigdl_tpu.visualization import TrainSummary

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE_XPLANE = os.path.join(REPO, "tests", "fixtures",
                              "synthetic.xplane.pb")

#: schema keys every step event must carry (docs/observability.md)
REQUIRED_STEP_KEYS = {"step", "wall_s", "data_wait_s", "records_per_s"}


def _small_fit(run_dir, log_dir):
    RNG.set_seed(0)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((96, 8)).astype("float32")
    y = rng.integers(0, 4, 96).astype("int32")
    train = array_dataset(x, y) >> SampleToMiniBatch(32)
    model = (nn.Sequential().add(nn.Linear(8, 16)).add(nn.ReLU())
             .add(nn.Linear(16, 4)))
    tel = StepTelemetry(run_dir, run_name="obs-smoke")
    opt = optim.LocalOptimizer(model, train, nn.CrossEntropyCriterion(),
                               optim.SGD(learning_rate=0.1))
    opt.set_end_when(optim.Trigger.max_iteration(3))
    opt.set_train_summary(TrainSummary(log_dir, "obs"))
    opt.set_telemetry(tel)
    opt.optimize()
    tel.close()
    return opt, tel


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    base = tmp_path_factory.mktemp("obs")
    run_dir, log_dir = str(base / "run"), str(base / "tb")
    opt, tel = _small_fit(run_dir, log_dir)
    events = [json.loads(ln)
              for ln in open(os.path.join(run_dir, "telemetry.jsonl"))]
    return {"dir": run_dir, "log_dir": log_dir, "opt": opt,
            "events": events}


class TestStepTelemetrySchema:
    def test_header_first_with_cost(self, run):
        header = run["events"][0]
        assert header["kind"] == "header"
        assert header["run"] == "obs-smoke"
        assert header["platform"] == "cpu"
        assert header["device_count"] >= 1
        # off a TPU there is no peak, and so no MFU
        assert header["peak_flops"] is None
        # cost_analysis of the compiled step rode in on the header
        assert header["cost"]["flops_per_step"] > 0
        assert header["cost"]["records_per_step"] == 32

    @pytest.mark.parametrize("kind,peak", [
        ("TPU v4", 275e12), ("TPU v5 lite", 197e12), ("TPU v5", 459e12),
        ("TPU v6 lite", 918e12)])
    def test_peak_flops_by_device_kind(self, kind, peak):
        from types import SimpleNamespace

        from bigdl_tpu.observability import peak_flops

        dev = SimpleNamespace(platform="tpu", device_kind=kind)
        assert peak_flops(dev) == peak

    def test_peak_flops_unknown_tpu_is_an_error(self):
        """A device that is not in the table is an error, not a default;
        off a TPU there is no peak at all."""
        from types import SimpleNamespace

        from bigdl_tpu.observability import peak_flops

        with pytest.raises(ValueError, match="TPU v9"):
            peak_flops(SimpleNamespace(platform="tpu",
                                       device_kind="TPU v9"))
        assert peak_flops(SimpleNamespace(platform="cpu",
                                          device_kind="cpu")) is None
        assert peak_flops() is None        # the test platform is the CPU

    def test_header_notes_compilation_cache(self, tmp_path):
        """The hit/miss note: a configured XLA compilation cache shows
        up on the header with its entry count (warm vs cold)."""
        import jax

        d = str(tmp_path / "cache")
        os.makedirs(d)
        open(os.path.join(d, "entry0"), "w").close()
        was = jax.config.jax_compilation_cache_dir
        jax.config.update("jax_compilation_cache_dir", d)
        try:
            tel = StepTelemetry(str(tmp_path / "run"), trace=False)
            header = tel.write_header()
            tel.close()
        finally:
            jax.config.update("jax_compilation_cache_dir", was)
        assert header["compilation_cache"] == {
            "dir": d, "entries": 1, "warm": True}

    def test_three_step_events_with_schema(self, run):
        steps = [e for e in run["events"] if e["kind"] == "step"]
        assert [e["step"] for e in steps] == [1, 2, 3]
        for e in steps:
            assert REQUIRED_STEP_KEYS <= set(e), e
            assert e["wall_s"] > 0
            assert 0 <= e["data_wait_s"] <= e["wall_s"]
            assert e["device_s"] == pytest.approx(
                e["wall_s"] - e["data_wait_s"])
            assert e["records"] == 32
            assert e["records_per_s"] > 0
            assert isinstance(e["loss"], float)
            assert e["epoch"] == 1

    def test_every_event_timestamped(self, run):
        assert all("ts" in e and "kind" in e for e in run["events"])

    def test_split_timers_in_metrics(self, run):
        d = run["opt"].metrics.to_dict()
        assert d["data_wait_s"]["count"] == 3
        assert d["device_s"]["count"] == 3
        assert d["device_s"]["sum"] > 0

    def test_chrome_trace_is_valid_json_with_host_spans(self, run):
        events = json.load(open(os.path.join(run["dir"], "trace.json")))
        assert isinstance(events, list)     # streamed array format
        names = {e["name"] for e in events if e.get("ph") == "X"}
        assert {"dispatch", "stage_next_batch", "loss_sync"} <= names
        assert sum(1 for e in events
                   if e.get("ph") == "X" and e["name"] == "dispatch") == 3
        for e in events:
            if e.get("ph") == "X":
                assert e["dur"] >= 0 and e["ts"] >= 0

    def test_tensorboard_scalars_derive_from_events(self, run):
        """Satellite: TB scalars and JSONL come from the same event
        dict, so loss/throughput can never disagree."""
        steps = [e for e in run["events"] if e["kind"] == "step"]
        summary = run["opt"].train_summary
        tb_loss = summary.read_scalar("Loss")
        assert [s for s, _, _ in tb_loss] == [e["step"] for e in steps]
        for (_, v, _), e in zip(tb_loss, steps):
            assert v == pytest.approx(e["loss"], rel=1e-6)
        tb_tp = summary.read_scalar("Throughput")
        for (_, v, _), e in zip(tb_tp, steps):
            assert v == pytest.approx(e["records_per_s"], rel=1e-6)
        assert len(summary.read_scalar("DataWaitSeconds")) == 3


class TestObsReportCLI:
    @pytest.mark.slow      # ISSUE-13 re-tier (~8s); the tier-1 CLI
    def test_report_merges_jsonl_and_xplane(self, run):
        # smoke of both report formats lives in test_health.py
        xdir = os.path.join(run["dir"], "xplane")
        os.makedirs(xdir, exist_ok=True)
        shutil.copy(FIXTURE_XPLANE, os.path.join(xdir, "host.xplane.pb"))
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "obs_report.py"),
             run["dir"]],
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        out = proc.stdout
        assert "run report" in out
        assert "steps: 3" in out
        assert "data-wait fraction" in out
        assert "host spans" in out and "dispatch" in out
        assert "top HLO ops" in out and "%fusion.1" in out
        assert "busy" in out

    @pytest.mark.slow
    def test_report_json_mode(self, run):
        # slow tier (~20s subprocess leg); the tier-1 CLI smoke of both
        # report formats lives in test_health.py::TestObsReportCLI
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "obs_report.py"),
             run["dir"], "--json"],
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        rep = json.loads(proc.stdout)
        assert rep["n_steps"] == 3
        assert rep["steps"]["wall_s_p50"] > 0
        assert 0 <= rep["steps"]["data_wait_fraction"] <= 1
        assert "mfu_p50" not in rep["steps"]    # no peak, no MFU, on the CPU
        assert rep["header"]["cost"]["flops_per_step"] > 0


class TestRecompileWatchdog:
    def test_fires_exactly_once_on_static_arg_change(self, caplog):
        """Acceptance: deliberately change a static arg mid-run -> ONE
        warning, carrying the offending step number."""
        wd = RecompileWatchdog(warmup_steps=1)
        f = jax.jit(lambda x, n: x * n, static_argnums=1)
        x = jnp.ones(4)
        with caplog.at_level(logging.WARNING,
                             logger="bigdl_tpu.observability"):
            for step, n in enumerate([2, 2, 3, 3], start=1):
                wd.step_begin(step)
                jax.block_until_ready(f(x, n))
                wd.step_end(step)
        assert len(wd.events) == 1
        assert wd.events[0]["step"] == 3        # the static arg flipped here
        warnings = [r for r in caplog.records
                    if "recompile detected" in r.message]
        assert len(warnings) == 1
        assert "step 3" in warnings[0].message

    def test_warmup_compile_not_flagged(self, caplog):
        wd = RecompileWatchdog(warmup_steps=1)
        f = jax.jit(lambda x: x + 1)
        x = jnp.ones(3)
        with caplog.at_level(logging.WARNING,
                             logger="bigdl_tpu.observability"):
            for step in (1, 2):
                wd.step_begin(step)
                jax.block_until_ready(f(x))
                wd.step_end(step)
        assert wd.events == []

    def test_fit_records_only_warmup_compile(self, run):
        steps = [e for e in run["events"] if e["kind"] == "step"]
        # step 1 compiled (informational "compiles"), but the watchdog
        # flagged nothing ("recompiles" absent everywhere)
        assert not any("recompiles" in e for e in steps)


class TestMemoryWatchdog:
    def test_flags_monotonic_growth_and_rearms(self):
        wd = MemoryWatchdog(window=3)
        flagged = []
        used = 1000
        for step in range(1, 9):
            used += 10                      # strictly monotonic
            flagged += wd.observe(step, {"tpu:0": used})
        # first firing after 3 consecutive increases (observation 4),
        # then re-armed: second firing 3 increases later
        assert len(wd.events) == 2
        assert wd.events[0]["step"] == 4
        assert wd.events[1]["step"] == 7

    def test_plateau_resets_streak(self):
        wd = MemoryWatchdog(window=3)
        seq = [100, 110, 120, 120, 130, 140, 140]   # never 3 in a row
        for step, used in enumerate(seq, start=1):
            wd.observe(step, {"tpu:0": used})
        assert wd.events == []

    def test_none_stats_are_ignored(self):
        wd = MemoryWatchdog(window=2)
        assert wd.observe(1, None) == []


class TestSpans:
    def test_ambient_span_records_into_active_tracer(self, tmp_path):
        path = str(tmp_path / "t.json")
        with SpanTracer(path) as tracer:
            with span("stage", foo=1):
                pass
        events = json.load(open(path))      # close() terminated the array
        evs = [e for e in events if e.get("ph") == "X"]
        assert evs[0]["name"] == "stage"
        assert evs[0]["args"] == {"foo": 1}
        origin = [e for e in events if e["name"] == "wall_time_origin"]
        assert origin and origin[0]["args"]["wall_time_origin"] > 0

    def test_span_without_tracer_is_noop(self):
        with span("nothing"):
            pass                            # must not raise

    def test_unterminated_stream_is_repairable(self, tmp_path):
        """A crash before close() leaves a comma-clean unterminated
        array; the report loader must still read it."""
        path = str(tmp_path / "t.json")
        tracer = SpanTracer(path)
        with tracer.span("stage"):
            pass
        tracer.flush()                      # no close(): simulated crash
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "obs_report", os.path.join(REPO, "tools", "obs_report.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        totals = mod.span_totals(path)
        assert totals and totals[0]["name"] == "stage"
        tracer.close()


class TestPredictorTelemetry:
    def test_inference_events_share_step_schema(self, run, tmp_path):
        model = run["opt"].model
        tel = StepTelemetry(str(tmp_path / "infer"), run_name="infer",
                            trace=False)
        pred = optim.Predictor(model, batch_size=16, telemetry=tel)
        outs = pred.predict(list(np.random.default_rng(0)
                                 .standard_normal((40, 8))
                                 .astype("float32")))
        tel.close()
        assert len(outs) == 40
        events = [json.loads(ln)
                  for ln in open(tel.jsonl_path)]
        inf = [e for e in events if e["kind"] == "inference"]
        assert [e["step"] for e in inf] == [1, 2, 3]   # 16+16+8
        for e in inf:
            assert REQUIRED_STEP_KEYS <= set(e)
            assert e["records"] in (16, 8)
