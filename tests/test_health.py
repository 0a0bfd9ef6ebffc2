"""Model-health observability (ISSUE 3).

The fused train step samples an on-device numerics tree every
``stats_every`` steps (loss, global + per-layer grad norms, update
ratios, non-finite counts) under ``lax.cond``; a ``HealthMonitor``
turns the samples into ``health`` telemetry events, TB scalars and
warn/dump/halt anomaly responses.  Acceptance: injecting a NaN into
one layer's gradient produces a health event NAMING that layer at the
first sampled step, and the ``dump`` policy writes an incident bundle
from which the failing step re-executes; ``stats_every=None`` keeps
the loss stream bit-identical to the unmonitored run.
"""

import json
import logging
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu import optim
from bigdl_tpu.dataset import SampleToMiniBatch, array_dataset
from bigdl_tpu.observability import (HealthMonitor, LossSpikeWatchdog,
                                     MemoryWatchdog, NonFiniteWatchdog,
                                     RecompileWatchdog, StepTelemetry,
                                     layer_labels, load_incident)
from bigdl_tpu.observability.health import (HEALTH_STATE_KEY,
                                            HEALTH_STEP_KEY,
                                            HealthProbeMethod)
from bigdl_tpu.optim.train_step import make_train_step
from bigdl_tpu.utils.errors import TrainingHaltedError
from bigdl_tpu.utils.random_generator import RNG
from bigdl_tpu.visualization import TrainSummary

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: keys every health event must carry (docs/observability.md)
REQUIRED_HEALTH_KEYS = {"step", "epoch", "loss", "grad_norm",
                        "update_ratio_max", "nonfinite_grads",
                        "nonfinite_params", "worst_layer", "layers"}

POISON_LAYER = "['2']['weight']"


def _data(n=96, features=8, classes=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, features)).astype("float32")
    y = rng.integers(0, classes, n).astype("int32")
    return x, y


def _mlp():
    return (nn.Sequential().add(nn.Linear(8, 16)).add(nn.ReLU())
            .add(nn.Linear(16, 4)))


def _poison(grads):
    """NaN into exactly one layer's gradient (the acceptance fixture)."""
    g = jax.tree.map(lambda a: a, grads)
    g["2"]["weight"] = g["2"]["weight"] * jnp.nan
    return g


def _fit(run_dir, steps=6, monitor=None, grad_transform=None,
         log_dir=None, distributed=False, sync_every=1, seed=0):
    RNG.set_seed(seed)
    x, y = _data(seed=seed)
    train = array_dataset(x, y) >> SampleToMiniBatch(32)
    model = _mlp()
    tel = StepTelemetry(run_dir, run_name="health", trace=False)
    klass = optim.DistriOptimizer if distributed else optim.LocalOptimizer
    opt = klass(model, train, nn.CrossEntropyCriterion(),
                optim.SGD(learning_rate=0.1))
    opt.set_end_when(optim.Trigger.max_iteration(steps))
    opt.set_telemetry(tel)
    if sync_every != 1:
        opt.set_sync_every(sync_every)
    if log_dir is not None:
        opt.set_train_summary(TrainSummary(log_dir, "health"))
    if monitor is not None:
        opt.set_health_monitor(monitor)
    if grad_transform is not None:
        opt.set_grad_transform(grad_transform)
    opt.optimize()
    tel.close()
    events = [json.loads(ln)
              for ln in open(os.path.join(run_dir, "telemetry.jsonl"))]
    return opt, events


def _kind(events, kind):
    return [e for e in events if e["kind"] == kind]


@pytest.fixture(scope="module")
def healthy_run(tmp_path_factory):
    base = tmp_path_factory.mktemp("health")
    run_dir, log_dir = str(base / "run"), str(base / "tb")
    opt, events = _fit(run_dir, steps=6, log_dir=log_dir,
                       monitor=HealthMonitor(stats_every=2, policy="warn"))
    return {"dir": run_dir, "opt": opt, "events": events}


class TestHealthEventSchema:
    def test_sample_cadence_and_schema(self, healthy_run):
        health = _kind(healthy_run["events"], "health")
        assert [e["step"] for e in health] == [1, 3, 5]
        for e in health:
            assert REQUIRED_HEALTH_KEYS <= set(e), e
            assert e["grad_norm"] > 0
            assert np.isfinite(e["loss"])
            assert e["nonfinite_grads"] == 0
            assert e["nonfinite_params"] == 0
            assert len(e["layers"]) == 4          # 2 Linear x (W, b)
            for rec in e["layers"].values():
                assert rec["grad_norm"] >= 0
                assert rec["update_ratio"] >= 0

    def test_health_loss_matches_step_loss(self, healthy_run):
        """A sample forces a point sync: the health event's loss is the
        step's fresh loss, not a placeholder."""
        steps = {e["step"]: e for e in _kind(healthy_run["events"], "step")}
        for e in _kind(healthy_run["events"], "health"):
            assert e["loss"] == pytest.approx(steps[e["step"]]["loss"])

    def test_labels_name_the_model_tree(self, healthy_run):
        params = healthy_run["opt"].model.parameters()[0]
        assert set(_kind(healthy_run["events"], "health")[0]["layers"]) \
            == set(layer_labels(params))

    def test_global_norm_consistent_with_layers(self, healthy_run):
        e = _kind(healthy_run["events"], "health")[0]
        per_layer = [rec["grad_norm"] for rec in e["layers"].values()]
        assert e["grad_norm"] == pytest.approx(
            np.sqrt(np.sum(np.square(per_layer))), rel=1e-5)

    def test_tb_scalars_derive_from_health_events(self, healthy_run):
        health = _kind(healthy_run["events"], "health")
        summary = healthy_run["opt"].train_summary
        tb = summary.read_scalar("Health/GradNorm")
        assert [s for s, _, _ in tb] == [e["step"] for e in health]
        for (_, v, _), e in zip(tb, health):
            assert v == pytest.approx(e["grad_norm"], rel=1e-6)
        layer = "Health/GradNorm" + POISON_LAYER
        assert len(summary.read_scalar(layer)) == len(health)

    def test_no_anomalies_on_healthy_run(self, healthy_run):
        assert _kind(healthy_run["events"], "anomaly") == []


class TestBitIdentity:
    def test_monitored_loss_stream_identical(self, tmp_path):
        """The stats branch reads, never perturbs, the step math: the
        monitored run's loss stream equals the unmonitored one's."""
        _, plain = _fit(str(tmp_path / "plain"), steps=5)
        _, monitored = _fit(str(tmp_path / "mon"), steps=5,
                            monitor=HealthMonitor(stats_every=2))
        assert [e["loss"] for e in _kind(plain, "step")] \
            == [e["loss"] for e in _kind(monitored, "step")]

    def test_disabled_monitor_builds_plain_step(self):
        """stats_every=None builds the exact 6-arg pre-PR step."""
        mon = HealthMonitor(stats_every=None)
        assert not mon.enabled and not mon.due(1)
        step = make_train_step(_mlp(), nn.CrossEntropyCriterion(),
                               optim.SGD())
        import inspect
        assert len(inspect.signature(step).parameters) == 6

    def test_deferred_sync_sample_forces_point_sync(self, tmp_path):
        _, events = _fit(str(tmp_path / "defer"), steps=6, sync_every=3,
                         monitor=HealthMonitor(stats_every=2))
        steps = {e["step"]: e for e in _kind(events, "step")}
        for e in _kind(events, "health"):
            assert steps[e["step"]]["sync_skew"] == 0


class TestDistriHealth:
    def test_flat_plane_stats_match_local(self, tmp_path):
        """ZeRO-1 segment-sum stats describe the GLOBAL mean gradient:
        identical per-layer norms to the single-device run on the same
        data/model/seed."""
        _, local = _fit(str(tmp_path / "local"), steps=4,
                        monitor=HealthMonitor(stats_every=3))
        _, distri = _fit(str(tmp_path / "distri"), steps=4,
                         monitor=HealthMonitor(stats_every=3),
                         distributed=True)
        hl, hd = _kind(local, "health")[0], _kind(distri, "health")[0]
        assert hd["grad_norm"] == pytest.approx(hl["grad_norm"], abs=1e-4)
        assert set(hd["layers"]) == set(hl["layers"])
        for name in hl["layers"]:
            assert hd["layers"][name]["grad_norm"] == pytest.approx(
                hl["layers"][name]["grad_norm"], abs=1e-4)
        assert hd["nonfinite_grads"] == 0 and hd["nonfinite_params"] == 0

    def test_frozen_layer_reports_zero_grad_in_both_drivers(self,
                                                            tmp_path):
        """Regression: the distri step captured the stats gradient
        before the freeze-mask zeroing; a frozen layer must report grad
        norm 0 in BOTH drivers (its raw gradient never updates params
        and must not trip the watchdogs)."""
        frozen = "['0']['weight']"
        for name, distributed in (("local", False), ("distri", True)):
            RNG.set_seed(0)
            x, y = _data()
            train = array_dataset(x, y) >> SampleToMiniBatch(32)
            model = _mlp()
            model.freeze([str(model.modules[0].name)])
            tel = StepTelemetry(str(tmp_path / name), run_name=name,
                                trace=False)
            klass = (optim.DistriOptimizer if distributed
                     else optim.LocalOptimizer)
            opt = klass(model, train, nn.CrossEntropyCriterion(),
                        optim.SGD(learning_rate=0.1))
            opt.set_end_when(optim.Trigger.max_iteration(2))
            opt.set_telemetry(tel)
            opt.set_health_monitor(stats_every=2)
            opt.optimize()
            tel.close()
            events = [json.loads(ln) for ln in open(tel.jsonl_path)]
            h = _kind(events, "health")[0]
            assert h["layers"][frozen]["grad_norm"] == 0.0, name
            assert h["layers"][frozen]["update_ratio"] == 0.0, name
            assert h["layers"][POISON_LAYER]["grad_norm"] > 0, name


class TestStrategyHealth:
    # tier-2: the TransformerLM tp compile alone costs ~13s; tier-1 keeps
    # the cheap HealthProbeMethod unit below (the same seam, no mesh)
    @pytest.mark.slow
    def test_tp_probe_emits_health_events(self, tmp_path):
        from bigdl_tpu.nn.attention import TransformerLM
        RNG.set_seed(0)
        model = TransformerLM(64, 32, 4, 2, max_len=32)
        model.build(jax.ShapeDtypeStruct((8, 16), jnp.int32))
        crit = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion())
        rng = np.random.default_rng(0)
        x = rng.integers(0, 64, (8, 16)).astype(np.int32)
        y = rng.integers(0, 64, (8, 16)).astype(np.int32)
        ds = array_dataset(x, y) >> SampleToMiniBatch(8)
        mesh = jax.sharding.Mesh(
            np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
        tel = StepTelemetry(str(tmp_path / "tp"), run_name="tp",
                            trace=False)
        opt = optim.Optimizer(model, ds, crit,
                              optim.SGD(learning_rate=0.05),
                              strategy="tp", mesh=mesh)
        opt.set_end_when(optim.Trigger.max_iteration(3))
        opt.set_telemetry(tel)
        opt.set_health_monitor(stats_every=2, policy="warn")
        opt.optimize()
        tel.close()
        events = [json.loads(ln) for ln in open(tel.jsonl_path)]
        health = _kind(events, "health")
        assert [e["step"] for e in health] == [1, 3]
        h = health[0]
        assert h["grad_norm"] > 0 and np.isfinite(h["loss"])
        assert h["nonfinite_grads"] == 0
        # labels name the strategy-native (= model) tree
        assert set(h["layers"]) == set(
            layer_labels(opt.model.parameters()[0]))

    def test_probe_method_threads_state(self):
        """Unit: the proxy samples on its own device counter, preserves
        the base method's state and stays transparent to LR queries."""
        base = optim.SGD(learning_rate=0.1, momentum=0.9, dampening=0.0)
        probe = HealthProbeMethod(base, stats_every=2)
        params = {"w": jnp.ones((3, 3)), "b": jnp.zeros((3,))}
        state = probe.init_state(params)
        assert HEALTH_STATE_KEY in state and HEALTH_STEP_KEY in state
        assert "velocity" in state    # base SGD momentum state intact
        assert float(probe.get_learning_rate(state)) == pytest.approx(0.1)
        grads = {"w": jnp.full((3, 3), 0.5), "b": jnp.ones((3,))}
        sampled = []
        for _ in range(4):
            params, state = probe.update(grads, state, params)
            sampled.append(bool(state[HEALTH_STATE_KEY]["sampled"]))
        assert sampled == [True, False, True, False]
        stats = state[HEALTH_STATE_KEY]
        assert stats["layer_grad_norms"].shape == (2,)


class TestNaNInjectionAcceptance:
    @pytest.fixture(scope="class")
    def blown_run(self, tmp_path_factory):
        run_dir = str(tmp_path_factory.mktemp("nan") / "run")
        opt, events = _fit(run_dir, steps=4, grad_transform=_poison,
                           monitor=HealthMonitor(stats_every=2,
                                                 policy="dump"))
        return {"dir": run_dir, "opt": opt, "events": events}

    def test_first_sampled_step_names_the_layer(self, blown_run):
        health = _kind(blown_run["events"], "health")
        assert health[0]["step"] == 1
        assert health[0]["worst_layer"] == POISON_LAYER
        assert health[0]["nonfinite_grads"] > 0
        assert health[0]["layers"][POISON_LAYER]["nonfinite_grads"] > 0
        clean = "['0']['weight']"
        assert health[0]["layers"][clean]["nonfinite_grads"] == 0

    def test_anomaly_event_with_incident_dir(self, blown_run):
        anomalies = _kind(blown_run["events"], "anomaly")
        assert anomalies and anomalies[0]["watchdog"] == "nonfinite"
        assert anomalies[0]["policy"] == "dump"
        d = anomalies[0]["incident_dir"]
        assert d and os.path.isdir(d)
        assert d.startswith(os.path.join(blown_run["dir"], "incidents"))
        for name in ("manifest.json", "batch.pkl", "snapshot.pkl",
                     "events.jsonl"):
            assert os.path.isfile(os.path.join(d, name)), name

    def test_manifest_is_strict_json(self, blown_run):
        """The canonical incident IS a NaN blow-up: manifest.json must
        still parse under strict consumers (jq, JS) -- non-finite
        values map to null, raw values live in events.jsonl."""
        d = _kind(blown_run["events"], "anomaly")[0]["incident_dir"]
        with open(os.path.join(d, "manifest.json")) as f:
            text = f.read()
        man = json.loads(text, parse_constant=lambda s: (_ for _ in
                                                         ()).throw(
            AssertionError(f"non-strict JSON literal {s}")))
        assert man["finding"]["worst_layer"] == POISON_LAYER
        assert man["layers"][POISON_LAYER]["grad_norm"] is None

    def test_bundle_reexecutes_the_failing_step(self, blown_run):
        """Acceptance: the failing step re-executes from the bundle
        alone and reproduces the non-finite gradient, by layer."""
        d = _kind(blown_run["events"], "anomaly")[0]["incident_dir"]
        inc = load_incident(d)
        assert inc["manifest"]["finding"]["worst_layer"] == POISON_LAYER
        assert any(ev.get("kind") == "health" for ev in inc["events"])
        snap = inc["snapshot"]
        params = jax.tree.map(jnp.asarray, snap["state"]["params"])
        mstate = jax.tree.map(jnp.asarray, snap["state"]["mstate"])
        opt_state = jax.tree.map(jnp.asarray, snap["state"]["opt_state"])
        RNG.set_state(snap["rng_state"])
        step = jax.jit(make_train_step(
            blown_run["opt"].model, nn.CrossEntropyCriterion(),
            optim.SGD(learning_rate=0.1), grad_transform=_poison,
            health_stats=True))
        *_, stats = step(params, mstate, opt_state,
                         jnp.asarray(inc["batch"].get_input()),
                         jnp.asarray(inc["batch"].get_target()),
                         RNG.next_key(), True)
        labels = layer_labels(params)
        nf = np.asarray(stats["layer_nonfinite_grads"])
        assert [labels[i] for i in np.nonzero(nf)[0]] == [POISON_LAYER]

    def test_incident_cap(self, blown_run):
        mon = blown_run["opt"].health_monitor
        assert len(mon.incidents) <= mon.max_incidents


class TestHaltPolicy:
    def test_halt_raises_and_skips_failure_retry(self, tmp_path,
                                                 monkeypatch):
        """halt must surface immediately -- the failure-retry loop would
        otherwise restore a checkpoint and replay the same blow-up."""
        monkeypatch.setenv("BIGDL_FAILURE_RETRY_TIMES", "3")
        RNG.set_seed(0)
        x, y = _data()
        train = array_dataset(x, y) >> SampleToMiniBatch(32)
        opt = optim.LocalOptimizer(_mlp(), train,
                                   nn.CrossEntropyCriterion(),
                                   optim.SGD(learning_rate=0.1))
        opt.set_end_when(optim.Trigger.max_iteration(6))
        opt.set_checkpoint(str(tmp_path / "ckpt"),
                           optim.Trigger.several_iteration(1))
        opt.set_grad_transform(_poison)
        opt.set_health_monitor(stats_every=2, policy="halt",
                               incident_dir=str(tmp_path / "inc"))
        with pytest.raises(TrainingHaltedError, match="step 1"):
            opt.optimize()
        # halt escalates over dump: the evidence bundle was still written
        assert opt.health_monitor.incidents


class TestLossSpikeWatchdog:
    def test_fires_on_spike_after_warmup(self):
        wd = LossSpikeWatchdog(sigma=4.0, beta=0.8, warmup=5)
        for step in range(1, 11):
            assert wd.observe(step, 1.0 + 0.01 * (step % 3)) is None
        finding = wd.observe(11, 50.0)
        assert finding and finding["watchdog"] == "loss_spike"
        assert finding["step"] == 11 and "reason" in finding

    def test_flat_stream_fires_on_moderate_spike_after_warmup(self):
        """Regression: a stale variance bias correction (beta**n for
        n+1 samples) seeded phantom variance on a flat stream, masking
        real spikes for dozens of samples past warmup."""
        wd = LossSpikeWatchdog(sigma=6.0, beta=0.9, warmup=5)
        for step in range(1, 13):
            assert wd.observe(step, 2.0) is None
        assert wd.observe(13, 4.9)            # 2.4x jump must fire

    def test_warmup_tolerates_fast_early_descent(self):
        wd = LossSpikeWatchdog(sigma=4.0, warmup=8)
        for step, loss in enumerate([9.0, 5.0, 3.0, 2.0, 1.5, 1.2, 1.1],
                                    start=1):
            assert wd.observe(step, loss) is None

    def test_persistent_new_level_renormalizes(self):
        wd = LossSpikeWatchdog(sigma=4.0, beta=0.5, warmup=3)
        for step in range(1, 8):
            wd.observe(step, 1.0)
        assert wd.observe(8, 10.0)            # the jump fires once
        fired = [bool(wd.observe(step, 10.0)) for step in range(9, 15)]
        assert fired[-1] is False             # EMAs re-adapted

    def test_ignores_nonfinite_losses(self):
        wd = LossSpikeWatchdog(warmup=1)
        assert wd.observe(1, float("nan")) is None
        assert wd.observe(2, None) is None


class TestNonFiniteWatchdogUnit:
    def test_tracks_first_step(self):
        wd = NonFiniteWatchdog()
        ok = {"nonfinite_grads": 0, "nonfinite_params": 0, "loss": 1.0,
              "grad_norm": 2.0, "worst_layer": "a"}
        assert wd.observe(1, ok) is None
        bad = dict(ok, nonfinite_grads=3, worst_layer="b")
        f = wd.observe(5, bad)
        assert f["worst_layer"] == "b" and wd.first_step == 5
        wd.observe(7, bad)
        assert wd.first_step == 5 and len(wd.events) == 2

    def test_nonfinite_loss_alone_fires(self):
        wd = NonFiniteWatchdog()
        f = wd.observe(2, {"nonfinite_grads": 0, "nonfinite_params": 0,
                           "loss": float("inf"), "grad_norm": 1.0,
                           "worst_layer": None})
        assert f and not f["loss_finite"]


class TestWatchdogEdgeCases:
    """Satellite: the PR-1 watchdogs beyond their happy paths."""

    def test_recompile_cache_fallback_without_monitoring(self, caplog):
        """Without the process-wide jax.monitoring listener the
        watch()-ed function's jit-cache size is the compile signal and
        still catches the static-arg leak."""
        wd = RecompileWatchdog(warmup_steps=1)
        wd._use_monitoring = False            # listener not registered
        f = wd.watch(jax.jit(lambda x, n: x * n, static_argnums=1))
        x = jnp.ones(3)
        with caplog.at_level(logging.WARNING,
                             logger="bigdl_tpu.observability"):
            for step, n in enumerate([2, 2, 3], start=1):
                wd.step_begin(step)
                jax.block_until_ready(f(x, n))
                wd.step_end(step)
        assert [e["step"] for e in wd.events] == [3]

    def test_recompile_no_signal_source_degrades_silently(self):
        wd = RecompileWatchdog(warmup_steps=0)
        wd._use_monitoring = False
        wd._watched = []
        wd.step_begin(1)
        assert wd.step_end(1) == 0 and wd.events == []

    def test_memory_window_longer_than_run_never_fires(self):
        wd = MemoryWatchdog(window=25)
        for step in range(1, 11):             # run << window
            assert wd.observe(step, {"tpu:0": 1000 + 10 * step}) == []
        assert wd.events == []

    def test_memory_zero_byte_backend(self):
        """CPU-style backends report 0 bytes forever: never a streak."""
        wd = MemoryWatchdog(window=2)
        for step in range(1, 8):
            assert wd.observe(step, {"cpu:0": 0}) == []
        assert wd.events == []

    def test_memory_empty_and_missing_devices(self):
        wd = MemoryWatchdog(window=2)
        assert wd.observe(1, {}) == []
        assert wd.observe(2, {"tpu:0": 5}) == []
        assert wd.observe(3, None) == []


class TestCrashSafeTelemetry:
    def test_truncated_final_line_tolerated(self, healthy_run, tmp_path):
        """Satellite: a run killed mid-write leaves a partial final
        line; the reader must skip it, not raise."""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "obs_report", os.path.join(REPO, "tools", "obs_report.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        src = os.path.join(healthy_run["dir"], "telemetry.jsonl")
        crashed = str(tmp_path / "telemetry.jsonl")
        with open(src, "rb") as f:
            data = f.read()
        with open(crashed, "wb") as f:        # cut mid-record + junk byte
            f.write(data[: int(len(data) * 0.8)] + b'{"kind": "st\xc3')
        header, steps, other = mod.load_events(crashed)
        assert header is not None and steps
        rep = mod.build_report(str(tmp_path))
        assert rep["n_steps"] == len(steps)

    def test_health_events_on_disk_before_close(self, tmp_path):
        """Durable kinds are flushed+fsynced at record time: the event
        is readable even though the telemetry was never closed."""
        tel = StepTelemetry(str(tmp_path), run_name="durable",
                            trace=False)
        tel.record("health", step=1, grad_norm=1.0)
        with open(tel.jsonl_path) as f:       # no close(): crash sim
            kinds = [json.loads(ln)["kind"] for ln in f]
        assert kinds == ["header", "health"]
        tel.close()


class TestObsReportCLI:
    """Satellite: tier-1 end-to-end smoke of both report formats on a
    generated run, so report regressions fail fast."""

    def _run_cli(self, run_dir, *extra):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "obs_report.py"),
             run_dir, *extra],
            capture_output=True, text=True, timeout=120)

    def test_text_report_has_health_section(self, healthy_run):
        proc = self._run_cli(healthy_run["dir"])
        assert proc.returncode == 0, proc.stderr
        assert "health: 3 samples" in proc.stdout
        assert "grad-norm" in proc.stdout
        assert "worst layers" in proc.stdout

    def test_format_json_is_strict_and_machine_readable(self, healthy_run):
        proc = self._run_cli(healthy_run["dir"], "--format", "json")
        assert proc.returncode == 0, proc.stderr
        # strict JSON: no NaN/Infinity literals may appear
        rep = json.loads(proc.stdout, parse_constant=lambda s: (_ for _ in
                                                                ()).throw(
            AssertionError(f"non-strict JSON literal {s}")))
        h = rep["health"]
        assert h["samples"] == 3
        assert h["grad_norm_first"] > 0 and h["grad_norm_last"] > 0
        assert len(h["grad_norm_trajectory"]) == 3
        assert len(h["worst_layers"]) <= 5
        assert "first_nonfinite_step" not in h
        assert rep["steps"]["wall_s_p50"] > 0

    def test_json_maps_nonfinite_to_null(self, tmp_path):
        run_dir = str(tmp_path / "nan")
        _fit(run_dir, steps=4, grad_transform=_poison,
             monitor=HealthMonitor(stats_every=2, policy="warn"))
        proc = self._run_cli(run_dir, "--format", "json")
        assert proc.returncode == 0, proc.stderr
        rep = json.loads(proc.stdout)
        assert rep["health"]["first_nonfinite_step"] == 1
        assert rep["health"]["first_nonfinite_layer"] == POISON_LAYER
        assert "NaN" not in proc.stdout
        proc = self._run_cli(run_dir)        # text renderer, same run
        assert "FIRST NON-FINITE numerics at step 1" in proc.stdout
        # warn policy records the anomaly but writes no bundle
        anomaly_lines = [ln for ln in proc.stdout.splitlines()
                         if ln.startswith("ANOMALY")]
        assert "ANOMALY [nonfinite] at step 1 (policy warn)" \
            in anomaly_lines
        assert not any("->" in ln for ln in anomaly_lines)


class TestGradientCheckerReuse:
    """Satellite: GradientChecker shares the per-layer norm helper with
    the health telemetry -- one naming/measuring scheme for layers."""

    def test_layer_grad_norms_match_adhoc(self):
        from bigdl_tpu.utils.gradient_checker import GradientChecker
        RNG.set_seed(0)
        model = _mlp()
        x = jnp.asarray(np.random.default_rng(0)
                        .standard_normal((4, 8)).astype("float32"))
        norms = GradientChecker().layer_grad_norms(model, x)
        params, state = model._params, model._state

        def scalar_loss(p):
            out, _ = model.apply(p, state, x, training=False, rng=None)
            return jnp.sum(out)

        adhoc = jax.grad(scalar_loss)(params)
        from jax.tree_util import keystr, tree_flatten_with_path
        leaves, _ = tree_flatten_with_path(adhoc)
        assert set(norms) == {keystr(p) for p, _ in leaves}
        for path, leaf in leaves:
            assert norms[keystr(path)] == pytest.approx(
                float(np.linalg.norm(np.asarray(leaf))), rel=1e-5)

    def test_check_weight_still_passes(self):
        from bigdl_tpu.utils.gradient_checker import GradientChecker
        RNG.set_seed(0)
        lin = nn.Linear(6, 3)
        x = jnp.asarray(np.random.default_rng(1)
                        .standard_normal((2, 6)).astype("float32"))
        assert GradientChecker(1e-3, 1e-2).check_weight(lin, x, sample=10)


class TestMonitorConfig:
    def test_rejects_bad_config(self):
        from bigdl_tpu.utils.errors import ConfigurationError
        with pytest.raises(ConfigurationError, match="stats_every"):
            HealthMonitor(stats_every=0)
        with pytest.raises(ConfigurationError, match="policy"):
            HealthMonitor(policy="explode")
        opt = optim.LocalOptimizer(_mlp(),
                                   array_dataset(*_data(n=32))
                                   >> SampleToMiniBatch(32),
                                   nn.CrossEntropyCriterion(), optim.SGD())
        with pytest.raises(ConfigurationError, match="not both"):
            opt.set_health_monitor(HealthMonitor(), policy="halt")

    def test_due_cadence(self):
        mon = HealthMonitor(stats_every=10)
        assert [n for n in range(1, 25) if mon.due(n)] == [1, 11, 21]

    def test_grad_transform_rejected_off_local(self):
        from bigdl_tpu.utils.errors import UnsupportedFeatureError
        x, y = _data(n=32)
        train = array_dataset(x, y) >> SampleToMiniBatch(32)
        opt = optim.DistriOptimizer(_mlp(), train,
                                    nn.CrossEntropyCriterion(),
                                    optim.SGD())
        opt.set_grad_transform(_poison)
        opt.set_end_when(optim.Trigger.max_iteration(1))
        with pytest.raises(UnsupportedFeatureError, match="gradient "):
            opt.optimize()
