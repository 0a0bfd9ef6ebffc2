"""Live fleet telemetry (ISSUE 9): the Counter/Gauge/Histogram registry
under concurrent writers, the /metrics + /healthz exporter over a real
socket, SLO burn-rate alerting with injected clocks (never sleeps), the
telemetry->metrics bridge, and the live wiring through all three tiers
(ServingEngine ticks, the shared driver loop, RunSupervisor restarts)."""

import json
import os
import re
import threading
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu import optim
from bigdl_tpu.dataset import SampleToMiniBatch, array_dataset
from bigdl_tpu.observability import StepTelemetry
from bigdl_tpu.observability.metrics import (Counter, Gauge, Histogram,
                                             MetricsExporter,
                                             MetricsRegistry, SloObjective,
                                             SloTracker)
from bigdl_tpu.observability.profiling import percentile
from bigdl_tpu.observability.telemetry import DURABLE_KINDS
from bigdl_tpu.serving import ServingEngine
from bigdl_tpu.utils.errors import TrainingHaltedError
from bigdl_tpu.utils.random_generator import RNG

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Prometheus text-format sample line (metric{labels} value)
SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [0-9eE.+-]+(inf)?$")


def _get(url, parse=False):
    body = urllib.request.urlopen(url, timeout=10).read().decode()
    return json.loads(body) if parse else body


def _load_jsonl(path):
    out = []
    with open(path) as f:
        for ln in f:
            out.append(json.loads(ln))
    return out


# --------------------------------------------------------------------------- #
# Metric primitives.
# --------------------------------------------------------------------------- #


class TestPrimitives:
    def test_counter_inc_and_labels(self):
        c = Counter("x_total", "help", labelnames=("k",))
        c.inc(k="a")
        c.inc(2.5, k="a")
        c.inc(k="b")
        assert c.value(k="a") == 3.5 and c.value(k="b") == 1.0

    def test_counter_refuses_decrease(self):
        with pytest.raises(ValueError, match="cannot decrease"):
            Counter("x_total").inc(-1)

    def test_gauge_set_inc_dec(self):
        g = Gauge("q")
        g.set(5)
        g.inc(2)
        g.dec(3)
        assert g.value() == 4.0

    def test_label_mismatch_raises(self):
        g = Gauge("q", labelnames=("a", "b"))
        with pytest.raises(ValueError, match="expects labels"):
            g.set(1, a="x")

    def test_invalid_metric_name_raises(self):
        with pytest.raises(ValueError, match="invalid metric name"):
            Counter("1bad-name")

    def test_histogram_buckets_cumulative_and_sum(self):
        h = Histogram("lat_seconds", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        text = "\n".join(h.render())
        assert 'lat_seconds_bucket{le="0.1"} 1' in text
        assert 'lat_seconds_bucket{le="1"} 3' in text
        assert 'lat_seconds_bucket{le="10"} 4' in text
        assert 'lat_seconds_bucket{le="+Inf"} 5' in text
        assert "lat_seconds_count 5" in text

    def test_histogram_reservoir_is_bounded(self):
        h = Histogram("lat_seconds", reservoir_size=64)
        for i in range(1000):
            h.observe(i * 1e-3)
        assert h.count() == 1000
        with h._lock:
            assert len(h._child({})["reservoir"]) == 64

    def test_histogram_quantile_matches_shared_percentile(self):
        h = Histogram("lat_seconds", reservoir_size=128)
        vals = [0.001 * i for i in range(100)]
        for v in vals:
            h.observe(v)
        # the ONE nearest-rank definition (profiling.percentile): a
        # scraped p99 and an obs_report p99 cannot disagree
        assert h.quantile_value(99) == percentile(sorted(vals), 99)
        assert h.quantile_value(50) == percentile(sorted(vals), 50)


class TestRegistry:
    def test_get_or_create_idempotent(self):
        reg = MetricsRegistry()
        a = reg.counter("bigdl_a_total", "x")
        assert reg.counter("bigdl_a_total") is a

    def test_type_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("bigdl_a_total")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("bigdl_a_total")
        with pytest.raises(ValueError, match="already registered"):
            reg.counter("bigdl_a_total", labelnames=("k",))

    def test_render_is_valid_prometheus_text(self):
        reg = MetricsRegistry()
        reg.counter("bigdl_a_total", "a counter").inc()
        reg.gauge("bigdl_g", "a gauge", labelnames=("k",)) \
            .set(1.5, k='va"l\nue')
        reg.histogram("bigdl_h_seconds", "a histogram",
                      buckets=(1.0,)).observe(0.5)
        for ln in reg.render().splitlines():
            if ln.startswith("#") or not ln:
                continue
            # escaped quotes/newlines inside label values stay inside
            # the braces: strip the label block before the shape check
            stripped = re.sub(r"\{.*\}", "{}", ln)
            assert SAMPLE_RE.match(stripped), ln

    def test_health_worst_status_wins(self):
        reg = MetricsRegistry()
        assert reg.health()["status"] == "ok"
        reg.set_health("slo:x", "degraded")
        reg.set_health("watchdog:nan", "halted")
        assert reg.health()["status"] == "halted"
        reg.clear_health("watchdog:nan")
        assert reg.health()["status"] == "degraded"
        with pytest.raises(ValueError, match="unknown health status"):
            reg.set_health("x", "sick")


class TestConcurrency:
    """ISSUE-9 satellite: serving dispatcher thread + training thread +
    scraper thread against one registry -- no lost updates, no torn
    reads, reservoir bounds hold."""

    def test_three_writers_one_scraper(self):
        reg = MetricsRegistry()
        c = reg.counter("bigdl_reqs_total", "w", labelnames=("tier",))
        h = reg.histogram("bigdl_lat_seconds", "w", reservoir_size=100)
        n, writers = 2000, 3
        stop = threading.Event()
        renders = []

        def writer(tier):
            for i in range(n):
                c.inc(tier=tier)
                h.observe(i * 1e-6)

        def scraper():
            while not stop.is_set():
                renders.append(reg.render())

        ts = [threading.Thread(target=writer, args=(f"t{w}",))
              for w in range(writers)]
        sc = threading.Thread(target=scraper)
        sc.start()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        stop.set()
        sc.join()
        # exact counts: a lost increment means a torn read-modify-write
        for w in range(writers):
            assert c.value(tier=f"t{w}") == n
        assert h.count() == writers * n
        with h._lock:
            assert len(h._child({})["reservoir"]) == 100
        # every mid-flight scrape was a structurally valid exposition
        assert renders
        for text in (renders[0], renders[-1]):
            for ln in text.splitlines():
                if ln and not ln.startswith("#"):
                    assert SAMPLE_RE.match(re.sub(r"\{.*\}", "{}", ln)), ln

    def test_concurrent_child_creation(self):
        reg = MetricsRegistry()
        c = reg.counter("bigdl_x_total", "w", labelnames=("k",))
        ts = [threading.Thread(
            target=lambda i=i: [c.inc(k=f"k{j}") for j in range(50)])
            for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert all(c.value(k=f"k{j}") == 4 for j in range(50))


# --------------------------------------------------------------------------- #
# Exporter over a real socket.
# --------------------------------------------------------------------------- #


class TestExporter:
    def test_metrics_and_healthz_over_socket(self):
        reg = MetricsRegistry()
        reg.counter("bigdl_up_total", "liveness").inc(7)
        with MetricsExporter(reg, port=0) as exp:
            assert exp.port != 0            # port 0 auto-assigned
            text = _get(exp.url + "/metrics")
            assert "bigdl_up_total 7" in text
            hz = _get(exp.url + "/healthz", parse=True)
            assert hz["status"] == "ok" and hz["reasons"] == []
            assert hz["uptime_s"] >= 0
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(exp.url + "/nope")
            assert e.value.code == 404

    def test_healthz_reflects_registry_and_sources(self):
        reg = MetricsRegistry()
        with MetricsExporter(reg, port=0) as exp:
            reg.set_health("watchdog:nonfinite", "degraded")
            assert _get(exp.url + "/healthz",
                        parse=True)["status"] == "degraded"
            exp.add_health_source(
                lambda: {"status": "halted",
                         "reasons": [{"reason": "slo:x",
                                      "status": "halted"}]})
            # halted answers 503 so a naive prober notices too
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(exp.url + "/healthz")
            assert e.value.code == 503
            assert json.loads(e.value.read())["status"] == "halted"

    def test_broken_health_source_does_not_kill_healthz(self):
        reg = MetricsRegistry()
        with MetricsExporter(reg, port=0) as exp:
            exp.add_health_source(lambda: 1 / 0)
            assert _get(exp.url + "/healthz", parse=True)["status"] == "ok"


# --------------------------------------------------------------------------- #
# SLO objectives + burn-rate alerting (injected clocks, no sleeps).
# --------------------------------------------------------------------------- #


def _tracker(tmp_path, policy="warn", target=0.99, threshold=0.1,
             alerts=((10.0, 60.0, 2.0),), min_samples=5, registry=None):
    tel = StepTelemetry(str(tmp_path / "slo_run"), trace=False)
    now = [1000.0]
    tracker = SloTracker(registry=registry, clock=lambda: now[0])
    tracker.add(name="p99_latency", kind="inference",
                field="request_latency_s", threshold=threshold,
                target=target, alerts=alerts, policy=policy,
                min_samples=min_samples)
    tracker.bind(tel)
    return tracker, tel, now


class TestSloObjective:
    def test_validation(self):
        with pytest.raises(ValueError, match="target must be in"):
            SloObjective("x", kind="step", field="wall_s", threshold=1,
                         target=1.0)
        with pytest.raises(ValueError, match="op must be"):
            SloObjective("x", kind="step", field="wall_s", threshold=1,
                         op="<")
        with pytest.raises(ValueError, match="unknown policy"):
            SloObjective("x", kind="step", field="wall_s", threshold=1,
                         policy="page")
        with pytest.raises(ValueError, match="short window"):
            SloObjective("x", kind="step", field="wall_s", threshold=1,
                         alerts=((60.0, 10.0, 2.0),))

    def test_good_both_directions(self):
        le = SloObjective("x", kind="step", field="wall_s", threshold=0.5)
        assert le.good(0.5) and not le.good(0.51)
        ge = SloObjective("x", kind="step", field="score", threshold=0.9,
                          op=">=")
        assert ge.good(0.95) and not ge.good(0.1)


class TestSloTracker:
    def test_breach_needs_both_windows_and_min_samples(self, tmp_path):
        tracker, tel, now = _tracker(tmp_path, min_samples=8)
        # 5 bad samples: below min_samples, burn must not fire
        for _ in range(5):
            tracker.observe("p99_latency", [1.0])
        assert tracker.active_breaches() == []
        for _ in range(5):
            tracker.observe("p99_latency", [1.0])
        assert tracker.active_breaches() == ["p99_latency"]
        tel.close()

    def test_durable_breach_and_resolve_events(self, tmp_path):
        tracker, tel, now = _tracker(tmp_path)
        assert "slo" in DURABLE_KINDS
        for _ in range(10):
            tracker.observe("p99_latency", [1.0])     # all bad -> breach
        # recovery: good samples age the bad ones out of both windows
        for _ in range(300):
            now[0] += 1.0
            tracker.observe("p99_latency", [0.001])
        tel.close()
        events = [e for e in _load_jsonl(tel.jsonl_path)
                  if e.get("kind") == "slo"]
        assert [e["breach"] for e in events] == [True, False]
        breach = events[0]
        assert breach["objective"] == "p99_latency"
        assert breach["policy"] == "warn"
        assert breach["alerts"][0]["burn_short"] >= 2.0
        assert "request_latency_s<=0.1" in breach["slo"]

    def test_events_flow_in_via_telemetry(self, tmp_path):
        tracker, tel, now = _tracker(tmp_path)
        for _ in range(4):
            tel.record("inference", step=1,
                       request_latency_s=[0.5, 0.6, 0.7])
        assert tracker.active_breaches() == ["p99_latency"]
        # the tracker never re-ingests its own slo events (no feedback)
        tel.close()

    def test_health_status_degraded_then_ok(self, tmp_path):
        tracker, tel, now = _tracker(tmp_path)
        for _ in range(10):
            tracker.observe("p99_latency", [1.0])
        assert tracker.health_status()["status"] == "degraded"
        for _ in range(300):
            now[0] += 1.0
            tracker.observe("p99_latency", [0.001])
        assert tracker.health_status()["status"] == "ok"
        tel.close()

    def test_burn_gauges_land_in_registry(self, tmp_path):
        reg = MetricsRegistry()
        tracker, tel, now = _tracker(tmp_path, registry=reg)
        for _ in range(10):
            tracker.observe("p99_latency", [1.0])
        text = reg.render()
        assert "bigdl_slo_burn_rate" in text
        assert 'objective="p99_latency"' in text
        assert reg.counter("bigdl_slo_breaches_total",
                           labelnames=("objective",)) \
            .value(objective="p99_latency") == 1
        assert reg.health()["status"] == "degraded"
        tel.close()

    def test_halt_policy_raises_like_a_nan(self, tmp_path):
        tracker, tel, now = _tracker(tmp_path, policy="halt")
        with pytest.raises(TrainingHaltedError, match="SLO watchdog"):
            for _ in range(10):
                # the halt surfaces out of the RECORDING call -- the
                # same machinery a NaN finding uses
                tel.record("inference", step=1,
                           request_latency_s=[1.0])
        assert tracker.health_status()["status"] == "halted"
        tel.close()
        events = [e for e in _load_jsonl(tel.jsonl_path)
                  if e.get("kind") == "slo"]
        assert events and events[0]["breach"] is True

    def test_dump_policy_writes_incident_bundle(self, tmp_path):
        tracker, tel, now = _tracker(tmp_path, policy="dump")
        for _ in range(10):
            tracker.observe("p99_latency", [1.0])
        tel.close()
        root = os.path.join(tel.out_dir, "incidents")
        bundles = os.listdir(root)
        assert len(bundles) == 1 and "slo" in bundles[0]
        with open(os.path.join(root, bundles[0], "manifest.json")) as f:
            manifest = json.load(f)
        assert manifest["finding"]["watchdog"] == "slo"

    def test_duplicate_and_unknown_objectives(self, tmp_path):
        tracker, tel, now = _tracker(tmp_path)
        with pytest.raises(ValueError, match="duplicate"):
            tracker.add(name="p99_latency", kind="step", field="wall_s",
                        threshold=1)
        with pytest.raises(KeyError, match="unknown SLO objective"):
            tracker.observe("nope", [1.0])
        tel.close()


# --------------------------------------------------------------------------- #
# The telemetry bridge: recorded events -> live series.
# --------------------------------------------------------------------------- #


class TestTelemetryBridge:
    def test_step_events_update_training_series(self, tmp_path):
        reg = MetricsRegistry()
        tel = StepTelemetry(str(tmp_path / "r"), trace=False, metrics=reg)
        tel.record("step", step=1, wall_s=0.2, data_wait_s=0.05,
                   loss=1.5, records=8, records_per_s=40.0,
                   step_blocked_s=0.1, wire_bytes=1000, recompiles=1)
        tel.close()
        assert reg.get("bigdl_train_steps_total").value() == 1
        assert reg.get("bigdl_train_loss").value() == 1.5
        assert reg.get("bigdl_train_data_wait_fraction").value() == 0.25
        assert reg.get("bigdl_train_step_blocked_seconds").count() == 1
        assert reg.get("bigdl_train_wire_bytes_total").value() == 1000
        assert reg.get("bigdl_train_recompiles_total").value() == 1

    def test_mfu_gauge_derives_from_header_cost(self):
        reg = MetricsRegistry()
        reg.observe_event({"kind": "header", "peak_flops": 1e13,
                           "cost": {"flops_per_step": 1e12}})
        reg.observe_event({"kind": "step", "step": 1, "wall_s": 0.5,
                           "step_blocked_s": 0.2})
        g = reg.get("bigdl_train_mfu")
        # blocked basis when the run is fenced, and labeled as such
        assert g.value(basis="blocked") == pytest.approx(0.5)

    def test_anomaly_events_degrade_health(self):
        reg = MetricsRegistry()
        reg.observe_event({"kind": "anomaly", "watchdog": "loss_spike",
                           "policy": "warn"})
        assert reg.get("bigdl_train_anomalies_total") \
            .value(watchdog="loss_spike") == 1
        assert reg.health()["status"] == "degraded"
        reg.observe_event({"kind": "anomaly", "watchdog": "nonfinite",
                           "policy": "halt"})
        assert reg.health()["status"] == "halted"

    def test_recovery_events_count_restarts(self):
        reg = MetricsRegistry()
        reg.observe_event({"kind": "recovery", "cause": "process_death",
                           "backoff_s": 0.5, "steps_replayed": 3})
        reg.observe_event({"kind": "recovery", "cause": "exception",
                           "backoff_s": 1.0, "steps_replayed": None})
        c = reg.get("bigdl_recovery_restarts_total")
        assert c.value(cause="process_death") == 1
        assert c.value(cause="exception") == 1
        assert reg.get("bigdl_recovery_backoff_seconds_total") \
            .value() == 1.5

    def test_observer_failure_never_kills_recording(self, tmp_path):
        tel = StepTelemetry(str(tmp_path / "r"), trace=False)
        tel.add_observer(lambda ev: 1 / 0)
        assert tel.record("step", step=1, wall_s=0.1) is not None
        tel.close()


# --------------------------------------------------------------------------- #
# Tier wiring: a live ServingEngine and a live driver loop, scraped.
# --------------------------------------------------------------------------- #


def _mlp(hidden=16, out=4):
    RNG.set_seed(0)
    m = (nn.Sequential().add(nn.Linear(8, hidden)).add(nn.ReLU())
         .add(nn.Linear(hidden, out)))
    m.build(jax.ShapeDtypeStruct((2, 8), jnp.float32))
    return m


class TestServingEngineLive:
    def test_scrape_live_engine(self, tmp_path):
        reg = MetricsRegistry()
        tel = StepTelemetry(str(tmp_path / "serve"), trace=False,
                            metrics=reg)
        xs = np.random.default_rng(0).standard_normal(
            (16, 8)).astype(np.float32)
        with MetricsExporter(reg, port=0) as exp:
            eng = ServingEngine(_mlp(), max_batch_size=4, max_wait_ms=1.0,
                                telemetry=tel)
            try:
                eng.precompile()
                for x in xs:
                    eng.predict(x, timeout=30)
                text = _get(exp.url + "/metrics")
            finally:
                eng.close()
                tel.close()
        assert "bigdl_serving_queue_depth " in text
        assert "bigdl_serving_batch_fill " in text
        assert "bigdl_serving_pad_waste " in text
        assert "bigdl_serving_request_latency_seconds_bucket" in text
        # every request is accounted for across the bucket labels
        c = reg.get("bigdl_serving_requests_total")
        with c._lock:
            total = sum(child[0] for child in c._children.values())
        assert total == len(xs)
        assert reg.get("bigdl_serving_ticks_total").value() >= 1
        assert reg.get("bigdl_serving_request_latency_seconds") \
            .count() == len(xs)

    def test_first_compile_stamped_as_serving_recompile(self, tmp_path):
        reg = MetricsRegistry()
        tel = StepTelemetry(str(tmp_path / "serve"), trace=False,
                            metrics=reg)
        eng = ServingEngine(_mlp(), max_batch_size=2, max_wait_ms=0.5,
                            telemetry=tel)
        try:
            # no precompile(): the first tick compiles, and the live
            # counter shows it (after precompile this staying 0 is the
            # zero-recompile serving contract)
            eng.predict(np.zeros(8, np.float32), timeout=30)
        finally:
            eng.close()
            tel.close()
        assert reg.get("bigdl_serving_recompiles_total").value() >= 1

    def test_refresh_params_outcomes_counted(self, tmp_path):
        reg = MetricsRegistry()
        tel = StepTelemetry(str(tmp_path / "serve"), trace=False,
                            metrics=reg)
        model = _mlp()
        eng = ServingEngine(model, max_batch_size=2, telemetry=tel)
        try:
            eng.refresh_params()
            bad = jax.tree.map(lambda a: np.zeros((1, 1), np.float32),
                               model.parameters()[0])
            with pytest.raises(ValueError):
                eng.refresh_params(params=bad)
        finally:
            eng.close()
            tel.close()
        c = reg.get("bigdl_serving_param_refresh_total")
        assert c.value(outcome="ok") == 1
        assert c.value(outcome="rejected") == 1
        events = [e for e in _load_jsonl(tel.jsonl_path)
                  if e.get("kind") == "param_refresh"]
        assert [e["outcome"] for e in events] == ["ok", "rejected"]
        assert "shape" in events[1]["reason"] \
            or "structure" in events[1]["reason"]


class TestDriverLoopLive:
    def _train(self, tmp_path, reg, steps=6, slo=None):
        RNG.set_seed(0)
        rng = np.random.default_rng(0)
        x = rng.standard_normal((64, 8)).astype(np.float32)
        y = rng.integers(0, 4, 64).astype(np.int32)
        ds = array_dataset(x, y, seed=0) >> SampleToMiniBatch(16)
        model = (nn.Sequential().add(nn.Linear(8, 16)).add(nn.ReLU())
                 .add(nn.Linear(16, 4)))
        opt = optim.LocalOptimizer(model, ds, nn.CrossEntropyCriterion(),
                                   optim.SGD(learning_rate=0.1))
        tel = StepTelemetry(str(tmp_path / "train"), trace=False,
                            metrics=reg)
        if slo is not None:
            slo.bind(tel)
        opt.set_telemetry(tel)
        opt.set_blocking_timing(True)
        opt.set_end_when(optim.Trigger.max_iteration(steps))
        try:
            opt.optimize()
        finally:
            tel.close()
        return opt

    def test_training_gauges_scrapeable(self, tmp_path):
        reg = MetricsRegistry()
        self._train(tmp_path, reg, steps=6)
        assert reg.get("bigdl_train_steps_total").value() == 6
        assert reg.get("bigdl_train_step_wall_seconds").count() == 6
        assert reg.get("bigdl_train_step_blocked_seconds").count() == 6
        assert 0.0 <= reg.get("bigdl_train_data_wait_fraction") \
            .value() <= 1.0
        # cost is attached (telemetry set), but the CPU has no peak
        # FLOP/s, so no MFU gauge derives here (on a TPU it does, on the
        # blocked basis: test_mfu_gauge_derives_from_header_cost)
        assert reg.get("bigdl_train_mfu") is None

    def test_slo_halt_trips_training_like_a_nan(self, tmp_path):
        reg = MetricsRegistry()
        tracker = SloTracker(registry=reg)
        # no training step can finish in <= 0 seconds: burns instantly
        tracker.add(name="step_time_p50", kind="step", field="wall_s",
                    threshold=0.0, target=0.5,
                    alerts=((60.0, 300.0, 1.0),), policy="halt",
                    min_samples=1)
        with pytest.raises(TrainingHaltedError, match="SLO watchdog"):
            self._train(tmp_path, reg, steps=6, slo=tracker)
        assert tracker.health_status()["status"] == "halted"
        jsonl = str(tmp_path / "train" / "telemetry.jsonl")
        kinds = [e.get("kind") for e in _load_jsonl(jsonl)]
        assert "slo" in kinds


class TestSupervisorLive:
    def test_recovery_counters_via_supervisor(self, tmp_path):
        from bigdl_tpu.optim.recovery import RunSupervisor

        reg = MetricsRegistry()
        tel = StepTelemetry(str(tmp_path / "sup"), trace=False,
                            metrics=reg)

        class Dummy:
            checkpoint_path = None
            sharded_checkpoint_path = None
            driver_state = {"neval": 3}

            def __init__(self, fail):
                self.fail = fail

            def optimize(self):
                if self.fail:
                    raise RuntimeError("preempted")

        sup = RunSupervisor(max_restarts=2, backoff_base_s=0.25,
                            telemetry=tel, sleep=lambda s: None,
                            stop_on_repeat=False)
        sup.run(lambda attempt: Dummy(fail=(attempt < 2)))
        tel.close()
        assert reg.get("bigdl_recovery_restarts_total") \
            .value(cause="exception") == 2
        assert reg.get("bigdl_recovery_backoff_seconds_total") \
            .value() == 0.25 + 0.5
