"""ISSUE 16: end-to-end distributed request tracing -- trace-context
propagation across fleet -> worker -> engine -> decode ticks, with
critical-path reports and histogram exemplars.

Pins, per the acceptance criteria:

- ``TraceContext`` round-trips its W3C-traceparent / versioned-wire
  encodings and tolerates garbage and FUTURE wire versions;
- the no-op path is near-zero cost (microbench guard) and an
  unsampled-ok workload writes NOTHING to ``traces.jsonl``;
- an in-process fleet at sample 1.0 records the full span chain
  (``fleet_request`` -> ``fleet_attempt`` -> ``engine_request``) plus
  ``serve_tick`` links, and errors/sheds/p99 tails FORCE unsampled
  traces onto disk;
- a hedged pair records exactly one ``hedge_lost`` span;
- generation traces carry the queue-wait vs decode split and every
  decode tick links back to the riding sequence;
- sampled latencies surface as OpenMetrics histogram exemplars;
- the tier-1 acceptance drill: ONE trace_id through a 3-replica
  subprocess fleet (including a SIGKILL mid-request) reconstructs a
  stitched cross-process timeline via ``tools/trace_report.py``.
"""

import importlib.util
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.nn.attention import TransformerLM
from bigdl_tpu.observability import StepTelemetry
from bigdl_tpu.observability.metrics import MetricsRegistry
from bigdl_tpu.observability.tracing import (TRACE_SAMPLE_ENV,
                                             HeadSampler, RequestTrace,
                                             TraceContext,
                                             default_sample_rate)
from bigdl_tpu.serving import (FleetOverloadedError,
                               FleetUnavailableError, InProcessReplica,
                               ServingEngine, ServingFleet)
from bigdl_tpu.serving.fleet import SubprocessReplica
from bigdl_tpu.utils.random_generator import RNG

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp(seed=0, hidden=16):
    RNG.set_seed(seed)
    m = (nn.Sequential().add(nn.Linear(8, hidden)).add(nn.ReLU())
         .add(nn.Linear(hidden, 4)))
    m.build(jax.ShapeDtypeStruct((2, 8), jnp.float32))
    return m


def _xs(n=64, seed=0):
    return np.random.default_rng(seed).standard_normal((n, 8)) \
        .astype("float32")


def _engine(seed=0, telemetry=None, **kw):
    eng = ServingEngine(_mlp(seed), max_batch_size=4, max_wait_ms=1.0,
                        telemetry=telemetry, **kw)
    eng.precompile(example_feature=_xs(2)[0])
    return eng


def _fleet(n=3, telemetry=None, metrics=None, **kw):
    engines = [_engine(telemetry=telemetry if i == 0 else None)
               for i in range(n)]
    kw.setdefault("retry_backoff_s", 0.003)
    kw.setdefault("retry_backoff_max_s", 0.02)
    fleet = ServingFleet([InProcessReplica(e) for e in engines],
                         telemetry=telemetry, metrics=metrics, **kw)
    return fleet, engines


def _lm():
    m = TransformerLM(vocab_size=32, hidden_size=16, num_heads=4,
                      num_layers=1, max_len=32)
    m.build(jax.ShapeDtypeStruct((2, 8), jnp.int32),
            rng=jax.random.PRNGKey(0))
    return m


def _spans(d):
    path = os.path.join(str(d), "traces.jsonl")
    if not os.path.exists(path):
        return []
    return [json.loads(l) for l in open(path) if l.strip()]


def _wait_spans(d, names, timeout=5.0, counts=None):
    """Engine tick spans land on the dispatcher thread slightly after
    the request future resolves -- poll instead of racing them.
    ``counts`` asks for that many of a name: the record of the LAST tick
    a request rode is written after the request's own."""
    deadline = time.time() + timeout
    counts = counts or {}
    while True:
        spans = _spans(d)
        seen = [s["name"] for s in spans]
        if set(names) <= set(seen) and all(
                seen.count(n) >= k for n, k in counts.items()):
            return spans
        if time.time() > deadline:
            raise AssertionError(
                f"span names {sorted(names)} never all appeared; got "
                f"{sorted({s['name'] for s in spans})}")
        time.sleep(0.02)


def _events(d, kind=None):
    path = os.path.join(str(d), "telemetry.jsonl")
    evs = [json.loads(l) for l in open(path)]
    return evs if kind is None else [e for e in evs if e["kind"] == kind]


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        f"_tracing_{name}", os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------------------- #
# Context encodings.
# --------------------------------------------------------------------------- #


class TestTraceContext:
    def test_mint_shapes_and_uniqueness(self):
        a, b = TraceContext.mint(), TraceContext.mint()
        assert len(a.trace_id) == 32 and len(a.span_id) == 16
        int(a.trace_id, 16), int(a.span_id, 16)
        assert a.parent_id is None and a.sampled
        assert a.trace_id != b.trace_id and a.span_id != b.span_id

    def test_child_inherits_trace_and_sampling(self):
        for sampled in (True, False):
            root = TraceContext.mint(sampled=sampled)
            kid = root.child()
            assert kid.trace_id == root.trace_id
            assert kid.span_id != root.span_id
            assert kid.parent_id == root.span_id
            assert kid.sampled is sampled

    def test_traceparent_round_trip(self):
        for sampled in (True, False):
            ctx = TraceContext.mint(sampled=sampled)
            tp = ctx.to_traceparent()
            assert tp.startswith("00-")
            back = TraceContext.from_traceparent(tp)
            assert back.trace_id == ctx.trace_id
            assert back.span_id == ctx.span_id
            assert back.sampled is sampled

    def test_traceparent_garbage_is_none_not_fatal(self):
        bad = [None, 42, "", "00-abc-def", "no-dashes-here",
               "00-" + "g" * 32 + "-" + "a" * 16 + "-01",     # non-hex
               "00-" + "a" * 31 + "-" + "b" * 16 + "-01",     # short
               "00-" + "a" * 32 + "-" + "b" * 15 + "-01",
               "00-" + "a" * 32 + "-" + "b" * 16 + "-zz"]
        for v in bad:
            assert TraceContext.from_traceparent(v) is None

    def test_wire_round_trip_and_future_version_tolerance(self):
        ctx = TraceContext.mint(sampled=True)
        wire = ctx.to_wire()
        assert wire["v"] == 1
        back = TraceContext.from_wire(wire)
        assert back.trace_id == ctx.trace_id and back.sampled
        # a FUTURE peer's extra fields are ignored, the core parses
        fut = {"v": 99, "traceparent": ctx.to_traceparent(),
               "baggage": {"x": 1}}
        assert TraceContext.from_wire(fut).trace_id == ctx.trace_id
        for garbage in (None, "x", 7, [], {}, {"v": 1},
                        {"traceparent": "junk"}):
            assert TraceContext.from_wire(garbage) is None


class TestHeadSampler:
    def test_rate_extremes_are_deterministic(self):
        assert all(HeadSampler(1.0).sample() for _ in range(50))
        assert not any(HeadSampler(0.0).sample() for _ in range(50))

    def test_env_default_rate(self, monkeypatch):
        monkeypatch.setenv(TRACE_SAMPLE_ENV, "0.25")
        assert default_sample_rate() == 0.25
        assert HeadSampler().rate == 0.25
        monkeypatch.setenv(TRACE_SAMPLE_ENV, "garbage")
        assert default_sample_rate() == 0.01    # fall back, don't crash
        monkeypatch.delenv(TRACE_SAMPLE_ENV)
        assert default_sample_rate() == 0.01


class TestRequestTrace:
    def test_error_and_shed_spans_force_the_trace(self):
        for status in ("shed", "error:RuntimeError"):
            rt = RequestTrace(TraceContext.mint(sampled=False))
            assert not rt.keep
            rt.add("fleet_request", rt.ctx, 0.0, 0.0, status=status)
            assert rt.forced and rt.keep

    def test_unsampled_ok_trace_is_dropped(self, tmp_path):
        tel = StepTelemetry(str(tmp_path), trace=False)
        rt = RequestTrace(TraceContext.mint(sampled=False))
        rt.add("fleet_request", rt.ctx, 0.0, 0.001, status="ok")
        assert rt.flush(tel) is False
        assert not os.path.exists(os.path.join(str(tmp_path),
                                               "traces.jsonl"))
        rt.force()                       # e.g. the p99-tail override
        assert rt.flush(tel) is True
        recs = _spans(tmp_path)
        assert len(recs) == 1 and recs[0]["status"] == "ok"
        assert recs[0]["trace"] == rt.ctx.trace_id
        assert recs[0]["span"] == rt.ctx.span_id
        assert recs[0]["pid"] == os.getpid()

    def test_flush_tolerates_traceless_telemetry(self):
        rt = RequestTrace(TraceContext.mint(sampled=True))
        rt.add("fleet_request", rt.ctx, 0.0, 0.0)
        assert rt.flush(None) is False
        assert rt.flush(object()) is False   # no record_trace method


# --------------------------------------------------------------------------- #
# Satellite 1: the no-op path costs (nearly) nothing.
# --------------------------------------------------------------------------- #


class TestNoOpCost:
    def test_fleet_without_telemetry_never_mints(self):
        fleet, _ = _fleet(1, trace_sample=1.0)
        try:
            assert fleet._tracing is False    # no sink -> no mint at all
            y = fleet.predict(_xs(2)[0], timeout=10.0)
            assert np.asarray(y).shape == (4,)
        finally:
            fleet.close()

    def test_unsampled_ok_workload_writes_nothing(self, tmp_path):
        tel = StepTelemetry(str(tmp_path), run_name="driver",
                            trace=False)
        fleet, _ = _fleet(1, telemetry=tel, trace_sample=0.0)
        try:
            for x in _xs(8):
                fleet.predict(x, timeout=10.0)
        finally:
            fleet.close()
        # lazy sink: never opened, so the artifact does not even exist
        assert not os.path.exists(os.path.join(str(tmp_path),
                                               "traces.jsonl"))

    def test_mint_and_buffer_microbench_guard(self):
        """The tier-1 overhead guard: one request's worth of tracing
        bookkeeping (sampler draw + mint + child + buffer + dropped
        flush) must stay in single-digit microseconds territory.  The
        bound is ~50x slack over the measured cost, so only a real
        regression (per-mint syscalls, I/O on the unsampled path)
        trips it -- not scheduler jitter."""
        sampler = HeadSampler(0.0)
        n = 20000
        t0 = time.perf_counter()
        for _ in range(n):
            rt = RequestTrace(TraceContext.mint(sampled=sampler.sample()))
            ctx = rt.ctx.child()
            rt.add("fleet_attempt", ctx, 0.0, 0.0, status="ok")
            rt.add("fleet_request", rt.ctx, 0.0, 0.0, status="ok")
            rt.flush(None)
        per_req = (time.perf_counter() - t0) / n
        assert per_req < 100e-6, \
            f"tracing no-op path costs {per_req * 1e6:.1f}us/request"


# --------------------------------------------------------------------------- #
# In-process fleet end to end.
# --------------------------------------------------------------------------- #


class TestFleetTracingE2E:
    def test_predict_records_the_full_span_chain(self, tmp_path):
        tel = StepTelemetry(str(tmp_path), run_name="driver",
                            trace=False)
        fleet, _ = _fleet(1, telemetry=tel, trace_sample=1.0)
        try:
            y = fleet.predict(_xs(2)[0], timeout=10.0)
            assert np.asarray(y).shape == (4,)
            spans = _wait_spans(tmp_path, {"fleet_request",
                                           "fleet_attempt",
                                           "engine_request",
                                           "serve_tick"})
        finally:
            fleet.close()
        root = [s for s in spans if s["name"] == "fleet_request"][0]
        att = [s for s in spans if s["name"] == "fleet_attempt"][0]
        eng = [s for s in spans if s["name"] == "engine_request"][0]
        tick = [s for s in spans if s["name"] == "serve_tick"][0]
        tid = root["trace"]
        # one trace, explicit parent chain: request -> attempt -> engine
        assert root["parent"] is None and root["status"] == "ok"
        assert root["op"] == "submit"
        assert att["trace"] == tid and att["parent"] == root["span"]
        assert att["status"] == "ok" and att["replica"] == 0
        assert eng["trace"] == tid and eng["parent"] == att["span"]
        assert eng["queue_wait_s"] >= 0 and eng["device_s"] > 0
        # the tick is its OWN trace, linked to every rider
        assert tick["trace"] != tid and tid in tick["links"]
        assert tick["records"] >= 1

    def test_tick_events_carry_parallel_trace_ids(self, tmp_path):
        tel = StepTelemetry(str(tmp_path), run_name="driver",
                            trace=False)
        fleet, _ = _fleet(1, telemetry=tel, trace_sample=1.0)
        try:
            fleet.predict(_xs(2)[0], timeout=10.0)
            spans = _wait_spans(tmp_path, {"fleet_request"})
        finally:
            fleet.close()
        # the request's own span: the tick's span (a trace of its own)
        # may land in the file after it
        tid = [s for s in spans if s["name"] == "fleet_request"][-1]["trace"]
        evs = [e for e in _events(tmp_path, "inference")
               if e.get("request_traces")]
        assert evs, "no inference event carried request_traces"
        ev = evs[0]
        assert len(ev["request_traces"]) == len(ev["request_latency_s"])
        assert tid in ev["request_traces"]

    def test_hedged_pair_records_exactly_one_hedge_lost(self, tmp_path):
        tel = StepTelemetry(str(tmp_path), run_name="driver",
                            trace=False)
        fleet, engines = _fleet(2, telemetry=tel, trace_sample=1.0,
                                hedge=True, hedge_min_delay_s=0.03,
                                hedge_min_samples=5)
        for _ in range(10):                 # calibrate the p99
            fleet._note_latency(0.005)
        backend = engines[0]._backend
        orig = backend.eval
        release = threading.Event()

        def straggler(*a, **kw):
            release.wait(3.0)               # one stuck tick
            return orig(*a, **kw)

        backend.eval = straggler
        try:
            y = fleet.predict(_xs(2)[0], timeout=10.0)
            assert np.asarray(y).shape == (4,)
            assert fleet.counters()["hedge_wins"] >= 1
            spans = _wait_spans(tmp_path, {"fleet_request",
                                           "fleet_attempt"})
        finally:
            release.set()
            backend.eval = orig
            fleet.close()
        atts = [s for s in spans if s["name"] == "fleet_attempt"]
        lost = [a for a in atts if a["status"] == "hedge_lost"]
        won = [a for a in atts if a["status"] == "ok"]
        assert len(lost) == 1 and len(won) == 1
        assert lost[0]["trace"] == won[0]["trace"]
        assert won[0].get("hedge") is True      # the hedge won the race
        assert lost[0]["replica"] != won[0]["replica"]

    def test_shed_is_forced_onto_disk_at_zero_sample(self, tmp_path):
        tel = StepTelemetry(str(tmp_path), run_name="driver",
                            trace=False)
        fleet, engines = _fleet(1, telemetry=tel, trace_sample=0.0,
                                admission_limit=1)
        backend = engines[0]._backend
        orig = backend.eval
        release = threading.Event()

        def slow(*a, **kw):
            release.wait(5.0)
            return orig(*a, **kw)

        backend.eval = slow
        try:
            results = []
            t = threading.Thread(
                target=lambda: results.append(
                    fleet.predict(_xs(2)[0], timeout=10.0)), daemon=True)
            t.start()
            time.sleep(0.1)                  # the slot is occupied
            with pytest.raises(FleetOverloadedError):
                fleet.predict(_xs(2)[1], timeout=10.0)
            release.set()
            t.join(5.0)
        finally:
            release.set()
            fleet.close()
        shed = [s for s in _spans(tmp_path) if s["status"] == "shed"]
        assert len(shed) == 1 and shed[0]["name"] == "fleet_request"

    def test_failed_request_is_forced_with_attempt_evidence(self,
                                                            tmp_path):
        tel = StepTelemetry(str(tmp_path), run_name="driver",
                            trace=False)
        fleet, _ = _fleet(2, telemetry=tel, trace_sample=0.0,
                          retry_limit=1)

        def boom(*a, **kw):
            raise RuntimeError("synthetic replica failure")

        for rep in fleet.replicas:
            rep.submit = boom
        try:
            with pytest.raises(FleetUnavailableError):
                fleet.predict(_xs(2)[0], timeout=5.0)
        finally:
            fleet.close()
        spans = _spans(tmp_path)
        root = [s for s in spans if s["name"] == "fleet_request"]
        atts = [s for s in spans if s["name"] == "fleet_attempt"]
        assert len(root) == 1
        assert root[0]["status"] == "error:FleetUnavailableError"
        assert atts and all(a["status"] == "error:RuntimeError"
                            for a in atts)
        assert {a["trace"] for a in atts} == {root[0]["trace"]}

    def test_p99_tail_latency_forces_an_unsampled_trace(self, tmp_path):
        tel = StepTelemetry(str(tmp_path), run_name="driver",
                            trace=False)
        fleet, _ = _fleet(1, telemetry=tel, trace_sample=0.0)
        try:
            # seed the reservoir with sub-real latencies: the next REAL
            # request (milliseconds) lands beyond their p99 and the
            # always-sample tail override must keep it
            for _ in range(fleet.hedge_min_samples):
                fleet._note_latency(1e-6)
            fleet.predict(_xs(2)[0], timeout=10.0)
        finally:
            fleet.close()
        spans = _spans(tmp_path)
        assert [s["name"] for s in spans].count("fleet_request") == 1
        assert spans[-1]["status"] == "ok"


# --------------------------------------------------------------------------- #
# Satellite 2: generation tracing -- queue-wait/decode split + tick links.
# --------------------------------------------------------------------------- #


class TestGenerateTracing:
    def test_generate_trace_splits_and_links_every_tick(self, tmp_path):
        tel = StepTelemetry(str(tmp_path), run_name="driver",
                            trace=False)
        ctx = TraceContext.mint(sampled=True)
        with ServingEngine(_lm(), decode_slots=2, decode_max_len=32,
                           telemetry=tel) as eng:
            fut = eng.generate([1, 2, 3], max_new_tokens=6, trace=ctx)
            out = fut.result(60)
            assert len(out) == 6
            assert fut.queue_wait_s is not None and fut.decode_s > 0
            assert abs((fut.queue_wait_s + fut.decode_s)
                       - fut.latency_s) < 1e-3
            spans = _wait_spans(tmp_path, {"generate_request",
                                           "prefill_tick",
                                           "decode_tick"},
                                counts={"decode_tick": 5})
        gen = [s for s in spans if s["name"] == "generate_request"][0]
        assert gen["trace"] == ctx.trace_id
        assert gen["parent"] == ctx.span_id
        assert gen["tokens"] == 6 and gen["finish_reason"] == "length"
        assert gen["queue_wait_s"] >= 0 and gen["decode_s"] > 0
        prefills = [s for s in spans if s["name"] == "prefill_tick"
                    and ctx.trace_id in s["links"]]
        decodes = [s for s in spans if s["name"] == "decode_tick"
                   and ctx.trace_id in s["links"]]
        # prefill emits token 1; EVERY later token is one linked decode
        # tick the sequence rode
        assert len(prefills) == 1
        assert len(decodes) == 5
        # the durable tick events carry the resident traced ids too
        evs = [e for e in _events(tmp_path, "inference")
               if e.get("trace_ids")]
        assert evs and all(ctx.trace_id in e["trace_ids"] for e in evs)

    def test_generate_split_reaches_tick_events(self, tmp_path):
        tel = StepTelemetry(str(tmp_path), run_name="driver",
                            trace=False)
        with ServingEngine(_lm(), decode_slots=2, decode_max_len=32,
                           telemetry=tel) as eng:
            eng.generate([1, 2, 3], max_new_tokens=4).result(60)
            deadline = time.time() + 5.0
            while time.time() < deadline:
                evs = [e for e in _events(tmp_path, "inference")
                       if e.get("generate_latency_s")]
                if evs:
                    break
                time.sleep(0.02)
        assert evs, "no tick event delivered generate latencies"
        ev = evs[0]
        n = len(ev["generate_latency_s"])
        assert len(ev["generate_queue_wait_s"]) == n
        assert len(ev["generate_decode_s"]) == n
        for lat, qw, dec in zip(ev["generate_latency_s"],
                                ev["generate_queue_wait_s"],
                                ev["generate_decode_s"]):
            assert abs((qw + dec) - lat) < 1e-3


# --------------------------------------------------------------------------- #
# Histogram exemplars.
# --------------------------------------------------------------------------- #


class TestExemplars:
    def test_histogram_renders_openmetrics_exemplars(self):
        reg = MetricsRegistry()
        h = reg.histogram("bigdl_test_latency_seconds", "test family")
        h.observe(0.004, exemplar="ab" * 16)
        h.observe(0.004)                     # untraced: no exemplar
        h.observe(1e9, exemplar="cd" * 16)   # lands in +Inf
        out = reg.render()
        assert '# {trace_id="%s"} 0.004' % ("ab" * 16) in out
        assert '# {trace_id="%s"}' % ("cd" * 16) in out
        # exactly the two exemplared buckets carry the suffix
        assert out.count("# {trace_id=") == 2

    def test_serving_bridge_attaches_request_exemplars(self, tmp_path):
        reg = MetricsRegistry()
        tel = StepTelemetry(str(tmp_path), run_name="driver",
                            trace=False, metrics=reg)
        fleet, _ = _fleet(1, telemetry=tel, trace_sample=1.0)
        try:
            fleet.predict(_xs(2)[0], timeout=10.0)
            spans = _wait_spans(tmp_path, {"fleet_request"})
        finally:
            fleet.close()
        tid = spans[-1]["trace"]
        out = reg.render()
        assert "bigdl_serving_request_latency_seconds_bucket" in out
        assert 'trace_id="%s"' % tid in out


# --------------------------------------------------------------------------- #
# trace_report + obs_report over an in-process run.
# --------------------------------------------------------------------------- #


class TestTraceReport:
    def _run(self, tmp_path, n_requests=3):
        tel = StepTelemetry(str(tmp_path), run_name="driver",
                            trace=False)
        fleet, _ = _fleet(1, telemetry=tel, trace_sample=1.0)
        try:
            for x in _xs(n_requests):
                fleet.predict(x, timeout=10.0)
            _wait_spans(tmp_path, {"fleet_request", "engine_request",
                                   "serve_tick"})
        finally:
            fleet.close()

    def test_summarize_builds_critical_paths(self, tmp_path):
        self._run(tmp_path)
        tr = _load_tool("trace_report")
        rep = tr.summarize([str(tmp_path)])
        agg = rep["summary"]
        assert agg["traces"] == 3 and agg["records"] > 0
        assert agg["errors"] == 0 and agg["shed"] == 0
        for cp in rep["traces"]:
            assert cp["op"] == "submit" and cp["status"] == "ok"
            assert cp["attempts"] and cp["total_s"] is not None
            assert cp["ticks"].get("serve_tick", 0) >= 1
            assert cp["stages"]["engine_device_s"] > 0
            # in-process: attempt and engine share a pid, NO wire stage
            assert "wire_s" not in cp["stages"]
        text = tr.render_text(rep)
        assert "== Trace report ==" in text and "attempt replica=" in text

    def test_cli_exits_nonzero_on_hollow_dir(self, tmp_path):
        tr = _load_tool("trace_report")
        assert tr.main([str(tmp_path)]) == 1

    def test_obs_report_gains_a_tracing_section(self, tmp_path, capsys):
        self._run(tmp_path)
        obs = _load_tool("obs_report")
        rep = obs.build_report(str(tmp_path))
        tr = rep.get("tracing")
        assert tr is not None
        assert tr["traces"] == 3 and tr["cross_process"] == 0
        assert tr["slowest"], "tracing section lists no slow traces"
        out = obs.format_report(rep)
        assert "tracing:" in out


# --------------------------------------------------------------------------- #
# Tier-1 acceptance: stitched cross-process trace through a 3-replica
# subprocess fleet, including trace continuity across a SIGKILL.
# --------------------------------------------------------------------------- #


def _boot_workers(out, n, slow_ms):
    """Spawn ``n`` tests/_trace_worker.py processes CONCURRENTLY (jax
    import + precompile dominates boot; serial spawns would triple it)
    and wait for every atomic port file."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    procs, port_files = [], []
    for rid in range(n):
        pf = os.path.join(out, f"replica_{rid}.port")
        cmd = [sys.executable,
               os.path.join(REPO, "tests", "_trace_worker.py"),
               "--out", out, "--replicaId", str(rid),
               "--portFile", pf]
        if slow_ms.get(rid):
            cmd += ["--slowMs", str(slow_ms[rid])]
        logf = open(os.path.join(out, f"replica_{rid}.log"), "w")
        procs.append(subprocess.Popen(cmd, env=env, stdout=logf,
                                      stderr=subprocess.STDOUT,
                                      cwd=REPO))
        logf.close()
        port_files.append(pf)
    ports = []
    deadline = time.time() + 240
    for rid, (proc, pf) in enumerate(zip(procs, port_files)):
        while True:
            if proc.poll() is not None:
                log = open(os.path.join(
                    out, f"replica_{rid}.log")).read()
                raise RuntimeError(f"worker {rid} died during boot "
                                   f"(rc={proc.poll()}):\n{log[-2000:]}")
            if os.path.exists(pf):
                port = open(pf).read().strip()
                if port:
                    ports.append(int(port))
                    break
            if time.time() > deadline:
                raise RuntimeError(f"worker {rid} boot timed out")
            time.sleep(0.1)
    return procs, ports


class TestSubprocessStitchedTrace:
    def test_cross_process_timeline_with_sigkill_continuity(
            self, tmp_path):
        out = str(tmp_path)
        # replica 0 answers predicts ~1.2s late: the window the drill
        # needs to SIGKILL it while a traced request is in flight
        procs, ports = _boot_workers(out, 3, slow_ms={0: 1200.0})
        tel = StepTelemetry(os.path.join(out, "driver"),
                            run_name="driver", trace=False)
        reps = [SubprocessReplica(
                    lambda attempt, p=procs[i], port=ports[i]: (p, port),
                    rid=i).start(0)
                for i in range(3)]
        fleet = ServingFleet(reps, telemetry=tel, trace_sample=1.0,
                             retry_backoff_s=0.01,
                             retry_backoff_max_s=0.05,
                             default_timeout_s=60.0)
        feat = np.zeros((8,), np.int32)
        try:
            # -- drill: kill the serving worker mid-request ------------ #
            results = []
            t = threading.Thread(
                target=lambda: results.append(
                    fleet.predict(feat, timeout=30.0)), daemon=True)
            t.start()
            time.sleep(0.4)       # the request is inside replica 0's
            #                       slow predict; now kill the process
            os.kill(procs[0].pid, signal.SIGKILL)
            t.join(30.0)
            assert results, "killed-worker request never completed"
            assert np.asarray(results[0]).shape[-1] == 32
            assert fleet.counters()["retries"] >= 1
            # take the corpse out of rotation: later traffic must not
            # add its OWN retry traces (the drill trace stays the one
            # ok-after-error predict in the report)
            fleet.mark_dead(fleet.replicas[0], reason="drill SIGKILL")
            # -- healthy traffic: a generation + one more predict ------ #
            toks = fleet.generate([1, 2, 3], max_new_tokens=5,
                                  timeout=60.0)
            assert len(toks) == 5
            y = fleet.predict(feat, timeout=30.0)
            assert np.asarray(y).shape[-1] == 32
            time.sleep(0.3)       # let worker tick spans hit their sinks
        finally:
            fleet.close()
            for p in procs:
                if p.poll() is None:
                    p.kill()
        tr = _load_tool("trace_report")
        rep = tr.summarize([out])
        agg = rep["summary"]
        assert agg["retried"] >= 1
        assert agg["cross_process"] >= 2, \
            "driver and worker spans did not stitch by trace_id"
        by_status = {}
        for cp in rep["traces"]:
            by_status.setdefault((cp["op"], cp["status"]),
                                 []).append(cp)
        # (1) the SIGKILL drill trace: ONE trace_id holding the dead
        # attempt's error span AND the winning retry
        drill = [cp for cp in by_status.get(("submit", "ok"), [])
                 if cp["errors"]]
        assert len(drill) == 1
        drill = drill[0]
        statuses = [a["status"] for a in drill["attempts"]]
        assert sum(1 for s in statuses
                   if s.startswith("error:")) >= 1
        assert statuses.count("ok") == 1
        replicas = {a["replica"] for a in drill["attempts"]}
        assert len(replicas) >= 2       # the retry moved replicas
        # (2) a clean cross-process predict: wire hop + engine
        # queue/batch stages all present in one stitched timeline
        clean = [cp for cp in by_status.get(("submit", "ok"), [])
                 if not cp["errors"] and len(cp["processes"]) > 1]
        assert clean, "no clean cross-process predict trace"
        cp = clean[0]
        names = {p for p, _pid in cp["processes"]}
        assert "driver" in names
        assert any(n.startswith("worker_") for n in names)
        assert cp["stages"]["wire_s"] >= 0
        assert cp["stages"]["engine_device_s"] > 0
        assert cp["stages"]["engine_queue_wait_s"] >= 0
        assert cp["ticks"].get("serve_tick", 0) >= 1
        # (3) the generation trace: worker-side split + EVERY decode
        # tick linked back across the process boundary
        gens = by_status.get(("submit_generate", "ok"), [])
        assert len(gens) == 1
        g = gens[0]
        assert g["tokens"] == 5 and g["finish_reason"] == "length"
        assert g["stages"]["generate_decode_s"] > 0
        assert g["ticks"].get("prefill_tick", 0) == 1
        assert g["ticks"].get("decode_tick", 0) == 4
        assert len(g["processes"]) > 1
        assert g["stages"]["wire_s"] >= 0
        # the whole story renders
        text = tr.render_text(rep)
        assert "cross-process" in text and "decode_tick" in text
