"""Headline benchmark: ResNet-50 training throughput on one TPU chip.

Mirrors the reference's perf harnesses (models/utils/DistriOptimizerPerf.scala,
nn/mkldnn/Perf.scala:56-126: imgs/sec on synthetic data) with the BASELINE.json
north-star metric: ResNet-50 images/sec/chip and MFU.

vs_baseline = achieved_MFU / 0.35 (the >=35% MFU target from BASELINE.md;
the reference publishes no absolute imgs/sec for its Xeon clusters).

Robustness (round-2): the parent process re-executes itself as a child and
retries on TPU backend init/compile failures (transient backend errors were
the whole of round 1's bench story), optionally falling back to CPU.

Robustness (round-4): total wall-clock is bounded by BENCH_TOTAL_BUDGET
(default 1100s) -- every stage's timeout is clamped to the remaining budget --
and a diagnostic JSON line is printed before each long stage, so even a
SIGKILL at any moment leaves the last printed line as a parseable artifact.
The LAST JSON line on stdout is the result.

Trusted timing (round-6, ISSUE 6): the published MFU derives from
``step_blocked_s`` ONLY (per-step ``block_until_ready``-fenced timing --
``observability.profiling.BlockingStepTimer``); the chained dispatch loop
and the profiler trace's device-busy time are retained as independent
triangulation estimates, and ``TimingAuditor`` stamps a machine-readable
``trust`` verdict (``trusted`` / ``suspect:async_dispatch`` /
``invalid:off_tpu`` / ``invalid:impossible``) top-level on every
step-time record this harness emits (the host-side A/B micro-benches
-- BENCH_PIPELINE/HEALTH/QCOMM/SERVE/DECODE -- measure ratios, not
device step time, and carry no verdict).
The device probe is fast and cancellable (BENCH_PROBE_TIMEOUT, default
60s, vs the old fixed 240s) and its outcome is recorded honestly
(``probe_result``/``probe_sec``; a CPU fallback after a hung probe reads
``probe: timeout→cpu`` instead of a killed run), and every record's
``extra`` carries the compilation-cache warm/cold state so cache reuse
across legs is verifiable from the artifact alone.
"""

import json
import os
import subprocess
import sys
import time


def _tracing_manifest():
    """The request-tracing config block (sample rate, always_sample)
    from ``observability/tracing.py``, spec-loaded by path so this
    harness keeps working without jax installed."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "bigdl_tpu", "observability", "tracing.py")
    spec = importlib.util.spec_from_file_location("_bench_tracing", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.tracing_manifest()


def emit_record(record):
    """Print one bench record with the tracing manifest stamped into
    ``extra``: tools/perf_gate.py refuses a number measured with
    always-sample tracing (every request paid forced span flushes the
    production path doesn't), and the manifest is what lets it tell."""
    extra = record.setdefault("extra", {})
    try:
        extra.setdefault("tracing", _tracing_manifest())
    except Exception:
        pass          # an unreadable manifest must never kill a bench
    print(json.dumps(record), flush=True)
    return record


# single source of truth for the model-variant flag vocabulary shared by
# the sweep suffix syntax here and tools/perf_ab.py:
# (kwarg name, suffix letter, env var giving the suffix-less default)
VARIANT_FLAGS = (("remat", "r", "BENCH_REMAT"),
                 ("s2d", "s", "BENCH_S2D"),
                 ("fused", "f", "BENCH_FUSED"))


def variant_defaults(env=None):
    """{name: bool} defaults from the BENCH_* env tier."""
    env = os.environ if env is None else env
    return {name: env.get(var, "0") == "1" for name, _, var in VARIANT_FLAGS}


def parse_variant(entry, defaults=None):
    """"512rf" -> (512, {"remat": True, "s2d": False, "fused": True})."""
    entry = entry.strip()
    flags = dict(variant_defaults() if defaults is None else defaults)
    letters = {letter: name for name, letter, _ in VARIANT_FLAGS}
    while entry and entry[-1] in letters:
        flags[letters[entry[-1]]] = True
        entry = entry[:-1]
    return int(entry), flags


def variant_suffix(flags):
    """{"remat": True, ...} -> "r..." (inverse of parse_variant)."""
    return "".join(letter for name, letter, _ in VARIANT_FLAGS
                   if flags.get(name))


def _enable_compile_cache():
    """Returns the compilation-cache status sampled at run START (before
    this run's own compiles land in the cache dir), so every bench
    record can carry the warm/cold state in its ``extra`` -- cache reuse
    across legs is then verifiable from BENCH_*.json alone, not just
    from a stderr line."""
    from bigdl_tpu.utils.config import (compilation_cache_note,
                                        compilation_cache_status,
                                        enable_compilation_cache)
    enable_compilation_cache()
    # one-line hit/miss note (stderr: stdout is the JSON artifact
    # channel) -- a warm cache is why repeat bench runs start fast
    print(compilation_cache_note(), file=sys.stderr, flush=True)
    return compilation_cache_status()


# --------------------------------------------------------------------------- #
# Input-pipeline micro-benchmark (ISSUE 2): synthetic per-sample host
# latency, synchronous vs PrefetchDataSet, data-wait fraction measured
# from the StepTelemetry JSONL via tools/obs_report.build_report.
# --------------------------------------------------------------------------- #

def _obs_report_module():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_bench_obs_report",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "tools", "obs_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _pipeline_leg(run_dir, num_workers, latency_s, steps, batch,
                  queue_depth=8, hidden=3072):
    """One training leg (synchronous when ``num_workers == 0``) with a
    ``latency_s``-per-sample synthetic transform; returns the obs_report
    ``steps`` block for the leg's telemetry JSONL."""
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim
    from bigdl_tpu.dataset import (FnTransformer, SampleToMiniBatch,
                                   array_dataset)
    from bigdl_tpu.observability import StepTelemetry

    rng = np.random.default_rng(0)
    # one epoch covers the whole run: an epoch rollover re-creates the
    # pipeline (reshuffle semantics), and the queue-refill stall would
    # measure epoch churn rather than steady-state pipeline behaviour
    n = batch * max(8, steps + 2)
    x = rng.standard_normal((n, 16)).astype("float32")
    y = rng.integers(0, 4, n).astype("int32")

    def slow_identity(sample):
        time.sleep(latency_s)       # the injected host-side transform cost
        return sample

    ds = (array_dataset(x, y) >> FnTransformer(slow_identity)
          >> SampleToMiniBatch(batch))
    if num_workers:
        ds = ds.prefetch(num_workers=num_workers, queue_depth=queue_depth)
    # enough device work per step that a hidden transform actually shows
    # up as a lower data-wait FRACTION, not just a lower absolute wait
    model = (nn.Sequential().add(nn.Linear(16, hidden)).add(nn.ReLU())
             .add(nn.Linear(hidden, hidden)).add(nn.ReLU())
             .add(nn.Linear(hidden, 4)))
    tel = StepTelemetry(run_dir, run_name=f"pipe-w{num_workers}",
                        trace=False)
    opt = optim.LocalOptimizer(model, ds, nn.CrossEntropyCriterion(),
                               optim.SGD(learning_rate=0.05))
    opt.set_end_when(optim.Trigger.max_iteration(steps))
    opt.set_telemetry(tel)
    opt.optimize()
    tel.close()
    return _obs_report_module().build_report(run_dir)["steps"]


def run_pipeline_bench(latency_s=None, steps=None, batch=None,
                       num_workers=None, hidden=None, out_dir=None):
    """A/B the input pipeline: synchronous vs prefetch workers.

    Knobs (env tier): BENCH_PIPE_LATENCY_MS (default 5), BENCH_PIPE_STEPS
    (default 24), BENCH_PIPE_BATCH (default 32), BENCH_PIPE_WORKERS
    (default 4), BENCH_PIPE_HIDDEN (default 3072 -- sized so the device
    step is comparable to the injected transform cost; a hidden
    transform then shows up as a lower data-wait FRACTION, not just a
    lower absolute wait).  Prints ONE JSON record whose ``vs_baseline``
    is the data-wait-fraction reduction factor (>= 2 is the ISSUE-2
    target).
    """
    cache_status = _enable_compile_cache()
    import tempfile

    env = os.environ
    latency_s = (float(env.get("BENCH_PIPE_LATENCY_MS", "5")) / 1e3
                 if latency_s is None else latency_s)
    steps = int(env.get("BENCH_PIPE_STEPS", "24")) if steps is None else steps
    batch = int(env.get("BENCH_PIPE_BATCH", "32")) if batch is None else batch
    num_workers = (int(env.get("BENCH_PIPE_WORKERS", "4"))
                   if num_workers is None else num_workers)
    hidden = (int(env.get("BENCH_PIPE_HIDDEN", "3072"))
              if hidden is None else hidden)

    def _run(base):
        sync = _pipeline_leg(os.path.join(base, "sync"), 0,
                             latency_s, steps, batch, hidden=hidden)
        pre = _pipeline_leg(os.path.join(base, f"prefetch{num_workers}"),
                            num_workers, latency_s, steps, batch,
                            hidden=hidden)
        return sync, pre

    if out_dir is None:
        with tempfile.TemporaryDirectory() as td:
            sync, pre = _run(td)
    else:
        sync, pre = _run(out_dir)
    reduction = (sync["data_wait_fraction"]
                 / max(pre["data_wait_fraction"], 1e-9))
    record = {
        "metric": "pipeline_data_wait_fraction_reduction",
        "value": round(reduction, 2),
        "unit": "x",
        "vs_baseline": round(reduction / 2.0, 4),   # target: >= 2x
        "extra": {
            "compilation_cache": cache_status,
            "latency_ms_per_sample": latency_s * 1e3,
            "steps": steps, "batch": batch, "num_workers": num_workers,
            "hidden": hidden,
            "sync": {"data_wait_fraction": sync["data_wait_fraction"],
                     "wall_s_p50": sync["wall_s_p50"]},
            "prefetch": {"data_wait_fraction": pre["data_wait_fraction"],
                         "wall_s_p50": pre["wall_s_p50"],
                         "queue": pre.get("prefetch_queue")},
        },
    }
    emit_record(record)
    return record


# --------------------------------------------------------------------------- #
# Health-telemetry overhead micro-benchmark (ISSUE 3): the sampled
# numerics branch (stats_every=K) must cost < 5% median step time vs
# stats_every=None, and stats_every=None must be loss-stream-identical
# to the plain step (the acceptance gates; tests/test_health.py pins
# the fast smoke, the CLI leg measures the real overhead).
# --------------------------------------------------------------------------- #

def _mlp_leg(run_dir, run_name, make_opt, steps, batch, hidden, seed=0):
    """The shared micro-bench leg recipe (health + qcomm A/Bs): seeded
    synthetic data sized so one epoch covers the run, a 3-layer MLP,
    StepTelemetry, train ``steps`` iterations, return the obs_report
    steps block + the raw step events.  ``make_opt(model, ds)`` builds
    the optimizer under test (Local vs Distri, monitors, compression)."""
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim
    from bigdl_tpu.dataset import SampleToMiniBatch, array_dataset
    from bigdl_tpu.observability import StepTelemetry
    from bigdl_tpu.utils.random_generator import RNG

    RNG.set_seed(seed)
    rng = np.random.default_rng(seed)
    n = batch * max(8, steps + 2)
    x = rng.standard_normal((n, 16)).astype("float32")
    y = rng.integers(0, 4, n).astype("int32")
    ds = array_dataset(x, y) >> SampleToMiniBatch(batch)
    model = (nn.Sequential().add(nn.Linear(16, hidden)).add(nn.ReLU())
             .add(nn.Linear(hidden, hidden)).add(nn.ReLU())
             .add(nn.Linear(hidden, 4)))
    tel = StepTelemetry(run_dir, run_name=run_name, trace=False)
    opt = make_opt(model, ds)
    opt.set_end_when(optim.Trigger.max_iteration(steps))
    opt.set_telemetry(tel)
    opt.optimize()
    tel.close()
    rep_mod = _obs_report_module()
    _, step_events, _ = rep_mod.load_events(
        os.path.join(run_dir, "telemetry.jsonl"))
    return rep_mod.build_report(run_dir)["steps"], step_events


def _health_leg(run_dir, stats_every, steps, batch, hidden, seed=0):
    """One training leg; returns (obs_report steps block, loss stream)."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim

    def make_opt(model, ds):
        opt = optim.LocalOptimizer(model, ds, nn.CrossEntropyCriterion(),
                                   optim.SGD(learning_rate=0.05))
        if stats_every is not None:
            opt.set_health_monitor(stats_every=stats_every, policy="warn")
        return opt

    steps_block, events = _mlp_leg(
        run_dir, f"health-k{stats_every}", make_opt, steps, batch, hidden,
        seed)
    return steps_block, [e["loss"] for e in events]


def run_health_bench(stats_every=None, steps=None, batch=None,
                     hidden=None, out_dir=None):
    """A/B the health-stats branch: stats_every=None vs stats_every=K.

    Knobs (env tier): BENCH_HEALTH_EVERY (default 10), BENCH_HEALTH_STEPS
    (default 40), BENCH_HEALTH_BATCH (default 32), BENCH_HEALTH_HIDDEN
    (default 1024 -- a LeNet-scale device step, so the cond branch cost
    is measured against realistic step time, not against noise).  Prints
    ONE JSON record; ``vs_baseline`` is the headroom under the 5%
    regression budget (>= 0 passes) and ``loss_stream_identical``
    asserts the off-path bit-identity witness.
    """
    cache_status = _enable_compile_cache()
    import tempfile

    env = os.environ
    stats_every = (int(env.get("BENCH_HEALTH_EVERY", "10"))
                   if stats_every is None else stats_every)
    steps = (int(env.get("BENCH_HEALTH_STEPS", "40"))
             if steps is None else steps)
    batch = (int(env.get("BENCH_HEALTH_BATCH", "32"))
             if batch is None else batch)
    hidden = (int(env.get("BENCH_HEALTH_HIDDEN", "1024"))
              if hidden is None else hidden)

    def _run(base):
        off, loss_off = _health_leg(os.path.join(base, "off"), None,
                                    steps, batch, hidden)
        # an unmonitored second run is the bit-identity witness for the
        # monitored-off path (same seed -> same loss stream)
        off2, loss_off2 = _health_leg(os.path.join(base, "off2"), None,
                                      steps, batch, hidden)
        on, loss_on = _health_leg(os.path.join(base, f"k{stats_every}"),
                                  stats_every, steps, batch, hidden)
        return off, loss_off, loss_off2, on, loss_on

    if out_dir is None:
        with tempfile.TemporaryDirectory() as td:
            off, loss_off, loss_off2, on, loss_on = _run(td)
    else:
        off, loss_off, loss_off2, on, loss_on = _run(out_dir)
    regression = on["wall_s_p50"] / max(off["wall_s_p50"], 1e-12) - 1.0
    record = {
        "metric": "health_stats_step_time_regression",
        "value": round(regression, 4),
        "unit": "fraction",
        # headroom under the 5% budget, normalized: 1.0 = zero overhead,
        # 0.0 = exactly at budget, negative = over budget
        "vs_baseline": round((0.05 - regression) / 0.05, 4),
        "extra": {
            "compilation_cache": cache_status,
            "stats_every": stats_every, "steps": steps, "batch": batch,
            "hidden": hidden,
            "wall_s_p50_off": off["wall_s_p50"],
            "wall_s_p50_on": on["wall_s_p50"],
            "loss_stream_identical": loss_off == loss_off2,
            # the monitored run's loss stream must MATCH the plain one:
            # the stats branch reads, never perturbs, the step math
            "monitored_loss_matches": loss_on == loss_off,
        },
    }
    emit_record(record)
    return record


# --------------------------------------------------------------------------- #
# Inference-serving micro-benchmark (ISSUE 5): a closed-loop load
# generator A/Bs the semaphore-serial PredictionService against the
# coalesced+bucketed ServingEngine at fixed offered load (C concurrent
# clients), reporting requests/sec and p99 latency plus the serving
# telemetry section from the engine leg's JSONL.
# --------------------------------------------------------------------------- #

def _serve_model(hidden):
    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu.utils.random_generator import RNG

    RNG.set_seed(0)
    m = (nn.Sequential().add(nn.Linear(16, hidden)).add(nn.ReLU())
         .add(nn.Linear(hidden, hidden)).add(nn.ReLU())
         .add(nn.Linear(hidden, 10)))
    m.build(jax.ShapeDtypeStruct((2, 16), jnp.float32))
    return m


def _closed_loop(predict, xs, concurrency, per_client):
    """C client threads, each issuing ``per_client`` sequential
    requests (closed loop: a client's next request waits for its
    previous response).  Returns ({(client, j): (sample_idx, out)},
    sorted latencies, wall seconds)."""
    import threading

    outs, errors = {}, []
    lats = [[] for _ in range(concurrency)]

    def worker(w):
        try:
            for j in range(per_client):
                i = (w * per_client + j) % len(xs)
                t0 = time.perf_counter()
                y = predict(xs[i])
                lats[w].append(time.perf_counter() - t0)
                outs[(w, j)] = (i, y)
        except Exception as e:           # pragma: no cover - surfaced below
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    return outs, sorted(lat for per in lats for lat in per), wall


def run_serve_bench(concurrency=None, per_client=None, hidden=None,
                    max_batch=None, max_wait_ms=None, out_dir=None):
    """A/B inference serving: semaphore-serial vs coalesced+bucketed.

    Knobs (env tier): BENCH_SERVE_CONC (default 8 concurrent clients),
    BENCH_SERVE_REQS (default 50 requests per client),
    BENCH_SERVE_HIDDEN (default 512), BENCH_SERVE_BATCH (default =
    concurrency, so a full coalescing tick matches the offered load),
    BENCH_SERVE_WAIT_MS (default 2).  Prints ONE JSON record whose
    ``value`` is the coalesced-over-serial requests/sec ratio
    (``vs_baseline`` = value / 2.0, the ISSUE-5 target at concurrency
    >= 8 on CPU).  ``extra.bit_exact`` witnesses the identical-outputs
    contract: a coalesced burst's per-sample logits equal the same
    requests served UNBATCHED at the same bucket, bit for bit (within
    one bucket shape XLA's reduction order is fixed and eval-mode rows
    are independent -- docs/performance.md, "Inference serving"), and
    ``extra.recompiles_after_precompile`` must be 0.
    """
    cache_status = _enable_compile_cache()
    import tempfile

    import numpy as np

    from bigdl_tpu import optim
    from bigdl_tpu.observability import StepTelemetry
    from bigdl_tpu.observability.watchdogs import backend_compile_count
    from bigdl_tpu.serving import ServingEngine

    env = os.environ
    concurrency = (int(env.get("BENCH_SERVE_CONC", "8"))
                   if concurrency is None else concurrency)
    per_client = (int(env.get("BENCH_SERVE_REQS", "50"))
                  if per_client is None else per_client)
    hidden = (int(env.get("BENCH_SERVE_HIDDEN", "512"))
              if hidden is None else hidden)
    max_batch = (int(env.get("BENCH_SERVE_BATCH", str(concurrency)))
                 if max_batch is None else max_batch)
    max_wait_ms = (float(env.get("BENCH_SERVE_WAIT_MS", "2"))
                   if max_wait_ms is None else max_wait_ms)

    model = _serve_model(hidden)
    rng = np.random.default_rng(0)
    xs = rng.standard_normal((256, 16)).astype("float32")
    total = concurrency * per_client

    # leg A: the semaphore-serial baseline (batch-1 eval per request)
    svc = optim.PredictionService(model, num_threads=concurrency)
    svc.predict(xs[0])                  # batch-1 warmup compile
    outs_a, lats_a, wall_a = _closed_loop(svc.predict, xs, concurrency,
                                          per_client)
    rps_a = total / wall_a

    def _engine_leg(run_dir):
        import threading
        import urllib.request

        from bigdl_tpu.observability.metrics import (MetricsExporter,
                                                     MetricsRegistry,
                                                     SloTracker)

        tel = StepTelemetry(run_dir, run_name="serve", trace=False)
        # live fleet telemetry (docs/observability.md, "Live metrics &
        # SLOs"): the same tick events feed a scrapeable registry, and
        # the record carries the mid-run scrape as evidence that a real
        # Prometheus poller would have seen the run live
        registry = MetricsRegistry()
        tel.attach_metrics(registry)
        tracker = SloTracker(registry=registry)
        tracker.add(name="p99_latency", kind="inference",
                    field="request_latency_s",
                    threshold=float(env.get("BENCH_SERVE_SLO_MS",
                                            "250")) / 1e3,
                    target=0.99, alerts=((5.0, 30.0, 14.4),),
                    min_samples=20)
        tracker.bind(tel)
        exporter = MetricsExporter(registry, port=0,
                                   health_sources=[tracker.health_status])

        def _get(path, parse=False):
            body = urllib.request.urlopen(exporter.url + path,
                                          timeout=10).read().decode()
            return json.loads(body) if parse else body

        scrape = {}

        def _scraper():          # polls WHILE the closed loop offers load
            time.sleep(0.2)
            try:
                text = _get("/metrics")
                scrape["serving_series"] = sum(
                    1 for ln in text.splitlines()
                    if ln.startswith("bigdl_serving_"))
                scrape["queue_depth_present"] = \
                    "bigdl_serving_queue_depth " in text
                scrape["latency_histogram_present"] = \
                    "bigdl_serving_request_latency_seconds_bucket" in text
                scrape["batch_fill_present"] = \
                    "bigdl_serving_batch_fill " in text
                scrape["healthz"] = _get("/healthz", parse=True)["status"]
            except Exception as e:   # recorded, not fatal: the scrape is
                scrape["error"] = str(e)[:200]   # evidence, not the bench
        eng = ServingEngine(model, max_batch_size=max_batch,
                            max_wait_ms=max_wait_ms, telemetry=tel)
        try:
            precompiles = eng.precompile()
            before = backend_compile_count()
            scraper = threading.Thread(target=_scraper, daemon=True)
            scraper.start()
            outs_b, lats_b, wall_b = _closed_loop(eng.predict, xs,
                                                  concurrency, per_client)
            scraper.join(15)
            recompiles = backend_compile_count() - before
            # identical-outputs witness: a coalesced burst, bit-compared
            # against each request served unbatched at the SAME bucket
            idxs = [i % len(xs) for i in range(max_batch)]
            futs = [eng.submit(xs[i]) for i in idxs]
            rows = [f.result(30) for f in futs]
            bit_exact = all(
                np.array_equal(rows[k], eng.predict_at(xs[i], f.bucket))
                for k, (i, f) in enumerate(zip(idxs, futs)))
            # SLO-breach drill (the ISSUE-9 acceptance): an objective no
            # real request can meet burns its budget within one tick and
            # /healthz flips to degraded, with the durable kind:"slo"
            # breach event in this leg's telemetry.jsonl
            healthz_before = _get("/healthz", parse=True)["status"]
            tracker.add(name="injected_breach", kind="inference",
                        field="request_latency_s", threshold=0.0,
                        target=0.999, alerts=((5.0, 10.0, 1.0),),
                        min_samples=1)
            for i in range(4):
                eng.predict(xs[i % len(xs)])
            healthz_after = _get("/healthz", parse=True)["status"]
            slo_drill = {"healthz_before": healthz_before,
                         "healthz_after": healthz_after}
        finally:
            eng.close()
            exporter.close()
            tel.close()
        report = _obs_report_module().build_report(run_dir)
        slo_drill["slo_events"] = (report.get("slo") or {}).get("events", 0)
        return outs_b, lats_b, wall_b, precompiles, recompiles, bit_exact, \
            report.get("serving"), scrape, slo_drill

    import contextlib

    run_dir = tempfile.TemporaryDirectory() if out_dir is None \
        else contextlib.nullcontext(out_dir)
    with run_dir as d:
        (outs_b, lats_b, wall_b, precompiles, recompiles, bit_exact,
         serving, live_scrape, slo_drill) = _engine_leg(d)
    rps_b = total / wall_b
    # cross-leg outputs agree to float rounding (different bucket shapes
    # pick different XLA reduction blockings; bit-exactness is the
    # within-bucket witness above)
    outputs_close = all(
        np.allclose(outs_b[k][1], outs_a[k][1], rtol=1e-5, atol=1e-6)
        for k in outs_a)

    # one nearest-rank percentile definition: the record's p50/p99 must
    # agree with the serving_report's, computed by the same function
    _p = _obs_report_module().percentile

    speedup = rps_b / max(rps_a, 1e-9)
    record = {
        "metric": "serving_coalesced_rps_speedup",
        "value": round(speedup, 2),
        "unit": "x",
        "vs_baseline": round(speedup / 2.0, 4),    # target: >= 2x
        "extra": {
            "compilation_cache": cache_status,
            "concurrency": concurrency, "requests": total,
            "hidden": hidden, "max_batch_size": max_batch,
            "max_wait_ms": max_wait_ms,
            "serial": {"requests_per_s": round(rps_a, 1),
                       "p50_ms": round(_p(lats_a, 50) * 1e3, 3),
                       "p99_ms": round(_p(lats_a, 99) * 1e3, 3)},
            "coalesced": {"requests_per_s": round(rps_b, 1),
                          "p50_ms": round(_p(lats_b, 50) * 1e3, 3),
                          "p99_ms": round(_p(lats_b, 99) * 1e3, 3)},
            "precompiles": precompiles,
            "recompiles_after_precompile": recompiles,
            "bit_exact": bool(bit_exact),
            "outputs_close": bool(outputs_close),
            "serving_report": serving,
            "live_scrape": live_scrape,
            "slo_drill": slo_drill,
        },
    }
    emit_record(record)
    return record


def run_serve_quant_bench(concurrency=None, per_client=None, hidden=None,
                          max_batch=None, max_wait_ms=None, out_dir=None):
    """A/B inference serving precision: fp32 vs int8 ``ServingEngine``
    (ISSUE 11; docs/performance.md, "Int8 inference").

    Both legs run the SAME coalescing engine, ladder and precompile
    discipline at the same closed-loop offered load; only the serving
    precision differs (``quantize=True`` + the accuracy-delta gate on
    the int8 leg).  Knobs (env tier): the ``BENCH_SERVE_*`` family of
    ``run_serve_bench`` plus ``BENCH_SERVE_INT8_AGREE`` (held-out-batch
    top-1 agreement the gate requires, default 0.98).

    Prints TWO JSON records:

    - ``serving_int8_rps_ratio`` -- int8-over-fp32 requests/sec at the
      same offered load.  No floor is promised on CPU (the int8 win is
      MXU/memory-bandwidth bound; the whitepaper's up-to-2x is a TPU
      number), so ``vs_baseline`` is the raw ratio: the perf gate
      tracks it as a host-side A/B ``ratio`` metric and trips on a
      regression against the checked-in history.
    - ``serving_int8_model_bytes_ratio`` -- fp32-over-int8 serving-tree
      bytes; ``vs_baseline`` is over the 3.5x acceptance floor (the
      whitepaper's ~4x claim minus the fp32 biases/scales the scheme
      deliberately keeps).

    Both legs must report ``recompiles_after_precompile == 0`` and the
    int8 leg's ``accuracy_gate.ok`` must be true for the record to mean
    anything; the tier-1 smoke pins both.
    """
    cache_status = _enable_compile_cache()
    import contextlib
    import tempfile

    import numpy as np

    from bigdl_tpu.observability import StepTelemetry
    from bigdl_tpu.observability.watchdogs import backend_compile_count
    from bigdl_tpu.serving import ServingEngine

    env = os.environ
    concurrency = (int(env.get("BENCH_SERVE_CONC", "8"))
                   if concurrency is None else concurrency)
    per_client = (int(env.get("BENCH_SERVE_REQS", "50"))
                  if per_client is None else per_client)
    hidden = (int(env.get("BENCH_SERVE_HIDDEN", "512"))
              if hidden is None else hidden)
    max_batch = (int(env.get("BENCH_SERVE_BATCH", str(concurrency)))
                 if max_batch is None else max_batch)
    max_wait_ms = (float(env.get("BENCH_SERVE_WAIT_MS", "2"))
                   if max_wait_ms is None else max_wait_ms)
    min_agree = float(env.get("BENCH_SERVE_INT8_AGREE", "0.98"))

    model = _serve_model(hidden)
    rng = np.random.default_rng(0)
    xs = rng.standard_normal((256, 16)).astype("float32")
    total = concurrency * per_client
    _p = _obs_report_module().percentile

    def _leg(run_dir, quantize):
        tel = StepTelemetry(run_dir, run_name="serve", trace=False)
        kw = {}
        if quantize:
            kw = {"quantize": True,
                  "accuracy_gate": {"features": xs[:64],
                                    "min_top1_agreement": min_agree}}
        eng = ServingEngine(model, max_batch_size=max_batch,
                            max_wait_ms=max_wait_ms, telemetry=tel, **kw)
        try:
            precompiles = eng.precompile()
            before = backend_compile_count()
            outs, lats, wall = _closed_loop(eng.predict, xs, concurrency,
                                            per_client)
            recompiles = backend_compile_count() - before
            bytes_ = eng.serving_model_bytes()
            gate = eng._gate_detail
        finally:
            eng.close()
            tel.close()
        report = _obs_report_module().build_report(run_dir)
        serving = {k: v for k, v in (report.get("serving") or {}).items()
                   if k in ("ticks", "requests", "requests_per_s",
                            "latency_s_p50", "latency_s_p99",
                            "pad_waste_fraction", "batch_fill_p50",
                            "quantized", "weight_dtype", "model_bytes")}
        return {"requests_per_s": round(total / wall, 1),
                "p50_ms": round(_p(lats, 50) * 1e3, 3),
                "p99_ms": round(_p(lats, 99) * 1e3, 3),
                "model_bytes": bytes_,
                "precompiles": precompiles,
                "recompiles_after_precompile": recompiles,
                "serving_report": serving,
                "accuracy_gate": gate}, outs

    run_dir = tempfile.TemporaryDirectory() if out_dir is None \
        else contextlib.nullcontext(out_dir)
    with run_dir as d:
        os.makedirs(os.path.join(d, "fp32"), exist_ok=True)
        os.makedirs(os.path.join(d, "int8"), exist_ok=True)
        leg_fp, outs_fp = _leg(os.path.join(d, "fp32"), quantize=False)
        leg_q, outs_q = _leg(os.path.join(d, "int8"), quantize=True)
    # cross-precision witness: int8 logits track fp32 within the quant
    # error (the gate's agreement number is the formal check)
    max_rel = max(
        float(np.abs(outs_q[k][1] - outs_fp[k][1]).max())
        for k in outs_fp) / max(
        float(np.abs(outs_fp[k][1]).max()) for k in outs_fp)
    ratio = leg_q["requests_per_s"] / max(leg_fp["requests_per_s"], 1e-9)
    shared_extra = {
        "compilation_cache": cache_status,
        "concurrency": concurrency, "requests": total, "hidden": hidden,
        "max_batch_size": max_batch, "max_wait_ms": max_wait_ms,
    }
    rec_rps = {
        "metric": "serving_int8_rps_ratio",
        "value": round(ratio, 3),
        "unit": "x",
        "vs_baseline": round(ratio, 4),   # no promised floor off-TPU
        "extra": {**shared_extra,
                  "fp32": leg_fp, "int8": leg_q,
                  "logit_max_rel_delta": round(max_rel, 5)},
    }
    emit_record(rec_rps)
    bytes_ratio = leg_fp["model_bytes"] / max(leg_q["model_bytes"], 1)
    rec_bytes = {
        "metric": "serving_int8_model_bytes_ratio",
        "value": round(bytes_ratio, 3),
        "unit": "x",
        "vs_baseline": round(bytes_ratio / 3.5, 4),   # >= 3.5x floor
        "extra": {**shared_extra,
                  "model_bytes_fp32": leg_fp["model_bytes"],
                  "model_bytes_int8": leg_q["model_bytes"],
                  "accuracy_gate": leg_q["accuracy_gate"]},
    }
    emit_record(rec_bytes)
    return rec_rps, rec_bytes


# --------------------------------------------------------------------------- #
# Fleet-wire A/B (ISSUE 20): pickle connection-per-request vs the binary
# frame protocol with persistent pooled connections, plus fp32-vs-int8
# weight-distribution bytes through the real stage_tree wire.
# --------------------------------------------------------------------------- #

def run_wire_bench(concurrency=None, per_client=None, hidden=None,
                   max_batch=None, max_wait_ms=None, pool_size=None):
    """A/B the fleet transport: legacy pickle wire (connection per
    request) vs the binary frame protocol (persistent ``WirePool``,
    request-id multiplexing, zero-copy tensor frames) against the SAME
    ``ServingEngine`` on loopback (ISSUE 20; docs/performance.md,
    "Fleet transport").

    Knobs (env tier): BENCH_WIRE_CONC (default 10 closed-loop clients),
    BENCH_WIRE_REQS (default 40 requests per client), BENCH_WIRE_HIDDEN
    (default 256), BENCH_WIRE_BATCH (default = conc), BENCH_WIRE_WAIT_MS
    (default 1), BENCH_WIRE_POOL (default 2 pooled connections).

    The default load (10 clients) is deliberately past the pickle
    transport's knee: dialling per request against the legacy server's
    default listen backlog (socketserver's 5) overflows the accept
    queue, and dropped SYNs stall clients on kernel retransmit timers.
    The pooled binary leg holds its connections open, so the same load
    never touches the backlog -- that collapse, not codec speed, is
    the production failure mode this transport removes (at <= 6
    clients, where pickle's backlog survives, the two wires are within
    noise of each other and the ratio is ~1x).

    Prints TWO JSON records:

    - ``fleet_wire_rps_ratio`` -- binary-over-pickle requests/sec at
      the same offered load; ``vs_baseline`` is over the 1.3x loopback
      acceptance floor.  Valid only when ``recompiles_after_precompile
      == 0`` (both legs hit the same warmed executables),
      ``pickle_fallbacks == 0`` (no array transited pickle on the
      binary leg) and ``outputs_bit_identical`` is true (the transport
      is a re-encoding, not an approximation) -- the tier-1 smoke pins
      all three.
    - ``fleet_wire_bytes_ratio`` -- fp32-over-int8 staged-weight bytes
      MEASURED on the wire (two real ``stage_tree`` round trips of the
      serving tree, one raw fp32, one through
      ``transport.quantize_tree_for_wire``); ``vs_baseline`` is over
      the 1/0.35 floor (int8 staging must cost <= 0.35x the fp32
      bytes).  ``extra.int8_max_abs_err`` witnesses the dequantized
      tree tracks fp32 within blockwise-int8 error.
    """
    cache_status = _enable_compile_cache()
    import tempfile

    import jax
    import numpy as np

    from bigdl_tpu.observability import StepTelemetry
    from bigdl_tpu.observability.watchdogs import backend_compile_count
    from bigdl_tpu.serving import ServingEngine, WireClient, WirePool
    from bigdl_tpu.serving import worker as worker_mod
    from bigdl_tpu.serving.transport import quantize_tree_for_wire
    from bigdl_tpu.serving.worker import ReplicaServer

    env = os.environ
    concurrency = (int(env.get("BENCH_WIRE_CONC", "10"))
                   if concurrency is None else concurrency)
    per_client = (int(env.get("BENCH_WIRE_REQS", "40"))
                  if per_client is None else per_client)
    hidden = (int(env.get("BENCH_WIRE_HIDDEN", "256"))
              if hidden is None else hidden)
    max_batch = (int(env.get("BENCH_WIRE_BATCH", str(concurrency)))
                 if max_batch is None else max_batch)
    max_wait_ms = (float(env.get("BENCH_WIRE_WAIT_MS", "1"))
                   if max_wait_ms is None else max_wait_ms)
    pool_size = (int(env.get("BENCH_WIRE_POOL", "2"))
                 if pool_size is None else pool_size)

    model = _serve_model(hidden)
    rng = np.random.default_rng(0)
    xs = rng.standard_normal((256, 16)).astype("float32")
    total = concurrency * per_client
    _p = _obs_report_module().percentile

    with tempfile.TemporaryDirectory() as d:
        tel = StepTelemetry(d, run_name="wire", trace=False)
        eng = ServingEngine(model, max_batch_size=max_batch,
                            max_wait_ms=max_wait_ms, telemetry=tel)
        try:
            eng.precompile()
            before = backend_compile_count()

            # ---- leg A: the PR 14 pickle wire, connection per request
            srv_p = ReplicaServer(eng, port=0, transport="pickle").start()
            try:
                def call_pickle(feature):
                    return worker_mod.call("127.0.0.1", srv_p.port,
                                           "predict", transport="pickle",
                                           feature=feature)
                outs_p, lats_p, wall_p = _closed_loop(
                    call_pickle, xs, concurrency, per_client)
                probe_p = [call_pickle(x) for x in xs[:8]]
            finally:
                srv_p.close()

            # ---- leg B: binary frames over a shared persistent pool
            srv_b = ReplicaServer(eng, port=0, transport="binary").start()
            pool = WirePool("127.0.0.1", srv_b.port, size=pool_size)
            try:
                def call_binary(feature):
                    return pool.request("predict", feature=feature)
                outs_b, lats_b, wall_b = _closed_loop(
                    call_binary, xs, concurrency, per_client)
                probe_b = [call_binary(x) for x in xs[:8]]
                pstats = pool.stats()
                bin_sent = pstats["bytes_sent"]
                bin_recv = pstats["bytes_recv"]
                fallbacks = pstats["pickle_fallbacks"]
            finally:
                pool.close()
                srv_b.close()
            recompiles = backend_compile_count() - before

            # what the engine itself answers for each feature the closed
            # loops sent, at every rung of its batch ladder: a tick's
            # bucket depends on timing, and the bits depend on the bucket
            used = sorted({i for i, _y in outs_p.values()})
            refs = {i: [jax.tree_util.tree_leaves(eng.predict_at(xs[i], b))
                        for b in eng.ladder] for i in used}

            # ---- weight-distribution leg: fp32 vs blockwise-int8
            # stage_tree bytes, measured on the real wire
            params = eng.model.parameters()[0]
            srv_w = ReplicaServer(eng, port=0, transport="binary").start()
            cli = WireClient("127.0.0.1", srv_w.port)
            try:
                tok_fp, fp32_out, _ = cli.request_ex(
                    "stage_tree", rpc_timeout=120.0, params=params,
                    weight_wire="fp32")
                cli.request_ex("release", token=tok_fp)
                qtree = quantize_tree_for_wire(params)
                tok_q, int8_out, _ = cli.request_ex(
                    "stage_tree", rpc_timeout=120.0, params=qtree,
                    weight_wire="int8")
                cli.request_ex("release", token=tok_q)
            finally:
                cli.close()
                srv_w.close()
        finally:
            eng.close()
            tel.close()

    from bigdl_tpu.serving.transport import dequantize_wire_tree

    deq = dequantize_wire_tree(qtree)
    int8_err = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
                   for a, b in zip(jax.tree_util.tree_leaves(params),
                                   jax.tree_util.tree_leaves(deq)))
    # the transport's witness, two parts.  Serial: the same features sent
    # ONE AT A TIME over each wire ride one-request ticks of one bucket,
    # so the two wires must agree bit for bit.  Under concurrency: the
    # closed loops coalesce by timing and the same row is not bit-equal
    # across batch buckets on every XLA CPU backend, so each wire's
    # output is held to what the engine answers in process at SOME rung
    # of its ladder -- still bit for bit, so a framing fault that only
    # shows when connections are multiplexed cannot hide in a tolerance
    def same(ya, yb):
        la, lb = (jax.tree_util.tree_leaves(y) for y in (ya, yb))
        return len(la) == len(lb) and all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(la, lb))

    bit_identical = (
        set(outs_p) == set(outs_b)
        and all(same(yp, yb) for yp, yb in zip(probe_p, probe_b))
        and all(any(same(y, ref) for ref in refs[i])
                for outs in (outs_p, outs_b) for i, y in outs.values()))

    rps_p = total / wall_p
    rps_b = total / wall_b
    ratio = rps_b / max(rps_p, 1e-9)
    shared_extra = {
        "compilation_cache": cache_status,
        "concurrency": concurrency, "requests": total, "hidden": hidden,
        "max_batch_size": max_batch, "max_wait_ms": max_wait_ms,
        "pool_size": pool_size,
        "recompiles_after_precompile": recompiles,
    }
    rec_rps = {
        "metric": "fleet_wire_rps_ratio",
        "value": round(ratio, 3),
        "unit": "x",
        "vs_baseline": round(ratio / 1.3, 4),   # >= 1.3x loopback floor
        "extra": {
            **shared_extra,
            "pickle": {"requests_per_s": round(rps_p, 1),
                       "p50_ms": round(_p(lats_p, 50) * 1e3, 3),
                       "p99_ms": round(_p(lats_p, 99) * 1e3, 3)},
            "binary": {"requests_per_s": round(rps_b, 1),
                       "p50_ms": round(_p(lats_b, 50) * 1e3, 3),
                       "p99_ms": round(_p(lats_b, 99) * 1e3, 3),
                       "bytes_sent": bin_sent, "bytes_recv": bin_recv},
            "pickle_fallbacks": fallbacks,
            "outputs_bit_identical": bool(bit_identical),
            "pickle_bound_by": ("listen-backlog SYN retransmit under "
                                "connect-per-request churn"
                                if concurrency >= 8 else "codec + rtt"),
        },
    }
    emit_record(rec_rps)
    bytes_ratio = fp32_out / max(int8_out, 1)
    rec_bytes = {
        "metric": "fleet_wire_bytes_ratio",
        "value": round(bytes_ratio, 3),
        "unit": "x",
        "vs_baseline": round(bytes_ratio * 0.35, 4),   # <= 0.35x floor
        "extra": {
            **shared_extra,
            "stage_bytes_fp32": fp32_out,
            "stage_bytes_int8": int8_out,
            "int8_max_abs_err": round(int8_err, 6),
        },
    }
    emit_record(rec_bytes)
    return rec_rps, rec_bytes


# --------------------------------------------------------------------------- #
# Autoregressive-decode micro-benchmark (ISSUE 15): KV-cache decode vs
# full-recompute generation on one transformer, host-side blocked
# timing, plus a continuous-batching leg through ServingEngine.generate.
# --------------------------------------------------------------------------- #

def run_decode_bench(prompt_len=None, new_tokens=None, out_dir=None):
    """A/B autoregressive generation: KV-cache decode vs full recompute.

    Both legs produce ``new_tokens`` greedy tokens from the same
    ``prompt_len``-token prompt on the same weights.  The UNCACHED leg
    is the honest naive spelling: ONE compiled full causal forward at
    the fixed padded total length, re-run over the whole prefix for
    every token (per-token O(L) recompute; keeping the shape fixed
    means it never pays per-length compiles, which would flatter the
    cached side).  The CACHED leg is the serving path's compiled
    prefill + single-token decode steps (``serving/generation
    .generate_steps``: donated fixed-shape KV cache, O(1) work per
    token).  Ratio = cached-over-uncached tokens/sec -- a host-side
    blocked-timing A/B in the bench's ratio stance (no device claim),
    target >= 3x at 512/128 (ISSUE 15).

    Knobs (env tier): BENCH_DECODE_PROMPT (default 512),
    BENCH_DECODE_NEW (128), BENCH_DECODE_HIDDEN (256),
    BENCH_DECODE_LAYERS (4), BENCH_DECODE_VOCAB (512),
    BENCH_DECODE_CONC (4 concurrent streams for the continuous-batching
    extra).  ``extra.greedy_tokens_match`` witnesses that the two legs
    emit the SAME token stream (the caching is a restructuring, not an
    approximation), and ``extra.cached.recompiles_after_warm`` /
    ``extra.continuous_batching.recompiles_after_precompile`` must be 0.
    """
    cache_status = _enable_compile_cache()
    import tempfile

    import numpy as np

    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models.transformer import synthetic_corpus
    from bigdl_tpu.nn.attention import TransformerLM
    from bigdl_tpu.observability import StepTelemetry
    from bigdl_tpu.observability.watchdogs import backend_compile_count
    from bigdl_tpu.serving import BucketLadder, ServingEngine
    from bigdl_tpu.serving.generation import generate_steps

    env = os.environ
    prompt_len = (int(env.get("BENCH_DECODE_PROMPT", "512"))
                  if prompt_len is None else prompt_len)
    new_tokens = (int(env.get("BENCH_DECODE_NEW", "128"))
                  if new_tokens is None else new_tokens)
    hidden = int(env.get("BENCH_DECODE_HIDDEN", "256"))
    layers = int(env.get("BENCH_DECODE_LAYERS", "4"))
    vocab = int(env.get("BENCH_DECODE_VOCAB", "512"))
    conc = int(env.get("BENCH_DECODE_CONC", "4"))
    total_len = prompt_len + new_tokens

    model = TransformerLM(vocab, hidden, 4, layers, max_len=total_len)
    model.build(jax.ShapeDtypeStruct((1, prompt_len), jnp.int32))
    params = model.parameters()[0]
    prompts, _ = synthetic_corpus(max(conc, 1), prompt_len, vocab, seed=0)
    prompt = prompts[0].astype(np.int32)
    _p = _obs_report_module().percentile

    # ----- leg A: full recompute (fixed shape, one executable) -------- #
    step_full = jax.jit(lambda p, toks, pos: jnp.argmax(
        model.apply(p, (), toks)[0][0, pos]).astype(jnp.int32))
    buf = np.zeros((1, total_len), np.int32)
    buf[0, :prompt_len] = prompt
    jax.block_until_ready(step_full(params, jnp.asarray(buf),
                                    prompt_len - 1))        # warm
    toks_a, inter_a = [], []
    cur = prompt_len
    t0 = time.perf_counter()
    for _ in range(new_tokens):
        ts = time.perf_counter()
        nxt = int(step_full(params, jnp.asarray(buf), cur - 1))
        buf[0, cur] = nxt
        toks_a.append(nxt)
        cur += 1
        inter_a.append(time.perf_counter() - ts)
    wall_a = time.perf_counter() - t0
    tps_a = new_tokens / wall_a

    # ----- leg B: compiled prefill + KV-cache decode ------------------ #
    prefill, decode = generate_steps(model)
    cache = model.init_cache(1, total_len)
    # warm both executables on a throwaway cache (both steps DONATE
    # their cache argument; the live one must survive warmup)
    dummy = jax.tree.map(jnp.zeros_like, cache)
    first, dummy = prefill(params, dummy,
                           np.zeros((1, prompt_len), np.int32),
                           np.ones((1,), np.int32),
                           np.zeros((1,), np.int32))
    jax.block_until_ready(first)
    nxt, dummy = decode(params, dummy, np.zeros((1,), np.int32),
                        np.zeros((1,), np.int32))
    jax.block_until_ready(nxt)
    del dummy
    before = backend_compile_count()
    toks_b, inter_b = [], []
    t0 = time.perf_counter()
    ts = t0
    first, cache = prefill(params, cache, prompt[None],
                           np.array([prompt_len], np.int32),
                           np.zeros((1,), np.int32))
    tok = int(np.asarray(first)[0])
    toks_b.append(tok)
    prefill_s = time.perf_counter() - ts
    inter_b.append(prefill_s)
    pos = prompt_len
    for _ in range(new_tokens - 1):
        ts = time.perf_counter()
        nxt, cache = decode(params, cache, np.array([tok], np.int32),
                            np.array([pos], np.int32))
        tok = int(np.asarray(nxt)[0])
        toks_b.append(tok)
        pos += 1
        inter_b.append(time.perf_counter() - ts)
    wall_b = time.perf_counter() - t0
    tps_b = new_tokens / wall_b
    recompiles_raw = backend_compile_count() - before
    agreement = sum(a == b for a, b in zip(toks_a, toks_b)) / new_tokens

    # ----- extra: continuous batching through the ServingEngine ------- #
    def _engine_leg(run_dir):
        tel = StepTelemetry(run_dir, run_name="decode", trace=False)
        eng = ServingEngine(
            model, decode_slots=conc, decode_max_len=total_len,
            prompt_ladder=BucketLadder(prompt_len, min_size=prompt_len),
            telemetry=tel)
        try:
            precompiles = eng.precompile(
                example_feature=np.zeros((prompt_len,), np.int32))
            before = backend_compile_count()
            t0 = time.perf_counter()
            futs = [eng.generate(prompts[i % len(prompts)],
                                 max_new_tokens=new_tokens)
                    for i in range(conc)]
            streams = [f.result(600) for f in futs]
            wall = time.perf_counter() - t0
            recompiles = backend_compile_count() - before
        finally:
            eng.close()
            tel.close()
        report = _obs_report_module().build_report(run_dir)
        return {"streams": len(streams),
                "tokens_per_s": round(conc * new_tokens / wall, 1),
                "precompiles": precompiles,
                "recompiles_after_precompile": recompiles,
                "serving_report": (report.get("serving") or {})
                .get("generate")}

    import contextlib

    run_dir = tempfile.TemporaryDirectory() if out_dir is None \
        else contextlib.nullcontext(out_dir)
    with run_dir as d:
        batching = _engine_leg(d)

    speedup = tps_b / max(tps_a, 1e-9)
    record = {
        "metric": "serving_decode_tokens_ratio",
        "value": round(speedup, 2),
        "unit": "x",
        "vs_baseline": round(speedup / 3.0, 4),    # ISSUE-15 target: 3x
        "extra": {
            "compilation_cache": cache_status,
            "prompt_len": prompt_len, "new_tokens": new_tokens,
            "hidden": hidden, "layers": layers, "vocab": vocab,
            "uncached": {
                "tokens_per_s": round(tps_a, 2),
                "inter_token_p50_ms": round(_p(sorted(inter_a), 50) * 1e3,
                                            3),
                "inter_token_p99_ms": round(_p(sorted(inter_a), 99) * 1e3,
                                            3)},
            "cached": {
                "tokens_per_s": round(tps_b, 2),
                "prefill_ms": round(prefill_s * 1e3, 3),
                # at new_tokens=1 there are no pure decode steps; the
                # prefill latency is then the only inter-token sample
                "inter_token_p50_ms": round(
                    _p(sorted(inter_b[1:] or inter_b), 50) * 1e3, 3),
                "inter_token_p99_ms": round(
                    _p(sorted(inter_b[1:] or inter_b), 99) * 1e3, 3),
                "recompiles_after_warm": recompiles_raw},
            "token_agreement": round(agreement, 4),
            "greedy_tokens_match": agreement == 1.0,
            "continuous_batching": batching,
        },
    }
    emit_record(record)
    return record


def run_paged_kv_bench(out_dir=None):
    """A/B the generation-cache LAYOUTS (ISSUE 17): the paged block
    pool vs the PR 15 contiguous ``slots x max_len`` pool, serving the
    SAME mixed-length workload at the same concurrency.

    Two records, both host-side ratios (no device/timing claim -- the
    byte and token counts are exact on any platform):

    - ``serving_paged_kv_bytes_ratio``: contiguous-over-paged device
      cache bytes.  The contiguous pool must size every slot for the
      worst-case admissible sequence; the paged pool holds only the
      blocks the workload's own reservations need, so the ratio is the
      memory the block indirection gives back (target >= 2x).  The
      extra witnesses the trade is free: ``greedy_tokens_match`` (both
      layouts emit identical streams), ``tokens_per_s_ratio`` (paged
      within ~10% of contiguous) and 0 recompiles after precompile on
      BOTH legs -- including a SAMPLED stretch on the paged leg
      (temperature/top-k riding runtime arrays, not shapes).
    - ``serving_prefix_prefill_saved``: N streams share a system
      prompt; the fraction of all prompt positions whose prefill
      compute the prefix cache absorbed (hit tokens / prompt tokens).

    Knobs: BENCH_PAGED_HIDDEN (128), BENCH_PAGED_LAYERS (2),
    BENCH_PAGED_VOCAB (256), BENCH_PAGED_MAXLEN (1024, the worst-case
    length both layouts must admit), BENCH_PAGED_NEW (64),
    BENCH_PAGED_BLOCK (16).
    """
    _enable_compile_cache()
    import numpy as np

    import jax
    import jax.numpy as jnp

    from bigdl_tpu.nn.attention import TransformerLM
    from bigdl_tpu.observability.watchdogs import backend_compile_count
    from bigdl_tpu.serving import BucketLadder, ServingEngine

    env = os.environ
    hidden = int(env.get("BENCH_PAGED_HIDDEN", "128"))
    layers = int(env.get("BENCH_PAGED_LAYERS", "2"))
    vocab = int(env.get("BENCH_PAGED_VOCAB", "256"))
    max_len = int(env.get("BENCH_PAGED_MAXLEN", "1024"))
    new_tokens = int(env.get("BENCH_PAGED_NEW", "64"))
    block = int(env.get("BENCH_PAGED_BLOCK", "16"))
    # the mixed-length workload: four concurrent streams, none close to
    # max_len -- the realistic shape the contiguous pool overpays for
    plens = (64, 96, 160, 256)
    conc = len(plens)

    model = TransformerLM(vocab, hidden, 4, layers, max_len=max_len)
    model.build(jax.ShapeDtypeStruct((1, 64), jnp.int32),
                rng=jax.random.PRNGKey(0))
    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, vocab, size=n).astype(np.int32)
               for n in plens]
    ladder = BucketLadder(max(plens), min_size=min(plens))
    # the paged pool reserves each admission's OWN worst case
    # (prompt + max_new), so size it for the workload, not max_len
    kv_blocks = conc * (-(-(max(plens) + new_tokens) // block))

    def _leg(kv_cache):
        eng = ServingEngine(model, decode_slots=conc,
                            decode_max_len=max_len, prompt_ladder=ladder,
                            kv_cache=kv_cache, kv_block_size=block,
                            kv_blocks=kv_blocks)
        try:
            sched = eng._generation()
            precompiles = sched.precompile()
            before = backend_compile_count()
            t0 = time.perf_counter()
            futs = [eng.generate(p, max_new_tokens=new_tokens)
                    for p in prompts]
            streams = [f.result(600) for f in futs]
            wall = time.perf_counter() - t0
            leg = {"cache_bytes": sched.cache_bytes(),
                   "tokens_per_s": round(conc * new_tokens / wall, 1),
                   "precompiles": precompiles,
                   "recompiles_after_precompile":
                       backend_compile_count() - before}
            if kv_cache == "paged":
                # sampled stretch: knobs are runtime arrays, so the
                # same executables serve it -- recompiles must stay 0
                sfuts = [eng.generate(prompts[i], max_new_tokens=8,
                                      temperature=0.8, top_k=20, seed=i)
                         for i in range(2)]
                [f.result(600) for f in sfuts]
                leg["recompiles_after_sampled"] = \
                    backend_compile_count() - before
                leg["kv"] = sched.stats()["kv"]
        finally:
            eng.close()
        return leg, streams

    contiguous, streams_c = _leg("contiguous")
    paged, streams_p = _leg("paged")
    ratio = contiguous["cache_bytes"] / max(paged["cache_bytes"], 1)
    emit_record({
        "metric": "serving_paged_kv_bytes_ratio",
        "value": round(ratio, 2),
        "unit": "x",
        "vs_baseline": round(ratio / 2.0, 4),       # ISSUE-17 floor: 2x
        "extra": {
            "hidden": hidden, "layers": layers, "vocab": vocab,
            "max_len": max_len, "new_tokens": new_tokens,
            "block_size": block, "kv_blocks": kv_blocks,
            "prompt_lens": list(plens),
            "contiguous": contiguous, "paged": paged,
            "tokens_per_s_ratio": round(
                paged["tokens_per_s"]
                / max(contiguous["tokens_per_s"], 1e-9), 3),
            "greedy_tokens_match": streams_p == streams_c,
        },
    })

    # ----- leg (b): shared-prefix prefill compute saved ---------------- #
    shared = rng.integers(0, vocab, size=192).astype(np.int32)
    n_streams = 6
    sprompts = [np.concatenate([
        shared, rng.integers(0, vocab, size=16).astype(np.int32)])
        for _ in range(n_streams)]
    eng = ServingEngine(model, decode_slots=conc, decode_max_len=max_len,
                        prompt_ladder=ladder, kv_block_size=block,
                        kv_blocks=kv_blocks)
    try:
        sched = eng._generation()
        sched.precompile()
        # the first stream WRITES the shared blocks (prefix matching
        # happens at admission, against already-committed blocks)...
        first = eng.generate(sprompts[0], max_new_tokens=8)
        first.result(600)
        # ...and the followers, admitted after, map them refcounted
        futs = [eng.generate(p, max_new_tokens=8) for p in sprompts[1:]]
        [f.result(600) for f in futs]
        hit_tokens = first.prefix_hit_tokens \
            + sum(f.prefix_hit_tokens for f in futs)
        prompt_tokens = sum(int(p.size) for p in sprompts)
        kv_stats = sched.stats()["kv"]
    finally:
        eng.close()
    saved = hit_tokens / prompt_tokens
    emit_record({
        "metric": "serving_prefix_prefill_saved",
        "value": round(saved, 4),
        "unit": "frac",
        "vs_baseline": round(saved / 0.5, 4),   # floor: half the prompt
        #                                         compute cache-absorbed
        "extra": {
            "streams": n_streams, "shared_prefix_len": int(shared.size),
            "prompt_len": int(sprompts[0].size),
            "block_size": block,
            "prefix_hit_tokens": hit_tokens,
            "prompt_tokens": prompt_tokens,
            "prefix_hits": kv_stats["prefix_hits"],
            "cow_copies": kv_stats["cow_copies"],
        },
    })


def run_spec_bench(out_dir=None):
    """Int8 KV blocks + speculative decoding A/B (ISSUE 19): three
    paged-serving legs over the same mixed-length greedy workload --
    fp32 KV (baseline), int8 KV, and speculative decoding with the
    int8 twin drafting ``k`` tokens per fp32 verify.

    Three records, all host-side ratios / exact byte counts (no device
    timing claim -- reproducible on any platform):

    - ``serving_int8_kv_bytes_ratio``: fp32-over-int8 KV pool device
      bytes, cited from the engine's MemoryLedger ``kv_cache`` source
      (the allocator-reported NARROW bytes: int8 payloads + fp32
      per-(position, head) scales).  At head_dim 32 the layout math
      says 128 B/vector vs 36 B, so the floor is 3x.
    - ``serving_int8_kv_peak_bytes``: the int8 leg's KV pool footprint
      itself, lower-is-better (``metric_direction`` classes
      ``*_kv_peak_bytes`` as a memory metric) -- memory creep in the
      quantized layout trips the gate even if the ratio still clears.
    - ``serving_spec_tokens_ratio``: accepted tokens emitted per
      verifier forward (= 1 + k * acceptance_rate).  Each verify is
      ONE fp32 forward, shape-identical to a plain decode step, so
      this is the platform-independent bound on the speculative
      speedup; wall tokens/s for both legs ride in ``extra`` with the
      honest CPU caveat (the drafter's k+1 small forwards are not free
      on CPU, so the wall ratio there understates a device run).

    Witnesses in the extras: the speculative leg's greedy stream is
    BIT-IDENTICAL to the baseline's (``greedy_tokens_match``), the
    int8 leg's tokens/s rides along (on CPU the in-kernel dequant
    costs ~20-25%; on TPU paged decode is memory-bound and the 3.6x
    narrower reads win it back), and recompiles stay 0 after
    precompile on every leg -- including a SAMPLED stretch on the
    speculative leg (temperature/top-k/seed ride runtime arrays).

    Knobs: BENCH_SPEC_HIDDEN (128), BENCH_SPEC_LAYERS (2),
    BENCH_SPEC_VOCAB (256), BENCH_SPEC_MAXLEN (512), BENCH_SPEC_NEW
    (32), BENCH_SPEC_BLOCK (16), BENCH_SPEC_K (4).
    """
    _enable_compile_cache()
    import numpy as np

    import jax
    import jax.numpy as jnp

    from bigdl_tpu.nn.attention import TransformerLM
    from bigdl_tpu.observability.watchdogs import backend_compile_count
    from bigdl_tpu.serving import BucketLadder, ServingEngine

    env = os.environ
    hidden = int(env.get("BENCH_SPEC_HIDDEN", "128"))
    layers = int(env.get("BENCH_SPEC_LAYERS", "2"))
    vocab = int(env.get("BENCH_SPEC_VOCAB", "256"))
    max_len = int(env.get("BENCH_SPEC_MAXLEN", "512"))
    new_tokens = int(env.get("BENCH_SPEC_NEW", "32"))
    block = int(env.get("BENCH_SPEC_BLOCK", "16"))
    spec_k = int(env.get("BENCH_SPEC_K", "4"))
    plens = (64, 96, 160, 256)
    conc = len(plens)

    # 4 heads -> head_dim = hidden/4 = 32, the layout the 3x floor is
    # quoted for (int8 payload 32 B + two fp32 scales vs 128 B fp32)
    model = TransformerLM(vocab, hidden, 4, layers, max_len=max_len)
    model.build(jax.ShapeDtypeStruct((1, 64), jnp.int32),
                rng=jax.random.PRNGKey(0))
    rng = np.random.default_rng(19)
    prompts = [rng.integers(0, vocab, size=n).astype(np.int32)
               for n in plens]
    ladder = BucketLadder(max(plens), min_size=min(plens))
    kv_blocks = conc * (-(-(max(plens) + new_tokens) // block))

    def _leg(kv_dtype, spec):
        eng = ServingEngine(model, decode_slots=conc,
                            decode_max_len=max_len, prompt_ladder=ladder,
                            kv_cache="paged", kv_block_size=block,
                            kv_blocks=kv_blocks, kv_cache_dtype=kv_dtype,
                            speculative=spec)
        try:
            sched = eng._generation()
            precompiles = sched.precompile()
            before = backend_compile_count()
            t0 = time.perf_counter()
            futs = [eng.generate(p, max_new_tokens=new_tokens)
                    for p in prompts]
            streams = [f.result(600) for f in futs]
            wall = time.perf_counter() - t0
            # the ledger's registered kv_cache source: pool bytes plus
            # the allocator's narrow-dtype block split
            kv = eng._kv_cache_bytes()
            leg = {"kv_bytes": kv["bytes"],
                   "kv_dtype": kv.get("kv_dtype"),
                   "bytes_per_block":
                       sched._alloc.stats().get("bytes_per_block"),
                   "tokens_per_s": round(conc * new_tokens / wall, 1),
                   "precompiles": precompiles,
                   "recompiles_after_precompile":
                       backend_compile_count() - before}
            if spec:
                leg["speculative"] = sched.stats()["speculative"]
                # sampled stretch: knobs are runtime arrays, so the
                # same draft/verify executables serve it
                sfuts = [eng.generate(prompts[i], max_new_tokens=8,
                                      temperature=0.8, top_k=20, seed=i)
                         for i in range(2)]
                [f.result(600) for f in sfuts]
                leg["recompiles_after_sampled"] = \
                    backend_compile_count() - before
        finally:
            eng.close()
        return leg, streams

    fp32, streams_f = _leg("fp32", 0)
    int8, streams_i = _leg("int8", 0)
    spec, streams_s = _leg("fp32", spec_k)

    shape = {"hidden": hidden, "layers": layers, "vocab": vocab,
             "max_len": max_len, "new_tokens": new_tokens,
             "block_size": block, "kv_blocks": kv_blocks,
             "prompt_lens": list(plens)}
    ratio = fp32["kv_bytes"] / max(int8["kv_bytes"], 1)
    rec_ratio = emit_record({
        "metric": "serving_int8_kv_bytes_ratio",
        "value": round(ratio, 2),
        "unit": "x",
        "vs_baseline": round(ratio / 3.0, 4),       # ISSUE-19 floor: 3x
        "extra": dict(
            shape, fp32=fp32, int8=int8,
            tokens_per_s_ratio=round(
                int8["tokens_per_s"]
                / max(fp32["tokens_per_s"], 1e-9), 3),
            # informational: int8 K/V perturbs logits ~1e-2, so greedy
            # streams MAY diverge at near-ties; not a gated witness
            greedy_tokens_match_fp32=streams_i == streams_f),
    })
    rec_peak = emit_record({
        "metric": "serving_int8_kv_peak_bytes",
        "value": int8["kv_bytes"],
        "unit": "bytes",
        # >= 1 iff the narrow pool actually holds the 3x claim against
        # the fp32 leg measured in THIS run (direction: lower)
        "vs_baseline": round(fp32["kv_bytes"]
                             / max(3.0 * int8["kv_bytes"], 1e-9), 4),
        "extra": dict(shape, fp32_kv_bytes=fp32["kv_bytes"],
                      bytes_per_block=int8["bytes_per_block"],
                      fp32_bytes_per_block=fp32["bytes_per_block"]),
    })
    st = spec["speculative"]
    verifies = max(st["drafted"] // max(st["k"], 1), 1)  # slot-ticks
    tpv = (verifies + st["accepted"]) / verifies
    rec_spec = emit_record({
        "metric": "serving_spec_tokens_ratio",
        "value": round(tpv, 3),
        "unit": "x",
        "vs_baseline": round(tpv / 1.5, 4),   # floor: 1.5 tokens/verify
        "extra": dict(
            shape, spec_k=spec_k, speculative=st,
            tokens_per_verify=round(tpv, 3),
            verify_steps=verifies,
            baseline=fp32, spec=spec,
            wall_tokens_per_s_ratio=round(
                spec["tokens_per_s"]
                / max(fp32["tokens_per_s"], 1e-9), 3),
            greedy_tokens_match=streams_s == streams_f),
    })
    return rec_ratio, rec_peak, rec_spec


# --------------------------------------------------------------------------- #
# Quantized-collective micro-benchmark (ISSUE 4): A/B the dp step's wire
# formats -- fp32 vs bf16 cast vs blockwise int8 + error feedback -- on
# sec/step and wire bytes, read back from the StepTelemetry JSONL.
# --------------------------------------------------------------------------- #

def _qcomm_leg(run_dir, compression, steps, batch, hidden, seed=0):
    """One DistriOptimizer leg under ``compression``; returns the
    obs_report steps block + the step event's wire/compression fields."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim
    from bigdl_tpu.utils.engine import Engine

    Engine.init()

    def make_opt(model, ds):
        return optim.DistriOptimizer(model, ds, nn.CrossEntropyCriterion(),
                                     optim.SGD(learning_rate=0.05),
                                     grad_compression=compression)

    steps_block, events = _mlp_leg(run_dir, "qcomm", make_opt, steps,
                                   batch, hidden, seed)
    last = events[-1]
    comm = {k: last.get(k) for k in
            ("wire_bytes", "grad_wire_bytes", "weight_wire_bytes",
             "compression_ratio", "grad_compression_ratio")}
    return steps_block, comm


def run_qcomm_bench(steps=None, batch=None, hidden=None, out_dir=None):
    """A/B the dp data plane's wire formats: fp32 vs bf16 cast vs
    blockwise int8 + error feedback (docs/performance.md, "Gradient
    compression").

    Knobs (env tier): BENCH_QCOMM_STEPS (default 20), BENCH_QCOMM_BATCH
    (default 64; must divide by the device count), BENCH_QCOMM_HIDDEN
    (default 512), BENCH_QCOMM_BLOCK (default 256).  Prints ONE JSON
    record whose ``value`` is the int8-vs-fp32 gradient wire-byte
    reduction read from the step telemetry and ``vs_baseline`` is that
    reduction over the 3.5x acceptance floor.  The per-leg sec/step is
    reported for completeness: on a single host (no DCN) the wire is
    memory bandwidth, so the time win only materializes on real
    cross-slice meshes -- the bytes number is the contract.
    """
    cache_status = _enable_compile_cache()
    import tempfile

    import jax

    from bigdl_tpu.ops.quantization import CompressionSpec

    env = os.environ
    steps = int(env.get("BENCH_QCOMM_STEPS", "20")) if steps is None else steps
    batch = int(env.get("BENCH_QCOMM_BATCH", "64")) if batch is None else batch
    hidden = (int(env.get("BENCH_QCOMM_HIDDEN", "512"))
              if hidden is None else hidden)
    block = int(env.get("BENCH_QCOMM_BLOCK", "256"))
    n_dev = jax.device_count()
    if batch % n_dev:
        batch = max(n_dev, batch // n_dev * n_dev)

    legs = [
        ("fp32", None),
        ("bf16", "bf16"),
        ("int8_ef", CompressionSpec(wire="int8", block_size=block,
                                    error_feedback=True)),
    ]

    def _run(base):
        out = {}
        for name, spec in legs:
            out[name] = _qcomm_leg(os.path.join(base, name), spec,
                                   steps, batch, hidden)
        return out

    if out_dir is None:
        with tempfile.TemporaryDirectory() as td:
            results = _run(td)
    else:
        results = _run(out_dir)

    grad_fp32 = results["fp32"][1]["grad_wire_bytes"]
    grad_int8 = results["int8_ef"][1]["grad_wire_bytes"]
    reduction = grad_fp32 / max(grad_int8, 1)
    record = {
        "metric": "qcomm_grad_wire_byte_reduction",
        "value": round(reduction, 2),
        "unit": "x",
        "vs_baseline": round(reduction / 3.5, 4),   # target: >= 3.5x
        "extra": {
            "compilation_cache": cache_status,
            "steps": steps, "batch": batch, "hidden": hidden,
            "block_size": block, "devices": n_dev,
            "legs": {
                name: {
                    "sec_per_step_p50": results[name][0]["wall_s_p50"],
                    "loss_last": results[name][0]["loss_last"],
                    **results[name][1],
                } for name, _ in legs
            },
        },
    }
    emit_record(record)
    return record


# --------------------------------------------------------------------------- #
# Transformer-LM step-time benchmark (ISSUE 7): A/B unrolled vs
# scan-compiled blocks, remat policies and flash on/off, publishing
# blocked-p50 step time ONLY (PR 6's TimingAuditor verdict on every
# record) and the per-leg compile seconds so the scan win is visible in
# the artifact.
# --------------------------------------------------------------------------- #

def _lm_leg(label, size, vocab, seq, batch, steps, scan, policy, flash):
    """One transformer train-step leg: build (same seed every leg --
    scan and unrolled init bit-identically, nn/attention.py), compile
    (wall seconds recorded), warm up once, then ``steps`` fenced
    dispatches (BlockingStepTimer) + a chained-dispatch triangulation
    window; returns the leg record with its own TimingAuditor verdict."""
    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim
    from bigdl_tpu.models.transformer import (synthetic_corpus,
                                              transformer_lm)
    from bigdl_tpu.observability import peak_flops
    from bigdl_tpu.observability.profiling import (BlockingStepTimer,
                                                   TimingAuditor)
    from bigdl_tpu.optim.train_step import make_train_step
    from bigdl_tpu.utils.random_generator import RNG

    dev = jax.devices()[0]
    RNG.set_seed(0)
    model = transformer_lm(size, vocab, max_len=seq, scan_layers=scan,
                           remat_policy=policy)
    for b in model.blocks:
        b.attn.use_flash = flash
    flash_active = bool(model.blocks[0].attn._flash_ok(seq))
    model.build(jax.ShapeDtypeStruct((batch, seq), jnp.int32))
    params, mstate = model.parameters()[0], model.state()
    crit = nn.TimeDistributedCriterion(
        nn.FusedSoftmaxCrossEntropyCriterion())
    method = optim.Adam(learning_rate=1e-3)
    opt_state = method.init_state(params)
    step = jax.jit(make_train_step(model, crit, method),
                   donate_argnums=(0, 1, 2))

    x, y = synthetic_corpus(batch * 4, seq, vocab, seed=1)
    xs = [jnp.asarray(x[i * batch:(i + 1) * batch]) for i in range(4)]
    ys = [jnp.asarray(y[i * batch:(i + 1) * batch]) for i in range(4)]
    key = jax.random.key(0)

    t0 = time.perf_counter()
    lowered = step.lower(params, mstate, opt_state, xs[0], ys[0], key)
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    try:
        flops = float(compiled.cost_analysis()["flops"])
    except Exception:
        flops = None

    # one warmup step (donated buffers: re-feed outputs), then the SAME
    # deterministic data sequence every leg so loss streams compare
    params, mstate, opt_state, loss = compiled(
        params, mstate, opt_state, xs[0], ys[0], key)
    jax.block_until_ready(loss)

    timer = BlockingStepTimer()
    losses = []
    for i in range(steps):
        timer.begin()
        params, mstate, opt_state, loss = compiled(
            params, mstate, opt_state, xs[i % 4], ys[i % 4], key)
        timer.end(loss)
        losses.append(float(loss))
    blocked = timer.summary()
    p50 = blocked["step_blocked_s_p50"]

    # chained-dispatch triangulation (donated chain -> serial device
    # dependency; a fenced p50 below total/N means the fence lied)
    t0 = time.perf_counter()
    for i in range(steps):
        params, mstate, opt_state, loss = compiled(
            params, mstate, opt_state, xs[i % 4], ys[i % 4], key)
    float(loss)
    chained = (time.perf_counter() - t0) / steps

    peak = peak_flops(dev)
    audit = TimingAuditor().audit(
        platform=dev.platform, step_blocked_s=p50,
        step_blocked_mean_s=blocked["total_s"] / steps,
        flops_per_step=flops, peak_flops=peak,
        dispatch_s_per_step=chained)
    return {
        "label": label, "scan": scan, "policy": policy, "flash": flash,
        "flash_active": flash_active,
        "compile_s": round(compile_s, 3),
        "sec_per_step_blocked": round(p50, 5),
        "blocked_p90": round(blocked["step_blocked_s_p90"], 5),
        "sec_per_step_chained": round(chained, 5),
        "tokens_per_s": round(batch * seq / p50, 1),
        "flops_per_step": flops,
        "mfu": round(flops / p50 / peak, 4) if flops and peak else None,
        "trust": audit["trust"],
        "timing_audit": audit,
        "loss_first": losses[0], "loss_last": losses[-1],
        "losses": losses,
    }


def _lm_compile_probe(size, vocab, seq, batch):
    """Jit-compile wall time, unrolled vs scan, at ``size`` -- measured
    on ABSTRACT avals only (eval_shape params; nothing materializes, so
    probing ``medium`` costs compile time, not model HBM) and with the
    persistent compilation cache disabled around the probe so a warm
    cache cannot fake the ratio."""
    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim
    from bigdl_tpu.models.transformer import transformer_lm
    from bigdl_tpu.optim.train_step import make_train_step

    crit = nn.TimeDistributedCriterion(
        nn.FusedSoftmaxCrossEntropyCriterion())
    method = optim.Adam(learning_rate=1e-3)
    x_spec = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    key_spec = jax.eval_shape(lambda: jax.random.key(0))
    out = {"size": size, "vocab": vocab, "seq": seq, "batch": batch,
           "cache_disabled": True}
    cache_was = jax.config.jax_enable_compilation_cache
    try:
        jax.config.update("jax_enable_compilation_cache", False)
        for mode, scan in (("unrolled", False), ("scan", True)):
            model = transformer_lm(size, vocab, max_len=seq,
                                   scan_layers=scan)
            params_eval, state_eval = jax.eval_shape(
                model.setup, key_spec, x_spec)
            opt_eval = jax.eval_shape(method.init_state, params_eval)
            step = jax.jit(make_train_step(model, crit, method),
                           donate_argnums=(0, 1, 2))
            t0 = time.perf_counter()
            step.lower(params_eval, state_eval, opt_eval, x_spec, x_spec,
                       key_spec).compile()
            out[f"{mode}_compile_s"] = round(time.perf_counter() - t0, 2)
    finally:
        # restore the caller's setting, not a hardcoded True: a process
        # that opted out of the persistent cache must stay opted out
        jax.config.update("jax_enable_compilation_cache", cache_was)
    out["compile_speedup"] = round(
        out["unrolled_compile_s"] / max(out["scan_compile_s"], 1e-9), 2)
    return out


def run_lm_bench(size=None, steps=None, batch=None, seq=None, vocab=None,
                 policies=None, compile_size=None):
    """A/B the transformer train step: unrolled vs scan-compiled blocks
    (nn.ScanLayers), remat policies, and flash attention on/off.

    Knobs (env tier): BENCH_LM_SIZE (default tiny), BENCH_LM_STEPS (8),
    BENCH_LM_BATCH (8), BENCH_LM_SEQ (128 -- flash-block-aligned),
    BENCH_LM_VOCAB (256), BENCH_LM_POLICIES (comma list, default
    "nothing_saveable,dots_saveable"), BENCH_LM_COMPILE_SIZE (default
    medium -- the compile-time probe's config; "off" skips it),
    BENCH_LM_COMPILE_SEQ (64), BENCH_LM_COMPILE_BATCH (1),
    BENCH_LM_COMPILE_VOCAB (32000).

    Prints ONE JSON record.  Every published number derives from
    blocked-p50 step time (BlockingStepTimer) and the record carries a
    top-level ``trust`` verdict (TimingAuditor; non-trusted ->
    ``vs_baseline: 0``, PR 6's contract).  ``extra.legs[*].compile_s``
    and ``extra.compile_probe`` record compile wall seconds -- the scan
    win the artifact exists to show (acceptance: medium scan compile
    >= 3x faster than unrolled on the same host); ``extra.
    scan_loss_matches_unrolled`` pins the numerics equivalence.
    """
    cache_status = _enable_compile_cache()
    import jax

    import numpy as np

    env = os.environ
    size = env.get("BENCH_LM_SIZE", "tiny") if size is None else size
    steps = int(env.get("BENCH_LM_STEPS", "8")) if steps is None else steps
    batch = int(env.get("BENCH_LM_BATCH", "8")) if batch is None else batch
    seq = int(env.get("BENCH_LM_SEQ", "128")) if seq is None else seq
    vocab = (int(env.get("BENCH_LM_VOCAB", "256"))
             if vocab is None else vocab)
    policies = (env.get("BENCH_LM_POLICIES",
                        "nothing_saveable,dots_saveable").split(",")
                if policies is None else policies)
    policies = [p.strip() for p in policies if p.strip()]
    compile_size = (env.get("BENCH_LM_COMPILE_SIZE", "medium")
                    if compile_size is None else compile_size)

    legs = {}
    plan = [("unrolled", False, None, "auto"),
            ("scan", True, None, "auto")]
    plan += [(f"scan:{p}", True, p, "auto") for p in policies]
    plan += [("scan:no_flash", True, None, "never")]
    for label, scan, policy, flash in plan:
        legs[label] = _lm_leg(label, size, vocab, seq, batch, steps,
                              scan, policy, flash)

    # numerics witness: same seed + same data => the scan legs' loss
    # stream must track the unrolled leg's (float-rounding close; the
    # layer math is identical, only the program structure differs)
    ref = np.asarray(legs["unrolled"]["losses"])
    got = np.asarray(legs["scan"]["losses"])
    loss_max_diff = float(np.max(np.abs(ref - got)))
    loss_match = bool(np.allclose(ref, got, rtol=1e-4, atol=1e-5))

    probe = None
    if compile_size != "off":
        probe = _lm_compile_probe(
            compile_size,
            int(env.get("BENCH_LM_COMPILE_VOCAB", "32000")),
            int(env.get("BENCH_LM_COMPILE_SEQ", "64")),
            int(env.get("BENCH_LM_COMPILE_BATCH", "1")))

    best_label = min(legs, key=lambda k: legs[k]["sec_per_step_blocked"])
    best = legs[best_label]
    record = {
        "metric": "transformer_lm_tokens_per_sec_per_chip",
        "value": best["tokens_per_s"],
        "unit": "tokens/sec",
        "vs_baseline": round((best["mfu"] or 0.0) / 0.35, 4),
        "trust": best["trust"],
        "extra": {
            "compilation_cache": cache_status,
            "platform": jax.devices()[0].platform,
            "size": size, "vocab": vocab, "seq": seq, "batch": batch,
            "steps": steps,
            "best_leg": best_label,
            "sec_per_step_blocked": best["sec_per_step_blocked"],
            "scan_loss_matches_unrolled": loss_match,
            "scan_loss_max_diff": loss_max_diff,
            "scan_compile_speedup": round(
                legs["unrolled"]["compile_s"]
                / max(legs["scan"]["compile_s"], 1e-9), 2),
            "legs": legs,
            "compile_probe": probe,
        },
    }
    if record["trust"] != "trusted":
        record["vs_baseline"] = 0.0   # PR 6's contract: no trust, no claim
    emit_record(record)
    return record


def run_bench():
    """Run the benchmark in-process and print the result JSON line.

    On TPU, sweeps BENCH_SWEEP batch sizes (default "128,128f,256f" --
    the plain-128 anchor plus the flat-fused-update legs the round-4 op
    accounting motivates) and reports
    the best physically-possible record -- larger batches usually lift MFU
    on the MXU.  Suffixes on a sweep entry select model variants: "r"
    (e.g. "512r") runs that leg with block rematerialisation (nn.Remat;
    frees activation HBM for the bigger batch), "s" with the
    space-to-depth stem (nn.SpaceToDepthStem), "f" with the flat fused
    optimizer update (optim.Fused); "512rf" combines them.
    BENCH_BATCH overrides with a single entry; BENCH_REMAT=1 /
    BENCH_S2D=1 / BENCH_FUSED=1 set the default for suffix-less entries.
    """
    _enable_compile_cache()
    steps = int(os.environ.get("BENCH_STEPS", "20"))
    defaults = variant_defaults()

    if os.environ.get("BENCH_BATCH"):
        batches = [parse_variant(os.environ["BENCH_BATCH"], defaults)]
    else:
        batches = [parse_variant(b, defaults) for b in
                   os.environ.get("BENCH_SWEEP",
                                  "128,128f,256f").split(",")]

    records, failures = [], []

    def best_so_far():
        valid = [r for r in records if r["vs_baseline"] > 0.0]
        best = max(valid or records, key=lambda r: r["vs_baseline"])
        if len(records) > 1 or failures:
            best["extra"]["sweep"] = [
                {"batch": r["extra"]["batch"], "mfu": r["extra"].get("mfu"),
                 "remat": r["extra"].get("remat"),
                 "s2d": r["extra"].get("s2d"),
                 "fused": r["extra"].get("fused"),
                 "imgs_per_sec": r["value"]} for r in records] + failures
        return best

    for batch, flags in batches:
        try:
            records.append(_bench_one(batch, steps, **flags))
        except Exception as e:          # e.g. OOM at the larger batch:
            failures.append({"batch": batch, "error": repr(e)[:300], **flags})
            if records:                 # keep the failure visible in any
                emit_record(best_so_far())  # salvage
            continue                    # keep any already-valid record
        # Print the best record after EVERY completed leg: a later leg
        # that hangs (a big-batch compile can wedge the backend) gets
        # this child killed, and the parent salvages this line instead
        # of losing the whole sweep.
        emit_record(best_so_far())
        if records[-1]["extra"]["platform"] == "cpu":
            break                      # no sweep off-TPU (smoke path)
    if not records:
        raise RuntimeError(f"all sweep batches failed: {failures}")
    # the final record was already flushed by the last loop iteration;
    # the completion sentinel lets the parent distinguish "full sweep
    # done, child died in teardown" from "killed mid-sweep" when rc != 0
    print(json.dumps({"bench_complete": True}), flush=True)


def _bench_one(batch, steps, remat=False, s2d=False, fused=False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu import optim
    from bigdl_tpu.models.resnet import ResNet
    from bigdl_tpu.nn import CrossEntropyCriterion
    from bigdl_tpu.optim.train_step import make_train_step

    dev = jax.devices()[0]
    platform = dev.platform
    # cache state at LEG START, before this leg's own compiles land in
    # the cache dir (config.py: a lazily-taken count misreports cold as
    # warm) -- leg 2 of a sweep then shows leg 1's entries, which is the
    # cross-leg reuse the record exists to make verifiable
    from bigdl_tpu.utils.config import compilation_cache_status
    cache_status = compilation_cache_status()

    # BENCH_REMAT_POLICY names a jax.checkpoint_policies entry for the
    # remat legs (A/B-able against the default save-block-inputs policy)
    remat_policy = os.environ.get("BENCH_REMAT_POLICY") or None
    model = ResNet(depth=50, class_num=1000, remat=remat, stem_s2d=s2d,
                   remat_policy=remat_policy if remat else None)
    model.build(jax.ShapeDtypeStruct((batch, 224, 224, 3), jnp.bfloat16))
    params, mstate = model.parameters()[0], model.state()
    method = optim.SGD(learning_rate=0.02, momentum=0.9, dampening=0.0,
                       weight_decay=1e-4)
    if fused:
        # flat-vector update: one HBM-bound kernel instead of ~100
        # per-tensor fusions (docs/performance.md, Fused docstring)
        method = optim.Fused(method)
    opt_state = method.init_state(params)

    step = jax.jit(
        make_train_step(model, CrossEntropyCriterion(), method,
                        compute_dtype=jnp.bfloat16),
        donate_argnums=(0, 1, 2))

    # Distinct input batches, cycled, so no layer of the stack can dedup or
    # cache "the same computation" (every step differs in BOTH params --
    # donated chain -- and data).
    rng = np.random.default_rng(0)
    xs = [jnp.asarray(rng.standard_normal((batch, 224, 224, 3)),
                      dtype=jnp.bfloat16) for _ in range(4)]
    ts = [jnp.asarray(rng.integers(0, 1000, batch), dtype=jnp.int32)
          for _ in range(4)]
    x, t = xs[0], ts[0]
    key = jax.random.key(0)

    lowered = step.lower(params, mstate, opt_state, x, t, key)
    compiled = lowered.compile()
    try:
        flops_per_step = float(compiled.cost_analysis()["flops"])
    except Exception:
        flops_per_step = 3 * 2 * 4.09e9 * batch  # 3x fwd MAC*2 estimate

    # warmup (donated buffers: re-feed outputs)
    for _ in range(3):
        params, mstate, opt_state, loss = compiled(
            params, mstate, opt_state, x, t, key)
    jax.block_until_ready((params, mstate, opt_state, loss))

    from bigdl_tpu.observability.profiling import (BlockingStepTimer,
                                                   TimingAuditor)

    # PUBLISHED timing: per-step blocking (BlockingStepTimer) -- each
    # dispatch is block_until_ready-fenced before the next one, so the
    # recorded span is dispatch + full device execution, no async
    # dispatch, no pipelining.  step_blocked_s (the p50) is the ONLY
    # number the MFU math below uses (docs/observability.md, "Profiling
    # & trusted timing"); the chained and trace estimates exist to
    # CATCH a blocked timing that lies, not to replace it.
    timer = BlockingStepTimer()
    for i in range(steps):
        timer.begin()
        params, mstate, opt_state, loss = compiled(
            params, mstate, opt_state, xs[i % 4], ts[i % 4], key)
        timer.end(loss)
    final_loss = float(loss)
    blocked = timer.summary()
    step_blocked_s = blocked["step_blocked_s_p50"]

    # Triangulation 1: N chained dispatches (params/opt state donated, so
    # step i+1 consumes step i's outputs -- a serial device-side
    # dependency chain), then fetch the final loss VALUE.  The value
    # cannot exist before all N steps execute, so total/N is a LOWER
    # bound on true step time with the dispatch latency amortised: a
    # blocked per-step time BELOW it means the fence did not hold (round
    # 3 measured exactly that on a remote device).
    t0 = time.perf_counter()
    for i in range(steps):
        params, mstate, opt_state, loss = compiled(
            params, mstate, opt_state, xs[i % 4], ts[i % 4], key)
    float(loss)                       # forces the whole chain
    dt_chain = time.perf_counter() - t0
    sec_per_step_chained = dt_chain / steps

    # Triangulation 2 (VERDICT r3 weak #3): the same chained window under
    # a jax.profiler trace; the device plane's own busy time per step is
    # a floor no honest published step time can undercut, and the per-op
    # attribution (compute vs collective vs idle) feeds the obs_report
    # Profiling section.
    trace_witness = None
    if platform == "tpu":
        try:
            import tempfile

            from bigdl_tpu.utils.xplane import (device_attribution,
                                                device_busy)

            with tempfile.TemporaryDirectory() as td:
                with jax.profiler.trace(td):
                    # clock only the chained window, not the profiler
                    # start/stop or trace serialization
                    t0 = time.perf_counter()
                    for i in range(steps):
                        params, mstate, opt_state, loss = compiled(
                            params, mstate, opt_state, xs[i % 4],
                            ts[i % 4], key)
                    float(loss)
                    wall = time.perf_counter() - t0
                attribution = device_attribution(td, top=5)
                trace_witness = {
                    "wall_sec_per_step": round(wall / steps, 4),
                    "device_plane": device_busy(td),
                    "attribution": attribution,
                }
        except Exception as e:          # the witness must never kill the
            trace_witness = {"error": repr(e)[:200]}   # measurement

    imgs_per_sec = batch / step_blocked_s
    # bf16 peak FLOP/s by device kind -- the ONE table, shared with the
    # telemetry/report MFU math so the two can never disagree.  Off a
    # TPU there is no peak and so no MFU (None)
    from bigdl_tpu.observability import peak_flops
    kind = getattr(dev, "device_kind", "") or ""
    peak = peak_flops(dev)
    mfu = None if peak is None else (flops_per_step / step_blocked_s) / peak

    # The trust verdict: triangulate the published (blocked) MFU against
    # the dispatch chain and the trace's own device-busy accounting.  A
    # non-trusted record cannot claim the baseline target -- the exact
    # gate BENCH_r02's 2.74 "MFU" would have failed.
    busy_per_step = None
    plane = (trace_witness or {}).get("device_plane") or {}
    if plane.get("busy_event_sec"):
        busy_per_step = plane["busy_event_sec"] / steps
    blocked_mean = blocked["total_s"] / steps
    audit = TimingAuditor().audit(
        platform=platform,
        step_blocked_s=step_blocked_s,
        # the chained/trace bounds are window MEANS: compare them
        # against the blocked mean (one straggler step inflates both
        # sides alike) while the p50 stays the published basis
        step_blocked_mean_s=blocked_mean,
        flops_per_step=flops_per_step,
        peak_flops=peak,
        dispatch_s_per_step=sec_per_step_chained,
        device_busy_s_per_step=busy_per_step)

    record = {
        "metric": "resnet50_train_imgs_per_sec_per_chip",
        "value": round(imgs_per_sec, 2),
        "unit": "images/sec",
        "vs_baseline": 0.0 if mfu is None else round(mfu / 0.35, 4),
        "trust": audit["trust"],
        "extra": {
            "platform": platform,
            "device_kind": kind,
            "peak_flops_assumed": peak,
            "batch": batch,
            "steps": steps,
            "remat": remat,
            "remat_policy": remat_policy if remat else None,
            "s2d": s2d,
            "fused": fused,
            # published basis + its spread, then the triangulation
            # estimates (diagnostics, never the MFU source)
            "sec_per_step": round(step_blocked_s, 4),
            "sec_per_step_blocked": round(step_blocked_s, 4),
            "sec_per_step_blocked_mean": round(blocked_mean, 4),
            "blocked_p10": round(blocked["step_blocked_s_p10"], 4),
            "blocked_p90": round(blocked["step_blocked_s_p90"], 4),
            "sec_per_step_chained": round(sec_per_step_chained, 4),
            "mfu": None if mfu is None else round(mfu, 4),
            "flops_per_step": flops_per_step,
            "loss": final_loss,
            "timing_audit": audit,
            "compilation_cache": cache_status,
            "trace_witness": trace_witness,
        },
    }
    if audit["trust"] != "trusted":
        # a suspect or invalid measurement can't claim the target; the
        # audit's checks carry the evidence trail
        record["vs_baseline"] = 0.0
    return record


_live_children = []


def _reap_children(signum=None, frame=None):
    """SIGTERM handler: kill any live child process groups before dying.

    The driver's timeout sends SIGTERM first; without this, a hung probe
    child (its own session) would outlive us, still holding the chip.
    """
    import signal

    for pid in _live_children:
        try:
            os.killpg(pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
    if signum is not None:
        sys.exit(128 + signum)


def _spawn_child(extra_env, timeout):
    """Run this file once more as a child and wait for it.  A chip
    belongs to one process at a time: the parent that calls this never
    initialises a JAX backend (it reads ``jax.config`` at most), and the
    children run one after another, so each in turn is the one process
    on the chip."""
    import signal
    import tempfile

    env = dict(os.environ)
    env["BENCH_CHILD"] = "1"
    env.update(extra_env)
    # pipe via files + own process group: a hung grandchild (TPU runtime
    # helper) holding the pipe open cannot block us, and killpg reaps it
    with tempfile.TemporaryFile("w+") as out, \
            tempfile.TemporaryFile("w+") as err:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)],
            stdout=out, stderr=err, env=env, start_new_session=True)
        _live_children.append(proc.pid)
        timed_out = False
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            timed_out = True
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            rc = proc.wait()
        _live_children.remove(proc.pid)
        out.seek(0)
        stdout = out.read()
        err.seek(0)
        stderr = err.read()
    # find the result JSON line on stdout; a timed-out or crashed child
    # may still have printed a completed sweep leg before dying on a
    # later one (run_bench flushes the best-so-far record after every
    # leg) -- salvage it, ANNOTATED, rather than discarding a valid
    # measurement.  The caller decides whether a salvaged record is
    # good enough to stop retrying.
    dirty = timed_out or rc != 0
    complete = False
    for line in reversed(stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if rec.get("bench_complete"):
                complete = True       # full sweep done; any non-zero rc
                continue              # was teardown, not a lost leg
            if dirty:
                if "extra" not in rec:   # probe line, not a record
                    break
                if complete:
                    if rc != 0:
                        rec["extra"]["teardown"] = (
                            f"child exited rc={rc} AFTER completing the "
                            f"sweep (teardown failure); measurement is "
                            f"whole")
                else:
                    how = (f"timed out after {timeout}s" if timed_out
                           else f"exited rc={rc}")
                    rec["extra"]["salvaged"] = (
                        f"child {how} mid-sweep; this is the last "
                        f"completed leg; stderr tail: " + stderr[-300:])
            return rec, None
    if timed_out:
        return None, (f"timeout after {timeout}s; stderr tail: "
                      + stderr[-500:])
    return None, f"rc={rc}; stderr tail: {stderr[-800:]}"


def _probe_device(stage_timeout, probe_timeout, attempts, failures,
                  spawn=None):
    """Fast cancellable device probe (ISSUE 6 satellite: seconds, not
    240 s).  One child inits jax and prints its platform, bounded by
    ``probe_timeout`` (clamped to the remaining budget); the child runs
    in its own process group so a hang is killed instantly, and the
    parent's SIGTERM handler reaps it (SIGTERM-safe).  Returns
    ``(probe_info, attempts)``: ``probe_info = {"probe_sec",
    "probe_result"}`` is stamped into the final record so an
    r04/r05-style death reads as ``probe: timeout→cpu`` instead of a
    killed run, and ``attempts`` is the (possibly clamped) TPU attempt
    budget.

    - ``"tpu"``: the chip answered -- keep the full attempts.
    - ``"cpu"`` (or another platform): deterministic non-TPU backend --
      skip straight to the CPU fallback (a full attempt would sweep
      ResNet-50 on CPU at batch 128).
    - ``"timeout"``: the probe hung through its whole window -- a dead
      backend hangs rather than erroring, and a full attempt would hang
      the same way and starve the fallback of budget, so skip the
      attempts (raise BENCH_PROBE_TIMEOUT for a slow-but-alive one).
    - ``"error"``: fast transient init error -- keep the full retry
      budget (round-1's failure story was exactly transient errors).
    - ``"skipped:budget"``: no budget left to probe at all.
    """
    spawn = spawn or _spawn_child
    t = stage_timeout(probe_timeout, "device probe", minimum=5)
    if t is None:
        return ({"probe_sec": None, "probe_result": "skipped:budget"},
                attempts)
    t0 = time.monotonic()
    probe, perr = spawn({"BENCH_PROBE": "1"}, t)
    info = {"probe_sec": round(time.monotonic() - t0, 1)}
    if probe is not None and probe.get("probe"):
        info["probe_result"] = probe["probe"]
        if probe["probe"] != "tpu":
            failures.append(
                f"device probe: platform {probe['probe']!r}, not tpu "
                f"(answered in {info['probe_sec']}s)")
            attempts = 0
    elif probe is None and str(perr).startswith("timeout"):
        info["probe_result"] = "timeout"
        failures.append(
            f"device probe: hung through {t:.0f}s -- the device never "
            f"answered; skipping TPU attempts (raise BENCH_PROBE_TIMEOUT "
            f"if it is merely slow)")
        attempts = 0
    else:
        info["probe_result"] = "error"
        failures.append(f"device probe: {perr or probe}")
    return info, attempts


def main():
    if os.environ.get("BENCH_PIPELINE") or "pipeline" in sys.argv[1:]:
        # input-pipeline A/B: in-process and CPU-runnable (no TPU probe /
        # retry orchestration -- the measurement is host-side by design)
        run_pipeline_bench()
        return
    if os.environ.get("BENCH_HEALTH") or "health" in sys.argv[1:]:
        # health-stats overhead A/B: in-process and CPU-runnable
        run_health_bench()
        return
    if os.environ.get("BENCH_QCOMM") or "qcomm" in sys.argv[1:]:
        # wire-format A/B on the dp step: in-process and CPU-runnable
        # (the wire-byte accounting is exact on any device count)
        run_qcomm_bench()
        return
    if os.environ.get("BENCH_DECODE") or "decode" in sys.argv[1:]:
        # autoregressive generation A/B (KV-cache decode vs full
        # recompute): in-process and CPU-runnable; the tokens/s ratio is
        # the gateable trajectory metric (host-side, ratio stance)
        run_decode_bench()
        # cache-LAYOUT A/B (paged block pool vs contiguous) + the
        # shared-prefix prefill-saved leg: exact byte/token ratios
        run_paged_kv_bench()
        return
    if os.environ.get("BENCH_PAGED") or "paged" in sys.argv[1:]:
        # the paged-KV legs alone (no decode-ratio re-measurement --
        # re-rolling that noisy ratio would churn ITS baseline)
        run_paged_kv_bench()
        return
    if os.environ.get("BENCH_SPEC") or "spec" in sys.argv[1:]:
        # int8-KV footprint + speculative-decoding A/B (ISSUE 19):
        # in-process and CPU-runnable; the byte ratio is exact
        # anywhere, tokens-per-verify is the platform-independent
        # bound on the speculative speedup
        run_spec_bench()
        return
    if os.environ.get("BENCH_WIRE") or "wire" in sys.argv[1:]:
        # fleet-transport A/B (pickle wire vs binary frames + pooled
        # connections) + fp32-vs-int8 weight-distribution bytes:
        # in-process loopback, CPU-runnable; the bytes ratio is exact
        # anywhere, the rps ratio is the gateable trajectory metric
        run_wire_bench()
        return
    if os.environ.get("BENCH_SERVE_INT8") or "serve-int8" in sys.argv[1:]:
        # serving-precision A/B (fp32 vs int8 engine): in-process and
        # CPU-runnable; the bytes ratio is exact anywhere, the rps
        # ratio is the gateable trajectory metric
        run_serve_quant_bench()
        return
    if os.environ.get("BENCH_SERVE") or "serve" in sys.argv[1:]:
        # serving A/B (semaphore-serial vs coalesced+bucketed):
        # in-process and CPU-runnable by design
        run_serve_bench()
        return
    if os.environ.get("BENCH_LM") or "lm" in sys.argv[1:]:
        # transformer step-time A/B (unrolled vs scan, remat policies,
        # flash on/off): in-process; blocked-p50 published, per-leg
        # TimingAuditor verdicts make the CPU smoke honestly off_tpu
        run_lm_bench()
        return
    if os.environ.get("BENCH_CHILD"):
        if os.environ.get("BENCH_FAKE_HANG"):  # test hook: hung-device sim
            time.sleep(100000)
        if os.environ.get("BENCH_PROBE"):
            if os.environ.get("BENCH_FAKE_HANG_MID_SWEEP") or \
                    os.environ.get("BENCH_FAKE_CRASH_MID_SWEEP"):
                print(json.dumps({"probe": "tpu"}), flush=True)
                return
            _enable_compile_cache()
            import jax

            print(json.dumps({"probe": jax.devices()[0].platform}))
            return
        if os.environ.get("BENCH_FAKE_HANG_MID_SWEEP") or \
                os.environ.get("BENCH_FAKE_CRASH_MID_SWEEP"):
            # test hook: first sweep leg completes, second wedges (a
            # big-batch compile that hangs) or crashes the child
            print(json.dumps({
                "metric": "resnet50_train_imgs_per_sec_per_chip",
                "value": 1234.0, "unit": "images/sec", "vs_baseline": 0.5,
                "trust": "trusted",
                "extra": {"platform": "tpu", "batch": 128}}), flush=True)
            if os.environ.get("BENCH_FAKE_CRASH_MID_SWEEP"):
                os._exit(3)
            time.sleep(100000)
        run_bench()
        return

    # Total wall-clock budget across probe + attempts + fallback.  Round 3
    # proved the failure mode of an unbounded sweep: the driver's timeout
    # fired first (rc=124) and NOTHING was printed.  Now every stage is
    # clamped to the remaining budget and a diagnostic JSON line is printed
    # BEFORE each long stage, so a kill at any moment leaves the last
    # printed line as a parseable artifact.
    import signal

    signal.signal(signal.SIGTERM, _reap_children)
    budget = int(os.environ.get("BENCH_TOTAL_BUDGET", "1100"))
    deadline = time.monotonic() + budget
    attempts = int(os.environ.get("BENCH_RETRIES", "3"))
    timeout = int(os.environ.get("BENCH_TIMEOUT", "700"))
    failures = []

    def remaining():
        return deadline - time.monotonic()

    def diagnostic(stage):
        # Superseded by any later line; the LAST JSON line is the result.
        print(json.dumps({
            "metric": "resnet50_train_imgs_per_sec_per_chip",
            "value": 0.0,
            "unit": "images/sec",
            "vs_baseline": 0.0,
            "trust": "invalid:impossible",   # no measurement exists yet
            "extra": {
                "error": f"incomplete: bench was killed during {stage} "
                         f"(pre-stage diagnostic; a later line supersedes "
                         f"this one)",
                "budget_sec": budget,
                "budget_left_sec": round(remaining(), 1),
                "failures": failures,
            },
        }), flush=True)

    def stage_timeout(want, stage, minimum=30):
        """Clamp a stage's timeout to the remaining budget (20s reserve).
        ``minimum`` is the floor below which the stage is pointless (30s
        for a full attempt; the fast probe passes 5s -- it answers in
        seconds or not at all)."""
        t = min(want, remaining() - 20)
        if t < minimum:
            failures.append(f"{stage}: skipped (clamped timeout {t:.0f}s "
                            f"< {minimum}s minimum; budget left "
                            f"{remaining():.0f}s)")
            return None
        return t

    # A dead backend HANGS rather than erroring; don't burn attempts x
    # timeout on it.  The fast cancellable probe (seconds, not the old
    # 240 s) decides whether full TPU attempts are worth making, and its
    # outcome is stamped into whatever record this run emits.
    diagnostic("device probe")
    probe_timeout = min(int(os.environ.get("BENCH_PROBE_TIMEOUT", "60")),
                        timeout)
    probe_info, attempts = _probe_device(stage_timeout, probe_timeout,
                                         attempts, failures)

    def stamp(rec, cpu_fallback=False):
        """Probe provenance + a trust verdict on EVERY exit path's
        record: a record without them is the old, diagnosable-only-by-
        archaeology failure mode (r04/r05)."""
        rec.setdefault("trust", "invalid:impossible")
        rec["probe_result"] = probe_info["probe_result"]
        extra = rec.setdefault("extra", {})
        extra["probe_sec"] = probe_info["probe_sec"]
        extra["probe_result"] = probe_info["probe_result"]
        try:
            extra.setdefault("tracing", _tracing_manifest())
        except Exception:
            pass
        if cpu_fallback:
            # the honest spelling of an r04/r05-style death: the probe
            # outcome -> cpu, recorded, instead of a killed run
            extra["probe"] = f"{probe_info['probe_result']}→cpu"
        return rec

    salvaged_invalid = None
    for i in range(attempts):
        diagnostic(f"tpu attempt {i + 1}")
        t = stage_timeout(timeout, f"tpu attempt {i + 1}")
        if t is None:
            break
        result, err = _spawn_child({}, t)
        if result is not None:
            # a salvaged record that is itself invalid (vs_baseline 0)
            # must not end the run: keep retrying / fall back, but hold
            # it as a last-resort artifact
            if ("salvaged" not in result.get("extra", {})
                    or result.get("vs_baseline", 0) > 0):
                print(json.dumps(stamp(result)), flush=True)
                return
            salvaged_invalid = result
            failures.append(f"attempt {i + 1}: salvaged record invalid: "
                            + result["extra"]["salvaged"][:300])
        else:
            failures.append(f"attempt {i + 1}: {err}")
        if i < attempts - 1:
            time.sleep(min(30, 5 * (i + 1)))

    # TPU unreachable after retries: take a CPU measurement so the round
    # still produces a perf artifact, and carry the TPU failure diagnostics.
    if os.environ.get("BENCH_NO_CPU_FALLBACK") != "1":
        diagnostic("cpu fallback")
        t = stage_timeout(timeout, "cpu fallback")
        if t is not None:
            result, err = _spawn_child(
                {"JAX_PLATFORMS": "cpu", "BENCH_BATCH": "8",
                 "BENCH_STEPS": "2"}, t)
            if result is not None:
                result["extra"]["tpu_failures"] = failures
                result["vs_baseline"] = 0.0  # CPU can't claim the target
                result["extra"]["last_onchip_evidence"] = (
                    "no TPU answered this run; the last recorded TPU "
                    "measurement (historical, older JAX) is in "
                    "docs/performance.md 'Round-4 on-chip measurement' with "
                    "the raw trace at docs/traces/")
                print(json.dumps(stamp(result, cpu_fallback=True)),
                      flush=True)
                return
            failures.append(f"cpu fallback: {err}")

    if salvaged_invalid is not None:
        salvaged_invalid["extra"]["failures"] = failures
        print(json.dumps(stamp(salvaged_invalid)), flush=True)
        return
    print(json.dumps(stamp({
        "metric": "resnet50_train_imgs_per_sec_per_chip",
        "value": 0.0,
        "unit": "images/sec",
        "vs_baseline": 0.0,
        "extra": {"error": "all attempts failed", "failures": failures},
    })), flush=True)


if __name__ == "__main__":
    main()
