"""The train->serve loop, live: a trainer writes snapshots while the
engine serves, shadows, canaries and promotes them.

The command-line face of ``bigdl_tpu/serving/deploy.py``
(docs/robustness.md, "Continuous deployment"): the DRIVER process
serves a workload through a ``ServingEngine`` under closed-loop client
load while a TRAINER child process retrains the same model, writing
crash-safe snapshots into ``--out/ckpt``.  A ``RolloutController``
polls that directory and walks every new snapshot through shadow ->
canary -> atomic cutover, with the whole audit trail durable in
``--out/serve/telemetry.jsonl`` (``kind: "deploy"``) and rendered by
``tools/obs_report.py``.

    # live-loop demo: transformer workload, 3 snapshot generations
    python -m tools.serve_live --out /tmp/live --steps 18 --ckptEvery 6

    # the BigDL-native second workload
    python -m tools.serve_live --out /tmp/live-ml --workload movielens

    # chaos drill legs (slow-tier tests drive these):
    python -m tools.serve_live --out /tmp/drill --poison         # bad
    #   candidate caught in shadow, auto-rejected, vN keeps serving
    python -m tools.serve_live --out /tmp/drill2 \
        --chaos kill:cutover:2                                   # SIGKILL
    #   mid-cutover; re-running with --noTrainer resumes from the
    #   durable registry and serves the last COMMITTED version
    #   bit-for-bit (result.json's probe digest proves it)

Artifacts under ``--out``:

- ``ckpt/``           -- the trainer's verified snapshots
- ``registry.json``   -- the durable version registry (live/previous)
- ``serve/``          -- the serving run's telemetry.jsonl
- ``live_history.jsonl`` -- one line per served version: version id,
  manifest digest and a probe-logits digest (``predict_at`` at a fixed
  bucket, so it is bit-for-bit comparable across processes)
- ``trainer.log`` / ``result.json``

Both workloads build their model under a fixed seed, so the trainer
child and the serving driver agree on the tree structure (and the
baseline version's weights) by construction.

One process for each chip: the DRIVER serves on whatever platform JAX
finds, so on a chip machine it holds the chip, and the trainer child it
starts would fail or hang there.  The trainer is therefore always pinned
to the CPU (snapshots are host arrays; nothing else crosses).  Both
roles print their platform, and ``result.json`` records them.
"""

import argparse
import hashlib
import json
import os
import signal
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: where the trainer child runs: never on the accelerator the driver holds
TRAINER_PLATFORM = "cpu"


def build_args(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    ap.add_argument("--out", required=True, help="artifact root directory")
    ap.add_argument("--workload", choices=("transformer", "movielens"),
                    default="transformer")
    ap.add_argument("--steps", type=int, default=18,
                    help="trainer steps (a snapshot every --ckptEvery)")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--datasetSize", type=int, default=256)
    ap.add_argument("--ckptEvery", type=int, default=6)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--maxBatch", type=int, default=8,
                    help="serving max_batch_size")
    ap.add_argument("--maxWaitMs", type=float, default=1.0)
    ap.add_argument("--clients", type=int, default=2,
                    help="closed-loop client threads")
    ap.add_argument("--shadowFraction", type=float, default=0.5)
    ap.add_argument("--shadowRows", type=int, default=16,
                    help="real rows the shadow stage must compare")
    ap.add_argument("--agreement", type=float, default=None,
                    help="shadow min top-1 agreement vs the LIVE version "
                         "(opt-in: right for incremental refreshes, wrong "
                         "for from-scratch retraining where a genuinely "
                         "better candidate legitimately disagrees)")
    ap.add_argument("--maxLogitRmse", type=float, default=100.0,
                    help="shadow max logit RMSE vs live -- the default "
                         "poison catch: honest training moves logits "
                         "modestly, an outlier-poisoned candidate's "
                         "collapse onto a huge rank-1 plane lands orders "
                         "of magnitude above this")
    ap.add_argument("--canaryFraction", type=float, default=0.25)
    ap.add_argument("--canaryTicks", type=int, default=4)
    ap.add_argument("--stageTimeout", type=float, default=60.0)
    ap.add_argument("--watchSeconds", type=float, default=1.0,
                    help="post-cutover rollback watch window")
    ap.add_argument("--sloLatencyMs", type=float, default=None,
                    help="arm a request-latency SLO objective whose "
                         "burn degrades /healthz and can trigger the "
                         "post-cutover auto-rollback")
    ap.add_argument("--metricsPort", type=int, default=None,
                    help="serve /metrics + /healthz (0 auto-assigns)")
    ap.add_argument("--poison", action="store_true",
                    help="after the trainer completes, drop a "
                         "deliberately poisoned candidate snapshot "
                         "(outlier-poisoned output channels) -- the "
                         "rollout must catch and reject it")
    ap.add_argument("--chaos", default=None,
                    help="deploy fault injection: kill:cutover:<n> "
                         "(SIGKILL the driver mid-way through its n-th "
                         "cutover)")
    ap.add_argument("--noTrainer", action="store_true",
                    help="serve + poll only (the restart leg of the "
                         "chaos drill re-runs with this set)")
    ap.add_argument("--idleRounds", type=int, default=8,
                    help="stop after this many quiet poll rounds once "
                         "the trainer exited")
    # internal: the driver spawning itself as the trainer child
    ap.add_argument("--role", choices=("driver", "trainer"),
                    default="driver", help=argparse.SUPPRESS)
    return ap.parse_args(argv)


# --------------------------------------------------------------------------- #
# Workloads: (model, eval features, labels, criterion) under a fixed seed.
# --------------------------------------------------------------------------- #


def build_workload(args):
    import numpy as np

    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu.utils.random_generator import RNG

    RNG.set_seed(args.seed)
    rng = np.random.default_rng(args.seed)
    if args.workload == "transformer":
        from bigdl_tpu.nn.attention import TransformerLM

        vocab, seq = 48, 16
        model = TransformerLM(vocab, 32, 4, num_layers=2, max_len=seq)
        model.build(jax.ShapeDtypeStruct((2, seq), jnp.int32))
        x = rng.integers(0, vocab, (args.datasetSize, seq)).astype("int32")
        y = np.roll(x, -1, axis=1).astype("int32")
        crit = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion())
        return model, x, y, crit

    from bigdl_tpu.dataset import movielens
    from bigdl_tpu.nn.sparse import sparse_recommender

    folder = os.path.join(args.out, "ml-mini")
    if not os.path.exists(os.path.join(folder, "ratings.dat")):
        movielens.write_ratings(folder, seed=args.seed)
    pairs, ratings = movielens.get_id_pairs(folder)
    n_users = int(pairs[:, 0].max())
    n_ids = n_users + int(pairs[:, 1].max())
    x = movielens.to_id_features(pairs, n_users)
    y = (ratings - 1).astype("int32")
    model = sparse_recommender(n_ids)
    model.build(jax.ShapeDtypeStruct((2, 2), jnp.float32))
    return model, x, y, nn.CrossEntropyCriterion()


# --------------------------------------------------------------------------- #
# Trainer child: ordinary supervised training with snapshot cadence.
# --------------------------------------------------------------------------- #


def run_trainer(args):
    import jax

    from bigdl_tpu import optim
    from bigdl_tpu.dataset import SampleToMiniBatch, array_dataset

    print(f"[trainer] platform {jax.devices()[0].platform}",
          file=sys.stderr, flush=True)
    model, x, y, crit = build_workload(args)
    ds = array_dataset(x, y, seed=args.seed) >> SampleToMiniBatch(args.batch)
    opt = optim.LocalOptimizer(
        model, ds, crit,
        optim.SGD(learning_rate=args.lr, momentum=0.9, dampening=0.0))
    opt.set_checkpoint(os.path.join(args.out, "ckpt"),
                       optim.Trigger.several_iteration(args.ckptEvery))
    opt.set_end_when(optim.Trigger.max_iteration(args.steps))
    opt.optimize()
    return 0


def poison_params(params):
    """The PR 10 outlier-poisoning recipe on the model's OUTPUT plane:
    every out-channel's weight is crushed to ~zero except one huge
    input column, so the logits collapse onto a rank-1 ruin -- the
    candidate a shadow comparison must catch."""
    import numpy as np

    import jax

    from jax.tree_util import keystr, tree_flatten_with_path, \
        tree_unflatten

    leaves, treedef = tree_flatten_with_path(params)
    mats = [i for i, (p, l) in enumerate(leaves)
            if getattr(l, "ndim", 0) == 2]
    if not mats:
        raise ValueError("no 2-D weight plane to poison")
    # the OUTPUT projection: nothing (layernorm included) normalizes
    # after it, so the outliers reach the logits undamped
    heads = [i for i in mats if "head" in keystr(leaves[i][0])]
    out = [l for _, l in leaves]
    i = heads[-1] if heads else mats[-1]
    w = np.asarray(out[i]).copy() * 1e-5
    w.reshape(w.shape[0], -1)[:, 0] = \
        np.random.default_rng(9).standard_normal(w.shape[0]) * 1e3
    out[i] = jax.numpy.asarray(w)
    return tree_unflatten(treedef, out)


def write_poisoned_snapshot(args, model):
    """Drop a poisoned candidate into the checkpoint dir with a tag
    newer than anything the trainer wrote (manifest-stamped, so it
    passes intact-resolution -- the ROLLOUT must reject it, not the
    integrity layer)."""
    from bigdl_tpu.utils import file_io

    ckpt = os.path.join(args.out, "ckpt")
    os.makedirs(ckpt, exist_ok=True)
    target = os.path.join(ckpt, f"checkpoint.{args.steps + 1000}.pkl")
    file_io.atomic_save(
        {"model_params": poison_params(model.parameters()[0]),
         "model_state": None}, target)
    file_io.write_snapshot_manifest(target)
    return target


# --------------------------------------------------------------------------- #
# Driver: engine + registry + rollout + client load (+ chaos).
# --------------------------------------------------------------------------- #


def make_chaos(spec, out):
    """-> a ``chaos(stage, version)`` hook for the RolloutController,
    or None.  On the configured cutover it leaves a marker file (the
    drill's evidence the kill actually fired) and SIGKILLs the
    process."""
    from bigdl_tpu.serving.deploy import parse_deploy_chaos

    parsed = parse_deploy_chaos(spec)
    if parsed is None:
        return None
    _, _, nth = parsed
    count = {"n": 0}

    def chaos(stage, version):
        if stage != "cutover":
            return
        count["n"] += 1
        if count["n"] == nth:
            with open(os.path.join(out, "chaos_fired.json"), "w") as f:
                json.dump({"cutover": nth, "version": version.version},
                          f)
            print(f"[serve_live] chaos: SIGKILL mid-cutover "
                  f"#{nth} (v{version.version})", file=sys.stderr)
            sys.stderr.flush()
            os.kill(os.getpid(), signal.SIGKILL)

    return chaos


def probe_digest(engine, probe_rows, bucket):
    """Bit-for-bit serving fingerprint: each probe row through the
    UNBATCHED reference path (``predict_at`` at one fixed bucket --
    within one bucket shape logits are bit-exact), digested."""
    import numpy as np

    h = hashlib.sha256()
    for r in probe_rows:
        h.update(np.ascontiguousarray(
            np.asarray(engine.predict_at(r, bucket))).tobytes())
    return h.hexdigest()[:16]


def run_driver(args):
    import jax
    import numpy as np

    from bigdl_tpu.observability import StepTelemetry
    from bigdl_tpu.observability.metrics import (MetricsExporter,
                                                 MetricsRegistry,
                                                 SloObjective, SloTracker)
    from bigdl_tpu.serving import (ModelRegistry, RolloutController,
                                   ServingEngine)

    os.makedirs(args.out, exist_ok=True)
    chaos = make_chaos(args.chaos, args.out)   # fail fast on a typo
    model, x, y, crit = build_workload(args)
    # one serve dir per invocation (StepTelemetry truncates its dir):
    # a restarted server must never destroy the previous run's durable
    # deploy audit trail -- the chaos drill reads it post-mortem
    serve_dir = os.path.join(args.out, "serve")
    k = 1
    while os.path.exists(os.path.join(serve_dir, "telemetry.jsonl")):
        serve_dir = os.path.join(args.out, f"serve_r{k}")
        k += 1
    tel = StepTelemetry(serve_dir, run_name="serve", trace=False)
    metrics = MetricsRegistry()
    tel.attach_metrics(metrics)
    exporter = None
    if args.metricsPort is not None:
        exporter = MetricsExporter(metrics, port=args.metricsPort)
        print(f"[serve_live] metrics at {exporter.url}/metrics",
              file=sys.stderr)
    slo = None
    health_sources = [metrics.health]
    if args.sloLatencyMs is not None:
        slo = SloTracker([SloObjective(
            "serve_latency", kind="inference", field="request_latency_s",
            threshold=args.sloLatencyMs / 1e3, target=0.99,
            alerts=((2.0, 6.0, 2.0),), min_samples=20)],
            registry=metrics)
        slo.bind(tel)
        health_sources.append(slo.health_status)
        if exporter is not None:
            exporter.add_health_source(slo.health_status)

    eng = ServingEngine(model, max_batch_size=args.maxBatch,
                        max_wait_ms=args.maxWaitMs, telemetry=tel)
    eng.precompile(example_feature=x[0])
    execs0 = eng._executables()
    probe_rows = x[:4]
    probe_bucket = min(4, args.maxBatch)

    registry = ModelRegistry(os.path.join(args.out, "registry.json"))
    ctl = RolloutController(
        eng, registry, os.path.join(args.out, "ckpt"), telemetry=tel,
        shadow_fraction=args.shadowFraction,
        shadow_min_rows=args.shadowRows,
        min_top1_agreement=args.agreement,
        max_logit_rmse=args.maxLogitRmse,
        canary_fraction=args.canaryFraction,
        canary_min_ticks=args.canaryTicks,
        health_sources=health_sources,
        stage_timeout_s=args.stageTimeout,
        post_cutover_watch_s=args.watchSeconds, chaos=chaos)
    resumed = registry.live is not None
    if resumed:
        ctl.resume()
    else:
        ctl.baseline()

    history_path = os.path.join(args.out, "live_history.jsonl")

    def record_live():
        live = registry.live
        rec = {"version": live.version, "digest": live.digest,
               "probe": probe_digest(eng, probe_rows, probe_bucket),
               "ts": time.time()}
        with open(history_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
            f.flush()
            os.fsync(f.fileno())
        return rec

    record_live()

    # closed-loop clients
    stop = threading.Event()
    stats = {"ok": 0, "failed": 0}
    stats_lock = threading.Lock()

    def client(seed):
        idx = np.random.default_rng(seed)
        while not stop.is_set():
            try:
                eng.predict(x[int(idx.integers(0, len(x)))], timeout=30.0)
                with stats_lock:
                    stats["ok"] += 1
            except Exception:
                if stop.is_set():
                    return
                with stats_lock:
                    stats["failed"] += 1

    clients = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(args.clients)]
    for t in clients:
        t.start()

    trainer = None
    logf = None
    if not args.noTrainer:
        cmd = [sys.executable, os.path.abspath(__file__), "--role",
               "trainer", "--out", args.out, "--workload", args.workload,
               "--steps", str(args.steps), "--batch", str(args.batch),
               "--datasetSize", str(args.datasetSize),
               "--ckptEvery", str(args.ckptEvery), "--lr", str(args.lr),
               "--seed", str(args.seed)]
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        # this process holds whatever accelerator JAX found: the child
        # must not ask for it
        env["JAX_PLATFORMS"] = TRAINER_PLATFORM
        logf = open(os.path.join(args.out, "trainer.log"), "w")
        trainer = subprocess.Popen(cmd, env=env, stdout=logf,
                                   stderr=subprocess.STDOUT, cwd=REPO)
        print(f"[serve_live] trainer pid {trainer.pid} on "
              f"{TRAINER_PLATFORM}; driver on "
              f"{jax.devices()[0].platform}", file=sys.stderr)

    # the loop: poll -> rollout -> watch, until the trainer is done and
    # the checkpoint dir has gone quiet
    poisoned_path = None
    idle = 0
    last_live = registry.live.version
    try:
        while True:
            v = ctl.poll_once()
            ctl.check_watch()
            if registry.live.version != last_live:
                last_live = registry.live.version
                record_live()
            with stats_lock:
                tel.record("client", **stats)
            trainer_done = trainer is None or trainer.poll() is not None
            if trainer_done and args.poison and poisoned_path is None:
                poisoned_path = write_poisoned_snapshot(args, model)
                print(f"[serve_live] poisoned candidate: {poisoned_path}",
                      file=sys.stderr)
                idle = 0
                continue
            idle = idle + 1 if (trainer_done and v is None) else 0
            if idle >= args.idleRounds:
                break
            time.sleep(0.1)
    finally:
        stop.set()
        for t in clients:
            t.join(5)
        if trainer is not None and trainer.poll() is None:
            trainer.terminate()
            trainer.wait(30)
        if logf is not None:
            logf.close()

    final = record_live()
    compiles = eng._executables() - execs0
    eng.close()
    with stats_lock:
        client_stats = dict(stats)
    tel.record("client", **client_stats)
    tel.close()
    if exporter is not None:
        exporter.close()

    deploys = [{k: e.get(k) for k in ("version", "stage", "verdict",
                                      "reason")}
               for e in ctl.events]
    result = {
        "workload": args.workload,
        "serve_dir": serve_dir,
        "resumed": resumed,
        "live_version": registry.live.version,
        "live_digest": registry.live.digest,
        "probe_digest": final["probe"],
        "client": client_stats,
        "compiles_after_precompile": compiles,
        "deploys": deploys,
        "versions": registry.describe(),
        "platforms": {"driver": jax.devices()[0].platform,
                      "trainer": None if args.noTrainer
                      else TRAINER_PLATFORM},
    }
    tmp = os.path.join(args.out, "result.json.tmp")
    with open(tmp, "w") as f:
        json.dump(result, f, indent=1)
    os.replace(tmp, os.path.join(args.out, "result.json"))
    print(json.dumps(result))
    # acceptance posture: the loop is only healthy if no client request
    # failed and steady-state serving never compiled
    return 0 if client_stats["failed"] == 0 and compiles == 0 else 3


def main(argv=None):
    args = build_args(argv)
    if args.role == "trainer":
        return run_trainer(args)
    return run_driver(args)


if __name__ == "__main__":
    sys.exit(main())
