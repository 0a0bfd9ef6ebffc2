"""The fleet chaos drill: N serving replicas under closed-loop client
load survive a SIGKILL and a rolling deploy with ZERO failed requests.

The command-line face of ``bigdl_tpu/serving/fleet.py``
(docs/robustness.md, "Serving fleets").  The DRIVER process runs
replica 0 in-process (the staged-exposure engine) plus ``--replicas``-1
subprocess workers (``--role worker`` re-invocations of this script,
speaking the ``serving/worker.py`` length-prefixed socket protocol),
all behind one ``ServingFleet``.  A trainer child
(``tools/serve_live.py --role trainer``) writes crash-safe snapshots;
the ``RolloutController`` walks each one through shadow -> canary on
replica 0, then a ROLLING cutover across the fleet -- drain one
replica, per-replica gate, commit, undrain, next -- while the clients
keep hammering ``fleet.predict``.

    # the acceptance drill: 3 replicas, kill replica 1 after ~40
    # completed client requests (post-first-promotion)
    python -m tools.serve_fleet --out /tmp/fleet --replicas 3 \\
        --chaos kill:replica:1@40

    # per-replica gate failure: replica 1's gate rejects -> the touched
    # replicas roll back, the untouched never left the old version
    python -m tools.serve_fleet --out /tmp/fleet2 --failGate 1

The acceptance posture lands in ``result.json``: client
``ok``/``failed``/``shed`` counts, fleet ``retries``/``hedges``,
supervisor restarts, the live version, and the bit-for-bit probe-digest
comparison between the driver's engine and every worker (a restarted
worker boots from the registry's COMMITTED version, so its digest must
match).  Exit 0 only when zero client requests failed, steady-state
serving never compiled, and -- under ``--chaos`` -- the killed replica
was restarted and rejoined bit-for-bit.

Artifacts under ``--out``: ``ckpt/`` (trainer snapshots),
``registry.json``, ``serve*/telemetry.jsonl`` (deploy + fleet audit
trail, obs_report-renderable), ``replica_<i>.log`` / ``.port``,
``trainer.log``, ``result.json``.

One process for each chip: this drill's replicas are PROCESSES whose
probe digests must agree bit for bit, and the driver (replica 0 runs in
it) would hold the chip that every worker and the trainer then ask for.
So every role runs on the CPU: ``main`` pins ``JAX_PLATFORMS=cpu`` for
itself and its children, refuses a caller who asked for another
platform, and every role prints the platform it came up on.
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:                      # --role worker re-invocation
    sys.path.insert(0, REPO)


def build_args(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    ap.add_argument("--out", required=True, help="artifact root directory")
    ap.add_argument("--workload", choices=("transformer", "movielens"),
                    default="transformer")
    ap.add_argument("--replicas", type=int, default=3,
                    help="fleet size: replica 0 in-process, the rest "
                         "subprocess workers")
    ap.add_argument("--steps", type=int, default=12,
                    help="trainer steps (a snapshot every --ckptEvery)")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--datasetSize", type=int, default=256)
    ap.add_argument("--ckptEvery", type=int, default=6)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--maxBatch", type=int, default=8)
    ap.add_argument("--maxWaitMs", type=float, default=1.0)
    ap.add_argument("--kvCacheDtype", choices=("fp32", "int8"),
                    default="fp32",
                    help="paged KV block storage dtype on every replica")
    ap.add_argument("--speculative", type=int, default=0,
                    help="draft tokens per verify step (0 disables; "
                    "the int8 twin drafts, the fp32 model verifies)")
    ap.add_argument("--clients", type=int, default=3,
                    help="closed-loop client threads")
    ap.add_argument("--hedge", action="store_true",
                    help="enable tail-latency hedging")
    ap.add_argument("--shadowRows", type=int, default=16)
    ap.add_argument("--canaryTicks", type=int, default=4)
    ap.add_argument("--maxLogitRmse", type=float, default=100.0)
    ap.add_argument("--stageTimeout", type=float, default=60.0)
    ap.add_argument("--drainTimeout", type=float, default=10.0)
    ap.add_argument("--chaos", default=None,
                    help="fleet fault injection: kill:replica:<i>@<tick>"
                         " (SIGKILL worker i once <tick> client requests"
                         " completed AND a version was promoted)")
    ap.add_argument("--failGate", type=int, default=None,
                    help="inject a per-replica deploy gate that fails "
                         "on this replica id (the rolling-rollback leg)")
    ap.add_argument("--noTrainer", action="store_true")
    ap.add_argument("--idleRounds", type=int, default=10,
                    help="stop after this many quiet poll rounds once "
                         "the trainer exited and chaos resolved")
    ap.add_argument("--maxSeconds", type=float, default=420.0,
                    help="hard wall deadline for the whole drill: a "
                         "rejoin that never happens must FAIL the "
                         "drill, not hang it")
    ap.add_argument("--metricsPort", type=int, default=None,
                    help="serve /metrics + /healthz (0 auto-assigns)")
    ap.add_argument("--traceSample", type=float, default=None,
                    help="head-sample rate for per-request distributed "
                         "tracing (1.0 = every request; default: the "
                         "BIGDL_TRACE_SAMPLE env, 0.01).  Spans land in "
                         "serve*/traces.jsonl (driver) and "
                         "worker_<i>/traces.jsonl, stitchable with "
                         "tools/trace_report.py")
    ap.add_argument("--transport", choices=("binary", "pickle"),
                    default="binary",
                    help="fleet wire protocol: the zero-copy binary "
                         "frame protocol (serving/transport.py) or the "
                         "legacy pickle wire")
    ap.add_argument("--weightWire", choices=("fp32", "int8"),
                    default="fp32",
                    help="weight-distribution encoding for rolling "
                         "deploys (int8 = blockwise-quantized staging "
                         "traffic, binary transport only)")
    # internal spellings: this script spawning itself
    ap.add_argument("--role", choices=("driver", "worker"),
                    default="driver", help=argparse.SUPPRESS)
    ap.add_argument("--replicaId", type=int, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--portFile", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--registry", default=None, help=argparse.SUPPRESS)
    return ap.parse_args(argv)


# --------------------------------------------------------------------------- #
# Worker role: one engine behind the socket protocol.
# --------------------------------------------------------------------------- #


def run_worker(args):
    import jax

    from tools.serve_live import build_workload

    from bigdl_tpu.serving import ServingEngine
    from bigdl_tpu.serving.worker import ReplicaServer, boot_from_registry

    model, x, y, crit = build_workload(args)   # fixed seed: the driver's
    #                                            tree structure + weights
    tel = None
    if args.traceSample is not None and args.traceSample > 0:
        # the worker-side traces.jsonl sink: engine spans for requests
        # whose sampled context crossed the wire land HERE, in this
        # process's artifact dir -- trace_report stitches them back to
        # the driver's spans by trace_id
        from bigdl_tpu.observability import StepTelemetry

        wdir = os.path.join(args.out, f"worker_{args.replicaId}")
        k = 1
        while os.path.exists(wdir):   # a respawn keeps its predecessor's
            wdir = os.path.join(      # trace evidence intact
                args.out, f"worker_{args.replicaId}_r{k}")
            k += 1
        tel = StepTelemetry(wdir, run_name=f"worker_{args.replicaId}",
                            trace=False)
    eng = ServingEngine(model, max_batch_size=args.maxBatch,
                        max_wait_ms=args.maxWaitMs, telemetry=tel,
                        kv_cache_dtype=args.kvCacheDtype,
                        speculative=args.speculative)
    eng.precompile(example_feature=x[0])
    booted = boot_from_registry(eng, args.registry)
    probe_bucket = min(4, args.maxBatch)
    srv = ReplicaServer(eng, port=0, probe_features=x[:4],
                        probe_bucket=probe_bucket,
                        transport=args.transport)
    if args.portFile:
        tmp = args.portFile + ".tmp"
        with open(tmp, "w") as f:           # atomic: a half-written port
            f.write(str(srv.port))          # file must not be readable
        os.replace(tmp, args.portFile)
    print(f"[worker {args.replicaId}] platform "
          f"{jax.devices()[0].platform}, serving on port {srv.port}"
          + (f", booted v{booted[0]}" if booted else ", boot weights"),
          file=sys.stderr)
    sys.stderr.flush()
    srv.serve_forever()
    return 0


# --------------------------------------------------------------------------- #
# Driver role: fleet + supervisor + rollout + clients + chaos.
# --------------------------------------------------------------------------- #


def make_spawn(args, rid):
    """-> ``spawn(attempt) -> (Popen, port)`` for worker ``rid``,
    blocking until the worker's atomic port file appears (the worker
    writes it only after its engine is precompiled and the server is
    listening, so a returned worker is ready to serve)."""
    port_file = os.path.join(args.out, f"replica_{rid}.port")

    def spawn(attempt):
        if os.path.exists(port_file):
            os.remove(port_file)
        cmd = [sys.executable, os.path.abspath(__file__),
               "--role", "worker", "--out", args.out,
               "--workload", args.workload, "--seed", str(args.seed),
               "--datasetSize", str(args.datasetSize),
               "--maxBatch", str(args.maxBatch),
               "--maxWaitMs", str(args.maxWaitMs),
               "--replicaId", str(rid), "--portFile", port_file,
               "--kvCacheDtype", args.kvCacheDtype,
               "--speculative", str(args.speculative),
               "--transport", args.transport,
               "--registry", os.path.join(args.out, "registry.json")]
        if args.traceSample is not None:
            cmd += ["--traceSample", str(args.traceSample)]
        env = dict(os.environ)        # carries main()'s JAX_PLATFORMS=cpu
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        logf = open(os.path.join(args.out, f"replica_{rid}.log"), "a")
        logf.write(f"--- spawn attempt {attempt} ---\n")
        proc = subprocess.Popen(cmd, env=env, stdout=logf,
                                stderr=subprocess.STDOUT, cwd=REPO)
        logf.close()                      # the child owns the fd now
        deadline = time.time() + 180
        while time.time() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"worker {rid} died during boot (rc={proc.poll()}, "
                    f"see replica_{rid}.log)")
            if os.path.exists(port_file):
                port = open(port_file).read().strip()
                if port:
                    return proc, int(port)
            time.sleep(0.1)
        proc.kill()
        raise RuntimeError(f"worker {rid} boot timed out")

    return spawn


def run_driver(args):
    import jax
    import numpy as np

    from tools.serve_live import build_workload

    from bigdl_tpu.observability import StepTelemetry
    from bigdl_tpu.observability.metrics import (MetricsExporter,
                                                 MetricsRegistry)
    from bigdl_tpu.serving import (FleetOverloadedError, FleetSupervisor,
                                   InProcessReplica, ModelRegistry,
                                   RolloutController, ServingEngine,
                                   ServingFleet, SubprocessReplica)
    from bigdl_tpu.serving.deploy import parse_fleet_chaos
    from bigdl_tpu.serving.worker import probe_digest

    os.makedirs(args.out, exist_ok=True)
    if args.transport == "binary" and "BIGDL_RUN_TOKEN" not in os.environ:
        # mint the shared handshake secret BEFORE any worker spawns:
        # the Popen env is a copy of os.environ, so every worker (and
        # every respawn) inherits the same token as the driver's pools
        from bigdl_tpu.serving.transport import mint_run_token

        os.environ["BIGDL_RUN_TOKEN"] = mint_run_token()
    chaos = parse_fleet_chaos(args.chaos)      # fail fast on a typo
    if chaos is not None and not 1 <= chaos[1] < args.replicas:
        # fail at ARGUMENT time, not minutes in at fire time: replica 0
        # is the in-process exposure replica, only workers can be shot
        from bigdl_tpu.utils.errors import ConfigurationError

        raise ConfigurationError(
            f"chaos target replica {chaos[1]} must be a subprocess "
            f"worker id in [1, {args.replicas - 1}] (replica 0 is the "
            f"driver's in-process exposure replica)")
    model, x, y, crit = build_workload(args)
    serve_dir = os.path.join(args.out, "serve")
    k = 1
    while os.path.exists(os.path.join(serve_dir, "telemetry.jsonl")):
        serve_dir = os.path.join(args.out, f"serve_r{k}")
        k += 1
    tel = StepTelemetry(serve_dir, run_name="serve_fleet", trace=False)
    metrics = MetricsRegistry()
    tel.attach_metrics(metrics)
    exporter = None
    if args.metricsPort is not None:
        exporter = MetricsExporter(metrics, port=args.metricsPort)
        print(f"[serve_fleet] metrics at {exporter.url}/metrics",
              file=sys.stderr)

    eng0 = ServingEngine(model, max_batch_size=args.maxBatch,
                         max_wait_ms=args.maxWaitMs, telemetry=tel,
                         kv_cache_dtype=args.kvCacheDtype,
                         speculative=args.speculative)
    eng0.precompile(example_feature=x[0])
    execs0 = eng0._executables()
    probe_rows = x[:4]
    probe_bucket = min(4, args.maxBatch)

    replicas = [InProcessReplica(eng0, rid=0)]
    for rid in range(1, args.replicas):
        rep = SubprocessReplica(make_spawn(args, rid), rid=rid,
                                transport=args.transport,
                                weight_wire=args.weightWire)
        rep.start(0)
        replicas.append(rep)
    fleet = ServingFleet(replicas, telemetry=tel, metrics=metrics,
                         hedge=args.hedge, probe_features=probe_rows,
                         probe_bucket=probe_bucket,
                         breaker_reset_s=1.0, retry_backoff_s=0.02,
                         trace_sample=args.traceSample)
    supervisor = FleetSupervisor(fleet, max_restarts=3,
                                 backoff_base_s=0.3, backoff_max_s=5.0,
                                 jitter=0.25).start()

    registry = ModelRegistry(os.path.join(args.out, "registry.json"))
    replica_gate = None
    if args.failGate is not None:
        def replica_gate(rid, flt, handle, _bad=int(args.failGate)):
            if rid == _bad:
                return False, "injected failing per-replica gate"
            return flt.gate_replica(rid, handle)
    ctl = RolloutController(
        fleet, registry, os.path.join(args.out, "ckpt"), telemetry=tel,
        shadow_fraction=0.5, shadow_min_rows=args.shadowRows,
        min_top1_agreement=None, max_logit_rmse=args.maxLogitRmse,
        canary_fraction=0.25, canary_min_ticks=args.canaryTicks,
        health_sources=[metrics.health],
        stage_timeout_s=args.stageTimeout,
        drain_timeout_s=args.drainTimeout, replica_gate=replica_gate)
    resumed = registry.live is not None
    if resumed:
        ctl.resume()
    else:
        ctl.baseline()

    # closed-loop clients
    stop = threading.Event()
    stats = {"ok": 0, "failed": 0, "shed": 0}
    stats_lock = threading.Lock()

    def client(seed):
        idx = np.random.default_rng(seed)
        while not stop.is_set():
            try:
                fleet.predict(x[int(idx.integers(0, len(x)))],
                              timeout=30.0)
                with stats_lock:
                    stats["ok"] += 1
            except FleetOverloadedError:
                with stats_lock:
                    stats["shed"] += 1
                time.sleep(0.01)
            except Exception as e:
                if stop.is_set():
                    return
                with stats_lock:
                    stats["failed"] += 1
                print(f"[serve_fleet] CLIENT FAILURE: {e}",
                      file=sys.stderr)

    clients = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(args.clients)]
    for t in clients:
        t.start()

    trainer = None
    if not args.noTrainer:
        cmd = [sys.executable,
               os.path.join(REPO, "tools", "serve_live.py"), "--role",
               "trainer", "--out", args.out, "--workload", args.workload,
               "--steps", str(args.steps), "--batch", str(args.batch),
               "--datasetSize", str(args.datasetSize),
               "--ckptEvery", str(args.ckptEvery), "--lr", str(args.lr),
               "--seed", str(args.seed)]
        env = dict(os.environ)        # carries main()'s JAX_PLATFORMS=cpu
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        logf = open(os.path.join(args.out, "trainer.log"), "w")
        trainer = subprocess.Popen(cmd, env=env, stdout=logf,
                                   stderr=subprocess.STDOUT, cwd=REPO)
        logf.close()
        print(f"[serve_fleet] trainer pid {trainer.pid}", file=sys.stderr)

    chaos_record = None
    rejoined = None
    idle = 0
    t_start = time.time()
    try:
        while True:
            v = ctl.poll_once()
            ctl.check_watch()
            with stats_lock:
                done_reqs = stats["ok"]
                tel.record("client", **stats)
            # chaos: SIGKILL the configured worker once enough client
            # requests completed AND a real snapshot version was
            # promoted (so the restart demonstrably boots from the
            # registry's COMMITTED version, not just boot weights)
            if chaos is not None and chaos_record is None \
                    and done_reqs >= chaos[2] \
                    and registry.live.path is not None:
                _, rid, _ = chaos
                rep = fleet._by_id(rid)
                if rep.kind != "subprocess" or rep.proc is None:
                    raise RuntimeError(
                        f"chaos target replica {rid} is not a "
                        f"subprocess worker")
                chaos_record = {"replica": rid, "pid": rep.proc.pid,
                                "at_requests": done_reqs,
                                "live_version": registry.live.version}
                print(f"[serve_fleet] chaos: SIGKILL replica {rid} "
                      f"(pid {rep.proc.pid}) at {done_reqs} requests",
                      file=sys.stderr)
                os.kill(rep.proc.pid, signal.SIGKILL)
                with open(os.path.join(args.out, "chaos_fired.json"),
                          "w") as f:
                    json.dump(chaos_record, f)
            # after a chaos kill: wait for the supervisor to bring the
            # replica back, then verify it serves the committed version
            # bit-for-bit
            if chaos_record is not None and rejoined is None:
                rep = fleet._by_id(chaos_record["replica"])
                if rep.state == "serving" and rep.alive() \
                        and rep.proc.pid != chaos_record["pid"]:
                    health = rep.health()
                    rejoined = {
                        "replica": rep.rid, "pid": rep.proc.pid,
                        "version": (health.get("version") or {}),
                        # the version the fleet was live on AT REJOIN
                        # time -- a later promotion (which the rolling
                        # deploy applies to this replica too) must not
                        # fail the comparison
                        "expected_version": registry.live.version,
                        "probe": rep.probe(bucket=probe_bucket),
                        "driver_probe": probe_digest(eng0, probe_rows,
                                                     probe_bucket)}
                    print(f"[serve_fleet] replica {rep.rid} rejoined: "
                          f"{rejoined}", file=sys.stderr)
            trainer_done = trainer is None or trainer.poll() is not None
            chaos_target_gone = chaos_record is not None and \
                fleet._by_id(chaos_record["replica"]).state == "closed"
            chaos_done = chaos is None or rejoined is not None \
                or chaos_target_gone
            idle = idle + 1 if (trainer_done and v is None
                                and chaos_done) else 0
            if idle >= args.idleRounds:
                break
            if time.time() - t_start > args.maxSeconds:
                # never hang the drill: time out with whatever posture
                # we have (a missing rejoin then fails the exit check)
                print("[serve_fleet] drill wall deadline reached",
                      file=sys.stderr)
                break
            time.sleep(0.1)
    finally:
        stop.set()
        for t in clients:
            t.join(5)
        if trainer is not None and trainer.poll() is None:
            trainer.terminate()
            trainer.wait(30)
        supervisor.close()

    worker_probes = {}
    for rep in fleet.replicas:
        if rep.kind == "subprocess" and rep.state == "serving":
            try:
                worker_probes[rep.rid] = rep.probe(bucket=probe_bucket)
            except Exception as e:
                worker_probes[rep.rid] = f"unreachable: {e}"
    driver_probe = probe_digest(eng0, probe_rows, probe_bucket)
    compiles = eng0._executables() - execs0
    counters = fleet.counters()
    states = {rid: {k: d[k] for k in ("kind", "state", "served",
                                      "failed", "breaker")}
              for rid, d in fleet.replica_states().items()}
    fleet.close()
    with stats_lock:
        client_stats = dict(stats)
    tel.record("client", **client_stats)
    tel.close()
    if exporter is not None:
        exporter.close()

    probes_ok = all(p == driver_probe for p in worker_probes.values())
    rejoin_ok = chaos is None or (
        rejoined is not None
        and rejoined["probe"] == rejoined["driver_probe"]
        and rejoined["version"].get("version")
        == rejoined["expected_version"])
    result = {
        "workload": args.workload,
        "serve_dir": serve_dir,
        "resumed": resumed,
        "replicas": args.replicas,
        "live_version": registry.live.version,
        "live_digest": registry.live.digest,
        "client": client_stats,
        "fleet": counters,
        "replica_states": states,
        "supervisor_restarts": supervisor.events,
        "chaos": chaos_record,
        "rejoined": rejoined,
        "driver_probe": driver_probe,
        "worker_probes": worker_probes,
        "probes_match": probes_ok,
        "compiles_after_precompile": compiles,
        "deploys": [{k: e.get(k) for k in ("version", "stage",
                                           "verdict", "reason",
                                           "replica")}
                    for e in ctl.events],
        "versions": registry.describe(),
        "platforms": {"driver": jax.devices()[0].platform,
                      "workers": os.environ["JAX_PLATFORMS"],
                      "trainer": None if args.noTrainer
                      else os.environ["JAX_PLATFORMS"]},
    }
    print(f"[serve_fleet] platforms: {result['platforms']}",
          file=sys.stderr)
    tmp = os.path.join(args.out, "result.json.tmp")
    with open(tmp, "w") as f:
        json.dump(result, f, indent=1)
    os.replace(tmp, os.path.join(args.out, "result.json"))
    print(json.dumps(result))
    # acceptance posture: zero failed client requests, zero
    # steady-state compiles, every reachable replica bit-for-bit on the
    # live version, and -- under chaos -- a verified rejoin
    ok = (client_stats["failed"] == 0 and compiles == 0
          and probes_ok and rejoin_ok)
    return 0 if ok else 3


def main(argv=None):
    args = build_args(argv)
    asked = os.environ.get("JAX_PLATFORMS")
    if asked not in (None, "", "cpu"):
        raise SystemExit(
            f"serve_fleet runs every role on the CPU (JAX_PLATFORMS="
            f"{asked!r} asked otherwise): its replicas are processes, a "
            f"chip belongs to one process at a time, and the driver "
            f"would hold the one its workers need")
    os.environ["JAX_PLATFORMS"] = "cpu"
    if args.role == "worker":
        return run_worker(args)
    return run_driver(args)


if __name__ == "__main__":
    sys.exit(main())
