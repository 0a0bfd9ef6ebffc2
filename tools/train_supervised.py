"""Supervised (auto-restarting) training driver + chaos drill.

The command-line face of ``bigdl_tpu/optim/recovery.RunSupervisor``
(docs/robustness.md): a SUPERVISOR process spawns the actual training
run as a child process, watches it, and on process death (SIGKILL /
preemption included) restarts it from the last healthy snapshot with
capped exponential backoff -- optionally on a DIFFERENT device count
(the dp flat plane re-chunks N->M on resume).  Every restart lands as a
durable ``kind: "recovery"`` telemetry event in the supervisor's run
dir, rendered by ``tools/obs_report.py`` under "Recovery".

    # smoke drill: 8 host devices, SIGKILL after step 9, restart on 4
    python -m tools.train_supervised --out /tmp/drill --devices 8 \
        --restartDevices 4 --steps 24 --ckptEvery 4 --chaos kill:9

``--chaos kill:<step>`` is DETERMINISTIC fault injection (applied to
the first attempt only): the child SIGKILLs itself the moment that step
completes.  The slow-tier acceptance test drives exactly this drill and
pins the recovered loss trajectory against an uninterrupted baseline.

Artifacts under ``--out``:

- ``ckpt/``            -- the (crash-safe, manifest-stamped) snapshots
- ``attempt_<i>/``     -- each attempt's telemetry.jsonl + worker.log
                          + result.json (written on clean completion)
- ``supervisor/``      -- the supervisor's telemetry.jsonl (header +
                          recovery events)

One process for each chip: the supervisor never needs an accelerator and
always pins ITSELF to the CPU before anything imports jax, because a
parent that has initialised JAX on the chip holds it, and the worker it
spawns would then fail or hang.  Only the worker may be on the chip
(``--platform native``); every role's platform is printed and lands in
the final JSON line.

The workload is a small synthetic-classification MLP trained
data-parallel (ZeRO-1) over every visible device -- a drill, not a
benchmark; swap in a real entry point by supervising your own command
with ``RunSupervisor.run_process``.
"""

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def build_args(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    ap.add_argument("--out", required=True, help="artifact root directory")
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--datasetSize", type=int, default=256)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--devices", type=int, default=8,
                    help="host-platform device count of the first attempt")
    ap.add_argument("--restartDevices", type=int, default=None,
                    help="device count after a restart (default: same -- "
                         "set lower to drill the N->M resume)")
    ap.add_argument("--strategy", choices=("dp", "tp"), default="dp",
                    help="workload: dp = ZeRO-1 MLP (the PR 8 drill); "
                         "tp = tensor-parallel TransformerLM over a "
                         "(data, model) mesh")
    ap.add_argument("--tpDegree", type=int, default=4,
                    help="tensor-parallel degree of the first attempt "
                         "(--strategy tp; must divide --devices)")
    ap.add_argument("--restartStrategy", default=None,
                    help="layout after a restart, e.g. tp:2 -- the "
                         "resumed attempts come up on a DIFFERENT tp "
                         "degree and resume through the redistribution "
                         "engine (parallel/reshard.py)")
    ap.add_argument("--ckptEvery", type=int, default=4)
    ap.add_argument("--sharded", action="store_true",
                    help="sharded (orbax) snapshots instead of pickle")
    ap.add_argument("--chaos", default=None,
                    help="deterministic fault injection: kill:<step> "
                         "(first attempt only)")
    ap.add_argument("--maxRestarts", type=int, default=3)
    ap.add_argument("--metricsPort", type=int, default=None,
                    help="serve the supervisor's live restart/backoff "
                         "counters on http://127.0.0.1:PORT/metrics "
                         "(+ /healthz); 0 auto-assigns a port")
    ap.add_argument("--backoff", type=float, default=0.25,
                    help="exponential backoff base (seconds)")
    ap.add_argument("--backoffMax", type=float, default=10.0)
    ap.add_argument("--platform", choices=("cpu", "native"), default="cpu",
                    help="cpu: force a JAX_PLATFORMS=cpu host mesh of "
                         "--devices (hermetic drill); native: inherit the "
                         "environment's accelerator")
    # internal plumbing (the supervisor spawning itself as the worker)
    ap.add_argument("--role", choices=("supervisor", "worker"),
                    default="supervisor", help=argparse.SUPPRESS)
    ap.add_argument("--attempt", type=int, default=0,
                    help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def worker_env(base_env, args, attempt):
    """The child's environment: platform pin + per-attempt device count
    (restarts may come up on FEWER devices -- the N->M drill).  Under
    ``--platform native`` the worker gets back the ``JAX_PLATFORMS`` the
    supervisor was started with, not the supervisor's own CPU pin."""
    env = dict(base_env)
    inherited = getattr(args, "inherited_platforms", None)
    if inherited is None:
        env.pop("JAX_PLATFORMS", None)
    else:
        env["JAX_PLATFORMS"] = inherited
    # the child is spawned by FILE path (sys.path[0] = tools/); the repo
    # root must be importable regardless of how the supervisor was run
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if args.platform == "cpu":
        ndev = args.devices if attempt == 0 else \
            (args.restartDevices or args.devices)
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(f"--xla_force_host_platform_device_count={ndev}")
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = " ".join(flags)
    return env


# --------------------------------------------------------------------------- #
# Worker: one training attempt (the process the chaos drill kills).
# --------------------------------------------------------------------------- #


def _build_dp(args, nn, optim, array_dataset, SampleToMiniBatch):
    """The PR 8 drill workload: a ZeRO-1 MLP over every visible device."""
    import numpy as np

    rng = np.random.default_rng(args.seed)
    x = rng.standard_normal((args.datasetSize, 12)).astype("float32")
    w = rng.standard_normal((12, 5)).astype("float32")
    y = np.argmax(x @ w, axis=1).astype("int32")   # learnable structure
    ds = array_dataset(x, y, seed=args.seed) >> SampleToMiniBatch(
        args.batch)
    model = (nn.Sequential().add(nn.Linear(12, 32)).add(nn.ReLU())
             .add(nn.Linear(32, 5)))
    return optim.DistriOptimizer(
        model, ds, nn.CrossEntropyCriterion(),
        optim.SGD(learning_rate=args.lr, momentum=0.9, dampening=0.0))


def _build_tp(args, nn, optim, array_dataset, SampleToMiniBatch):
    """The elastic-tp drill workload: a tensor-parallel TransformerLM
    over a (data, model) mesh of every visible device.  ``--tpDegree``
    sizes the model axis; restarts may come up on a DIFFERENT degree
    (``--restartStrategy tp:<d>``) and resume through the
    redistribution engine (docs/robustness.md, "Portable
    resharding")."""
    import numpy as np

    import jax
    from bigdl_tpu.nn.attention import TransformerLM

    ndev = jax.device_count()
    tp = int(args.tpDegree)
    if ndev % tp:
        raise SystemExit(
            f"--tpDegree {tp} does not divide the {ndev} visible devices")
    mesh = jax.sharding.Mesh(
        np.asarray(jax.devices()).reshape(ndev // tp, tp),
        ("data", "model"))
    vocab, seq = 32, 16
    rng = np.random.default_rng(args.seed)
    x = rng.integers(0, vocab, (args.datasetSize, seq)).astype("int32")
    y = np.roll(x, -1, axis=1).astype("int32")     # learnable structure
    ds = array_dataset(x, y, seed=args.seed) >> SampleToMiniBatch(
        args.batch)
    model = TransformerLM(vocab, 32, 4, num_layers=2, max_len=seq)
    crit = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion())
    return optim.Optimizer(
        model, ds, crit,
        optim.SGD(learning_rate=args.lr, momentum=0.9, dampening=0.0),
        strategy="tp", mesh=mesh)


def run_worker(args):
    import jax

    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim
    from bigdl_tpu.dataset import SampleToMiniBatch, array_dataset
    from bigdl_tpu.observability import StepTelemetry
    from bigdl_tpu.optim.recovery import ChaosKillTrigger, parse_chaos
    from bigdl_tpu.utils.random_generator import RNG

    RNG.set_seed(args.seed)
    build = _build_tp if args.strategy == "tp" else _build_dp
    opt = build(args, nn, optim, array_dataset, SampleToMiniBatch)

    run_dir = os.path.join(args.out, f"attempt_{args.attempt}")
    tel = StepTelemetry(run_dir, run_name=f"attempt_{args.attempt}",
                        trace=False)
    opt.set_telemetry(tel)
    ckpt = os.path.join(args.out, "ckpt")
    trig = optim.Trigger.several_iteration(args.ckptEvery)
    if args.sharded:
        opt.set_sharded_checkpoint(ckpt, trig)
        opt.resume_from_sharded_checkpoint()
    else:
        opt.set_checkpoint(ckpt, trig)
        opt.resume_from_checkpoint()

    end = optim.Trigger.max_iteration(args.steps)
    chaos = parse_chaos(args.chaos)
    if chaos is not None:
        end = optim.Trigger.or_(ChaosKillTrigger(chaos[1]), end)
    opt.set_end_when(end)
    try:
        opt.optimize()
    finally:
        tel.close()
    loss = opt.driver_state.get("loss")   # absent when the resumed run
    result = {"neval": opt.driver_state["neval"],   # had no steps left
              "epoch": opt.driver_state["epoch"],
              "final_loss": None if loss is None else float(loss),
              "attempt": args.attempt,
              "platform": jax.devices()[0].platform,
              "device_count": jax.device_count()}
    with open(os.path.join(run_dir, "result.json"), "w") as f:
        json.dump(result, f)
    print(json.dumps(result))
    return 0


# --------------------------------------------------------------------------- #
# Supervisor: spawn -> watch -> restart.
# --------------------------------------------------------------------------- #


def run_supervisor(args):
    from bigdl_tpu.observability import StepTelemetry
    from bigdl_tpu.optim.recovery import (RunSupervisor,
                                          last_step_in_telemetry,
                                          parse_chaos,
                                          parse_restart_strategy)

    parse_chaos(args.chaos)            # fail fast on a typo'd drill spec
    restart_layout = parse_restart_strategy(args.restartStrategy)
    if restart_layout is not None and args.strategy != "tp":
        raise SystemExit(
            "--restartStrategy tp:<d> needs --strategy tp (dp restarts "
            "resize with --restartDevices)")
    os.makedirs(args.out, exist_ok=True)
    tel = StepTelemetry(os.path.join(args.out, "supervisor"),
                        run_name="supervisor", trace=False)
    exporter = None
    if args.metricsPort is not None:
        # live fleet telemetry for the supervisor tier: restart/backoff
        # counters scrapeable while the drill churns
        # (docs/observability.md, "Live metrics & SLOs")
        from bigdl_tpu.observability.metrics import (MetricsExporter,
                                                     MetricsRegistry)
        registry = MetricsRegistry()
        tel.attach_metrics(registry)
        exporter = MetricsExporter(registry, port=args.metricsPort)
        print(f"[supervisor] metrics at {exporter.url}/metrics",
              file=sys.stderr)
    sup = RunSupervisor(max_restarts=args.maxRestarts,
                        backoff_base_s=args.backoff,
                        backoff_max_s=args.backoffMax, telemetry=tel)
    logs = []

    def spawn(attempt):
        cmd = [sys.executable, os.path.abspath(__file__),
               "--role", "worker", "--attempt", str(attempt),
               "--out", args.out, "--steps", str(args.steps),
               "--batch", str(args.batch),
               "--datasetSize", str(args.datasetSize),
               "--lr", str(args.lr), "--seed", str(args.seed),
               "--ckptEvery", str(args.ckptEvery),
               "--strategy", args.strategy]
        if args.strategy == "tp":
            degree = args.tpDegree if attempt == 0 or \
                restart_layout is None else restart_layout[1]
            cmd += ["--tpDegree", str(degree)]
        if args.sharded:
            cmd.append("--sharded")
        if attempt == 0 and args.chaos:
            cmd += ["--chaos", args.chaos]   # the drill kills ONCE
        run_dir = os.path.join(args.out, f"attempt_{attempt}")
        os.makedirs(run_dir, exist_ok=True)
        logf = open(os.path.join(run_dir, "worker.log"), "w")
        logs.append(logf)
        print(f"[supervisor] attempt {attempt}: {' '.join(cmd)}",
              file=sys.stderr)
        return subprocess.Popen(cmd, env=worker_env(os.environ, args,
                                                    attempt),
                                stdout=logf, stderr=subprocess.STDOUT,
                                cwd=REPO)

    ckpt = os.path.join(args.out, "ckpt")
    probe = lambda: last_step_in_telemetry(
        os.path.join(args.out, f"attempt_{sup.restarts}",
                     "telemetry.jsonl"))
    try:
        restarts = sup.run_process(spawn, checkpoint_path=ckpt,
                                   probe_step=probe, sharded=args.sharded)
        rc = 0
    except RuntimeError as e:
        print(f"[supervisor] giving up: {e}", file=sys.stderr)
        restarts, rc = sup.restarts, 2
    finally:
        if exporter is not None:
            exporter.close()
        tel.close()
        for f in logs:
            f.close()
    result_path = os.path.join(args.out, f"attempt_{restarts}",
                               "result.json")
    result = None
    if rc == 0 and os.path.isfile(result_path):
        with open(result_path) as f:
            result = json.load(f)
    platforms = {"supervisor": "cpu",
                 "worker": (result or {}).get("platform")}
    print(f"[supervisor] platforms: {platforms}", file=sys.stderr)
    print(json.dumps({"restarts": restarts, "rc": rc, "result": result,
                      "platforms": platforms,
                      "recovery_events": sup.events}))
    return rc


def main(argv=None):
    args = build_args(argv)
    if args.role == "worker":
        return run_worker(args)
    # the supervisor itself never needs an accelerator and must not hold
    # the chip its worker needs: pin it to the CPU BEFORE any
    # jax-importing bigdl_tpu module loads, whatever --platform says
    args.inherited_platforms = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    return run_supervisor(args)


if __name__ == "__main__":
    sys.exit(main())
