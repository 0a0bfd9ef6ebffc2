#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process for every run.  It finds the cell's files by name (see
``harness/resolve.py``), fails without a result when JAX finds no TPU or
fewer chips than the cell asks for, sets everything up from ``--seed``,
measures for ``--seconds``, decides ``correct`` against the plain
reference once the window has closed, and prints the result as the LAST
line of standard output.  With ``--trace 0`` the metrics are the cell's
end-to-end metrics; with ``--trace 1`` a profiler trace of part of the
window is taken and the metrics are the cell's per-layer metrics.

``--rehearse`` runs the same path at the toy sizes of the files'
``rehearsal`` blocks on whatever platform JAX finds, and prints a line that
holds NO metric: a number from the CPU is never written under the name of
a device metric.
"""

import time

T_START = time.perf_counter()      # process start, as near as python gets

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)           # harness/
sys.path.insert(0, ROOT)           # the program: bigdl_tpu


class Context:
    """What a driver is handed."""

    def __init__(self, cell, args, devices):
        self.cell = cell
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.rehearse = args.rehearse
        self.devices = devices
        self.t_start = T_START
        # inside the checkout, fixed, git-ignored; emptied before and after
        self.trace_dir = os.path.join(ROOT, ".bench_tmp", cell.name)
        self.timeline = {}
        self.memory_stats = {}

    def mark(self, name):
        """Seconds since process start at which set-up reached ``name``."""
        self.timeline[name] = round(time.perf_counter() - T_START, 3)

    def memory_peak(self):
        """Read once the window has closed, before the reference runs."""
        from harness.device import memory_peak_bytes

        self.memory_stats = {k: v for k, v in (
            self.devices[0].memory_stats() or {}).items()
            if isinstance(v, (int, float))}
        return memory_peak_bytes(self.devices)


def compile_cache():
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), for every
    program however small; set before the program can choose another."""
    path = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                 os.path.join(ROOT, ".jax_cache"))
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def per_layer_metrics(cell, result, devices, ctx):
    """Every per-layer metric of the cell through its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    from harness import trace
    from harness.device import peaks

    planes = trace.load(ctx.trace_dir)
    summary = trace.summary(planes)
    if summary is None:
        raise RuntimeError("the trace holds no operation on a device")
    env = {"planes": planes, "summary": summary,
           "counters": result["counters"], "cell": cell,
           "config": result.get("config", cell.config),
           "mix": result.get("mix", cell.traffic), "model": cell.model,
           "peaks": peaks(devices[0].device_kind)}
    out = {}
    for m in cell.metrics("per_layer"):
        spec = cell.metric_file(m["name"])
        value = cell.reader(spec["reader"]).read(env, spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out, summary


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--bench", default=None,
                    help="another BENCHMARK.json (to try a cell before "
                         "it is entered)")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the trace under .bench_tmp/ (to look at "
                         "one by hand)")
    args = ap.parse_args(argv)

    from harness.resolve import Cell, benchmark_json, load_json

    bench = load_json(args.bench) if args.bench else benchmark_json()
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    cell = Cell(args.workload, bench)

    compile_cache()
    from harness.device import (CompileCount, NoChip, describe,
                                require_chips)

    try:
        devices = require_chips(cell.chips, args.rehearse)
    except NoChip as e:
        print(f"benchmark: {e}; no result", file=sys.stderr)
        return 2
    compiles = CompileCount()
    ctx = Context(cell, args, devices)
    ctx.mark("jax_up")
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)

    try:
        result = cell.driver.run(ctx)
        checks = result["checks"]
        from harness.compare import all_ok

        correct = all_ok(checks)
        device = describe(devices,
                          memory_peak_bytes=result["memory_peak_bytes"])
        line = {"correct": correct, "attempted": result["attempted"],
                "failed": result["failed"]}
        if args.trace:
            metrics, summary = per_layer_metrics(cell, result, devices, ctx)
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            line["breakdown"] = summary["breakdown"]
        else:
            wanted = {m["name"]: m["unit"]
                      for m in cell.metrics("end_to_end")}
            metrics = {k: {"value": float(v), "unit": wanted[k]}
                       for k, v in result["end_to_end"].items()
                       if k in wanted}
    finally:
        if not args.keep_trace:
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)

    line["metrics"] = metrics
    line["device"] = device
    line["info"] = {k: v for k, v in result["counters"].items()
                    if isinstance(v, (int, float))}
    line["info"].update(timeline=ctx.timeline,
                        memory_stats=ctx.memory_stats, compiles=compiles.compiles,
                        cache_hits=compiles.hits, seed=args.seed,
                        total_s=time.perf_counter() - T_START)
    line["checks"] = [{k: c[k] for k in ("name", "value", "limit")}
                      for c in checks]
    for c in checks:
        print(f"check {c['name']}: {c['value']:.6g} (limit {c['limit']})"
              f"{'' if c['ok'] else '  <-- over'}", file=sys.stderr)
    sys.stderr.flush()
    if args.rehearse:
        print(json.dumps({"rehearsal": "passed" if correct else "failed",
                          "device": describe(devices),
                          "checks": line["checks"]}))
        return 0
    print(json.dumps(line))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
