#!/usr/bin/env python3
"""The readings a cell's limits are set from, in one process on the chip:
for each seed the program's numbers (through the driver's own path, with a
short window at the cell's own sizes), and for the seeds asked the
control's (the plain reference in the next lower precision, put in the
program's place) and each planted fault's, all against the float32
reference.  One JSON line per reading on standard output.

    python3 benchmark/tools/readings.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3] [--seconds 4]

The control's precision and the faults are the mix's (``control``,
``faults`` in the traffic file).  This tool sets no limit: PERF.md records
the readings and the limit chosen between them.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)


def say(**fields):
    print(json.dumps(fields), flush=True)


def values(checks):
    return {c["name"]: c["value"] for c in checks}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--bench", default=None)
    args = ap.parse_args(argv)
    ints = lambda s: [int(x) for x in s.split(",") if x]

    from run import compile_cache
    from harness.device import CompileCount, require_chips
    from harness.resolve import Cell, load_json

    cell = Cell(args.workload, load_json(args.bench) if args.bench else None)
    compile_cache()
    require_chips(cell.chips, args.rehearse)
    meter = CompileCount()
    control = cell.traffic.get("control")
    faults = cell.traffic.get("faults", [])
    kind = cell.traffic["driver"]
    for seed in ints(args.seeds):
        t0 = time.perf_counter()
        s = cell.driver.Session(cell, seed, args.rehearse)
        if kind == "train_loop":
            s.make_data()
            s.build()
            window = s.run(args.seconds)
            got = s.program_reading()
            s.free()
            ref = s.reference()
            say(seed=seed, what="program", **values(s.compare(got, ref)),
                train_step_ms=window["train_step_ms"],
                seconds=time.perf_counter() - t0)
            if seed in ints(args.control_seeds):
                say(seed=seed, what="control:" + control, **values(
                    s.compare(s.reference(mode=control), ref)))
            if seed in ints(args.fault_seeds):
                for fault in faults:
                    say(seed=seed, what="fault:" + fault, **values(
                        s.compare(s.reference(fault=fault), ref)))
        else:
            s.build()
            s.warm(meter)
            window = s.run(args.seconds, t0)
            sample = s.sample()
            s.free()
            gaps = s.reference_gaps(sample)
            say(seed=seed, what="program", **values(s.compare(gaps, window)),
                tokens_checked=len(gaps),
                serve_tokens_per_s=window["serve_tokens_per_s"],
                seconds=time.perf_counter() - t0)
            if seed in ints(args.control_seeds):
                cg = s.reference_gaps(sample, control=control)
                say(seed=seed, what="control:" + control,
                    **values(s.compare(cg, window)))
        del s
    return 0


if __name__ == "__main__":
    sys.exit(main())
