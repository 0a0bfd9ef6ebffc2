#!/usr/bin/env python3
"""The readings a serving cell's limits are set from, in one process on
the chip: for each seed the program's numbers (through the driver's own
path at the cell's own sizes; with ``--seconds`` the cell's
``run_seconds``, its ``serve_tokens_per_s`` is a run's), then on the same
sample of served requests each control asked (the plain reference in a
lower precision, put in the program's place) and each planted fault, all
through the driver's own comparison against the float32 reference.  One
JSON line per reading on standard output.

    python3 benchmark/tools/serve_readings.py --workload <cell> \
        --seeds 1,2 [--controls fp8,bf16_state] [--faults token_altered] \
        [--seconds 4] [--rehearse]

``--controls`` defaults to the mix's ``control``; a model file may offer
more precisions than that one (its ``reference_logits``' ``mode``).
Faults:

- ``token_altered``: in every sampled request, the served token in the
  middle of its answer is replaced by the next id of the vocabulary.

This tool sets no limit: PERF.md records the readings and the limit
chosen between them.
"""

import argparse
import copy
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


def token_altered(sample, vocab):
    """The sample with one served token a request changed."""
    out = []
    for r in sample:
        r = copy.copy(r)
        r.tokens = list(r.tokens)
        i = len(r.tokens) // 2
        r.tokens[i] = (r.tokens[i] + 1) % vocab
        out.append(r)
    return out


FAULTS = {"token_altered": token_altered}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default=None)
    ap.add_argument("--faults", default="")
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    words = lambda s: [x for x in s.split(",") if x]

    from run import compile_cache
    from harness.device import CompileCount, require_chips
    from harness.resolve import Cell

    cell = Cell(args.workload)
    if cell.traffic["driver"] != "closed_loop_generate":
        raise SystemExit("serve_readings reads generation cells only")
    compile_cache()
    require_chips(cell.chips, args.rehearse)
    controls = words(args.controls if args.controls is not None
                     else cell.traffic.get("control", ""))
    faults = words(args.faults)
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise SystemExit(f"unknown faults {sorted(unknown)}")
    meter = CompileCount()
    for seed in (int(x) for x in words(args.seeds)):
        t0 = time.perf_counter()
        s = cell.driver.Session(cell, seed, args.rehearse)
        s.build()
        s.warm(meter)
        window = s.run(args.seconds, t0)
        sample = s.sample()
        s.free()
        gaps = s.reference_gaps(sample)
        say(seed=seed, what="program", **values(s.compare(gaps, window)),
            tokens_checked=len(gaps),
            serve_tokens_per_s=window["serve_tokens_per_s"],
            requests_started=window["requests_started"],
            tokens=window["tokens"], seconds=time.perf_counter() - t0)
        for control in controls:
            say(seed=seed, what="control:" + control, **values(s.compare(
                s.reference_gaps(sample, control=control), window)))
        for fault in faults:
            bad = FAULTS[fault](sample, int(s.cfg["vocab_size"]))
            say(seed=seed, what="fault:" + fault,
                **values(s.compare(s.reference_gaps(bad), window)))
        del s
    return 0


if __name__ == "__main__":
    sys.exit(main())
