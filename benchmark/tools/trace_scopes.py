#!/usr/bin/env python3
"""One trace by program and by what the program called its operations.

    python3 benchmark/tools/trace_scopes.py <trace dir or .xplane.pb> [--top N] [--text CHARS]

For the first device, one block a program: its runs and the ms a run
(mean and median), then the ms a run of its leaf operations by scope x
phase, then the N heaviest operations INSIDE that program with their
scope, phase, ``hlo_category`` and HLO text; last, the time under no
scope by ``hlo_category``, program and whether the operation carries a
``tf_op`` path at all, with the heaviest such operations.
Scope, phase and program are read from the trace's own operation
metadata (``harness/trace_meta.py``): an operation is counted in the
program its ``program_id`` names, so a short name that two programs
share (``fusion.93``) is never summed across them.  This is what a
``perf_opt`` issue quotes (``run.py --trace 1 --keep-trace`` leaves the
trace under ``.bench_tmp/<cell>``).
"""

import argparse
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import trace, trace_meta  # noqa: E402

NO_SCOPE = "(no scope)"


def by_program(meta):
    """``{program name: [indices of its leaf operations]}``, the program
    that took most device time first."""
    out = {}
    for i, op in enumerate(meta.op):
        out.setdefault(op.program or "(no program)", []).append(i)
    return dict(sorted(out.items(),
                       key=lambda kv: -meta.dur[kv[1]].sum()))


def scope_table(meta, idx, runs):
    """``[(scope, {phase: ms a run}, ms a run)]`` with the heaviest scope
    first, then the total row."""
    cells = {}
    for i in idx:
        op = meta.op[i]
        row = cells.setdefault(op.scope or NO_SCOPE, {})
        row[op.phase] = row.get(op.phase, 0.0) + meta.dur[i] * 1e-6 / runs
    rows = sorted(((s, r, sum(r.values())) for s, r in cells.items()),
                  key=lambda t: -t[2])
    total = {p: sum(r.get(p, 0.0) for _, r, _ in rows)
             for p in trace_meta.PHASES}
    return rows + [("total", total, sum(total.values()))]


def heaviest(meta, idx, runs, top):
    """``[(ms a run, events, operation index)]`` summed by operation
    (one ``XEventMetadata``: one instruction of one program)."""
    total = {}
    for i in idx:
        ms, n, _ = total.get(id(meta.op[i]), (0.0, 0, i))
        total[id(meta.op[i])] = (ms + meta.dur[i] * 1e-6 / runs, n + 1, i)
    return sorted(total.values(), key=lambda t: -t[0])[:top]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path")
    ap.add_argument("--top", type=int, default=12)
    ap.add_argument("--text", type=int, default=240,
                    help="characters of an operation's HLO text")
    ap.add_argument("--min-ms", type=float, default=1.0,
                    help="leave out programs under this many ms in all")
    args = ap.parse_args(argv)

    file = trace.newest_xplane(args.path) if os.path.isdir(args.path) \
        else args.path
    print("file", file, os.path.getsize(file), "bytes")
    meta = trace_meta.load(file)
    if meta is None:
        print("no operation on a TPU device plane")
        return 1
    busy = meta.busy_ns * 1e-6
    named = meta.dur[meta.select(named=True)].sum() * 1e-6
    print(f"{meta.name} busy_ms {busy:.3f} leaf_ms "
          f"{meta.dur.sum() * 1e-6:.3f} under_a_scope_ms {named:.3f} "
          f"({100 * named / busy:.2f}% of busy)")
    phases = trace_meta.PHASES
    for name, idx in by_program(meta).items():
        spans = meta.module_runs.get(name, [])
        runs = max(len(spans), 1)
        total = meta.dur[idx].sum() * 1e-6
        if total < args.min_ms:
            continue
        wall = [(e - s) * 1e-6 for s, e in spans] or [0.0]
        print(f"\nprogram {name}: runs {len(spans)}, ms a run mean "
              f"{statistics.mean(wall):.3f} median "
              f"{statistics.median(wall):.3f}; its leaf operations "
              f"{total / runs:.3f} ms a run, {100 * total / busy:.2f}% "
              "of busy")
        print("  %-14s" % "scope" + "".join("%11s" % p for p in phases)
              + "%11s%8s" % ("ms a run", "share"))
        for scope, row, ms in scope_table(meta, idx, runs):
            print("  %-14s" % scope
                  + "".join("%11.3f" % row.get(p, 0.0) for p in phases)
                  + "%11.3f%7.2f%%" % (ms, 100 * ms * runs / busy))
        print(f"  the {args.top} heaviest operations of this program:")
        for ms, events, i in heaviest(meta, idx, runs, args.top):
            op = meta.op[i]
            print("  %9.3f ms/run %6d ev  %-12s %-9s %-18s %s" % (
                ms, events, op.scope or NO_SCOPE, op.phase,
                op.hlo_category, op.name[:args.text]))
            if op.tf_op:
                print(" " * 30 + "path " + op.tf_op)
    bare = [i for i, op in enumerate(meta.op) if op.scope is None]
    total = meta.dur[bare].sum() * 1e-6
    print(f"\nunder no scope: {total:.3f} ms ({100 * total / busy:.2f}% of "
          "busy), by hlo_category, program and whether the operation "
          "carries a tf_op path:")
    groups = {}
    for i in bare:
        op = meta.op[i]
        key = (op.hlo_category, op.program,
               "path" if op.tf_op else "no path")
        groups[key] = groups.get(key, 0.0) + meta.dur[i] * 1e-6
    for (cat, prog, path), ms in sorted(groups.items(),
                                        key=lambda kv: -kv[1])[:24]:
        print("  %10.3f ms %6.2f%%  %-22s %-22s %s" % (
            ms, 100 * ms / busy, cat, prog, path))
    print(f"  the {args.top} heaviest operations under no scope, ms in "
          "all:")
    for ms, events, i in heaviest(meta, bare, 1, args.top):
        op = meta.op[i]
        print("  %9.3f ms %6d ev  %-22s %-18s %s" % (
            ms, events, op.program, op.hlo_category,
            op.name[:args.text]))
        if op.tf_op:
            print(" " * 26 + "path " + op.tf_op)
    return 0


if __name__ == "__main__":
    sys.exit(main())
