"""One-shot perf A/B matrix on the live chip: batch x remat configs.

Each config is a fresh child process, so one wedged compile cannot take
down the earlier results, and the parent stays off JAX so that each child
in turn is the one process on the chip:

    python tools/perf_ab.py                      # default matrix
    PERF_AB="128:0,256:0,256:r,512:r,256:rs" python tools/perf_ab.py

Config flags after the colon: "r" = nn.Remat blocks, "s" =
space-to-depth stem, "f" = flat fused optimizer update (optim.Fused),
"1" = legacy alias for "r", "0"/empty = plain.

Prints one JSON line per config as it completes (crash/hang-safe), then
a final summary line.  Timing is bench.py's chained-value-fetch method
(docs/performance.md); child spawn/kill/salvage is bench.py's own
_spawn_child, so a wedged or crashed config is reaped and annotated the
same way the driver bench does.  Per-config wall budget: PERF_AB_TIMEOUT
(420 s default; a config that cannot finish in 7 min is wedged, move
on).
"""

import json
import os
import signal
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402  (the shared child-process machinery)


def _run_config(batch, flags, steps, timeout):
    # pin every variant env default to 0 so an inherited BENCH_REMAT etc.
    # can't silently turn a labeled-plain leg into a variant run
    child_env = {"BENCH_BATCH": str(batch) + bench.variant_suffix(flags),
                 "BENCH_STEPS": str(steps)}
    child_env.update({var: "0" for _, _, var in bench.VARIANT_FLAGS})
    rec, err = bench._spawn_child(child_env, timeout)
    if rec is None:
        return {"batch": batch, "error": err, **flags}
    e = rec.get("extra", {})
    out = {"batch": batch, **flags,
           "platform": e.get("platform"),
           "imgs_per_sec": rec.get("value"),
           "sec_per_step": e.get("sec_per_step"),
           "mfu": e.get("mfu")}
    for k in ("error", "salvaged", "teardown"):
        if e.get(k):
            out[k] = e[k]
    return out


def _valid(r):
    """A record worth crowning: on-TPU, physically possible, unflagged."""
    return (r.get("platform") == "tpu" and r.get("mfu")
            and 0.0 < r["mfu"] <= 1.0 and not r.get("error"))


def main():
    signal.signal(signal.SIGTERM, bench._reap_children)
    spec = os.environ.get(
        "PERF_AB", "128:0,256:0,128:r,256:r,512:r,256:rs")
    steps = int(os.environ.get("PERF_AB_STEPS", "12"))
    timeout = int(os.environ.get("PERF_AB_TIMEOUT", "420"))
    results = []
    for item in spec.split(","):
        batch, _, letters = item.strip().partition(":")
        if "1" in letters:              # legacy alias for "r"
            letters = letters.replace("1", "r")
        _, flags = bench.parse_variant(
            batch + letters.replace("0", ""),
            {name: False for name, _, _ in bench.VARIANT_FLAGS})
        t0 = time.perf_counter()
        rec = _run_config(int(batch), flags, steps, timeout)
        rec["wall_sec"] = round(time.perf_counter() - t0, 1)
        results.append(rec)
        print(json.dumps(rec), flush=True)
    ok = [r for r in results if _valid(r)]
    best = max(ok, key=lambda r: r["mfu"]) if ok else None
    print(json.dumps({"summary": results, "best": best}), flush=True)


if __name__ == "__main__":
    main()
