"""The program's own spans, laid on the device trace's clock.

The program keeps its spans in a ring in memory
(``bigdl_tpu.observability.spans.recorder()``), stamped in nanoseconds
since the Unix epoch.  A profiler trace counts device events in
nanoseconds from the session's start and records that start as
``profile_start_time`` (nanoseconds since the epoch; a stat of the
``Task Environment`` plane), so ``device ns + profile_start_time`` is the
host's clock.  That offset is not taken on trust: it is held to a FENCE,
a host span that ends only when a device program has ended (the
``fetch`` under ``generate_decode`` waits for ``jit_decode``;
``loss_sync`` waits for ``jit_train_step``).  ``fence end - device end``
can never be negative and is small; where the plane's offset puts it
below zero or over ``RESIDUAL_LIMIT_NS`` (or puts a program's start
before the span that launched it), the offset is moved so that the least
residual is zero (the tightest bound a fence gives), and where even that
leaves the median over the limit there is no clock, and the readers
return nothing.  The two sides bracket the true offset: no higher than
the least residual allows, no lower than the least launch lead allows.

A program without the recorder (any commit before PR 27) gives
``records() is None``: the readers return None and their metrics are
left out of the line.
"""

import functools
import os
import sys

import numpy as np

from harness import resolve, trace
from harness.stats import median

#: a fence residual beyond this (median), or below ``-NEGATIVE_SLACK_NS``
#: (least), says that the two clocks do not agree
RESIDUAL_LIMIT_NS = 2e6
NEGATIVE_SLACK_NS = 2e5


def records():
    """The recorder's ring (oldest first), or None where the program has
    no recorder."""
    try:
        from bigdl_tpu.observability.spans import recorder
    except ImportError:
        return None
    return recorder().snapshot()


@functools.lru_cache(maxsize=4)
def _session_times(path, _mtime):
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        if plane.name == "Task Environment":
            stats = dict(plane.stats)
            return (stats.get("profile_start_time"),
                    stats.get("profile_stop_time"))
    return None, None


def profile_start_ns(cell_name):
    """``profile_start_time`` of the run's trace (``run.py`` writes it
    under ``.bench_tmp/<cell>``), or None when the file or the stat is
    not there."""
    try:
        path = trace.newest_xplane(
            os.path.join(resolve.ROOT, ".bench_tmp", cell_name))
    except FileNotFoundError:
        return None
    start, _stop = _session_times(path, os.path.getmtime(path))
    return None if start is None else int(start)


def children_of(recs):
    """``{parent span_id: [records]}``."""
    kids = {}
    for r in recs:
        kids.setdefault(r.parent_id, []).append(r)
    return kids


def named(recs, name, under=None):
    """Records called ``name``; with ``under`` only those whose parent is
    called that."""
    out = [r for r in recs if r.name == name]
    if under is not None:
        by_id = {r.span_id: r for r in recs}
        out = [r for r in out if r.parent_id in by_id
               and by_id[r.parent_id].name == under]
    return out


#: how far the clocks may disagree and a fence still find its program
#: (``fence["slack_ns"]`` where a cell's programs follow each other faster)
MATCH_SLACK_NS = 5e6


def fence_residuals(plane, recs, offset_ns, fence):
    """For every execution of the fence's device program: the end of the
    first host span that ends no earlier than ``MATCH_SLACK_NS`` before
    it, less the execution's end, both in the device's nanoseconds (host
    stamps less the whole number ``offset_ns``: a float cannot hold
    nanoseconds since 1970).  A host thread that was kept waiting reads
    large; nothing but a wrong clock reads negative."""
    ends = np.sort(np.asarray(
        [r.end_ns - offset_ns
         for r in named(recs, fence["span"], fence.get("under"))],
        np.float64))
    dev_end = np.asarray([e for _s, e in plane.module_runs(fence["module"])])
    at = np.searchsorted(ends, dev_end - fence.get("slack_ns", MATCH_SLACK_NS))
    found = at < len(ends)
    return ends[at[found]] - dev_end[found]


def launch_leads(plane, recs, offset_ns, launch):
    """The other side of the clock: a device program cannot start before
    the host span that launches it does.  For every execution of
    ``launch["module"]``: its start less the start of the last
    ``launch["span"]`` that starts no later than ``MATCH_SLACK_NS`` after
    it; negative means the offset puts the device early."""
    starts = np.sort(np.asarray(
        [r.start_ns - offset_ns
         for r in named(recs, launch["span"], launch.get("under"))],
        np.float64))
    dev_start = np.asarray([s for s, _e
                            in plane.module_runs(launch["module"])])
    at = np.searchsorted(
        starts, dev_start + launch.get("slack_ns", MATCH_SLACK_NS),
        "right") - 1
    found = at >= 0
    return dev_start[found] - starts[at[found]]


def clock(plane, recs, start_ns, fence):
    """The whole number of nanoseconds that puts ``plane``'s on the host's
    clock, checked against the fence: ``{"offset_ns", "source",
    "residual_ns": {"least", "median", "worst"}, "fences",
    "launch_lead_ns"}``, or None where there is no ``profile_start_time``
    or no offset brings the fence under the limit.  ``source`` is
    ``profile_start_time``, or ``fence`` where that put a fence before the
    end of its program, the median fence over the limit, or (with
    ``fence["launch"]``) a program before its launch: the offset is then
    moved to the least residual.  ``launch_lead_ns`` is the least start of
    a program after its launch under the offset taken: with the least
    residual it brackets the true offset, which may lie up to that far
    below the one taken."""
    if start_ns is None:
        return None
    res = fence_residuals(plane, recs, start_ns, fence)
    if not len(res):
        return None
    launch = fence.get("launch")

    def lead(offset):
        leads = launch_leads(plane, recs, offset, launch) if launch else ()
        return float(np.min(leads)) if len(leads) else None

    def summary(res):
        return {"least": float(res.min()),
                "median": float(median(list(res))),
                "worst": float(res.max())}

    offset, source, r = int(start_ns), "profile_start_time", summary(res)
    if r["least"] < -NEGATIVE_SLACK_NS or r["median"] > RESIDUAL_LIMIT_NS \
            or (lead(offset) or 0.0) < -NEGATIVE_SLACK_NS:
        if abs(r["least"]) > fence.get("slack_ns", MATCH_SLACK_NS):
            return None      # a fence cannot tell one period from the next
        offset, source = offset + int(r["least"]), "fence"
        res = fence_residuals(plane, recs, offset, fence)
        r = summary(res)
        if r["median"] > RESIDUAL_LIMIT_NS:
            return None
    return {"offset_ns": offset, "source": source, "fences": int(len(res)),
            "residual_ns": r, "launch_lead_ns": lead(offset)}


def window(env, fence):
    """``(records, (start, end), offset_ns)``: the ring, the traced window
    of the first device on the host's clock, and what was added to the
    device's nanoseconds to get there; None where any is missing.  Worked
    out once a run (kept in ``env``), and the clock's residuals said once
    on standard error, for PERF.md."""
    key = ("span_window", fence["span"], fence["module"])
    if key not in env:
        env[key] = _window(env, fence)
    return env[key]


def _window(env, fence):
    recs = records()
    if not recs:
        return None
    plane = env["planes"][0]
    span = plane.window()
    if span is None:
        return None
    c = clock(plane, recs, profile_start_ns(env["cell"].name), fence)
    if c is None:
        print("clock: no offset puts the fence under its limit",
              file=sys.stderr)
        return None
    r = c["residual_ns"]
    lead = c["launch_lead_ns"]
    print(f"clock: offset from {c['source']}; fence {fence['span']} end - "
          f"{fence['module']} end over {c['fences']} fences: least "
          f"{r['least'] * 1e-3:.1f} us, median {r['median'] * 1e-3:.1f} us, "
          f"worst {r['worst'] * 1e-3:.1f} us; least program start after its "
          f"launch: " + ("not asked" if lead is None
                         else f"{lead * 1e-3:.1f} us"), file=sys.stderr)
    off = c["offset_ns"]
    return recs, (int(span[0]) + off, int(span[1]) + off), off
