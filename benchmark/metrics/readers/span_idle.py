"""Where the device's idle time lies among the program's own spans.

The device's idle intervals in the traced window (the complement of the
union of its ``XLA Ops`` events, as ``DeviceTrace.busy_ns`` takes it) are
laid on the host's clock (``harness/spans.py``) and each is cut by the
INNERMOST program span that covers it on the thread that recorded the
``roots`` spans (``tick`` and ``dispatcher_idle``, or ``step``) -- the
thread that feeds the device.  The number is the share, in percent, of
the idle time that lies under a named span other than those of
``not_counted`` (the bare ``tick`` or ``step``: time in a root that no
child accounts for is as good as unnamed).  The table ``idle seconds by
span name`` goes to standard error.

Nothing to read (no recorder, no clock, no root span in the window): None.
"""

import sys

import numpy as np

from harness import spans

UNCOVERED = "(no span)"


def idle_intervals(plane):
    """``[(start, end), ...]`` ns within the traced window (first start to
    last end) in which no operation ran: the complement of the union of
    the operations' intervals."""
    if not len(plane.op_start):
        return []
    order = np.argsort(plane.op_start)
    starts = plane.op_start[order]
    reach = np.maximum.accumulate(starts + plane.op_dur[order])
    # a gap opens where a start lies past everything before it
    new = starts[1:] > reach[:-1]
    return list(zip(reach[:-1][new].tolist(), starts[1:][new].tolist()))


def innermost_segments(recs, roots):
    """The feeding thread's time line as ``[(start, end, name), ...]`` in
    order, each stretch named by the innermost span that covers it; only
    spans under a root called one of ``roots`` count."""
    rooted = [r for r in recs if r.name in roots and r.parent_id is None]
    if not rooted:
        return []
    threads = {}
    for r in rooted:
        threads[r.thread] = threads.get(r.thread, 0) + 1
    thread = max(threads, key=threads.get)
    mine = {r.span_id: r for r in recs
            if r.thread == thread and r.span_id is not None}
    keep = {}

    def under_root(r):
        if r.span_id not in keep:
            parent = mine.get(r.parent_id)
            keep[r.span_id] = (r.name in roots) if r.parent_id is None \
                else (parent is not None and under_root(parent))
        return keep[r.span_id]

    tree = sorted((r for r in mine.values()
                   if r.end_ns > r.start_ns and under_root(r)),
                  key=lambda r: (r.start_ns, -r.end_ns))
    out, stack, cursor = [], [], None

    def close_until(t):
        nonlocal cursor
        while stack and stack[-1].end_ns <= t:
            top = stack.pop()
            if top.end_ns > cursor:
                out.append((cursor, top.end_ns, top.name))
                cursor = top.end_ns

    for r in tree:
        if cursor is None:
            cursor = r.start_ns
        close_until(r.start_ns)
        if r.start_ns > cursor:
            out.append((cursor, r.start_ns,
                        stack[-1].name if stack else UNCOVERED))
            cursor = r.start_ns
        stack.append(r)
    close_until(float("inf"))
    return out


def idle_by_span(plane, recs, offset_ns, args):
    """``{span name: idle seconds}`` over the traced window."""
    gaps = idle_intervals(plane)
    # the spans in the device's nanoseconds (whole numbers: a float cannot
    # hold nanoseconds since 1970)
    segs = [(a - offset_ns, b - offset_ns, name) for a, b, name
            in innermost_segments(recs, set(args["roots"]))]
    if not gaps or not segs:
        return None
    out, j = {}, 0
    for a, b in gaps:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k, covered = j, 0.0
        while k < len(segs) and segs[k][0] < b:
            cut = min(b, segs[k][1]) - max(a, segs[k][0])
            if cut > 0:
                out[segs[k][2]] = out.get(segs[k][2], 0.0) + cut * 1e-9
                covered += cut
            k += 1
        if b - a > covered:
            out[UNCOVERED] = out.get(UNCOVERED, 0.0) \
                + (b - a - covered) * 1e-9
    return out


def share(table, not_counted):
    total = sum(table.values())
    named = sum(v for k, v in table.items()
                if k != UNCOVERED and k not in not_counted)
    return 100.0 * named / total if total else None


def read(env, args):
    found = spans.window(env, args["fence"])
    if found is None:
        return None
    recs, _window, offset_ns = found
    table = idle_by_span(env["planes"][0], recs, offset_ns, args)
    if table is None:
        return None
    print("idle seconds by span name: " + ", ".join(
        f"{k} {v:.6f}" for k, v in sorted(table.items(),
                                          key=lambda kv: -kv[1])),
          file=sys.stderr)
    return share(table, set(args.get("not_counted", ())))
