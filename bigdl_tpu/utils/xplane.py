"""Minimal xplane (jax.profiler trace) reader.

Used to cross-validate wall-clock step timings with the device plane's
own busy time (docs/performance.md: a host clock needs an independent
witness on the device).  Parses the
``*.xplane.pb`` files a ``jax.profiler.trace`` context writes, via the
TF-shipped proto when available, else a hand-rolled decoder for the few
XSpace fields the readers touch (the twin of the hand-rolled Event
encoder in ``visualization/tensorboard.py`` -- no TF dependency on the
read side either).

All public readers (``device_busy``, ``op_breakdown``,
``device_attribution``) return None -- never raise -- on a
missing/empty/corrupt trace dir, so report tooling can always call
them unconditionally.
"""

import glob
import os
import re

_UNSET = object()
_xplane_pb2 = _UNSET  # import not attempted yet (None = unavailable)


def _load_proto():
    """The TF-shipped XSpace proto module, or None (cached)."""
    global _xplane_pb2
    if _xplane_pb2 is _UNSET:
        try:
            from tensorflow.tsl.profiler.protobuf import xplane_pb2
            _xplane_pb2 = xplane_pb2
        except Exception:
            try:
                from tensorflow.core.profiler.protobuf import xplane_pb2
                _xplane_pb2 = xplane_pb2
            except Exception:
                _xplane_pb2 = None
    return _xplane_pb2


# --------------------------------------------------------------------------- #
# Pure-python XSpace decoder (fallback when TF's proto is absent).  Only
# the fields the readers consume: XSpace.planes / XPlane.{name, lines,
# event_metadata} / XLine.{name, timestamp_ns, events} /
# XEvent.{metadata_id, offset_ps, duration_ps}.
# --------------------------------------------------------------------------- #


def _uvarint(data, off):
    shift = n = 0
    while True:
        b = data[off]
        off += 1
        n |= (b & 0x7F) << shift
        if not b & 0x80:
            return n, off
        shift += 7


def _decode_fields(data):
    off = 0
    while off < len(data):
        key, off = _uvarint(data, off)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, off = _uvarint(data, off)
        elif wire == 1:
            val = data[off:off + 8]
            off += 8
        elif wire == 2:
            ln, off = _uvarint(data, off)
            val = data[off:off + ln]
            off += ln
        elif wire == 5:
            val = data[off:off + 4]
            off += 4
        else:
            return
        yield field, wire, val


class _PureEvent:
    __slots__ = ("metadata_id", "offset_ps", "duration_ps")

    def __init__(self, data):
        self.metadata_id = self.offset_ps = self.duration_ps = 0
        for f, w, v in _decode_fields(data):
            if w != 0:
                continue
            if f == 1:
                self.metadata_id = v
            elif f == 2:
                self.offset_ps = v
            elif f == 3:
                self.duration_ps = v


class _PureLine:
    __slots__ = ("name", "timestamp_ns", "events")

    def __init__(self, data):
        self.name, self.timestamp_ns, self.events = "", 0, []
        for f, w, v in _decode_fields(data):
            if f == 2 and w == 2:
                self.name = v.decode("utf-8", "replace")
            elif f == 3 and w == 0:
                self.timestamp_ns = v
            elif f == 4 and w == 2:
                self.events.append(_PureEvent(v))


class _PureEventMetadata:
    __slots__ = ("id", "name")

    def __init__(self, data):
        self.id, self.name = 0, ""
        for f, w, v in _decode_fields(data):
            if f == 1 and w == 0:
                self.id = v
            elif f == 2 and w == 2:
                self.name = v.decode("utf-8", "replace")


class _PurePlane:
    __slots__ = ("name", "lines", "event_metadata")

    def __init__(self, data):
        self.name, self.lines, self.event_metadata = "", [], {}
        for f, w, v in _decode_fields(data):
            if f == 2 and w == 2:
                self.name = v.decode("utf-8", "replace")
            elif f == 3 and w == 2:
                self.lines.append(_PureLine(v))
            elif f == 4 and w == 2:   # map<int64, XEventMetadata> entry
                key, meta = 0, None
                for f2, w2, v2 in _decode_fields(v):
                    if f2 == 1 and w2 == 0:
                        key = v2
                    elif f2 == 2 and w2 == 2:
                        meta = _PureEventMetadata(v2)
                if meta is not None:
                    self.event_metadata[key or meta.id] = meta


class _PureXSpace:
    __slots__ = ("planes",)

    def __init__(self, data):
        self.planes = [_PurePlane(v) for f, w, v in _decode_fields(data)
                       if f == 1 and w == 2]


def _parse_xspace(data):
    pb2 = _load_proto()
    if pb2 is not None:
        xs = pb2.XSpace()
        xs.ParseFromString(data)
        return xs
    return _PureXSpace(data)


def _iter_device_planes(trace_dir):
    """Yield every device (TPU/XLA) plane in the trace's xplane files.

    Yields nothing (so the public readers return None) for a None /
    nonexistent / empty trace dir; a corrupt xplane file is skipped
    rather than raised.  A list/tuple of already-parsed planes (from
    ``load_device_planes``) passes through unchanged, so one decode can
    feed all three readers.
    """
    if isinstance(trace_dir, (list, tuple)):
        yield from trace_dir
        return
    if not trace_dir or not os.path.isdir(str(trace_dir)):
        return
    for path in glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                          recursive=True):
        try:
            with open(path, "rb") as f:
                xs = _parse_xspace(f.read())
        except Exception:
            continue   # partial/corrupt trace file: skip, never raise
        for plane in xs.planes:
            name = plane.name.lower()
            if "tpu" in name or "device" in name or "xla" in name:
                yield plane


def load_device_planes(trace_dir):
    """Decode the trace ONCE: returns the parsed device planes as a
    list that every reader (``device_busy`` / ``op_breakdown`` /
    ``device_attribution``) accepts in place of the directory -- report
    tooling that wants all three summaries pays one proto decode, not
    three."""
    return list(_iter_device_planes(trace_dir))


def device_busy(trace_dir):
    """Largest device-plane span in the trace.

    Returns ``{"plane", "span_sec", "busy_event_sec"}`` for the device
    (TPU/XLA) plane with the longest span, or None when no device plane
    or proto support is available (e.g. CPU-only traces).
    """
    best = None
    for plane in _iter_device_planes(trace_dir):
        lo, hi, busiest = None, None, 0
        for line in plane.lines:
            # event offsets are relative to the LINE's timestamp;
            # align to absolute picoseconds before comparing lines
            base = line.timestamp_ns * 1000
            line_busy = 0
            for ev in line.events:
                start = base + ev.offset_ps
                end = start + ev.duration_ps
                lo = start if lo is None else min(lo, start)
                hi = end if hi is None else max(hi, end)
                line_busy += ev.duration_ps
            # lines nest hierarchically (modules > ops): summing
            # across lines double-counts, and async lines (e.g.
            # "Async XLA Ops") hold in-flight spans that overlap
            # compute -- so busy = the busiest synchronous line
            if "async" not in line.name.lower():
                busiest = max(busiest, line_busy)
        if hi is not None:
            rec = {"plane": plane.name,
                   "span_sec": (hi - lo) / 1e12,
                   "busy_event_sec": busiest / 1e12}
            if best is None or rec["span_sec"] > best["span_sec"]:
                best = rec
    return best


#: HLO opcode categories that are cross-device communication, not local
#: compute (the attribution split ``device_attribution`` reports).
#: Start/done pairs cover the async-collective HLO spellings.
COLLECTIVE_CATEGORIES = frozenset({
    "all-reduce", "all-gather", "all-to-all", "reduce-scatter",
    "collective-permute", "collective-broadcast",
    "all-reduce-start", "all-reduce-done",
    "all-gather-start", "all-gather-done",
    "all-to-all-start", "all-to-all-done",
    "reduce-scatter-start", "reduce-scatter-done",
    "collective-permute-start", "collective-permute-done",
    "send", "recv", "send-done", "recv-done",
})


def _op_category(op_name):
    """HLO opcode category of an op name: ``"%all-reduce.9 = f32[...]
    all-reduce(%g)"`` -> ``"all-reduce"`` (falls back to the name stem
    for non-HLO event names)."""
    m = re.search(r"= \S+ ([a-z][a-z0-9_-]*)\(", op_name)
    return m.group(1) if m else op_name.split(".")[0].lstrip("%")


def _op_line(plane):
    """The plane's op-level accounting line: "XLA Ops" (serialized,
    non-overlapping) when present, else the busiest line that is not an
    async (in-flight, overlapping) line; None when the plane has no
    usable line."""
    busiest_line, busiest = None, 0
    for line in plane.lines:
        if line.name == "XLA Ops":
            return line
        if "async" in line.name.lower():
            continue
        line_busy = sum(ev.duration_ps for ev in line.events)
        if line_busy > busiest:
            busiest, busiest_line = line_busy, line
    return busiest_line


def op_breakdown(trace_dir, top=30):
    """Aggregate device-plane event time by op name and opcode category.

    The per-op HLO time accounting the perf docs cite: for the device
    plane's op-level line, sums event durations by name and returns
    ``{"plane", "total_sec", "categories": [...], "ops": [{"name",
    "sec", "pct", "count"}, ...]}`` with the top-N ops by total time, or
    None when no device plane / proto support exists.  Event names are
    resolved through the plane's metadata table (events carry metadata
    ids, not strings).
    """
    best = None
    for plane in _iter_device_planes(trace_dir):
        meta = {m_id: m.name for m_id, m in plane.event_metadata.items()}
        busiest_line = _op_line(plane)
        if busiest_line is None:
            continue
        by_op, by_cat = {}, {}
        for ev in busiest_line.events:
            op = meta.get(ev.metadata_id, str(ev.metadata_id))
            sec, cnt = by_op.get(op, (0, 0))
            by_op[op] = (sec + ev.duration_ps, cnt + 1)
            cat = _op_category(op)
            sec, cnt = by_cat.get(cat, (0, 0))
            by_cat[cat] = (sec + ev.duration_ps, cnt + 1)
        total = sum(s for s, _ in by_op.values())
        if not total:
            continue
        ops = sorted(by_op.items(), key=lambda kv: -kv[1][0])[:top]
        cats = sorted(by_cat.items(), key=lambda kv: -kv[1][0])
        rec = {"plane": plane.name, "total_sec": total / 1e12,
               "categories": [{"name": cat, "sec": s / 1e12,
                               "pct": round(100.0 * s / total, 2),
                               "count": c} for cat, (s, c) in cats],
               "ops": [{"name": op, "sec": s / 1e12,
                        "pct": round(100.0 * s / total, 2), "count": c}
                       for op, (s, c) in ops]}
        if best is None or rec["total_sec"] > best["total_sec"]:
            best = rec
    return best


def device_attribution(trace_dir, top=10):
    """Compute vs collective vs idle device-time attribution.

    Over the busiest device plane's op-level line (serialized,
    non-overlapping -- see ``_op_line``):

    - ``span_sec``: the line's envelope (first op start -> last op end);
    - ``busy_sec``: summed op durations, split into ``compute_sec`` and
      ``collective_sec`` by HLO opcode category
      (``COLLECTIVE_CATEGORIES``);
    - ``idle_sec`` = span - busy: time the device spent waiting (host
      dispatch gaps, input stalls) inside the traced window;
    - the ``*_fraction`` triple is each part over the span, so the
      three fractions sum to 1;
    - ``ops``: the top-N ops by device time, each tagged with its
      ``flavor`` (``"compute"`` | ``"collective"``).

    Returns None (never raises) when no device plane exists -- same
    contract as the other readers.
    """
    best = None
    for plane in _iter_device_planes(trace_dir):
        meta = {m_id: m.name for m_id, m in plane.event_metadata.items()}
        line = _op_line(plane)
        if line is None or not line.events:
            continue
        lo = hi = None
        busy = collective = 0
        by_op = {}
        for ev in line.events:
            start = ev.offset_ps
            end = start + ev.duration_ps
            lo = start if lo is None else min(lo, start)
            hi = end if hi is None else max(hi, end)
            busy += ev.duration_ps
            op = meta.get(ev.metadata_id, str(ev.metadata_id))
            is_coll = _op_category(op) in COLLECTIVE_CATEGORIES
            if is_coll:
                collective += ev.duration_ps
            sec, cnt, _ = by_op.get(op, (0, 0, is_coll))
            by_op[op] = (sec + ev.duration_ps, cnt + 1, is_coll)
        span = hi - lo
        if not busy or not span:
            continue
        # the "XLA Ops" line is serialized, but the busiest-line
        # FALLBACK can carry overlapping events: summed durations then
        # exceed the envelope.  Widen the span to the busy total so the
        # three fractions still partition it (idle reads 0, honestly:
        # overlap means the device was never observed waiting)
        span = max(span, busy)
        compute = busy - collective
        idle = span - busy
        ops = sorted(by_op.items(), key=lambda kv: -kv[1][0])[:top]
        rec = {
            "plane": plane.name,
            "span_sec": span / 1e12,
            "busy_sec": busy / 1e12,
            "compute_sec": compute / 1e12,
            "collective_sec": collective / 1e12,
            "idle_sec": idle / 1e12,
            "compute_fraction": round(compute / span, 4),
            "collective_fraction": round(collective / span, 4),
            "idle_fraction": round(idle / span, 4),
            "ops": [{"name": op, "sec": s / 1e12,
                     "pct": round(100.0 * s / busy, 2), "count": c,
                     "flavor": "collective" if coll else "compute"}
                    for op, (s, c, coll) in ops],
        }
        if best is None or rec["busy_sec"] > best["busy_sec"]:
            best = rec
    return best
