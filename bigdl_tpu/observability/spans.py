"""The span recorder: what the host did, on the device trace's clock.

``span(name, **attrs)`` is the ONE way program code opens a span.  Every
completed span lands in one process-wide, bounded, in-memory ring -- no
telemetry object, no tracer and no switch is needed, and the recording
path takes no lock and does no I/O.  Readers take a copy::

    from bigdl_tpu.observability.spans import recorder, span

    with span("prefill_prep", rows=3) as s:
        ...
        s.set(bucket=4)              # attributes known only at the end
    recs = recorder().snapshot()     # list copy, oldest first
    recorder().clear()
    recorder().enabled = False       # plain attribute; default True

**A record** is the tuple ``(name, start_ns, end_ns, thread, span_id,
parent_id, request_id, attrs)`` (``Record``; fields by name).
``parent_id`` is the span that enclosed it on the same thread (a
thread-local stack), so a span's self time is its duration less its
children's; ``request_id`` is set on request spans and equal on every
span of one request; ``attrs`` is a dict or None.  ``instant(name,
**attrs)`` records an event without duration (a counter sample) and
``record_span(name, start_ns, end_ns, ...)`` one whose stamps were taken
elsewhere (``GenerateFuture`` stamps a request as it goes and records it
once, when it finishes).

**The clock.**  Stamps are nanoseconds since the Unix epoch on a
monotonic base: one anchor pair ``(time.time_ns(), perf_counter_ns())``
is taken when this module is imported and every stamp is ``anchor_wall +
(perf_counter_ns() - anchor_perf)`` (``now_ns()``; ``to_ns`` maps a
``time.perf_counter()`` reading onto it).  A JAX profiler trace counts
its device events in nanoseconds from the session's start, and records
that start (``profile_start_time``, nanoseconds since the epoch, a stat
of its ``Task Environment`` plane): ``profile_start_time + start_ns`` is
a device event on this clock.  That is how a device idle gap gets the
name of the host span that covers it (``benchmark/metrics/readers/
span_idle.py``).

``SpanTracer`` is a SINK of the recorder: a streaming chrome-trace file
(Perfetto-viewable) that, while active, is handed every completed record.
Events stream straight to disk; ``close()`` terminates the JSON array; a
crash leaves an unterminated array, which Perfetto accepts by spec and
``read_trace_events`` repairs on read.

A backend compile is recorded as a ``compile`` span (from
``jax.monitoring``'s ``backend_compile_duration``; the listener is
registered on the first ``span()`` of a process in which ``jax`` is
already imported), so a tick or step that compiled is visible as such.

Standard library only: ``tools/obs_report.py`` and
``tools/trace_report.py`` load this file by path.
"""

import collections
import itertools
import json
import os
import sys
import threading
import time
import weakref

#: the anchor pair: wall-clock nanoseconds and the monotonic counter,
#: read together once; every stamp is the monotonic distance from it
_ANCHOR_WALL_NS = time.time_ns()
_ANCHOR_PERF_NS = time.perf_counter_ns()
_OFFSET_NS = _ANCHOR_WALL_NS - _ANCHOR_PERF_NS

#: ring capacity: about 20 minutes of serving ticks (some 50 records each)
RING_RECORDS = 65536

#: the duration event of ``jax.monitoring`` that a real backend (XLA)
#: compile emits
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

Record = collections.namedtuple(
    "Record", "name start_ns end_ns thread span_id parent_id request_id "
              "attrs")


def now_ns():
    """Nanoseconds since the Unix epoch, monotonic within the process."""
    return _OFFSET_NS + time.perf_counter_ns()


def to_ns(perf_counter_s):
    """A ``time.perf_counter()`` reading on the recorder's clock."""
    return _OFFSET_NS + int(perf_counter_s * 1e9)


class Recorder:
    """The ring and its sinks.  One per process (``recorder()``)."""

    def __init__(self, maxlen=RING_RECORDS):
        #: plain attribute: False records nothing (spans still stamp their
        #: own start and end, which the serving loop's events are made of)
        self.enabled = True
        self._ring = collections.deque(maxlen=maxlen)
        #: replaced whole under ``_sinks_lock``; read without it
        self._sinks = ()
        self._sinks_lock = threading.Lock()

    def _add(self, rec):
        self._ring.append(rec)
        for ref in self._sinks:
            sink = ref()
            if sink is not None:
                sink.write_record(rec)

    def snapshot(self, since_ns=None):
        """A list copy of the ring, oldest first; with ``since_ns`` only
        the records that ended at or after it."""
        recs = list(self._ring)
        if since_ns is not None:
            recs = [r for r in recs if r.end_ns >= since_ns]
        return recs

    def clear(self):
        self._ring.clear()

    def add_sink(self, sink):
        """``sink.write_record(record)`` is called for every completed
        record, on the thread that recorded it.  Held weakly: a sink
        nobody closed goes with its owner."""
        with self._sinks_lock:
            live = tuple(r for r in self._sinks if r() is not None)
            if all(r() is not sink for r in live):
                live += (weakref.ref(sink),)
            self._sinks = live

    def remove_sink(self, sink):
        with self._sinks_lock:
            self._sinks = tuple(r for r in self._sinks
                                if r() not in (None, sink))


_RECORDER = Recorder()
_IDS = itertools.count(1)
_LOCAL = threading.local()
_compile_hooked = False


def recorder():
    """The process-wide recorder."""
    return _RECORDER


def _stack():
    try:
        return _LOCAL.stack
    except AttributeError:
        _LOCAL.stack = []
        return _LOCAL.stack


class Span:
    """One open span; a context manager.  ``start_ns``/``end_ns`` are
    readable by the code that opened it (the serving loop takes its event
    times from them instead of stamping twice)."""

    __slots__ = ("name", "attrs", "request_id", "start_ns", "end_ns",
                 "span_id", "parent_id")

    def __init__(self, name, attrs, request_id=None):
        self.name = name
        self.attrs = attrs or None
        self.request_id = request_id
        self.start_ns = self.end_ns = None
        self.span_id = self.parent_id = None

    def set(self, **attrs):
        """Add attributes before the span closes."""
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)

    def __enter__(self):
        if _RECORDER.enabled:
            stack = _stack()
            self.parent_id = stack[-1].span_id if stack else None
            self.span_id = next(_IDS)
            stack.append(self)
        self.start_ns = _OFFSET_NS + time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.end_ns = _OFFSET_NS + time.perf_counter_ns()
        if self.span_id is not None:
            stack = _stack()
            if stack and stack[-1] is self:
                stack.pop()
            elif self in stack:      # a span opened by hand was left open
                del stack[stack.index(self):]
            _RECORDER._add(Record(
                self.name, self.start_ns, self.end_ns,
                threading.get_ident(), self.span_id, self.parent_id,
                self.request_id, self.attrs))
        return False


def span(name, request_id=None, **attrs):
    """Open a span: ``with span("deliver", tokens=3) as s: ...``."""
    if not _compile_hooked:
        _hook_compiles()
    return Span(name, attrs, request_id)


def record_span(name, start_ns, end_ns, request_id=None, nest=False,
                **attrs):
    """Record a span whose stamps were taken elsewhere (on this clock).
    ``nest`` parents it under the calling thread's open span; otherwise it
    has no parent, as a request that outlives many ticks has none."""
    if not _RECORDER.enabled:
        return
    stack = _stack() if nest else None
    _RECORDER._add(Record(
        name, int(start_ns), int(end_ns), threading.get_ident(),
        next(_IDS), stack[-1].span_id if stack else None, request_id,
        attrs or None))


def instant(name, **attrs):
    """Record an event without duration (a counter sample) under the
    calling thread's open span."""
    now = now_ns()
    record_span(name, now, now, nest=True, **attrs)


def _hook_compiles():
    """Register once for ``jax.monitoring``'s backend-compile duration and
    record each as a ``compile`` span ending now -- but only where ``jax``
    is already imported: this module stays standard-library-only."""
    global _compile_hooked
    jax = sys.modules.get("jax")
    if jax is None or not hasattr(jax, "monitoring"):
        return
    _compile_hooked = True

    def on_duration(event, duration_secs, **_kw):
        if event == COMPILE_EVENT:
            end = now_ns()
            record_span("compile", end - int(duration_secs * 1e9), end,
                        nest=True)

    try:
        jax.monitoring.register_event_duration_secs_listener(on_duration)
    except Exception:    # pragma: no cover - a jax without the listener
        pass


def read_trace_events(trace_path):
    """Chrome-trace events from either container format: the streamed
    JSON array (possibly unterminated after a crash -- repaired here,
    as Perfetto does by spec) or the object form with a
    ``traceEvents`` key.  None when the file is missing or beyond
    repair.  The ONE shared reader: ``tools/obs_report.py`` and
    ``tools/trace_report.py`` both spec-load it from here instead of
    each carrying its own copy of the repair."""
    try:
        with open(trace_path, errors="replace") as f:
            text = f.read()
    except OSError:
        return None
    try:
        doc = json.loads(text)
    except ValueError:
        try:   # unterminated streamed array from a crashed run
            doc = json.loads(text.rstrip().rstrip(",") + "]")
        except ValueError:
            return None
    return doc if isinstance(doc, list) else doc.get("traceEvents")


class SpanTracer:
    """Streaming chrome-trace JSON writer: a sink of the recorder.

    While active (``activate()`` / ``with tracer:``) every record the
    process completes is written as a complete ("X") event, zero-length
    ones as instants.  Timestamps are microseconds of the recorder's clock
    from the tracer's creation; the wall-clock origin rides on the leading
    ``wall_time_origin`` instant event so reports can align the trace with
    JSONL event timestamps.
    """

    def __init__(self, path, process_name="bigdl_tpu host"):
        self.path = path
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._origin_ns = now_ns()
        self._lock = threading.Lock()
        self._thread_seen = set()
        self._n = 0
        self._closed = False
        self._f = open(path, "w")
        self._f.write("[\n")
        pid = os.getpid()
        self._emit({"name": "process_name", "ph": "M", "pid": pid,
                    "tid": 0, "args": {"name": process_name}})
        self._emit({"name": "wall_time_origin", "ph": "i", "s": "g",
                    "ts": 0, "pid": pid, "tid": 0,
                    "args": {"wall_time_origin": self._origin_ns * 1e-9}})

    def _emit(self, ev):
        """Append one event to the stream (comma BEFORE each event after
        the first, so the array needs only ``]`` to be valid JSON)."""
        with self._lock:
            if self._closed:
                return
            tid = ev.get("tid", 0)
            if tid and tid not in self._thread_seen:
                self._thread_seen.add(tid)
                self._write({"name": "thread_name", "ph": "M",
                             "pid": ev["pid"], "tid": tid,
                             "args": {"name":
                                      threading.current_thread().name}})
            self._write(ev)

    def _write(self, ev):
        if self._n:
            self._f.write(",\n")
        self._n += 1
        self._f.write(json.dumps(ev, default=str))

    def write_record(self, rec):
        """The sink's side: one recorder record as a chrome-trace event
        (a record that began before this tracer did is not its to tell)."""
        if rec.start_ns < self._origin_ns:
            return
        ev = {"name": rec.name, "ph": "X",
              "ts": (rec.start_ns - self._origin_ns) * 1e-3,
              "dur": (rec.end_ns - rec.start_ns) * 1e-3,
              "pid": os.getpid(), "tid": rec.thread}
        if rec.end_ns == rec.start_ns:
            ev.update(ph="i", s="p")
            del ev["dur"]
        args = dict(rec.attrs) if rec.attrs else {}
        if rec.request_id is not None:
            args["request_id"] = rec.request_id
        if args:
            ev["args"] = args
        self._emit(ev)

    def span(self, name, **attrs):
        """The module's ``span``, with this tracer made a sink first."""
        self.activate()
        return span(name, **attrs)

    def complete_at(self, name, wall_ts, dur_s, **args):
        """Write (to this file only, not to the ring) an event whose
        timing is GIVEN: ``wall_ts`` epoch seconds, which is the
        recorder's clock, + ``dur_s``.  The request-trace mirror
        (``StepTelemetry.record_trace``) uses this: its records carry
        trace contexts of their own and live in ``traces.jsonl``."""
        start = int(wall_ts * 1e9)
        self.write_record(Record(name, start, start + int(dur_s * 1e9),
                                 threading.get_ident(), None, None, None,
                                 args))

    def flush(self):
        with self._lock:
            if not self._closed:
                self._f.flush()

    def close(self):
        """Terminate the JSON array and close the file (idempotent);
        later spans are dropped."""
        self.deactivate()
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._f.write("\n]\n")
            self._f.close()

    def activate(self):
        """Become a sink: every record completed from now on is written
        here too, until ``deactivate()`` or ``close()``."""
        _RECORDER.add_sink(self)
        return self

    def deactivate(self):
        _RECORDER.remove_sink(self)
        self.flush()

    def __enter__(self):
        return self.activate()

    def __exit__(self, *exc):
        self.close()
        return False
