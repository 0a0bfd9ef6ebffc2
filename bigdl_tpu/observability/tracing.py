"""Per-request distributed trace context for the serving stack.

A ``TraceContext`` is minted once per request at the fleet edge
(``ServingFleet._request``) and flows DOWN the serving stack: routing
attempts, hedges and retries become child spans, the context crosses
the ``serving/worker.py`` socket protocol as a versioned ``trace``
field (W3C-traceparent encoding inside, so a future cross-host
transport can interop), and lands in ``ServingEngine`` /
``GenerateScheduler`` where batch ticks record span links back to
every request riding them.  Span records are durable JSONL lines
(``traces.jsonl``, written by ``StepTelemetry.record_trace``) plus a
chrome-trace mirror when a ``SpanTracer`` is attached;
``tools/trace_report.py`` stitches the records back into per-request
critical paths by trace_id.

Sampling is head-based: the root mints ``sampled`` from a
``HeadSampler`` and every child inherits the bit.  The root-side
buffer (``RequestTrace``) defers the final keep/drop decision to
request completion, so errors, shed requests and p99-tail latencies
can FORCE an unsampled trace onto disk -- the interesting tails are
never lost.  Only the fleet-local spans of a late-forced trace exist
(the wire carries the context only when ``sampled`` is already true);
that is the documented trade for keeping the unsampled path free of
remote work.

No jax import, stdlib only: tools spec-load this file by path.
"""

import os
import random
import threading
import time

#: version of the wire dict carrying the context across the socket
#: protocol; unknown higher versions still parse the traceparent field
WIRE_VERSION = 1

#: env knob for the default head-sample rate (fraction of requests)
TRACE_SAMPLE_ENV = "BIGDL_TRACE_SAMPLE"
_DEFAULT_RATE = 0.01

# one process-wide RNG, seeded once from the OS: minting ids must not
# cost a urandom syscall per request (the no-op-path microbench guards
# the whole mint at microseconds)
_rng = random.Random()
_rng.seed(int.from_bytes(os.urandom(16), "big"))
_rng_lock = threading.Lock()


def _hex_id(bits):
    with _rng_lock:
        v = _rng.getrandbits(bits)
    # zero ids are reserved/invalid in W3C trace-context; re-roll
    while not v:        # pragma: no cover - 2^-bits probability
        with _rng_lock:
            v = _rng.getrandbits(bits)
    return format(v, "0%dx" % (bits // 4))


def default_sample_rate():
    """The head-sample rate from ``BIGDL_TRACE_SAMPLE`` (default 1%)."""
    raw = os.environ.get(TRACE_SAMPLE_ENV)
    if raw is None:
        return _DEFAULT_RATE
    try:
        return float(raw)
    except ValueError:
        return _DEFAULT_RATE


class TraceContext:
    """trace_id / span_id / parent_id / sampled -- one span's identity.

    ``trace_id`` (32 hex chars) names the whole request; ``span_id``
    (16 hex chars) names this span; ``parent_id`` links to the span
    that minted this one via ``child()``.  The string encoding is the
    W3C traceparent form ``00-<trace_id>-<span_id>-<flags>``.
    """

    __slots__ = ("trace_id", "span_id", "parent_id", "sampled")

    def __init__(self, trace_id, span_id, parent_id=None, sampled=True):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.sampled = bool(sampled)

    def __repr__(self):
        return (f"TraceContext({self.trace_id}, {self.span_id}, "
                f"parent={self.parent_id}, sampled={self.sampled})")

    @classmethod
    def mint(cls, sampled=True):
        """A fresh root context (new trace_id, no parent)."""
        return cls(_hex_id(128), _hex_id(64), None, sampled)

    def child(self):
        """A child context: same trace, new span, parented here."""
        return TraceContext(self.trace_id, _hex_id(64), self.span_id,
                            self.sampled)

    # ----- encodings -------------------------------------------------- #
    def to_traceparent(self):
        return "00-%s-%s-%02x" % (self.trace_id, self.span_id,
                                  1 if self.sampled else 0)

    @classmethod
    def from_traceparent(cls, value):
        """Parse a traceparent string; None for anything malformed
        (a peer speaking garbage must not take the request down)."""
        if not isinstance(value, str):
            return None
        parts = value.strip().split("-")
        if len(parts) != 4:
            return None
        _ver, trace_id, span_id, flags = parts
        if len(trace_id) != 32 or len(span_id) != 16:
            return None
        try:
            int(trace_id, 16), int(span_id, 16)
            sampled = bool(int(flags, 16) & 1)
        except ValueError:
            return None
        return cls(trace_id.lower(), span_id.lower(), None, sampled)

    def to_wire(self):
        """The versioned dict that rides the socket protocol's request
        pickle as an optional ``trace`` field (traceless peers simply
        never read it)."""
        return {"v": WIRE_VERSION, "traceparent": self.to_traceparent()}

    @classmethod
    def from_wire(cls, obj):
        """Parse the wire dict; tolerant of None, garbage, and FUTURE
        versions (a newer peer's extra fields are ignored, the
        traceparent core still parses)."""
        if not isinstance(obj, dict):
            return None
        return cls.from_traceparent(obj.get("traceparent"))


class HeadSampler:
    """Head-based keep/drop decision, made once at the trace root."""

    def __init__(self, rate=None):
        self.rate = default_sample_rate() if rate is None else float(rate)

    def sample(self):
        if self.rate >= 1.0:
            return True
        if self.rate <= 0.0:
            return False
        with _rng_lock:
            return _rng.random() < self.rate


class RequestTrace:
    """Root-side span buffer with a deferred keep/drop decision.

    The fleet buffers every span of a request here (cheap tuples, no
    I/O) and calls ``flush`` exactly once at completion: records hit
    ``traces.jsonl`` only when the head sampler said yes OR something
    interesting forced the trace (error, shed, p99 tail).  Buffering
    instead of streaming is what makes always-sample-on-error possible
    without paying write costs for the 99% of unsampled-ok requests.
    """

    __slots__ = ("ctx", "records", "forced")

    def __init__(self, ctx):
        self.ctx = ctx
        self.records = []
        self.forced = False

    def add(self, name, ctx, t_wall, dur_s, status="ok", **fields):
        self.records.append((name, ctx, t_wall, dur_s, status, fields))
        # any error/shed span forces the whole trace: a request that
        # RETRIED to success still keeps its dead attempt's evidence
        if status == "shed" or status.startswith("error:"):
            self.forced = True

    def force(self):
        """Override the head sampler: this trace must survive."""
        self.forced = True

    @property
    def keep(self):
        return self.ctx.sampled or self.forced

    def flush(self, telemetry):
        if telemetry is None or not self.records or not self.keep:
            return False
        emit = getattr(telemetry, "record_trace", None)
        if emit is None:
            return False
        for name, ctx, t_wall, dur_s, status, fields in self.records:
            emit(name, ctx, t_wall, dur_s, status=status, **fields)
        self.records = []
        return True
