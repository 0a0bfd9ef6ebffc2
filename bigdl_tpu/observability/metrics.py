"""Live fleet telemetry: metrics registry, /metrics exporter, SLO burn.

Everything observability built so far (StepTelemetry JSONL, health
events, trusted timing, the HLO audit) is post-hoc: the artifacts tell
you a run was sick AFTER it ends.  A serving engine under live traffic
-- and the train->serve loop around it -- needs the *current* queue
depth, the *rolling* p99, the error-budget burn and the restart churn
while the process is still alive.  The reference leaned on Spark's live
web UI for exactly this role (BigDL, arxiv 1804.05839); this module is
the JAX-rebuild equivalent, with zero dependencies beyond the stdlib:

- ``Counter`` / ``Gauge`` / ``Histogram`` -- thread-safe, labeled
  metric primitives.  Histograms keep cumulative Prometheus buckets
  AND a bounded reservoir of recent samples, so live percentiles
  (nearest-rank, the one shared definition in ``profiling.percentile``)
  are queryable without unbounded memory.
- ``MetricsRegistry`` -- the process-wide metric hub.  Besides
  get-or-create metric constructors and the Prometheus text rendering,
  it carries the telemetry bridge (``observe_event``): attach it to a
  ``StepTelemetry`` (``tel.attach_metrics(registry)``) and every event
  the run records -- serving ticks, training steps, health samples,
  anomalies, recovery restarts -- updates the live series.  One bridge
  wires all three tiers: ``ServingEngine`` (queue depth, batch fill,
  pad waste, request latency, per-bucket requests, recompiles,
  ``refresh_params`` swaps), the shared driver loop (step times,
  data-wait fraction, MFU when the compiled step's cost is attached,
  wire bytes, anomaly counts) and ``RunSupervisor`` (restart/backoff
  counters).
- ``MetricsExporter`` -- a stdlib ``http.server`` thread serving the
  registry in Prometheus text format on ``/metrics`` plus a
  ``/healthz`` JSON endpoint whose status (``ok`` / ``degraded`` /
  ``halted``) derives from the watchdog/health layer: anomalies mark
  the run degraded (a ``halt``-policy finding: halted), an active SLO
  breach marks it degraded while it burns.
- ``SloTracker`` -- declarative objectives (``p99_latency_ms <= X at
  99.9%`` style: per-sample good/bad against a threshold, a compliance
  target) evaluated over rolling windows with multi-window burn-rate
  alerting (the SRE pattern: a breach needs BOTH the short and the
  long window burning faster than ``factor`` x budget, so a single
  slow request cannot page and a slow hour cannot hide).  A breach
  emits a durable ``kind: "slo"`` telemetry event and feeds the same
  warn/dump/halt policy framework as the numerics watchdogs -- under
  ``policy="halt"`` an SLO breach raises ``TrainingHaltedError`` out
  of the recording driver loop exactly like a NaN.

Metric naming scheme (docs/observability.md, "Live metrics & SLOs"):
``bigdl_<tier>_<what>[_total|_seconds]`` with tiers ``serving`` /
``train`` / ``recovery`` / ``slo``.  No jax/numpy at module top: a
supervisor process exporting restart counters needs no accelerator.
"""

import json
import logging
import threading
import time

from bigdl_tpu.observability.profiling import percentile

log = logging.getLogger("bigdl_tpu.observability")

#: /healthz statuses in escalation order (worst wins)
HEALTH_STATUSES = ("ok", "degraded", "halted")

#: default Histogram buckets: latency-shaped, 1 ms .. 60 s (Prometheus
#: convention: upper bounds, +Inf implicit)
DEFAULT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                   0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)


def _validate_name(name):
    ok = name and (name[0].isalpha() or name[0] == "_") and all(
        c.isalnum() or c in "_:" for c in name)
    if not ok:
        raise ValueError(f"invalid metric name {name!r} (Prometheus: "
                         "[a-zA-Z_:][a-zA-Z0-9_:]*)")
    return name


def _escape_label(value):
    return str(value).replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", "\\n")


def _fmt(value):
    """Prometheus float formatting: integers stay integral."""
    f = float(value)
    return str(int(f)) if f == int(f) and abs(f) < 1e15 else repr(f)


class _Metric:
    """Shared label plumbing: a metric owns child series keyed by the
    label-value tuple (the empty tuple for an unlabeled metric).  One
    lock per metric serializes child creation and value updates -- the
    scraper renders under the same lock, so a reader can never see a
    torn update."""

    type = "untyped"

    def __init__(self, name, help="", labelnames=()):
        self.name = _validate_name(name)
        self.help = help
        self.labelnames = tuple(labelnames)
        self._lock = threading.Lock()
        self._children = {}

    def _labelvalues(self, labels):
        if set(labels) != set(self.labelnames):
            raise ValueError(
                f"metric {self.name} expects labels {self.labelnames}, "
                f"got {tuple(sorted(labels))}")
        return tuple(str(labels[k]) for k in self.labelnames)

    def _child(self, labels):
        key = self._labelvalues(labels)
        child = self._children.get(key)
        if child is None:
            child = self._children[key] = self._new_child()
        return child

    def _series_name(self, key, suffix="", extra=()):
        """``extra`` label pairs come FIRST: the scoped-view injection
        (``render_scoped``: one /metrics port, N registries, a
        ``replica=`` label) without touching the child keys."""
        pairs = [f'{n}="{_escape_label(v)}"' for n, v in extra]
        pairs += [f'{n}="{_escape_label(v)}"'
                  for n, v in zip(self.labelnames, key)]
        if not pairs:
            return self.name + suffix
        return f"{self.name}{suffix}{{{','.join(pairs)}}}"

    def render_series(self, extra=()):
        """Just the sample lines (no HELP/TYPE headers) -- what a
        scoped multi-registry render groups under ONE family header."""
        with self._lock:
            return [line for key in sorted(self._children)
                    for line in self._render_child(
                        key, self._children[key], extra)]

    def render(self):
        lines = []
        if self.help:
            lines.append(f"# HELP {self.name} {self.help}")
        lines.append(f"# TYPE {self.name} {self.type}")
        lines.extend(self.render_series())
        return lines


class Counter(_Metric):
    """Monotonically increasing value (resets only with the process)."""

    type = "counter"

    def _new_child(self):
        return [0.0]

    def inc(self, amount=1.0, **labels):
        if amount < 0:
            raise ValueError(f"counter {self.name} cannot decrease "
                             f"(inc {amount})")
        with self._lock:
            self._child(labels)[0] += float(amount)

    def value(self, **labels):
        with self._lock:
            return self._child(labels)[0]

    def _render_child(self, key, child, extra=()):
        return [f"{self._series_name(key, extra=extra)} "
                f"{_fmt(child[0])}"]


class Gauge(_Metric):
    """A value that goes up and down (current queue depth, last loss)."""

    type = "gauge"

    def _new_child(self):
        return [0.0]

    def set(self, value, **labels):
        with self._lock:
            self._child(labels)[0] = float(value)

    def inc(self, amount=1.0, **labels):
        with self._lock:
            self._child(labels)[0] += float(amount)

    def dec(self, amount=1.0, **labels):
        self.inc(-amount, **labels)

    def value(self, **labels):
        with self._lock:
            return self._child(labels)[0]

    def _render_child(self, key, child, extra=()):
        return [f"{self._series_name(key, extra=extra)} "
                f"{_fmt(child[0])}"]


class Histogram(_Metric):
    """Cumulative-bucket histogram + a BOUNDED reservoir.

    The buckets render in Prometheus text format (``_bucket{le=...}`` /
    ``_sum`` / ``_count``); the reservoir keeps the most recent
    ``reservoir_size`` observations per child so live percentiles
    (``quantile_value``) answer from recent data with memory bounded no
    matter how long the process serves.  Percentiles use the shared
    nearest-rank definition (``profiling.percentile``) -- a scraped p99
    and an obs_report p99 over the same samples agree exactly.
    """

    type = "histogram"

    def __init__(self, name, help="", labelnames=(),
                 buckets=DEFAULT_BUCKETS, reservoir_size=1024):
        super().__init__(name, help, labelnames)
        self.buckets = tuple(sorted(float(b) for b in buckets))
        if not self.buckets:
            raise ValueError(f"histogram {self.name} needs >= 1 bucket")
        self.reservoir_size = int(reservoir_size)
        if self.reservoir_size < 1:
            raise ValueError(f"histogram {self.name}: reservoir_size "
                             f"must be >= 1, got {reservoir_size}")

    def _new_child(self):
        from collections import deque
        return {"counts": [0] * (len(self.buckets) + 1), "sum": 0.0,
                "count": 0,
                "reservoir": deque(maxlen=self.reservoir_size),
                # bucket index -> (trace_id, value, unix ts): the most
                # recent sampled request that landed in that bucket
                "exemplars": {}}

    def observe(self, value, exemplar=None, **labels):
        """Record one observation.  ``exemplar`` (a trace_id string)
        attaches the observation to a distributed trace: the rendered
        bucket line gains an OpenMetrics exemplar (``# {trace_id=...}
        value ts``), which is how a dashboard jumps from "the p99
        bucket is filling" to ONE concrete slow request's trace."""
        v = float(value)
        with self._lock:
            child = self._child(labels)
            i = len(self.buckets)
            for j, b in enumerate(self.buckets):
                if v <= b:
                    i = j
                    break
            child["counts"][i] += 1
            child["sum"] += v
            child["count"] += 1
            child["reservoir"].append(v)
            if exemplar:
                child["exemplars"][i] = (str(exemplar), v, time.time())

    def count(self, **labels):
        with self._lock:
            return self._child(labels)["count"]

    def quantile_value(self, q, **labels):
        """Nearest-rank percentile over the (bounded) reservoir of the
        most recent observations; None before the first sample."""
        with self._lock:
            samples = sorted(self._child(labels)["reservoir"])
        return percentile(samples, q)

    def _bucket_series(self, key, le, extra=()):
        # the le label joins the child's own labels in one brace set
        pairs = [f'{n}="{_escape_label(v)}"' for n, v in extra]
        pairs += [f'{n}="{_escape_label(v)}"'
                  for n, v in zip(self.labelnames, key)]
        pairs.append(f'le="{le}"')
        return f"{self.name}_bucket{{{','.join(pairs)}}}"

    @staticmethod
    def _exemplar_suffix(child, i):
        ex = child["exemplars"].get(i)
        if ex is None:
            return ""
        tid, v, ts = ex
        return (f' # {{trace_id="{_escape_label(tid)}"}} '
                f'{_fmt(v)} {ts:.3f}')

    def _render_child(self, key, child, extra=()):
        lines, cum = [], 0
        for j, (b, n) in enumerate(zip(self.buckets, child["counts"])):
            cum += n
            lines.append(
                f"{self._bucket_series(key, _fmt(b), extra)} {cum}"
                f"{self._exemplar_suffix(child, j)}")
        cum += child["counts"][-1]
        lines.append(f"{self._bucket_series(key, '+Inf', extra)} {cum}"
                     f"{self._exemplar_suffix(child, len(self.buckets))}")
        lines.append(f"{self._series_name(key, '_sum', extra)} "
                     f"{_fmt(child['sum'])}")
        lines.append(f"{self._series_name(key, '_count', extra)} "
                     f"{child['count']}")
        return lines


# --------------------------------------------------------------------------- #
# The registry: metric hub + telemetry bridge + health state.
# --------------------------------------------------------------------------- #


class MetricsRegistry:
    """Process-local metric hub.

    >>> reg = MetricsRegistry()
    >>> reg.counter("bigdl_requests_total", "served requests").inc()
    >>> print(reg.render())                    # Prometheus text format

    ``observe_event(event)`` is the telemetry bridge: attach the
    registry to a run's ``StepTelemetry`` and every recorded event
    updates the live series -- the serving/training/recovery metric
    families below come from the SAME event dicts the JSONL records, so
    a scrape and the artifact can never disagree about what happened.
    ``health()`` aggregates the watchdog-derived run status that
    ``MetricsExporter`` serves on ``/healthz``.
    """

    def __init__(self, prefix="bigdl"):
        self.prefix = prefix
        self._lock = threading.Lock()
        self._metrics = {}
        # reason -> status; /healthz reports the worst active one
        self._health = {}
        # header facts the bridge needs for derived gauges (MFU)
        self._flops_per_step = None
        self._peak_flops = None

    # ----- constructors (get-or-create, type-checked) ----------------------- #
    def _get_or_create(self, cls, name, help, labelnames, **kw):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, help,
                                              labelnames=labelnames, **kw)
            elif not isinstance(m, cls) or \
                    m.labelnames != tuple(labelnames):
                raise ValueError(
                    f"metric {name} already registered as "
                    f"{type(m).__name__}{m.labelnames}, not "
                    f"{cls.__name__}{tuple(labelnames)}")
            return m

    def counter(self, name, help="", labelnames=()):
        return self._get_or_create(Counter, name, help, labelnames)

    def gauge(self, name, help="", labelnames=()):
        return self._get_or_create(Gauge, name, help, labelnames)

    def histogram(self, name, help="", labelnames=(),
                  buckets=DEFAULT_BUCKETS, reservoir_size=1024):
        h = self._get_or_create(Histogram, name, help, labelnames,
                                buckets=buckets,
                                reservoir_size=reservoir_size)
        # class/labelnames conflicts raise above; a silently-dropped
        # bucket layout would serve le= boundaries the caller never
        # configured -- reject that mismatch just as loudly
        want = tuple(sorted(float(b) for b in buckets))
        if h.buckets != want or h.reservoir_size != int(reservoir_size):
            raise ValueError(
                f"histogram {name} already registered with buckets "
                f"{h.buckets} / reservoir {h.reservoir_size}, not "
                f"{want} / {reservoir_size}")
        return h

    def get(self, name):
        with self._lock:
            return self._metrics.get(name)

    def render(self):
        """The whole registry in Prometheus text exposition format."""
        with self._lock:
            metrics = sorted(self._metrics.values(), key=lambda m: m.name)
        lines = []
        for m in metrics:
            lines.extend(m.render())
        return "\n".join(lines) + ("\n" if lines else "")

    # ----- health state ------------------------------------------------------ #
    def set_health(self, reason, status):
        """Mark one named condition (``"slo:p99_latency"``,
        ``"watchdog:nonfinite"``) at a status; ``/healthz`` reports the
        worst across all active conditions."""
        if status not in HEALTH_STATUSES:
            raise ValueError(f"unknown health status {status!r}; expected "
                             f"one of {HEALTH_STATUSES}")
        with self._lock:
            if status == "ok":
                self._health.pop(reason, None)
            else:
                self._health[reason] = status

    def clear_health(self, reason):
        self.set_health(reason, "ok")

    def health(self):
        """-> ``{"status", "reasons"}`` -- the /healthz payload core."""
        with self._lock:
            conditions = dict(self._health)
        status = "ok"
        for s in conditions.values():
            if HEALTH_STATUSES.index(s) > HEALTH_STATUSES.index(status):
                status = s
        return {"status": status,
                "reasons": [{"reason": r, "status": s}
                            for r, s in sorted(conditions.items())]}

    # ----- the telemetry bridge ---------------------------------------------- #
    def observe_event(self, event):
        """Map one recorded telemetry event onto the live series.

        Attach via ``StepTelemetry.attach_metrics(registry)`` (or pass
        ``metrics=`` at telemetry construction): the driver loop's step
        events, the serving engine's tick events, the supervisor's
        recovery events, health samples and anomaly findings all flow
        through ``record()`` and land here.  Unknown kinds are ignored
        -- the bridge must never make recording an event unsafe."""
        kind = event.get("kind")
        if kind == "header":
            self._note_cost((event.get("cost") or {}), event)
            if event.get("serving"):
                self._observe_serving_info(event["serving"])
        elif kind == "cost":
            self._note_cost((event.get("cost") or {}), None)
        elif kind == "serving_info":
            self._observe_serving_info(event.get("serving") or {})
        elif kind == "deploy":
            self._observe_deploy(event)
        elif kind == "fleet":
            self._observe_fleet(event)
        elif kind == "step":
            self._observe_step(event)
        elif kind == "inference":
            self._observe_inference(event)
        elif kind == "health":
            self._observe_health(event)
        elif kind == "anomaly":
            self._observe_anomaly(event)
        elif kind == "recovery":
            self._observe_recovery(event)
        elif kind == "reshard":
            self._observe_reshard(event)
        elif kind == "slo":
            self._observe_slo(event)
        elif kind == "memory":
            self._observe_memory(event)
        elif kind == "memory_dump":
            self._observe_memory_dump(event)
        elif kind == "param_refresh":
            self.counter(
                f"{self.prefix}_serving_param_refresh_total",
                "ServingEngine.refresh_params outcomes",
                labelnames=("outcome",)).inc(
                    outcome=event.get("outcome", "ok"))

    def _note_cost(self, cost, header):
        if cost.get("flops_per_step"):
            self._flops_per_step = float(cost["flops_per_step"])
        if header and header.get("peak_flops"):
            self._peak_flops = float(header["peak_flops"])

    # -- training tier -------------------------------------------------------- #
    def _observe_step(self, event):
        p = self.prefix
        self.counter(f"{p}_train_steps_total", "completed train steps") \
            .inc()
        wall = event.get("wall_s")
        if wall is not None:
            self.histogram(f"{p}_train_step_wall_seconds",
                           "per-step wall time").observe(wall)
        loss = event.get("loss")
        if isinstance(loss, (int, float)) and loss == loss:  # not NaN
            self.gauge(f"{p}_train_loss", "last synced loss").set(loss)
        if event.get("records_per_s") is not None:
            self.gauge(f"{p}_train_records_per_second",
                       "last step's records/s").set(event["records_per_s"])
        if wall and event.get("data_wait_s") is not None:
            self.gauge(
                f"{p}_train_data_wait_fraction",
                "host input work fraction of the last step's wall time"
            ).set(min(1.0, event["data_wait_s"] / wall))
        blocked = event.get("step_blocked_s")
        if blocked is not None:
            self.histogram(f"{p}_train_step_blocked_seconds",
                           "fenced per-step time (trusted basis)") \
                .observe(blocked)
        # MFU needs the compiled step's cost (attach_cost header) and
        # the device peak; published basis mirrors obs_report: blocked
        # when the run is fenced, wall otherwise (labeled, so a scrape
        # can never pass an un-fenced number off as a fenced one)
        basis_s = blocked if blocked else wall
        if self._flops_per_step and self._peak_flops and basis_s:
            self.gauge(f"{p}_train_mfu",
                       "model flops utilization of the last step",
                       labelnames=("basis",)).set(
                self._flops_per_step / basis_s / self._peak_flops,
                basis="blocked" if blocked else "wall")
        if event.get("wire_bytes"):
            self.counter(f"{p}_train_wire_bytes_total",
                         "collective wire bytes moved") \
                .inc(event["wire_bytes"])
        if event.get("recompiles"):
            self.counter(f"{p}_train_recompiles_total",
                         "post-warmup compiles inside step windows") \
                .inc(event["recompiles"])
        if event.get("queue_depth") is not None:
            self.gauge(f"{p}_train_prefetch_queue_depth",
                       "prefetch queue occupancy") \
                .set(event["queue_depth"])

    # -- serving tier --------------------------------------------------------- #
    def _observe_inference(self, event):
        p = self.prefix
        self.counter(f"{p}_serving_ticks_total", "dispatcher ticks").inc()
        bucket = event.get("bucket")
        self.counter(f"{p}_serving_requests_total",
                     "requests served, by batch bucket",
                     labelnames=("bucket",)) \
            .inc(event.get("records", 0) or 0,
                 bucket=str(bucket) if bucket is not None else "none")
        if event.get("queue_depth") is not None:
            self.gauge(f"{p}_serving_queue_depth",
                       "pending requests after the last tick drained") \
                .set(event["queue_depth"])
        if event.get("queue_capacity") is not None:
            self.gauge(f"{p}_serving_queue_capacity",
                       "bounded request-queue capacity") \
                .set(event["queue_capacity"])
        if event.get("batch_fill") is not None:
            self.gauge(f"{p}_serving_batch_fill",
                       "real rows / bucket rows of the last tick") \
                .set(event["batch_fill"])
        if event.get("pad_waste") is not None:
            self.gauge(f"{p}_serving_pad_waste",
                       "padded-row fraction of the last tick") \
                .set(event["pad_waste"])
        lat = self.histogram(f"{p}_serving_request_latency_seconds",
                             "end-to-end request latency")
        # request_traces is parallel to request_latency_s (None for
        # untraced rows): sampled requests become bucket exemplars
        traces = event.get("request_traces") or []
        for i, v in enumerate(event.get("request_latency_s") or []):
            lat.observe(v, exemplar=traces[i] if i < len(traces)
                        else None)
        # generation ticks (serving/generation.py) additionally stamp
        # tick_kind ("prefill"/"decode"), tokens emitted and slot
        # occupancy -- the live tokens/s + slot-utilization signals
        if event.get("tokens"):
            self.counter(f"{p}_serving_tokens_total",
                         "generated tokens, by tick kind",
                         labelnames=("kind",)) \
                .inc(event["tokens"],
                     kind=str(event.get("tick_kind") or "decode"))
        if event.get("slots_total"):
            self.gauge(f"{p}_serving_slot_fill",
                       "occupied decode slots / slot pool size") \
                .set((event.get("slots_active") or 0)
                     / event["slots_total"])
        if event.get("generate_latency_s"):
            glat = self.histogram(
                f"{p}_serving_generate_latency_seconds",
                "end-to-end generation latency (submit -> last token); "
                "its own family so second-scale generations never "
                "pollute the predict latency series an SLO is tuned "
                "against")
            gtraces = event.get("generate_traces") or []
            for i, v in enumerate(event["generate_latency_s"]):
                glat.observe(v, exemplar=gtraces[i]
                             if i < len(gtraces) else None)
            # the segregated split (serving/generation.py): queue wait
            # for a free decode slot vs actual prefill+decode time --
            # one merged series reads slot starvation as slow decode
            for fam, field, doc in (
                    ("generate_queue_wait", "generate_queue_wait_s",
                     "generation time queued waiting for a decode slot"),
                    ("generate_decode", "generate_decode_s",
                     "generation time actually prefilling/decoding")):
                vals = event.get(field)
                if vals:
                    h = self.histogram(f"{p}_serving_{fam}_seconds", doc)
                    for i, v in enumerate(vals):
                        h.observe(v, exemplar=gtraces[i]
                                  if i < len(gtraces) else None)
        # paged-KV ticks (serving/paging.py) stamp block-pool occupancy
        # and prefix-cache hit deltas: the capacity signal ("are we
        # about to shed?") and the sharing payoff ("what fraction of
        # prefill compute did the cache absorb?")
        if event.get("kv_blocks_total"):
            occ = self.gauge(f"{p}_serving_kv_blocks",
                             "KV block-pool occupancy, by state",
                             labelnames=("state",))
            for state in ("used", "cached", "free"):
                occ.set(event.get(f"kv_blocks_{state}") or 0, state=state)
        if event.get("prefix_hits"):
            self.counter(f"{p}_serving_prefix_hits_total",
                         "prompt blocks served from the prefix cache") \
                .inc(event["prefix_hits"])
        if event.get("prefix_hit_tokens"):
            self.counter(f"{p}_serving_prefix_hit_tokens_total",
                         "prompt positions whose prefill compute the "
                         "prefix cache absorbed") \
                .inc(event["prefix_hit_tokens"])
        # speculative ticks (serving/generation.py SpeculativeScheduler)
        # stamp drafted/accepted deltas: accepted/drafted is the live
        # acceptance rate, and accepted+rounds bounds tokens-per-verify
        if event.get("spec_drafted"):
            self.counter(f"{p}_serving_spec_drafted_total",
                         "draft tokens proposed by the speculative "
                         "drafter") \
                .inc(event["spec_drafted"])
        if event.get("spec_accepted"):
            self.counter(f"{p}_serving_spec_accepted_total",
                         "draft tokens the fp32 verifier accepted") \
                .inc(event["spec_accepted"])
        if event.get("compiles"):
            self.counter(f"{p}_serving_recompiles_total",
                         "XLA compiles inside serving ticks (nonzero "
                         "after precompile = a shape leak)") \
                .inc(event["compiles"])

    def _observe_serving_info(self, info):
        """Which model version a replica serves, as the Prometheus
        version-info idiom: ``bigdl_serving_version_info{version,
        digest}`` is 1 for the currently-served version and 0 for every
        version this process served before -- a scrape (or a PromQL
        join) can always answer "which checkpoint is live?"."""
        if info.get("version") is None:
            return
        g = self.gauge(f"{self.prefix}_serving_version_info",
                       "1 on the currently-served model version",
                       labelnames=("version", "digest"))
        # zero the predecessors AND raise the new version under ONE
        # lock acquisition (render() scrapes under the same lock): a
        # scrape must never observe the all-zero in-between state
        with g._lock:
            for child in g._children.values():
                child[0] = 0.0
            g._child({"version": str(info["version"]),
                      "digest": str(info.get("digest") or "")})[0] = 1.0

    # -- deploy tier ----------------------------------------------------------- #
    def _observe_deploy(self, event):
        """Staged-rollout verdicts (serving/deploy.py): one counter per
        (stage, verdict) so a fleet dashboard sees cutovers, rejections
        and rollbacks as they land."""
        self.counter(f"{self.prefix}_deploy_total",
                     "deploy stage verdicts, by stage and outcome",
                     labelnames=("stage", "outcome")) \
            .inc(stage=str(event.get("stage", "?")),
                 outcome=str(event.get("verdict", "?")))
        if event.get("stage") == "rollback":
            self.counter(f"{self.prefix}_deploy_rollbacks_total",
                         "automatic/operator rollbacks").inc()

    # -- fleet tier ------------------------------------------------------------ #
    def _observe_fleet(self, event):
        """Replica lifecycle + breaker edges + supervisor restarts
        (serving/fleet.py).  The request-path counters
        (requests/retries/hedges/sheds) are updated DIRECTLY by the
        fleet -- they are not telemetry events -- so the bridge only
        owns the durable-event-backed series; neither side double
        counts."""
        p = self.prefix
        what = event.get("event")
        rid = str(event.get("replica", "?"))
        if what == "breaker":
            self.counter(f"{p}_fleet_breaker_transitions_total",
                         "circuit-breaker state edges, by replica and "
                         "target state",
                         labelnames=("replica", "to")) \
                .inc(replica=rid, to=str(event.get("to", "?")))
        elif what == "state":
            g = self.gauge(f"{p}_fleet_replica_state",
                           "1 on each replica's current lifecycle "
                           "state", labelnames=("replica", "state"))
            # one-hot per replica, zeroed + set under ONE lock like the
            # serving version-info gauge: a scrape never sees two
            # states (or none) active for a replica
            with g._lock:
                for key, child in g._children.items():
                    if key[0] == rid:
                        child[0] = 0.0
                g._child({"replica": rid,
                          "state": str(event.get("state", "?"))})[0] = 1.0
            if event.get("state") == "dead":
                self.counter(f"{p}_fleet_replica_deaths_total",
                             "replica processes observed dead, by "
                             "replica", labelnames=("replica",)) \
                    .inc(replica=rid)
        elif what == "restart":
            self.counter(f"{p}_fleet_restarts_total",
                         "supervisor restarts of dead replicas, by "
                         "replica", labelnames=("replica",)) \
                .inc(replica=rid)
        elif what == "wire":
            # the fleet flushes per-verb wire deltas as durable events
            # (serving/fleet.py _note_wire); counters and the RTT
            # histogram are event-backed ONLY, so replaying a
            # telemetry file into a fresh registry reproduces them
            verb = str(event.get("verb", "?"))
            c = self.counter(f"{p}_fleet_wire_bytes_total",
                             "bytes over the fleet worker wire, by "
                             "verb and direction",
                             labelnames=("verb", "direction"))
            c.inc(float(event.get("bytes_sent") or 0),
                  verb=verb, direction="sent")
            c.inc(float(event.get("bytes_recv") or 0),
                  verb=verb, direction="recv")
            h = self.histogram(f"{p}_fleet_wire_rtt_seconds",
                               "worker RPC round-trip latency, by "
                               "verb", labelnames=("verb",))
            for rtt in (event.get("rtt_s") or ())[:4096]:
                if isinstance(rtt, (int, float)):
                    h.observe(float(rtt), verb=verb)

    # -- health / anomalies --------------------------------------------------- #
    def _observe_health(self, event):
        p = self.prefix
        gn = event.get("grad_norm")
        if isinstance(gn, (int, float)) and gn == gn:
            self.gauge(f"{p}_train_grad_norm",
                       "last sampled global gradient norm").set(gn)
        nf = (event.get("nonfinite_grads") or 0) + \
            (event.get("nonfinite_params") or 0)
        if nf:
            self.counter(f"{p}_train_nonfinite_total",
                         "non-finite elements seen in health samples") \
                .inc(nf)

    def _observe_anomaly(self, event):
        self.counter(f"{self.prefix}_train_anomalies_total",
                     "watchdog findings, by watchdog",
                     labelnames=("watchdog",)) \
            .inc(watchdog=event.get("watchdog", "?"))
        # the watchdog layer drives /healthz: any finding degrades the
        # run; a halt-policy finding is exactly a halted run
        status = "halted" if event.get("policy") == "halt" else "degraded"
        self.set_health(f"watchdog:{event.get('watchdog', '?')}", status)

    # -- recovery tier -------------------------------------------------------- #
    def _observe_recovery(self, event):
        p = self.prefix
        self.counter(f"{p}_recovery_restarts_total",
                     "supervisor restarts, by cause",
                     labelnames=("cause",)) \
            .inc(cause=event.get("cause", "?"))
        if event.get("backoff_s"):
            self.counter(f"{p}_recovery_backoff_seconds_total",
                         "total backoff slept before restarts") \
                .inc(event["backoff_s"])
        if event.get("steps_replayed"):
            self.counter(f"{p}_recovery_steps_replayed_total",
                         "steps re-run after restarts") \
                .inc(event["steps_replayed"])

    def _observe_reshard(self, event):
        """Cross-layout redistributions (parallel/reshard.py): how
        often checkpoints move between mesh layouts, and how many host
        bytes/seconds each move costs -- the elastic-restart and
        layout-aware-serving-refresh audit series."""
        p = self.prefix
        self.counter(f"{p}_reshard_total",
                     "checkpoint redistributions, by src/dst layout",
                     labelnames=("src", "dst")) \
            .inc(src=str(event.get("src", "?")),
                 dst=str(event.get("dst", "?")))
        if event.get("host_bytes"):
            self.counter(f"{p}_reshard_host_bytes_total",
                         "host bytes moved by redistributions") \
                .inc(event["host_bytes"])
        if event.get("wall_s"):
            self.counter(f"{p}_reshard_seconds_total",
                         "wall seconds spent redistributing") \
                .inc(event["wall_s"])

    # -- slo tier ------------------------------------------------------------- #
    def _observe_slo(self, event):
        p = self.prefix
        obj = event.get("objective", "?")
        if event.get("breach"):
            self.counter(f"{p}_slo_breaches_total",
                         "SLO burn-rate breaches, by objective",
                         labelnames=("objective",)).inc(objective=obj)
        self.gauge(f"{p}_slo_active",
                   "1 while the objective's burn-rate alert is firing",
                   labelnames=("objective",)) \
            .set(1.0 if event.get("breach") else 0.0, objective=obj)
        status = "ok"
        if event.get("breach"):
            status = "halted" if event.get("policy") == "halt" \
                else "degraded"
        self.set_health(f"slo:{obj}", status)

    # -- memory tier ----------------------------------------------------------- #
    #: headroom fraction below which /healthz degrades (memory:headroom)
    memory_headroom_warn_fraction = 0.1

    def _observe_memory(self, event):
        """``kind: "memory"`` ledger snapshots (observability/memory.py)
        -> the ``bigdl_memory_bytes{device,subsystem}`` gauge family.
        Subsystem attribution rows carry ``device="all"`` (the ledger
        sums across devices); per-device allocator truth carries
        ``subsystem="in_use"``; the reconciliation residual is its own
        subsystem row so a leak is scrapeable as a growing gauge."""
        p = self.prefix
        g = self.gauge(f"{p}_memory_bytes",
                       "live device bytes, by owning subsystem",
                       labelnames=("device", "subsystem"))
        for name, rec in (event.get("subsystems") or {}).items():
            b = rec.get("bytes") if isinstance(rec, dict) else rec
            if b is not None:
                g.set(b, device="all", subsystem=name)
        if event.get("residual_bytes") is not None:
            g.set(event["residual_bytes"], device="all",
                  subsystem="residual")
        if event.get("live_bytes") is not None:
            g.set(event["live_bytes"], device="all", subsystem="in_use")
        for dev, rec in (event.get("devices") or {}).items():
            if isinstance(rec, dict) and rec.get("bytes_in_use") is not None:
                g.set(rec["bytes_in_use"], device=dev, subsystem="in_use")
        if event.get("headroom_bytes") is not None:
            self.gauge(f"{p}_memory_headroom_bytes",
                       "device bytes left before the allocator limit") \
                .set(event["headroom_bytes"])
        frac = event.get("headroom_fraction")
        if frac is not None:
            self.gauge(f"{p}_memory_headroom_fraction",
                       "headroom as a fraction of the allocator limit") \
                .set(frac)
            # the memory watchdog side of /healthz: burning through
            # headroom degrades the run before the OOM kills it
            self.set_health(
                "memory:headroom",
                "ok" if frac >= self.memory_headroom_warn_fraction
                else "degraded")

    def _observe_memory_dump(self, event):
        """Forensic ``kind: "memory_dump"`` events: count them (by
        reason) and degrade /healthz -- a process that dumped its
        ledger hit an allocation wall even if it survived the shed."""
        self.counter(f"{self.prefix}_memory_dumps_total",
                     "forensic memory dumps, by reason",
                     labelnames=("reason",)) \
            .inc(reason=str(event.get("reason", "?")))
        self.set_health("memory:dump", "degraded")


def render_scoped(registries, label="replica"):
    """N registries on ONE Prometheus page: every series from
    ``registries[scope]`` gets ``label="scope"`` injected, and families
    sharing a metric name across registries merge under one HELP/TYPE
    header (the text format requires each family to appear once).

    This is how N serving replicas in one process share one /metrics
    port with a ``replica=`` label instead of N ports
    (docs/observability.md, "Live metrics & SLOs").  A name registered
    with a different TYPE in two registries cannot merge -- the later
    one is skipped with a warning rather than emitting an invalid
    page."""
    families = {}
    for scope in sorted(registries, key=str):
        reg = registries[scope]
        with reg._lock:
            metrics = sorted(reg._metrics.values(), key=lambda m: m.name)
        for m in metrics:
            fam = families.get(m.name)
            if fam is None:
                fam = families[m.name] = {"type": m.type, "help": m.help,
                                          "members": []}
            elif fam["type"] != m.type:
                log.warning(
                    "scoped render: metric %s is a %s in scope %r but "
                    "a %s elsewhere; skipping the conflicting series",
                    m.name, m.type, scope, fam["type"])
                continue
            fam["members"].append((scope, m))
    lines = []
    for name in sorted(families):
        fam = families[name]
        if fam["help"]:
            lines.append(f"# HELP {name} {fam['help']}")
        lines.append(f"# TYPE {name} {fam['type']}")
        for scope, m in fam["members"]:
            lines.extend(m.render_series(extra=((label, str(scope)),)))
    return "\n".join(lines) + ("\n" if lines else "")


# --------------------------------------------------------------------------- #
# The exporter: /metrics + /healthz over a real socket.
# --------------------------------------------------------------------------- #


class MetricsExporter:
    """Serve a registry on ``/metrics`` (Prometheus text format) and
    ``/healthz`` (JSON) from a daemon ``http.server`` thread.

    >>> exp = MetricsExporter(registry, port=0)     # 0 = auto-assign
    >>> exp.url                                     # http://127.0.0.1:NNN
    >>> exp.close()

    ``/healthz`` aggregates the registry's watchdog-derived conditions
    with any extra ``health_sources`` (callables returning a
    ``{"status", ...}`` dict -- ``SloTracker.health_status`` is one);
    the worst status wins.  ``ok``/``degraded`` answer 200 (degraded is
    an alert, not an outage), ``halted`` answers 503 so a naive HTTP
    prober also notices.  Scraping must never perturb the run: requests
    are handled on the server thread(s), read the registry under its
    own locks, and any handler error answers 500 instead of raising
    into the serving/training process.

    ``registry`` may instead be a DICT of label-scoped registries
    (``{"0": reg0, "1": reg1}``): one port serves all of them with a
    ``scope_label`` (default ``replica``) injected into every series
    (``render_scoped``), and ``/healthz`` aggregates worst-of across
    the scopes (ok < degraded < halted) with each reason prefixed by
    its scope -- N replicas in one process, one scrape endpoint.
    ``add_registry`` grows the scoped view live.
    """

    def __init__(self, registry, port=0, host="127.0.0.1",
                 health_sources=(), scope_label="replica"):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        self.registry = registry
        self.scope_label = str(scope_label)
        self._scoped = isinstance(registry, dict)
        self.registries = dict(registry) if self._scoped else None
        self.health_sources = list(health_sources)
        self._t0 = time.time()
        exporter = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):       # scrape spam stays out of
                pass                         # the training console

            def do_GET(self):
                try:
                    path = self.path.split("?", 1)[0]
                    if path == "/metrics":
                        body = exporter.render().encode()
                        self.send_response(200)
                        self.send_header(
                            "Content-Type",
                            "text/plain; version=0.0.4; charset=utf-8")
                    elif path == "/healthz":
                        health = exporter.healthz()
                        body = (json.dumps(health, indent=2) + "\n") \
                            .encode()
                        self.send_response(
                            503 if health["status"] == "halted" else 200)
                        self.send_header("Content-Type",
                                         "application/json")
                    else:
                        body = b"try /metrics or /healthz\n"
                        self.send_response(404)
                        self.send_header("Content-Type", "text/plain")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                except BrokenPipeError:      # scraper hung up mid-write
                    pass
                except Exception:
                    log.exception("metrics exporter request failed")
                    try:
                        self.send_error(500)
                    except Exception:
                        pass

        self._server = ThreadingHTTPServer((host, int(port)), Handler)
        self._server.daemon_threads = True
        self.host, self.port = self._server.server_address[:2]
        self._thread = threading.Thread(
            target=self._server.serve_forever,
            name="bigdl-metrics-exporter", daemon=True)
        self._thread.start()

    @property
    def url(self):
        return f"http://{self.host}:{self.port}"

    def add_health_source(self, fn):
        """Register a ``() -> {"status": ..., ...}`` callable consulted
        by ``/healthz`` (e.g. ``SloTracker.health_status``)."""
        self.health_sources.append(fn)
        return self

    def add_registry(self, scope, registry):
        """Grow a SCOPED exporter live (a replica restarted with a
        fresh registry, a late-joining replica)."""
        if not self._scoped:
            raise ValueError(
                "add_registry needs a scoped exporter (construct with "
                "a dict of registries)")
        # copy-on-write: server threads iterate self.registries in
        # render_scoped/_aggregate_health without a lock -- an in-place
        # insert would race them into "dict changed size during
        # iteration" (a failed scrape exactly when topology changes)
        self.registries = {**self.registries, str(scope): registry}
        return self

    def render(self):
        if self._scoped:
            return render_scoped(self.registries, self.scope_label)
        return self.registry.render()

    def _aggregate_health(self):
        """Worst-of across the (possibly scoped) registries."""
        if not self._scoped:
            agg = self.registry.health()
            return agg["status"], list(agg["reasons"])
        status, reasons = "ok", []
        for scope in sorted(self.registries, key=str):
            agg = self.registries[scope].health()
            s = agg["status"]
            if HEALTH_STATUSES.index(s) > HEALTH_STATUSES.index(status):
                status = s
            for r in agg["reasons"]:
                reasons.append(
                    {"reason": f"{self.scope_label}={scope}: "
                               f"{r['reason']}",
                     "status": r["status"]})
        return status, reasons

    def healthz(self):
        status, reasons = self._aggregate_health()
        for src in self.health_sources:
            try:
                extra = src()
            except Exception:
                log.exception("healthz source %r failed", src)
                continue
            s = extra.get("status", "ok")
            if s not in HEALTH_STATUSES:
                continue
            if HEALTH_STATUSES.index(s) > HEALTH_STATUSES.index(status):
                status = s
            reasons.extend(extra.get("reasons", []))
        return {"status": status, "reasons": reasons,
                "uptime_s": round(time.time() - self._t0, 3)}

    def close(self):
        self._server.shutdown()
        self._server.server_close()
        self._thread.join(5)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


# --------------------------------------------------------------------------- #
# SLO objectives + multi-window burn-rate alerting.
# --------------------------------------------------------------------------- #


class SloObjective:
    """One declarative objective: samples of ``field`` from telemetry
    events of ``kind`` are good when ``value <op> threshold``; the run
    complies when at least ``target`` of samples are good.

    >>> SloObjective("p99_latency", kind="inference",
    ...              field="request_latency_s", threshold=0.250,
    ...              target=0.999)            # p99_latency_ms<=250 @ 99.9%
    >>> SloObjective("step_time_p50", kind="step", field="step_blocked_s",
    ...              threshold=0.5, target=0.50)   # step_time_p50<=0.5s

    ``alerts`` is the multi-window burn-rate policy: ``(short_s,
    long_s, factor)`` triples; the alert fires when the error budget
    (``1 - target``) burns at >= ``factor`` x the sustainable rate over
    BOTH windows (SRE workbook chapter 5: the long window proves it is
    real, the short window proves it is still happening -- and clears
    the alert promptly once it stops).  ``min_samples`` keeps an empty
    window from dividing noise by a tiny budget.
    """

    def __init__(self, name, kind, field, threshold, target=0.999,
                 op="<=", alerts=((60.0, 300.0, 14.4),), policy="warn",
                 min_samples=10):
        if not 0.0 < float(target) < 1.0:
            raise ValueError(f"objective {name}: target must be in (0, 1) "
                             f"-- a budget of exactly zero cannot burn")
        if op not in ("<=", ">="):
            raise ValueError(f"objective {name}: op must be '<=' or '>=', "
                             f"got {op!r}")
        if policy not in ("warn", "dump", "halt"):
            raise ValueError(f"objective {name}: unknown policy "
                             f"{policy!r}; expected warn/dump/halt")
        self.name = str(name)
        self.kind = str(kind)
        self.field = str(field)
        self.threshold = float(threshold)
        self.target = float(target)
        self.op = op
        self.alerts = tuple((float(s), float(l), float(f))
                            for s, l, f in alerts)
        for s, l, f in self.alerts:
            if s > l:
                raise ValueError(
                    f"objective {name}: alert short window {s}s exceeds "
                    f"long window {l}s")
        self.policy = policy
        self.min_samples = int(min_samples)
        self.budget = 1.0 - self.target

    def good(self, value):
        v = float(value)
        return v <= self.threshold if self.op == "<=" \
            else v >= self.threshold

    def describe(self):
        return (f"{self.field}{self.op}{self.threshold:g} at "
                f"{self.target:.4%} (kind {self.kind})")


class SloTracker:
    """Evaluate ``SloObjective``s over rolling windows; alert on burn.

    >>> tracker = SloTracker([obj1, obj2])
    >>> tracker.bind(telemetry)       # samples flow in via record()
    >>> tracker.health_status()       # {"status": "ok"|"degraded"|...}

    Each observed sample is classified good/bad and appended to the
    objective's rolling window (pruned to the longest alert window,
    additionally bounded to ``max_samples`` -- memory stays flat under
    any request rate).  On every arrival the burn rates are re-derived:
    ``burn(W) = bad_fraction(W) / (1 - target)`` -- burn 1.0 spends the
    budget exactly at the sustainable rate.  A breach (every alert
    window >= its factor) emits a durable ``kind: "slo"`` telemetry
    event on its RISING edge and applies the objective's policy --
    ``warn`` logs, ``dump`` writes an incident bundle
    (``health.dump_incident``), ``halt`` raises ``TrainingHaltedError``
    into whatever loop recorded the sample: a training driver halts
    exactly like a NaN finding (the serving dispatcher's telemetry
    guard catches it, and /healthz reports ``halted`` instead).  The
    falling edge emits a resolving ``kind: "slo"`` event
    (``breach: false``) so the JSONL carries the full burn timeline.

    ``clock`` is injectable (tests drive windows without sleeping).
    """

    def __init__(self, objectives=(), telemetry=None, registry=None,
                 clock=time.monotonic, max_samples=8192,
                 incident_dir=None):
        self.objectives = []
        self.telemetry = telemetry
        self.registry = registry
        self.clock = clock
        self.max_samples = int(max_samples)
        self.incident_dir = incident_dir
        self._lock = threading.Lock()
        self._windows = {}          # name -> deque[(t, bad)]
        self._active = {}           # name -> bool (alert currently firing)
        self._halted = set()        # objectives whose halt policy fired
        for obj in objectives:
            self.add(obj)

    def add(self, objective=None, **kw):
        """Add an ``SloObjective`` (or construct one from kwargs).
        Safe on a LIVE tracker: the window state exists (under the
        lock) before the objective becomes visible to observer threads
        -- a serving dispatcher recording matching events mid-add must
        never hit a half-registered objective."""
        from collections import deque

        if objective is None:
            objective = SloObjective(**kw)
        with self._lock:
            if any(o.name == objective.name for o in self.objectives):
                raise ValueError(
                    f"duplicate SLO objective {objective.name!r}")
            self._windows[objective.name] = deque(maxlen=self.max_samples)
            self._active[objective.name] = False
            self.objectives.append(objective)
        return objective

    def bind(self, telemetry):
        """Subscribe to a run's telemetry: every recorded event is
        offered to ``observe_event``, and breach events are emitted
        back through the same recorder (durable)."""
        self.telemetry = telemetry
        telemetry.add_observer(self.observe_event)
        return self

    # ----- sample ingestion -------------------------------------------------- #
    def observe_event(self, event):
        kind = event.get("kind")
        if kind == "slo":          # never re-ingest our own emissions
            return
        for obj in self.objectives:
            if obj.kind != kind:
                continue
            value = event.get(obj.field)
            if value is None:
                continue
            values = value if isinstance(value, (list, tuple)) else [value]
            self.observe(obj.name, values)

    def observe(self, name, values, t=None):
        """Feed samples directly (drills, tests); evaluates the
        objective's alerts after ingestion."""
        obj = next((o for o in self.objectives if o.name == name), None)
        if obj is None:
            raise KeyError(f"unknown SLO objective {name!r}")
        t = self.clock() if t is None else float(t)
        finding = None
        with self._lock:
            window = self._windows[name]
            for v in values:
                window.append((t, not obj.good(v)))
            finding = self._evaluate(obj, t)
        # policy runs OUTSIDE the tracker lock: dump writes files, halt
        # raises into the caller -- neither may hold up a concurrent
        # scraper reading burn gauges
        if finding is not None:
            self._apply_policy(obj, finding)

    # ----- evaluation (under self._lock) ------------------------------------- #
    def _burn(self, obj, window, horizon_s, now):
        cutoff = now - horizon_s
        total = bad = 0
        for t, is_bad in reversed(window):
            if t < cutoff:
                break
            total += 1
            bad += int(is_bad)
        if total < obj.min_samples:
            return None, total
        return (bad / total) / max(obj.budget, 1e-12), total

    def _evaluate(self, obj, now):
        """Re-derive burn rates; returns a breach/resolve finding dict
        on an edge, else None."""
        window = self._windows[obj.name]
        longest = max(l for _, l, _ in obj.alerts)
        while window and window[0][0] < now - longest:
            window.popleft()
        burns, firing = [], True
        for short_s, long_s, factor in obj.alerts:
            b_short, n_short = self._burn(obj, window, short_s, now)
            b_long, n_long = self._burn(obj, window, long_s, now)
            burns.append({"short_s": short_s, "long_s": long_s,
                          "factor": factor,
                          "burn_short": None if b_short is None
                          else round(b_short, 4),
                          "burn_long": None if b_long is None
                          else round(b_long, 4),
                          "samples": n_long})
            if b_short is None or b_long is None \
                    or b_short < factor or b_long < factor:
                firing = False
        if self.registry is not None:
            g = self.registry.gauge(
                f"{self.registry.prefix}_slo_burn_rate",
                "error-budget burn rate (1.0 = sustainable)",
                labelnames=("objective", "window"))
            for b in burns:
                if b["burn_short"] is not None:
                    g.set(b["burn_short"], objective=obj.name,
                          window=f"{b['short_s']:g}s")
                if b["burn_long"] is not None:
                    g.set(b["burn_long"], objective=obj.name,
                          window=f"{b['long_s']:g}s")
        was = self._active[obj.name]
        if firing == was:
            return None
        self._active[obj.name] = firing
        return {"objective": obj.name, "breach": firing,
                "slo": obj.describe(), "threshold": obj.threshold,
                "target": obj.target, "policy": obj.policy,
                "alerts": burns}

    # ----- policy (outside the lock) ----------------------------------------- #
    def _apply_policy(self, obj, finding):
        from bigdl_tpu.utils.errors import TrainingHaltedError

        if self.telemetry is not None:
            try:
                self.telemetry.record("slo", **finding)
            except Exception:
                log.exception("slo telemetry record failed")
        if self.registry is not None and \
                getattr(self.telemetry, "metrics", None) \
                is not self.registry:
            # the record() above only reaches the registry when the
            # telemetry bridges to THIS registry; otherwise update the
            # live series directly (never both: no double counting)
            self.registry.observe_event({"kind": "slo", **finding})
        if not finding["breach"]:
            log.info("SLO %s recovered: burn back under the alert "
                     "thresholds", obj.name)
            return
        log.warning("SLO BREACH [%s]: %s -- burn %s", obj.name,
                    finding["slo"],
                    ", ".join(f"{b['burn_short']}x/{b['short_s']:g}s + "
                              f"{b['burn_long']}x/{b['long_s']:g}s "
                              f"(>= {b['factor']}x)"
                              for b in finding["alerts"]))
        if obj.policy in ("dump", "halt") and self.incident_dir is None \
                and self.telemetry is None:
            log.warning("SLO policy %r has nowhere to write an incident "
                        "bundle (no incident_dir, no telemetry)",
                        obj.policy)
        elif obj.policy in ("dump", "halt"):
            try:
                from bigdl_tpu.observability.health import dump_incident
                import os
                root = self.incident_dir or os.path.join(
                    self.telemetry.out_dir, "incidents")
                d = dump_incident(
                    root,
                    {"watchdog": "slo", "step": 0, **finding},
                    dict(finding))
                finding["incident_dir"] = d
                log.warning("SLO incident bundle written to %s", d)
            except Exception:
                log.exception("SLO incident dump failed")
        if obj.policy == "halt":
            self._halted.add(obj.name)
            raise TrainingHaltedError(
                f"SLO watchdog halted the run: objective {obj.name} "
                f"({finding['slo']}) is burning its error budget past "
                f"every alert window")

    # ----- status surface ---------------------------------------------------- #
    def active_breaches(self):
        with self._lock:
            return sorted(n for n, a in self._active.items() if a)

    def health_status(self):
        """``{"status", "reasons"}`` for /healthz: an actively burning
        objective degrades the run; one whose halt policy fired marks
        it halted (sticky -- the run was told to stop)."""
        with self._lock:
            active = [n for n, a in self._active.items() if a]
            halted = sorted(self._halted)
        status = "ok"
        reasons = []
        for n in active:
            s = "halted" if n in halted else "degraded"
            reasons.append({"reason": f"slo:{n}", "status": s})
        for n in halted:
            if n not in active:
                reasons.append({"reason": f"slo:{n}", "status": "halted"})
        for r in reasons:
            if HEALTH_STATUSES.index(r["status"]) \
                    > HEALTH_STATUSES.index(status):
                status = r["status"]
        return {"status": status, "reasons": reasons}
