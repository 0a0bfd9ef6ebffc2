"""Structured per-step training telemetry (JSONL) + run metadata.

One ``StepTelemetry`` instance owns a run directory and produces:

- ``telemetry.jsonl`` -- one JSON object per line.  The first event is
  the run header (``kind: "header"``: devices, platform, jax version,
  and the compiled step's ``cost_analysis`` flops/bytes when attached);
  every training step appends a ``kind: "step"`` event carrying the
  split timers (``wall_s`` / ``data_wait_s`` / ``device_s``), loss,
  ``records_per_s``, epoch/step counters, per-device memory stats,
  the deferred-loss-sync staleness (``sync_skew``, 0 when the loss is
  fresh) and -- when a ``PrefetchDataSet`` feeds the run -- the
  prefetch queue occupancy (``queue_depth`` / ``queue_capacity``).
- ``trace.json`` -- chrome-trace host spans: every span the process
  records (``spans.span``) while this object is open, streamed by a
  ``spans.SpanTracer``; viewable in Perfetto next to the device xplane
  traces.

The watchdogs (``watchdogs.py``) ride on the same step cadence:
``step_begin``/``record_step`` bracket the no-compile window for the
recompile detector, and each step's ``bytes_in_use`` feeds the
memory-growth detector.  When a ``HealthMonitor`` is attached
(``health.py``), sampled steps additionally append ``kind: "health"``
numerics events (grad norms, update ratios, non-finite counts) and
``kind: "anomaly"`` watchdog findings -- both fsynced on write, so a
run that dies right after detecting its own divergence still leaves
the evidence on disk.  ``tools/obs_report.py`` merges the JSONL with
an xplane trace into one run report.

The recorder is driver-agnostic: the shared driver loop
(``optim/local_optimizer.py:_run_driver_loop``) emits the events, so
Local/Distri/Strategy training all produce the identical schema.
"""

import json
import logging
import os
import threading
import time

from bigdl_tpu.observability.spans import SpanTracer
from bigdl_tpu.observability.watchdogs import (MemoryWatchdog,
                                               RecompileWatchdog)

#: JSONL schema version (bump on breaking key changes)
SCHEMA_VERSION = 1

#: event kinds that must survive a crash on the NEXT line: flushed AND
#: fsynced to disk the moment they are recorded (a run that blows up
#: right after a health anomaly must leave the evidence on disk; a
#: timing-audit verdict is the line a perf claim stands on; a recovery
#: event is the record of a restart whose successor may itself die; an
#: slo breach under the halt policy is about to END the run; a reshard
#: event is the audit trail of a cross-layout restore whose run may
#: die before its first step; a deploy event is the stage/rollback
#: verdict of a live version swap -- the line the chaos drill audits
#: after SIGKILLing the server mid-cutover; a fleet event is a replica
#: lifecycle/breaker edge whose process may be SIGKILLed the next
#: instant -- the breaker open->half_open->closed trail the fleet
#: drill audits post-mortem; a memory event is the headroom timeline
#: an OOM'd run is judged by, and a memory_dump is the forensic ledger
#: written precisely because the process is about to die)
DURABLE_KINDS = frozenset({"health", "anomaly", "timing_audit",
                           "recovery", "slo", "reshard", "deploy",
                           "fleet", "memory", "memory_dump"})

log = logging.getLogger("bigdl_tpu.observability")


#: peak dense bf16 FLOP/s of one chip, keyed by ``device_kind`` exactly as
#: JAX reports it (source: Google Cloud TPU documentation, the "TPU v4",
#: "TPU v5e", "TPU v5p" and "TPU v6e" system-architecture pages)
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5": 459e12,        # v5p
    "TPU v6 lite": 918e12,   # v6e
}


def peak_flops(device=None):
    """Peak bf16 FLOP/s of ``device`` (default: the first JAX device)
    from ``PEAK_BF16_FLOPS``.  Off a TPU there is no peak, and so no MFU:
    returns ``None``.  A TPU whose ``device_kind`` is not in the table is
    an error, not a default."""
    if device is None:
        import jax
        device = jax.devices()[0]
    if getattr(device, "platform", "cpu") != "tpu":
        return None
    kind = getattr(device, "device_kind", "") or ""
    if kind not in PEAK_BF16_FLOPS:
        raise ValueError(
            f"no peak FLOP/s known for TPU device_kind {kind!r}; add it, "
            f"with its source, to PEAK_BF16_FLOPS "
            f"(bigdl_tpu/observability/telemetry.py)")
    return PEAK_BF16_FLOPS[kind]


def device_memory_stats():
    """Per-device ``{label: {"bytes_in_use", "peak_bytes_in_use"}}``, or
    None where the backend exposes no allocator stats (CPU)."""
    import jax

    out = {}
    for d in jax.devices():
        try:
            s = d.memory_stats()
        except Exception:
            s = None
        if not s:
            continue
        rec = {}
        for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            if key in s:
                rec[key] = int(s[key])
        if rec:
            out[f"{d.platform}:{d.id}"] = rec
    return out or None


def _normalize_cost(analysis):
    """``compiled.cost_analysis()`` returns a dict (or a 1-list of dicts
    on older jax); pull out the portable totals."""
    if analysis is None:
        return None
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else None
    if not isinstance(analysis, dict):
        return None
    out = {}
    if "flops" in analysis:
        out["flops_per_step"] = float(analysis["flops"])
    if "bytes accessed" in analysis:
        out["bytes_accessed_per_step"] = float(analysis["bytes accessed"])
    return out or None


class StepTelemetry:
    """Per-run structured telemetry recorder.

    >>> tel = StepTelemetry(run_dir)
    >>> opt.set_telemetry(tel)         # any of the optimizer drivers
    >>> opt.optimize()
    >>> tel.close()

    The driver loop calls ``step_begin``/``record_step`` around every
    step and ``flush`` when training ends, so artifacts are complete
    even if the caller forgets ``close()``.
    """

    def __init__(self, out_dir, run_name="train", trace=True,
                 recompile_warmup_steps=1, memory_window=25,
                 metrics=None):
        os.makedirs(out_dir, exist_ok=True)
        self.out_dir = out_dir
        self.run_name = run_name
        self.jsonl_path = os.path.join(out_dir, "telemetry.jsonl")
        # truncate: one run dir = one run (two appended headers would
        # silently merge runs in obs_report); pick a fresh dir to keep
        # a previous attempt's artifacts
        self._f = open(self.jsonl_path, "w")
        # a sink of the span recorder (spans.py) from now until close():
        # the run's trace.json holds every span the process completes
        # meanwhile; what is RECORDED does not depend on this object
        self.tracer = SpanTracer(
            os.path.join(out_dir, "trace.json")).activate() \
            if trace else None
        # distributed request-trace spans (docs/observability.md,
        # "Request tracing"): opened lazily on the first record_trace
        # so runs without serving traces leave no empty artifact
        self.traces_path = os.path.join(out_dir, "traces.jsonl")
        self._traces_f = None
        self._traces_lock = threading.Lock()
        self.recompile_watchdog = RecompileWatchdog(recompile_warmup_steps)
        self.memory_watchdog = MemoryWatchdog(memory_window)
        # sampled at construction -- BEFORE this run's own compiles land
        # in the cache dir, which a lazy header write would miscount
        from bigdl_tpu.utils.config import compilation_cache_status
        self._cache_status = compilation_cache_status()
        self._cost = None
        self._compiled_step = None
        self._memory_budget = None
        self._timing = None
        self._serving_info = None
        self._wrote_header = False
        self._closed = False
        # a ServingEngine records inference events from its dispatcher
        # thread while the owning thread may be training against the
        # same run dir: serialize the lazy header write and the JSONL
        # appends (reentrant -- record() calls write_header())
        self._write_lock = threading.RLock()
        # live-telemetry observers (docs/observability.md, "Live
        # metrics & SLOs"): every recorded event is offered to each
        self._observers = []
        self.metrics = None
        if metrics is not None:
            self.attach_metrics(metrics)

    # ----- generic event plumbing ----------------------------------------- #
    def add_observer(self, fn):
        """Subscribe ``fn(event_dict)`` to every recorded event -- the
        seam live consumers ride: a ``MetricsRegistry`` bridge turns
        events into scrapeable series, an ``SloTracker`` classifies
        them against objectives.  Observers run AFTER the line is on
        disk; an observer exception is logged and swallowed EXCEPT
        ``TrainingHaltedError`` -- that is an SLO/watchdog halt policy
        firing, and it must propagate into the recording loop exactly
        like a NaN finding does."""
        self._observers.append(fn)
        return self

    def attach_metrics(self, registry):
        """Bridge this run's events onto a live ``MetricsRegistry``
        (``observability/metrics.py``): serving ticks, training steps,
        health samples, anomalies and recovery events all become
        current Prometheus series a ``MetricsExporter`` can serve.
        Idempotent: re-attaching the registry already bridged (e.g.
        ``metrics=`` at construction AND an explicit call) must not
        subscribe it twice and double-count every counter."""
        if registry is self.metrics:
            return self
        self.metrics = registry
        return self.add_observer(registry.observe_event)

    def _notify(self, event):
        if not self._observers:
            return
        from bigdl_tpu.utils.errors import TrainingHaltedError
        for fn in self._observers:
            try:
                fn(event)
            except TrainingHaltedError:
                raise          # a halt-policy breach ends the run
            except Exception:
                log.exception("telemetry observer %r failed on a %r "
                              "event", fn, event.get("kind"))

    def record(self, kind, **fields):
        """Append one JSONL event (header is written lazily first).
        Health/anomaly/incident events are additionally fsynced: they
        are exactly the lines a crashing run must not lose."""
        with self._write_lock:
            if self._closed:
                # a still-running serving dispatcher may outlive the
                # owner's close(); dropping the event beats raising
                # "I/O operation on closed file" into its tick -- but a
                # DURABLE kind is exactly the line a run must not lose,
                # so its loss is at least loud
                if kind in DURABLE_KINDS:
                    log.warning(
                        "dropping %r telemetry event recorded after "
                        "close(): %s", kind, json.dumps(fields, default=str))
                return None
            if kind != "header" and not self._wrote_header:
                self.write_header()
            event = {"kind": kind, "ts": time.time(), **fields}
            self._f.write(json.dumps(event) + "\n")
            self._f.flush()
            if kind in DURABLE_KINDS:
                try:
                    os.fsync(self._f.fileno())
                except OSError:  # pragma: no cover - exotic filesystems
                    pass
        # observers run with the line already durable on disk, outside
        # the write lock where possible (a nested write_header call
        # still holds it -- the lock is reentrant and observers never
        # block on telemetry)
        self._notify(event)
        return event

    def write_header(self, **extra):
        """Run-level metadata event; called lazily before the first step
        (or eagerly by a driver once the compiled step's cost is known)."""
        with self._write_lock:   # held through the record() below, so a
            if self._wrote_header:   # concurrent first event can't land
                return None          # ahead of the header line
            self._wrote_header = True
            import jax

            dev = jax.devices()[0]
            fields = {"run": self.run_name, "schema_version": SCHEMA_VERSION,
                      "jax_version": jax.__version__,
                      "platform": dev.platform,
                      "device_kind": getattr(dev, "device_kind", ""),
                      "device_count": jax.device_count(),
                      "process_count": jax.process_count(),
                      "peak_flops": peak_flops(dev)}
            try:
                # per-device allocator stats at run start, bounded to 8
                # devices so a big pod doesn't bloat every header; None
                # (CPU backends expose no memory_stats) is silently
                # fine -- no warning spam for the common host case
                mem = device_memory_stats()
            except Exception:
                mem = None
            if mem:
                labels = sorted(mem)
                fields["device_memory"] = {d: mem[d] for d in labels[:8]}
                if len(labels) > 8:
                    fields["device_memory_devices"] = len(labels)
            if self._memory_budget:
                # the compiled executable's static memory budget
                # (attach_cost + utils/hlo.memory_analysis_summary):
                # argument/output/temp/generated bytes, the number the
                # live MemoryLedger residual is read against
                fields["memory_budget"] = self._memory_budget
            if self._cache_status is not None:
                # hit/miss note for the run report: a warm cache means the
                # big XLA compiles were (probably) skipped this run
                fields["compilation_cache"] = self._cache_status
            if self._timing is not None:
                # the run's timing discipline (set_timing_mode): under
                # "blocking", step_blocked_s is the trust basis for any
                # MFU derived from this run's events
                fields["timing"] = self._timing
            if self._serving_info is not None:
                # which precision serves this run (ServingEngine stamps
                # it: quantized flag, weight dtype, model bytes) -- the
                # obs_report Serving section reads this
                fields["serving"] = self._serving_info
            if self._cost:
                fields["cost"] = self._cost
            if self._compiled_step:
                # the lowering-text audit (attach_cost): donation
                # coverage, dot/conv dtypes, collectives -- the
                # obs_report "Compiled step" section reads this
                fields["compiled_step"] = self._compiled_step
            fields.update(extra)
            return self.record("header", **fields)

    def set_timing_mode(self, mode, basis="step_blocked_s"):
        """Stamp the run's timing discipline on the header:
        ``timing: {"mode": "blocking", "trust_basis": "step_blocked_s"}``.
        Drivers call this when ``set_blocking_timing(True)`` is active,
        BEFORE the lazy header write; if the header already went out
        (e.g. ``attach_cost`` wrote it first), a standalone
        ``kind: "timing"`` event records the mode instead -- obs_report
        reads both (docs/observability.md, Profiling & trusted timing).
        """
        timing = {"mode": mode, "trust_basis": basis}
        with self._write_lock:
            if self._timing == timing:
                return None
            self._timing = timing
            if self._wrote_header:
                return self.record("timing", timing=timing)
        return None

    def set_serving_info(self, info):
        """Stamp the serving precision block on the header:
        ``serving: {quantized, weight_dtype, model_bytes, ...}``
        (``ServingEngine`` calls this at construction and after every
        successful ``refresh_params``).  If the header already went out
        (e.g. the engine shares a run with a training driver whose
        ``attach_cost`` wrote it first), a standalone
        ``kind: "serving_info"`` event records it instead -- obs_report
        reads both (docs/observability.md, "Serving telemetry")."""
        info = dict(info)
        with self._write_lock:
            if self._serving_info == info:
                return None
            self._serving_info = info
            if self._wrote_header:
                return self.record("serving_info", serving=info)
        return None

    @property
    def cost(self):
        """The attached compiled-step cost block (``attach_cost``), or
        None -- the flops source the end-of-run timing audit reads."""
        return self._cost

    # ----- step cadence ---------------------------------------------------- #
    def step_begin(self, step):
        """Open the no-compile window (call right before dispatch)."""
        self.recompile_watchdog.step_begin(step)

    def record_step(self, event):
        """Close the step window and append the step event.

        ``event`` must carry ``step``, ``wall_s``, ``data_wait_s`` and
        ``records_per_s`` (the documented schema); memory stats and any
        watchdog findings are attached here.
        """
        wd = self.recompile_watchdog
        compiles = wd.step_end(event.get("step"))
        if compiles:
            # "compiles": any backend compile inside the step window
            # (warmup included); "recompiles": only watchdog-FLAGGED
            # post-warmup compiles -- what reports alarm on
            event["compiles"] = compiles
            if wd.events and wd.events[-1]["step"] == event.get("step"):
                event["recompiles"] = compiles
        mem = device_memory_stats()
        if mem:
            event["memory"] = mem
            flagged = self.memory_watchdog.observe(
                event.get("step"),
                {dev: s["bytes_in_use"] for dev, s in mem.items()
                 if "bytes_in_use" in s})
            if flagged:
                event["memory_growth"] = flagged
        return self.record("step", **event)

    # ----- compiled-step cost ---------------------------------------------- #
    def attach_cost(self, jitted, *example_args, records_per_step=None,
                    arg_labels=None, memory_budget=False):
        """Lower the step for ``cost_analysis`` and put the flops/bytes
        totals on the run header.  The lowering's own cost analysis is
        preferred -- it needs no backend compile, so enabling telemetry
        does not pay the train step's XLA compile twice; only when the
        lowering exposes nothing is the AOT compile consulted.  Failure
        is never fatal -- cost is an annotation, not a dependency.

        The same lowering additionally feeds the compiled-step audit
        (``utils/hlo.py``, docs/observability.md "Compiled step
        audit"): per-plane buffer-donation coverage, dot/conv dtypes
        and collective counts parsed from the lowering TEXT (still no
        backend compile), stamped on the header as ``compiled_step``.
        ``arg_labels`` names the step's positional args (``("params",
        "mstate", "opt_state", ...)``) so the coverage reads per plane;
        the drivers all pass theirs.

        ``memory_budget=True`` additionally AOT-compiles the step and
        stamps its ``memory_analysis()`` (argument/output/temp/
        generated bytes, via ``utils/hlo.memory_analysis_summary``) on
        the header as ``memory_budget`` -- the static side of the live
        ``MemoryLedger``.  This pays one backend compile (usually
        served by the compilation cache); when the cost fallback
        already compiled, the same executable is reused for free."""
        try:
            lowered = jitted.lower(*example_args)
        except Exception:
            return None
        try:
            from bigdl_tpu.utils import hlo
            self._compiled_step = hlo.lowering_summary(
                lowered, example_args, arg_labels=arg_labels)
        except Exception:       # the audit is an annotation, like cost
            self._compiled_step = None
        compiled = None
        try:
            cost = _normalize_cost(lowered.cost_analysis())
        except Exception:
            cost = None
        if cost is None:
            try:
                compiled = lowered.compile()
                cost = _normalize_cost(compiled.cost_analysis())
            except Exception:
                cost = None
        if memory_budget and compiled is None:
            try:
                compiled = lowered.compile()
            except Exception:
                compiled = None
        if compiled is not None:
            try:
                from bigdl_tpu.utils import hlo
                self._memory_budget = hlo.memory_analysis_summary(compiled)
            except Exception:   # an annotation, never fatal
                self._memory_budget = None
        if cost is None and self._compiled_step is None \
                and self._memory_budget is None:
            return None
        if cost is not None and records_per_step:
            cost["records_per_step"] = int(records_per_step)
        self._cost = cost
        if not self._wrote_header:
            self.write_header()           # header carries the cost block
        else:
            fields = {"cost": cost}
            if self._compiled_step is not None:
                fields["compiled_step"] = self._compiled_step
            if self._memory_budget is not None:
                fields["memory_budget"] = self._memory_budget
            self.record("cost", **fields)
        return cost

    # ----- distributed request traces --------------------------------------- #
    def record_trace(self, name, ctx, t_wall, dur_s, status="ok",
                     **fields):
        """Append one request-trace span record to ``traces.jsonl``.

        ``ctx`` is a ``tracing.TraceContext`` (span identity),
        ``t_wall``/``dur_s`` the span's wall-clock start and duration.
        JSONL by design: a SIGKILLed process loses at most the line
        being written -- every flushed span of a dead worker is still
        stitchable by ``tools/trace_report.py``.  When a chrome tracer
        is attached the span is mirrored into ``trace.json`` too, so
        one Perfetto tab shows request spans next to host stages.
        """
        rec = {"trace": ctx.trace_id, "span": ctx.span_id,
               "parent": ctx.parent_id, "name": name,
               "ts": round(float(t_wall), 6),
               "dur_s": round(float(dur_s), 6), "status": status,
               "process": self.run_name, "pid": os.getpid()}
        if fields:
            rec.update(fields)
        with self._traces_lock:
            if self._closed:
                return None
            if self._traces_f is None:
                self._traces_f = open(self.traces_path, "w")
            self._traces_f.write(json.dumps(rec, default=str) + "\n")
            self._traces_f.flush()
        if self.tracer is not None:
            args = {"trace": ctx.trace_id, "status": status}
            if fields:
                args.update(fields)
            self.tracer.complete_at(name, t_wall, dur_s, **args)
        return rec

    # ----- lifecycle -------------------------------------------------------- #
    def flush(self):
        with self._write_lock:   # same shared-owner ordering as record():
            if not self._closed:     # a finally-path flush after another
                self._f.flush()      # owner's close() must not raise
        with self._traces_lock:
            if self._traces_f is not None and not self._traces_f.closed:
                self._traces_f.flush()
        if self.tracer is not None:
            self.tracer.flush()

    def close(self):
        with self._write_lock:            # don't close the file out from
            if self._closed:              # under a mid-record dispatcher
                return
            if not self._wrote_header:
                self.write_header()
            self._closed = True
            self._f.flush()
            try:
                os.fsync(self._f.fileno())  # the artifact is the deliverable
            except OSError:  # pragma: no cover - exotic filesystems
                pass
            self._f.close()
        with self._traces_lock:
            if self._traces_f is not None and not self._traces_f.closed:
                self._traces_f.flush()
                try:
                    os.fsync(self._traces_f.fileno())
                except OSError:  # pragma: no cover - exotic filesystems
                    pass
                self._traces_f.close()
        if self.tracer is not None:
            self.tracer.close()           # deactivates + terminates JSON

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
