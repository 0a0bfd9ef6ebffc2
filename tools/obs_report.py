"""Merge one run's telemetry artifacts into a single run report.

Inputs (produced by ``StepTelemetry``, see docs/observability.md):

- ``RUN_DIR/telemetry.jsonl`` -- header + per-step structured events
- ``RUN_DIR/trace.json``      -- host-span chrome trace (optional)
- an xplane trace dir         -- device planes (optional; ``--xplane``,
  default ``RUN_DIR/xplane`` when it exists)

Output: step-time percentiles, the data-wait fraction of wall time, the
device-busy fraction from the xplane witness, MFU from the compiled
step's ``cost_analysis`` flops (over the BLOCKED per-step time when the
run was fenced -- ``mfu_basis`` says which; docs/observability.md,
"Profiling & trusted timing"), a profiling section (timing mode, the
``timing_audit`` trust verdict, compute/collective/idle device-time
attribution), watchdog findings, model-health numerics (grad-norm
trajectory, worst-layer table, first non-finite step, anomalies -- when
a ``HealthMonitor`` fed the run), serving metrics (request-latency
percentiles, queue-depth trajectory, bucket histogram and pad waste --
when ``kind: "inference"`` events are present), host-span totals, and
the top-N HLO ops by device time.

    python tools/obs_report.py runs/resnet50 [--xplane DIR] [--format json]

A ``tools/train_supervised.py`` artifact ROOT (``attempt_<i>/`` dirs +
``supervisor/``) is accepted directly: the attempts' step events merge
into one report (with a per-attempt summary) and the supervisor's
``kind: "recovery"`` events feed the Recovery section -- one command
covers the whole supervised run instead of one report per attempt.

``--format json`` emits the same dict the text renderer consumes, with
non-finite floats mapped to null (strictly valid JSON), so scripts
can assert on health/occupancy numbers.  The reader tolerates
a truncated final JSONL line / undecodable bytes from a crashed run.
A run dir whose artifacts carry ZERO events worth reporting (no steps,
no serving/recovery/health/validation/memory) exits nonzero: a hollow
report silently passing in scripts is how a broken telemetry hookup
hides.  Memory events count -- the lone ``memory_dump`` a crashed run
left behind is exactly an artifact worth reporting.

No jax import -- the report runs anywhere the artifacts were copied.
"""

import argparse
import importlib.util
import json
import math
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# load utils/xplane.py by file path: going through the bigdl_tpu package
# would import jax (utils.engine) at package init, breaking the
# "runs anywhere the artifacts were copied" contract
_spec = importlib.util.spec_from_file_location(
    "_obs_xplane", os.path.join(REPO, "bigdl_tpu", "utils", "xplane.py"))
_xplane = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_xplane)
device_busy, op_breakdown = _xplane.device_busy, _xplane.op_breakdown
device_attribution = _xplane.device_attribution
load_device_planes = _xplane.load_device_planes

# same mechanism for observability/profiling.py (it has no top-level jax
# import by design): its nearest-rank percentile is THE one definition,
# shared with BlockingStepTimer's summaries, so a timer's summary and
# its run report can never disagree
_pspec = importlib.util.spec_from_file_location(
    "_obs_profiling",
    os.path.join(REPO, "bigdl_tpu", "observability", "profiling.py"))
_profiling = importlib.util.module_from_spec(_pspec)
_pspec.loader.exec_module(_profiling)
percentile = _profiling.percentile

# and for utils/hlo.py (pure text->dict parsers, no jax at module top):
# its format_summary_lines is THE one compiled-step text rendering,
# shared with tools/hlo_audit.py
_hspec = importlib.util.spec_from_file_location(
    "_obs_hlo", os.path.join(REPO, "bigdl_tpu", "utils", "hlo.py"))
_hlo = importlib.util.module_from_spec(_hspec)
_hspec.loader.exec_module(_hlo)
format_hlo_summary_lines = _hlo.format_summary_lines

# and for observability/spans.py (stdlib-only): its read_trace_events
# is THE one crash-tolerant chrome-trace reader, shared with
# tools/trace_report.py and the SpanTracer tests
_sspec = importlib.util.spec_from_file_location(
    "_obs_spans",
    os.path.join(REPO, "bigdl_tpu", "observability", "spans.py"))
_spans = importlib.util.module_from_spec(_sspec)
_sspec.loader.exec_module(_spans)
read_trace_events = _spans.read_trace_events

# tools/trace_report.py stitches traces.jsonl spans into per-request
# critical paths; the Tracing section below reuses it so the report
# and the standalone tool can never disagree about a trace
_tspec = importlib.util.spec_from_file_location(
    "_obs_trace_report", os.path.join(REPO, "tools", "trace_report.py"))
_trace_report = importlib.util.module_from_spec(_tspec)
_tspec.loader.exec_module(_trace_report)


def load_events(jsonl_path):
    """-> (header dict or None, [step events], [other events]).

    Crash-tolerant by contract: a truncated final line (process died
    mid-write) fails its json parse and is skipped, and
    ``errors="replace"`` keeps a half-written multibyte character from
    killing the whole read."""
    header, steps, other = None, [], []
    with open(jsonl_path, errors="replace") as f:
        for ln in f:
            ln = ln.strip()
            if not ln:
                continue
            try:
                ev = json.loads(ln)
            except ValueError:
                continue   # truncated tail of a crashed run
            kind = ev.get("kind")
            if kind == "header" and header is None:
                header = ev
            elif kind == "step":
                steps.append(ev)
            else:
                other.append(ev)
    return header, steps, other


def load_trace_events(trace_path):
    """Chrome-trace events from either container format (kept as an
    alias: the shared implementation moved to
    ``observability/spans.read_trace_events`` so every reader repairs
    a crash-truncated streamed array the same way)."""
    return read_trace_events(trace_path)


def span_totals(trace_path):
    """Aggregate the chrome trace's complete events by span name."""
    events = load_trace_events(trace_path)
    totals = {}
    for ev in events or []:
        if ev.get("ph") != "X":
            continue
        sec, cnt = totals.get(ev["name"], (0.0, 0))
        totals[ev["name"]] = (sec + ev.get("dur", 0.0) / 1e6, cnt + 1)
    if not totals:
        return None
    return [{"name": name, "sec": round(sec, 6), "count": cnt}
            for name, (sec, cnt) in
            sorted(totals.items(), key=lambda kv: -kv[1][0])]


def _finite(v):
    return isinstance(v, (int, float)) and math.isfinite(v)


def _health_section(events):
    """Summarize ``health`` + ``anomaly`` events: grad-norm trajectory,
    first non-finite step, worst-layer table (or None without any)."""
    health = [e for e in events if e.get("kind") == "health"]
    anomalies = [e for e in events if e.get("kind") == "anomaly"]
    if not health and not anomalies:
        return None
    sec = {"samples": len(health),
           "anomalies": [{k: v for k, v in a.items()
                          if k not in ("kind", "ts")} for a in anomalies]}
    if not health:
        return sec
    norms = [(e.get("step"), e.get("grad_norm")) for e in health]
    finite = [g for _, g in norms if _finite(g)]
    sec["grad_norm_first"] = norms[0][1] if _finite(norms[0][1]) else None
    sec["grad_norm_last"] = norms[-1][1] if _finite(norms[-1][1]) else None
    sec["grad_norm_max"] = max(finite) if finite else None
    stride = max(1, len(norms) // 40)     # <= ~40 trajectory points
    sec["grad_norm_trajectory"] = [
        {"step": s, "grad_norm": g if _finite(g) else None}
        for s, g in norms[::stride]]
    ratios = [e.get("update_ratio_max") for e in health]
    fin_ur = [u for u in ratios if _finite(u)]
    if fin_ur:
        sec["update_ratio_max"] = max(fin_ur)
    for e in health:
        bad = (e.get("nonfinite_grads") or e.get("nonfinite_params")
               or (e.get("loss") is not None and not _finite(e["loss"]))
               or (e.get("grad_norm") is not None
                   and not _finite(e["grad_norm"])))
        if bad:
            sec["first_nonfinite_step"] = e.get("step")
            sec["first_nonfinite_layer"] = e.get("worst_layer")
            break
    last = health[-1]
    layers = last.get("layers") or {}

    def badness(item):
        _, rec = item
        nf = int(rec.get("nonfinite_grads", 0)) \
            + int(rec.get("nonfinite_params", 0))
        gn = rec.get("grad_norm")
        return (nf > 0, not _finite(gn), gn if _finite(gn) else 0.0)

    worst = sorted(layers.items(), key=badness, reverse=True)[:5]
    sec["worst_layers"] = [
        {"layer": name,
         "grad_norm": rec.get("grad_norm") if _finite(rec.get("grad_norm"))
         else None,
         "update_ratio": rec.get("update_ratio")
         if _finite(rec.get("update_ratio")) else None,
         "nonfinite": int(rec.get("nonfinite_grads", 0))
         + int(rec.get("nonfinite_params", 0))}
        for name, rec in worst]
    sec["last_sample_step"] = last.get("step")
    return sec


def _communication_section(steps, other):
    """Summarize the dp wire plane: per-step wire bytes / compression
    ratio (stamped on every distributed step event) and the
    error-feedback residual-norm trajectory (riding the health samples
    when the compression spec has error feedback on).  None for runs
    without wire telemetry (local training)."""
    wired = [e for e in steps if "wire_bytes" in e]
    residuals = [(e.get("step"), e["ef_residual_norm"])
                 for e in other
                 if e.get("kind") == "health" and "ef_residual_norm" in e]
    if not wired and not residuals:
        return None
    sec = {}
    if wired:
        last = wired[-1]
        sec["wire_bytes_per_step"] = last["wire_bytes"]
        sec["wire_bytes_total"] = sum(e["wire_bytes"] for e in wired)
        for key in ("grad_wire_bytes", "weight_wire_bytes",
                    "compression_ratio", "grad_compression_ratio"):
            if key in last:
                sec[key] = last[key]
    if residuals:
        finite = [r for _, r in residuals if _finite(r)]
        sec["ef_residual_norm_first"] = residuals[0][1] \
            if _finite(residuals[0][1]) else None
        sec["ef_residual_norm_last"] = residuals[-1][1] \
            if _finite(residuals[-1][1]) else None
        sec["ef_residual_norm_max"] = max(finite) if finite else None
        stride = max(1, len(residuals) // 40)
        sec["ef_residual_trajectory"] = [
            {"step": s, "residual_norm": r if _finite(r) else None}
            for s, r in residuals[::stride]]
    return sec


def _serving_section(other, header=None):
    """Summarize ``kind: "inference"`` events -- the Predictor's batch
    path and the ServingEngine's coalescing ticks: per-request latency
    percentiles, queue-depth trajectory, bucket histogram and the
    pad-waste fraction (padded rows the bucket ladder spent to keep the
    executable set closed).  The header's ``serving`` block (or a later
    standalone ``serving_info`` event) adds WHICH precision served the
    run: ``quantized`` flag, weight dtype, model bytes.  None for runs
    without inference events."""
    inf = [e for e in other if e.get("kind") == "inference"]
    info = (header or {}).get("serving")
    for e in other:
        if e.get("kind") == "serving_info" and e.get("serving"):
            info = e["serving"]
    if not inf:
        # a deploy-only artifact (rollout loop audited, ticks recorded
        # elsewhere) still reports: the deploy trail is serving evidence
        deploy_only = _deploy_block(other)
        if deploy_only is None:
            return None
        sec = {"ticks": 0, "requests": 0, "deploys": deploy_only}
        if info:
            for k in ("quantized", "weight_dtype", "backend",
                      "version", "digest"):
                if info.get(k) is not None:
                    sec[k] = info[k]
        return sec
    # generation ticks (tick_kind set) report through their own block
    # below: folding second-scale decode ticks / slot-admission buckets
    # into the predict aggregates would corrupt every figure an
    # operator compares across runs (the same segregation reasoning as
    # generate_latency_s vs request_latency_s)
    pred = [e for e in inf if not e.get("tick_kind")]
    requests = sum(int(e.get("records", 0)) for e in pred)
    busy = sum(e.get("wall_s", 0.0) for e in pred)
    sec = {"ticks": len(pred), "requests": requests,
           "requests_per_s": (requests / busy) if busy > 0 else None}
    lats = sorted(l for e in pred
                  for l in (e.get("request_latency_s") or [])
                  if _finite(l))
    if lats:
        sec["latency_s_p50"] = percentile(lats, 50)
        sec["latency_s_p95"] = percentile(lats, 95)
        sec["latency_s_p99"] = percentile(lats, 99)
    depths = [(e.get("step"), e["queue_depth"])
              for e in pred if "queue_depth" in e]
    if depths:
        d = sorted(x for _, x in depths)
        sec["queue_depth_p50"] = percentile(d, 50)
        sec["queue_depth_p90"] = percentile(d, 90)
        caps = [e.get("queue_capacity") for e in pred
                if e.get("queue_capacity")]
        sec["queue_capacity"] = max(caps) if caps else None
        stride = max(1, len(depths) // 40)    # <= ~40 trajectory points
        sec["queue_depth_trajectory"] = [
            {"step": s, "depth": x} for s, x in depths[::stride]]
    bucketed = [e for e in pred if e.get("bucket")]
    if bucketed:
        hist = {}
        for e in bucketed:
            b = int(e["bucket"])
            hist[b] = hist.get(b, 0) + 1
        sec["bucket_histogram"] = {str(b): hist[b] for b in sorted(hist)}
        rows = sum(int(e["bucket"]) for e in bucketed)
        real = sum(int(e.get("records", 0)) for e in bucketed)
        if rows:
            sec["pad_waste_fraction"] = (rows - real) / rows
        fills = sorted(e["batch_fill"] for e in bucketed
                       if _finite(e.get("batch_fill")))
        if fills:
            sec["batch_fill_p50"] = percentile(fills, 50)
    # autoregressive generation ticks (serving/generation.py): the
    # tick_kind stamp splits prefill/decode, ``tokens`` accumulates the
    # emitted stream, and slot occupancy averages into the utilization
    # figure an operator sizes the slot pool by
    gen = [e for e in inf if e.get("tick_kind")]
    if gen:
        toks = sum(int(e.get("tokens", 0) or 0) for e in gen)
        # the rendered figure is "tok/s WHILE DECODING": decode ticks
        # only, so prefill-heavy runs don't dilute the decode rate
        dec = [e for e in gen if e["tick_kind"] == "decode"]
        dtoks = sum(int(e.get("tokens", 0) or 0) for e in dec)
        dwall = sum(e.get("wall_s", 0.0) for e in dec
                    if _finite(e.get("wall_s")))
        block = {"prefill_ticks": sum(1 for e in gen
                                      if e["tick_kind"] == "prefill"),
                 "decode_ticks": len(dec),
                 "requests": sum(int(e.get("records", 0) or 0)
                                 for e in gen
                                 if e["tick_kind"] == "prefill"),
                 "tokens": toks,
                 "tokens_per_s": (dtoks / dwall) if dwall > 0 else None}
        fills = [e["slots_active"] / e["slots_total"] for e in gen
                 if e.get("slots_total") and e["tick_kind"] == "decode"
                 and _finite(e.get("slots_active"))]
        if fills:
            block["slot_fill_mean"] = sum(fills) / len(fills)
        glats = sorted(l for e in gen
                       for l in (e.get("generate_latency_s") or [])
                       if _finite(l))
        if glats:
            block["latency_s_p50"] = percentile(glats, 50)
            block["latency_s_p99"] = percentile(glats, 99)
        # the segregated split (serving/generation.py): queue-wait
        # p99 blowing up while decode p99 holds = slot starvation,
        # not a slow model -- the merged latency alone can't say which
        for field, key in (("generate_queue_wait_s", "queue_wait"),
                           ("generate_decode_s", "decode")):
            vals = sorted(l for e in gen for l in (e.get(field) or [])
                          if _finite(l))
            if vals:
                block["%s_s_p50" % key] = percentile(vals, 50)
                block["%s_s_p99" % key] = percentile(vals, 99)
        # slot-occupancy attribution: which traced sequences were
        # resident, and for how many ticks each rode the pool
        rides = {}
        for e in gen:
            for tid in e.get("trace_ids") or []:
                rides[tid] = rides.get(tid, 0) + 1
        if rides:
            block["traced_sequences"] = len(rides)
            block["traced_tick_rides"] = sum(rides.values())
        slots = [e.get("slots_total") for e in gen if e.get("slots_total")]
        if slots:
            block["slots"] = max(slots)
        # paged-KV occupancy (serving/paging.py): the LAST tick's pool
        # state (a gauge, not a sum) plus the run's prefix-cache payoff
        # -- hit tokens over total prompt positions admitted is the
        # fraction of prefill compute the cache absorbed
        kv = [e for e in gen if e.get("kv_blocks_total")]
        if kv:
            last = kv[-1]
            block["kv_blocks"] = {
                "total": last["kv_blocks_total"],
                "used": last.get("kv_blocks_used", 0),
                "cached": last.get("kv_blocks_cached", 0),
                "free": last.get("kv_blocks_free", 0)}
            hit_tokens = sum(int(e.get("prefix_hit_tokens", 0) or 0)
                             for e in gen)
            if hit_tokens:
                block["prefix_hits"] = sum(
                    int(e.get("prefix_hits", 0) or 0) for e in gen)
                block["prefix_hit_tokens"] = hit_tokens
                prompt_tokens = sum(
                    int(e.get("prompt_tokens", 0) or 0) for e in gen)
                if prompt_tokens > 0:
                    block["prefix_hit_rate"] = hit_tokens / prompt_tokens
        if info and info.get("kv_cache_dtype"):
            block["kv_dtype"] = info["kv_cache_dtype"]
        # speculative ticks (SpeculativeScheduler): acceptance rate =
        # accepted/drafted, and tokens-per-verify = emitted tokens over
        # verify rounds -- the two figures the speedup claim rests on
        spec = [e for e in gen if e.get("spec_drafted") is not None]
        if spec:
            drafted = sum(int(e.get("spec_drafted", 0) or 0)
                          for e in spec)
            accepted = sum(int(e.get("spec_accepted", 0) or 0)
                           for e in spec)
            stoks = sum(int(e.get("tokens", 0) or 0) for e in spec)
            sblock = {"k": max(int(e.get("spec_k", 0) or 0)
                               for e in spec),
                      "rounds": len(spec), "drafted": drafted,
                      "accepted": accepted}
            if drafted:
                sblock["acceptance_rate"] = accepted / drafted
            if spec:
                sblock["tokens_per_verify"] = stoks / len(spec)
            block["speculative"] = sblock
        sec["generate"] = block
    if info:
        for k in ("quantized", "weight_dtype", "model_bytes",
                  "model_bytes_fp32", "backend", "replicas",
                  "version", "digest"):
            if info.get(k) is not None:
                sec[k] = info[k]
        if info.get("accuracy_gate"):
            sec["accuracy_gate"] = info["accuracy_gate"]
    # weight-swap audit: every refresh outcome, with the rejections'
    # reasons -- a run that served through a bad-checkpoint window shows
    # it here
    refreshes = [e for e in other if e.get("kind") == "param_refresh"]
    if refreshes:
        sec["param_refreshes"] = {
            "ok": sum(1 for e in refreshes if e.get("outcome") == "ok"),
            "rejected": sum(1 for e in refreshes
                            if e.get("outcome") == "rejected")}
        reasons = [e.get("reason") for e in refreshes
                   if e.get("outcome") == "rejected" and e.get("reason")]
        if reasons:
            sec["param_refreshes"]["rejection_reasons"] = reasons[-4:]
    # continuous deployment: the staged-rollout audit trail
    # (serving/deploy.py, docs/robustness.md "Continuous deployment")
    dep = _deploy_block(other)
    if dep is not None:
        sec["deploys"] = dep
    return sec


def _deploy_block(other):
    """Summarize ``kind: "deploy"`` events, or None without any."""
    deploys = [e for e in other if e.get("kind") == "deploy"]
    if not deploys:
        return None
    last_live = None
    for e in deploys:
        if e.get("stage") in ("live", "resume") \
                and e.get("verdict") == "ok":
            last_live = {"version": e.get("version"),
                         "digest": e.get("digest")}
        elif e.get("stage") == "rollback" \
                and e.get("rolled_back_to") is not None:
            # a rollback makes the RETAINED previous version live again
            last_live = {"version": e.get("rolled_back_to"),
                         "digest": None}
    dep = {
        "events": len(deploys),
        "cutovers": sum(1 for e in deploys
                        if e.get("stage") == "live"
                        and e.get("verdict") == "ok"),
        "rejected": sum(1 for e in deploys
                        if e.get("verdict") == "rejected"),
        "rollbacks": sum(1 for e in deploys
                         if e.get("stage") == "rollback"),
        "trail": [{k: e.get(k) for k in
                   ("version", "stage", "verdict", "reason",
                    "digest", "top1_agreement", "rolled_back_to")
                   if e.get(k) is not None}
                  for e in deploys[-10:]],
    }
    if last_live is not None:
        dep["live_version"] = last_live.get("version")
        dep["live_digest"] = last_live.get("digest")
    return dep


def _fleet_section(other):
    """Summarize ``kind: "fleet"`` events -- the ServingFleet's
    replica lifecycle/breaker edges, supervisor restarts and the final
    request-counter stats event (docs/robustness.md, "Serving
    fleets"): per-replica last state + death counts, the breaker
    transition trail, and ok/failed/shed/retries/hedges totals.  None
    for runs without fleet events."""
    evs = [e for e in other if e.get("kind") == "fleet"]
    if not evs:
        return None
    replicas, transitions, restarts, stats = {}, [], 0, None
    wire = {}
    for e in evs:
        rid = e.get("replica")
        what = e.get("event")
        if what == "state" and rid is not None:
            rec = replicas.setdefault(str(rid), {"replica": rid})
            rec["state"] = e.get("state")
            if e.get("state") == "dead":
                rec["deaths"] = rec.get("deaths", 0) + 1
                if e.get("reason"):
                    rec["last_death_reason"] = e["reason"]
        elif what == "breaker" and rid is not None:
            transitions.append({"replica": rid, "from": e.get("from"),
                                "to": e.get("to")})
            replicas.setdefault(str(rid), {"replica": rid})["breaker"] \
                = e.get("to")
        elif what == "restart":
            restarts += 1
            if rid is not None:
                rec = replicas.setdefault(str(rid), {"replica": rid})
                rec["restarts"] = rec.get("restarts", 0) + 1
        elif what == "stats":
            stats = {k: e[k] for k in ("ok", "failed", "shed", "retries",
                                       "hedges", "hedge_wins")
                     if e.get(k) is not None}
        elif what == "wire":
            # per-verb wire-traffic deltas flushed by the fleet
            # (docs/performance.md, "Fleet transport"); RTT samples
            # are bounded per report (the fleet bounds them per flush)
            verb = str(e.get("verb") or "?")
            w = wire.setdefault(verb, {"verb": verb, "calls": 0,
                                       "bytes_sent": 0, "bytes_recv": 0,
                                       "rtt_s": []})
            w["calls"] += int(e.get("calls") or 0)
            w["bytes_sent"] += int(e.get("bytes_sent") or 0)
            w["bytes_recv"] += int(e.get("bytes_recv") or 0)
            if len(w["rtt_s"]) < 4096:
                w["rtt_s"].extend(
                    float(v) for v in (e.get("rtt_s") or ())
                    if isinstance(v, (int, float)))
    sec = {"events": len(evs),
           "replicas": [replicas[k] for k in sorted(replicas)],
           "breaker_transitions": transitions[-12:],
           "breaker_transitions_total": len(transitions),
           "restarts": restarts}
    if stats is not None:
        sec["requests"] = stats
    if wire:
        rows = []
        for verb in sorted(wire):
            w = wire[verb]
            rtts = w.pop("rtt_s")
            if rtts:
                w["rtt_p50_ms"] = round(1e3 * percentile(rtts, 50), 3)
                w["rtt_p99_ms"] = round(1e3 * percentile(rtts, 99), 3)
            rows.append(w)
        sec["wire"] = rows
    return sec


def _slo_section(other):
    """Summarize ``kind: "slo"`` events -- the SloTracker's burn-rate
    breach/resolve edges (docs/observability.md, "Live metrics &
    SLOs"): per-objective breach counts and whether each objective is
    still breached at end of run.  None for runs without SLO events."""
    evs = [e for e in other if e.get("kind") == "slo"]
    if not evs:
        return None
    objectives = {}
    for e in evs:
        name = e.get("objective") or "?"
        rec = objectives.setdefault(
            name, {"objective": name, "slo": e.get("slo"),
                   "policy": e.get("policy"), "breaches": 0,
                   "breached_at_end": False})
        if e.get("breach"):
            rec["breaches"] += 1
            rec["breached_at_end"] = True
        else:
            rec["breached_at_end"] = False
    return {"events": len(evs),
            "objectives": [objectives[k] for k in sorted(objectives)]}


def _memory_section(other, header=None):
    """Summarize the device-memory ledger (observability/memory.py):
    ``kind: "memory"`` snapshots (per-subsystem attribution reconciled
    against ``device_memory_stats()``), forensic ``memory_dump``
    events, and the compiled-program ``memory_budget`` stamped by
    ``attach_cost(memory_budget=True)``.  The residual trajectory is
    the leak detector: a residual that only grows is bytes no
    registered subsystem owns up to.  None when the run recorded none
    of the three."""
    snaps = [e for e in other if e.get("kind") == "memory"]
    dumps = [e for e in other if e.get("kind") == "memory_dump"]
    budget = (header or {}).get("memory_budget")
    for ev in other:
        if ev.get("kind") == "cost" and ev.get("memory_budget"):
            budget = ev["memory_budget"]
    if not snaps and not dumps and not budget:
        return None
    sec = {"snapshots": len(snaps)}
    last = snaps[-1] if snaps \
        else (dumps[-1].get("ledger") if dumps else None)
    if last:
        sec["last"] = {k: last.get(k) for k in
                       ("subsystems", "attributed_bytes", "live_bytes",
                        "residual_bytes", "limit_bytes",
                        "headroom_bytes", "headroom_fraction")}
    residuals = [e["residual_bytes"] for e in snaps
                 if e.get("residual_bytes") is not None]
    if residuals:
        sec["residual_first_bytes"] = residuals[0]
        sec["residual_last_bytes"] = residuals[-1]
        sec["residual_max_bytes"] = max(residuals)
    if dumps:
        sec["dumps"] = [{"reason": d.get("reason"),
                         "error": d.get("error"), "ts": d.get("ts"),
                         "detail": d.get("detail"),
                         "last_ticks": len(d.get("last_ticks") or ())}
                        for d in dumps]
    if budget:
        sec["compiled_budget"] = budget
    return sec


def _recovery_section(other):
    """Summarize ``kind: "recovery"`` events -- the RunSupervisor's
    restart records (docs/robustness.md): one entry per restart (cause,
    snapshot resumed from, steps replayed, backoff), plus totals --
    and ``kind: "reshard"`` events (the cross-layout redistributions an
    elastic restart or a layout-aware serving refresh performed:
    src/dst layout, planes moved, host bytes, wall seconds).  None for
    runs with neither."""
    recs = [e for e in other if e.get("kind") == "recovery"]
    resh = [e for e in other if e.get("kind") == "reshard"]
    if not recs and not resh:
        return None
    causes = {}
    for e in recs:
        c = e.get("cause") or "?"
        causes[c] = causes.get(c, 0) + 1
    replayed = [e.get("steps_replayed") for e in recs
                if isinstance(e.get("steps_replayed"), (int, float))]
    sec = {
        "restarts": len(recs),
        "causes": causes,
        "steps_replayed_total": int(sum(replayed)) if replayed else None,
        "backoff_s_total": sum(e.get("backoff_s") or 0.0 for e in recs),
        "events": [{k: e.get(k) for k in
                    ("restart", "cause", "error", "at_step", "snapshot",
                     "snapshot_step", "steps_replayed", "backoff_s")}
                   for e in recs],
    }
    if resh:
        sec["reshards"] = [{k: e.get(k) for k in
                            ("src", "dst", "what", "planes",
                             "host_bytes", "wall_s")}
                           for e in resh]
    return sec


def _profiling_section(header, blocked, other, planes, top=10):
    """Summarize the trusted-timing evidence (docs/observability.md,
    "Profiling & trusted timing"): the blocked per-step percentiles
    (``blocked`` is the sorted list build_report already extracted --
    computed once, reported in both sections), the run's timing mode,
    the ``timing_audit`` trust verdict, and the trace-derived
    device-time attribution (compute vs collective vs idle fractions,
    top ops; ``planes`` is the once-decoded trace from
    ``load_device_planes``).  None for runs with none of these."""
    sec = {}
    if blocked:
        sec["steps_timed"] = len(blocked)
        sec["step_blocked_s_p50"] = percentile(blocked, 50)
        sec["step_blocked_s_p90"] = percentile(blocked, 90)
    timing = (header or {}).get("timing")
    for ev in other:   # a late set_timing_mode records a standalone event
        if ev.get("kind") == "timing" and ev.get("timing"):
            timing = ev["timing"]
    if timing:
        sec["timing_mode"] = timing.get("mode")
        sec["trust_basis"] = timing.get("trust_basis")
    audits = [e for e in other if e.get("kind") == "timing_audit"]
    if audits:
        last = audits[-1]
        sec["trust"] = last.get("trust")
        sec["published"] = last.get("published")
        sec["estimates"] = last.get("estimates")
        sec["checks"] = last.get("checks")
    if planes:
        attribution = device_attribution(planes, top=top)
        if attribution:
            sec["device_attribution"] = attribution
    return sec or None


def supervisor_sources(run_dir):
    """A ``tools/train_supervised.py`` artifact root's telemetry files:
    ordered ``[(attempt_index, jsonl_path)]`` plus the supervisor's own
    jsonl (or None)."""
    attempts = []
    try:
        names = os.listdir(run_dir)
    except OSError:
        return [], None
    for name in names:
        m = re.fullmatch(r"attempt_(\d+)", name)
        p = os.path.join(run_dir, name, "telemetry.jsonl")
        if m and os.path.isfile(p):
            attempts.append((int(m.group(1)), p))
    attempts.sort()
    sup = os.path.join(run_dir, "supervisor", "telemetry.jsonl")
    return attempts, (sup if os.path.isfile(sup) else None)


def load_supervised_run(run_dir):
    """Merge a supervised run's attempts into one event stream:
    -> (header, steps, other, attempts_summary).  Steps concatenate in
    attempt order (each annotated with its ``attempt``), the
    supervisor's recovery events ride in ``other``, and the header is
    the first attempt's (the run's devices/cost provenance)."""
    attempts, sup = supervisor_sources(run_dir)
    header, steps, other, summary = None, [], [], []
    for idx, path in attempts:
        h, s, o = load_events(path)
        if header is None:
            header = h
        for ev in s:
            ev["attempt"] = idx
        steps.extend(s)
        other.extend(o)
        summary.append({
            "attempt": idx, "steps": len(s),
            "first_step": s[0].get("step") if s else None,
            "last_step": s[-1].get("step") if s else None,
            "loss_last": s[-1].get("loss") if s else None,
        })
    if sup is not None:
        _, s_steps, s_other = load_events(sup)
        other.extend(s_other)      # the recovery events live here
        steps.extend(s_steps)      # (a supervisor records no steps today)
    return header, steps, other, summary


def _tracing_section(run_dir):
    """Distributed-tracing summary from ``traces.jsonl`` sinks under
    the run dir (the driver's and, in a fleet artifact root, every
    worker's): per-request critical paths stitched by trace_id via
    tools/trace_report.py.  None for untraced runs."""
    report = _trace_report.summarize([run_dir], limit=5)
    if report["summary"]["records"] == 0:
        return None
    sec = dict(report["summary"])
    sec["slowest"] = [
        {"trace": c["trace"], "op": c.get("op"),
         "status": c.get("status"), "total_s": c.get("total_s"),
         "stages": c.get("stages") or {}, "ticks": c.get("ticks") or {}}
        for c in report["traces"]]
    return sec


def build_report(run_dir, xplane_dir=None, top=10):
    jsonl = os.path.join(run_dir, "telemetry.jsonl")
    attempts_summary = None
    if os.path.isfile(jsonl):
        header, steps, other = load_events(jsonl)
    else:
        # a train_supervised artifact root is a first-class run dir
        header, steps, other, attempts_summary = \
            load_supervised_run(run_dir)
        if not attempts_summary and not other:
            raise FileNotFoundError(
                f"no telemetry.jsonl (and no attempt_<i>/ or supervisor/ "
                f"artifacts) under {run_dir}")

    rep = {"run_dir": run_dir, "header": header, "n_steps": len(steps)}
    if attempts_summary is not None:
        rep["attempts"] = attempts_summary
    # fenced per-step times, extracted ONCE: the steps block and the
    # profiling section both report from this list
    blocked = sorted(e["step_blocked_s"] for e in steps
                     if "step_blocked_s" in e)
    if steps:
        walls = sorted(e["wall_s"] for e in steps)
        waits = [e.get("data_wait_s", 0.0) for e in steps]
        rates = sorted(e["records_per_s"] for e in steps)
        total_wall = sum(walls)
        rep["steps"] = {
            "wall_s_p50": percentile(walls, 50),
            "wall_s_p90": percentile(walls, 90),
            "wall_s_p99": percentile(walls, 99),
            "wall_s_total": total_wall,
            "data_wait_fraction": sum(waits) / max(total_wall, 1e-12),
            "records_per_s_p50": percentile(rates, 50),
            "records_total": sum(e.get("records", 0) for e in steps),
            "loss_first": steps[0].get("loss"),
            "loss_last": steps[-1].get("loss"),
        }
        skews = [e.get("sync_skew", 0) for e in steps]
        if any(skews):
            # deferred loss sync was active: loss/throughput per step are
            # fresh only at sync points (sync_skew counts the staleness)
            rep["steps"]["sync_skew_max"] = max(skews)
        # prefetch-queue occupancy: a STARVED queue (occupancy pinned at
        # 0 -> high data-wait) is a pipeline problem; a FULL one with high
        # wall times is a slow device.  Percentiles make the two
        # distinguishable at a glance.
        depths = sorted(e["queue_depth"] for e in steps
                        if "queue_depth" in e)
        if depths:
            caps = [e.get("queue_capacity") for e in steps
                    if e.get("queue_capacity")]
            rep["steps"]["prefetch_queue"] = {
                "depth_p10": percentile(depths, 10),
                "depth_p50": percentile(depths, 50),
                "depth_p90": percentile(depths, 90),
                "capacity": max(caps) if caps else None,
                "starved_fraction": sum(1 for d in depths if d == 0)
                / len(depths),
            }
        # trusted timing (set_blocking_timing): the ONLY basis MFU
        # below may use when present (docs/observability.md, Profiling)
        if blocked:
            rep["steps"]["step_blocked_s_p50"] = percentile(blocked, 50)
            rep["steps"]["step_blocked_s_p90"] = percentile(blocked, 90)
        # MFU: flops of the compiled step over the median step's
        # BLOCKED time when the run was fenced (step_blocked_s), else
        # the wall time -- mfu_basis says which, so a report can never
        # pass off an un-fenced number as a fenced one.  Cost lives on
        # the header, or on a later standalone "cost" event when
        # attach_cost ran after the lazy header write.
        cost = (header or {}).get("cost") or {}
        for ev in other:
            if ev.get("kind") == "cost" and ev.get("cost"):
                cost = ev["cost"]
        peak = (header or {}).get("peak_flops")
        basis_key = "step_blocked_s" if blocked else "wall_s"
        basis_p50 = (rep["steps"]["step_blocked_s_p50"] if blocked
                     else rep["steps"]["wall_s_p50"])
        # the basis is stated whether or not an MFU follows from it: off
        # a TPU the header carries no peak FLOP/s, so there is no MFU,
        # but an un-fenced step time still must not read as a fenced one
        rep["steps"]["mfu_basis"] = basis_key
        if cost.get("flops_per_step") and peak and basis_p50:
            rep["steps"]["mfu_p50"] = (
                cost["flops_per_step"] / basis_p50 / peak)
        mems = [e["memory"] for e in steps if e.get("memory")]
        if mems:
            rep["memory_last"] = mems[-1]
        recompiles = [{"step": e["step"], "compiles": e["recompiles"]}
                      for e in steps if e.get("recompiles")]
        growth = [{"step": e["step"], "devices": e["memory_growth"]}
                  for e in steps if e.get("memory_growth")]
        rep["watchdogs"] = {"recompile_steps": recompiles,
                            "memory_growth": growth}
    # compiled-step audit (attach_cost's lowering-text summary, stamped
    # on the header -- or on a later standalone "cost" event when
    # attach_cost ran after the lazy header write): donation coverage,
    # dot/conv dtypes, collective counts (docs/observability.md,
    # "Compiled step audit")
    compiled_step = (header or {}).get("compiled_step")
    for ev in other:
        if ev.get("kind") == "cost" and ev.get("compiled_step"):
            compiled_step = ev["compiled_step"]
    if compiled_step:
        rep["compiled_step"] = compiled_step

    validations = [e for e in other if e.get("kind") == "validation"]
    if validations:
        rep["validations"] = validations
    health = _health_section(other)
    if health:
        rep["health"] = health
    comm = _communication_section(steps, other)
    if comm:
        rep["communication"] = comm
    serving = _serving_section(other, header)
    if serving:
        rep["serving"] = serving
    fleet = _fleet_section(other)
    if fleet:
        rep["fleet"] = fleet
    recovery = _recovery_section(other)
    if recovery:
        rep["recovery"] = recovery
    slo = _slo_section(other)
    if slo:
        rep["slo"] = slo
    memory = _memory_section(other, header)
    if memory:
        rep["memory"] = memory
    tracing = _tracing_section(run_dir)
    if tracing:
        rep["tracing"] = tracing

    rep["host_spans"] = span_totals(os.path.join(run_dir, "trace.json"))

    if xplane_dir is None:
        cand = os.path.join(run_dir, "xplane")
        xplane_dir = cand if os.path.isdir(cand) else None
    planes = load_device_planes(xplane_dir) if xplane_dir else None
    if planes:
        # ONE proto decode feeds all three trace summaries
        busy = device_busy(planes)
        rep["device"] = busy
        if busy and busy.get("span_sec"):
            rep["device"]["busy_fraction"] = (
                busy["busy_event_sec"] / busy["span_sec"])
        ops = op_breakdown(planes, top=top)
        if ops:
            rep["top_ops"] = ops["ops"][:top]
            rep["op_categories"] = ops["categories"][:top]
    profiling = _profiling_section(header, blocked, other, planes,
                                   top=top)
    if profiling:
        rep["profiling"] = profiling
    return rep


def _fmt_s(v):
    return "-" if v is None else f"{v * 1e3:.2f} ms"


def _fmt_b(v):
    """Bytes for humans: 12_345_678 -> '12.35 MB'; None -> '-'."""
    if v is None:
        return "-"
    if abs(v) >= 1e9:
        return f"{v / 1e9:.2f} GB"
    if abs(v) >= 1e6:
        return f"{v / 1e6:.2f} MB"
    if abs(v) >= 1e3:
        return f"{v / 1e3:.1f} kB"
    return f"{int(v)} B"


def format_report(rep):
    out = [f"== run report: {rep['run_dir']} =="]
    h = rep.get("header") or {}
    if h:
        out.append(
            f"platform {h.get('platform', '?')} "
            f"({h.get('device_kind', '?')} x{h.get('device_count', '?')}), "
            f"jax {h.get('jax_version', '?')}, run '{h.get('run', '?')}'")
        cost = h.get("cost") or {}
        if cost.get("flops_per_step"):
            out.append(f"compiled step: {cost['flops_per_step']:.3e} flops, "
                       f"{cost.get('bytes_accessed_per_step', 0):.3e} bytes "
                       "accessed")
    att = rep.get("attempts")
    if att is not None:
        out.append(f"supervised run: {len(att)} attempt(s)")
        for a in att:
            loss = a.get("loss_last")
            out.append(
                f"  attempt {a['attempt']}: {a['steps']} steps "
                f"({a.get('first_step')} -> {a.get('last_step')})"
                + (f", last loss {loss:.6f}" if _finite(loss) else ""))
    s = rep.get("steps")
    if s:
        out.append(f"steps: {rep['n_steps']}  "
                   f"wall p50/p90/p99: {_fmt_s(s['wall_s_p50'])} / "
                   f"{_fmt_s(s['wall_s_p90'])} / {_fmt_s(s['wall_s_p99'])}")
        out.append(f"data-wait fraction: {s['data_wait_fraction']:.2%}   "
                   f"records/s p50: {s['records_per_s_p50']:.1f}   "
                   f"records total: {s['records_total']}")
        q = s.get("prefetch_queue")
        if q:
            cap = q["capacity"] if q["capacity"] is not None else "?"
            out.append(
                f"prefetch queue occupancy p10/p50/p90: "
                f"{q['depth_p10']}/{q['depth_p50']}/{q['depth_p90']} "
                f"of {cap}   starved {q['starved_fraction']:.1%} of steps")
        if s.get("sync_skew_max"):
            out.append(f"deferred loss sync: skew up to "
                       f"{s['sync_skew_max']} steps (loss/throughput "
                       f"fresh at sync points only)")
        out.append(f"loss: {s['loss_first']:.6f} -> {s['loss_last']:.6f}")
        basis_note = ("blocking-fenced step time"
                      if s.get("mfu_basis", "wall_s") == "step_blocked_s"
                      else "UN-FENCED wall time -- not publishable")
        if s.get("mfu_p50") is not None:
            out.append(f"MFU @ p50 step time: {s['mfu_p50']:.2%} "
                       f"(peak {h.get('peak_flops', 0):.0f} FLOP/s assumed; "
                       f"basis: {basis_note})")
        else:
            out.append(f"MFU: none (no peak FLOP/s off a TPU, or no cost "
                       f"attached); step-time basis: {basis_note}")
    pf = rep.get("profiling")
    if pf:
        line = "profiling:"
        if pf.get("timing_mode"):
            line += f" timing mode {pf['timing_mode']}"
        if pf.get("trust"):
            line += f"   trust {pf['trust']}"
        if line != "profiling:":
            out.append(line)
        if pf.get("step_blocked_s_p50") is not None:
            out.append(
                f"step_blocked p50/p90: {_fmt_s(pf['step_blocked_s_p50'])} "
                f"/ {_fmt_s(pf.get('step_blocked_s_p90'))} over "
                f"{pf.get('steps_timed')} fenced steps")
        for c in pf.get("checks") or []:
            out.append(f"  [audit] {c}")
        da = pf.get("device_attribution")
        if da:
            out.append(
                f"device attribution '{da['plane']}': compute "
                f"{da['compute_fraction']:.1%} / collective "
                f"{da['collective_fraction']:.1%} / idle "
                f"{da['idle_fraction']:.1%} of {da['span_sec']:.4f}s span")
            for op in da.get("ops", [])[:8]:
                out.append(f"  {op['pct']:>6.2f}%  {op['sec']:.6f}s  "
                           f"x{op['count']:<4} [{op['flavor']:<10}] "
                           f"{op['name'][:70]}")
    cs = rep.get("compiled_step")
    if cs:
        out.append(f"compiled step ({cs.get('source', '?')} audit):")
        out.extend(format_hlo_summary_lines(cs))
    hl = rep.get("health")
    if hl:
        def _g(v):
            return "non-finite" if v is None else f"{v:.4g}"
        if hl.get("samples"):
            out.append(
                f"health: {hl['samples']} samples  grad-norm "
                f"{_g(hl.get('grad_norm_first'))} -> "
                f"{_g(hl.get('grad_norm_last'))}"
                + (f" (max {hl['grad_norm_max']:.4g})"
                   if hl.get("grad_norm_max") is not None else ""))
        if hl.get("first_nonfinite_step") is not None:
            out.append(
                f"FIRST NON-FINITE numerics at step "
                f"{hl['first_nonfinite_step']} "
                f"(layer {hl.get('first_nonfinite_layer')})")
        if hl.get("worst_layers"):
            out.append(f"worst layers (sample @ step "
                       f"{hl.get('last_sample_step')}):")
            for w in hl["worst_layers"]:
                line = (f"  {w['layer']:<32} grad-norm {_g(w['grad_norm'])}"
                        f"  update-ratio {_g(w['update_ratio'])}")
                if w.get("nonfinite"):
                    line += f"  NONFINITE x{w['nonfinite']}"
                out.append(line)
        for a in hl.get("anomalies", []):
            line = (f"ANOMALY [{a.get('watchdog')}] at step {a.get('step')}"
                    f" (policy {a.get('policy')})")
            if a.get("incident_dir"):
                line += f" -> {a['incident_dir']}"
            out.append(line)
    cm = rep.get("communication")
    if cm:
        if cm.get("wire_bytes_per_step") is not None:
            line = (f"communication: {cm['wire_bytes_per_step']:,} wire "
                    f"bytes/step")
            if cm.get("grad_wire_bytes") is not None:
                line += (f" (grad {cm['grad_wire_bytes']:,} + weights "
                         f"{cm.get('weight_wire_bytes', 0):,})")
            if cm.get("compression_ratio") is not None:
                line += (f"   compression {cm['compression_ratio']:.2f}x"
                         f" (grad plane "
                         f"{cm.get('grad_compression_ratio', 0):.2f}x)")
            out.append(line)
        # gate on residual data being PRESENT, not on the last sample
        # being finite -- a blown-up residual is the case the line
        # exists to surface ("non-finite" renders via _r)
        if cm.get("ef_residual_trajectory"):
            def _r(v):
                return "non-finite" if v is None else f"{v:.4g}"
            out.append(
                f"error-feedback residual norm: "
                f"{_r(cm.get('ef_residual_norm_first'))} -> "
                f"{_r(cm.get('ef_residual_norm_last'))}"
                + (f" (max {cm['ef_residual_norm_max']:.4g})"
                   if cm.get("ef_residual_norm_max") is not None else ""))
    sv = rep.get("serving")
    if sv:
        line = f"serving: {sv['ticks']} ticks / {sv['requests']} requests"
        if sv.get("requests_per_s") is not None:
            line += f" ({sv['requests_per_s']:.1f} req/s while serving)"
        out.append(line)
        if sv.get("version") is not None:
            out.append(
                f"serving version: v{sv['version']}"
                + (f" (digest {sv['digest']})" if sv.get("digest")
                   else ""))
        dep = sv.get("deploys")
        if dep:
            line = (f"deploys: {dep['cutovers']} cutover(s), "
                    f"{dep['rejected']} rejected, "
                    f"{dep['rollbacks']} rollback(s)")
            if dep.get("live_version") is not None:
                line += f"   live v{dep['live_version']}"
            out.append(line)
            for e in dep.get("trail", [])[-6:]:
                ln = (f"  v{e.get('version')} {e.get('stage')}: "
                      f"{e.get('verdict')}")
                if e.get("top1_agreement") is not None:
                    ln += f" (agreement {e['top1_agreement']:.4f})"
                if e.get("rolled_back_to") is not None:
                    ln += f" -> v{e['rolled_back_to']}"
                if e.get("reason"):
                    ln += f" -- {str(e['reason'])[:80]}"
                out.append(ln)
        if sv.get("weight_dtype"):
            line = (f"serving precision: {sv['weight_dtype']}"
                    + (" (quantized)" if sv.get("quantized") else ""))
            if sv.get("model_bytes") is not None:
                line += f", model {sv['model_bytes'] / 1e6:.2f} MB"
                if sv.get("model_bytes_fp32"):
                    ratio = sv["model_bytes_fp32"] / sv["model_bytes"]
                    line += (f" (fp32 {sv['model_bytes_fp32'] / 1e6:.2f} MB,"
                             f" {ratio:.1f}x)")
            out.append(line)
            gate = sv.get("accuracy_gate")
            if gate:
                out.append(
                    f"accuracy gate: "
                    f"{'ok' if gate.get('ok') else 'FAILED'}"
                    + (f", top-1 agreement {gate['top1_agreement']:.4f}"
                       if gate.get("top1_agreement") is not None else "")
                    + (f", logit rmse {gate['logit_rmse']:.4g}"
                       if gate.get("logit_rmse") is not None else ""))
        pr = sv.get("param_refreshes")
        if pr:
            line = (f"param refreshes: {pr['ok']} ok / "
                    f"{pr['rejected']} rejected")
            for r in pr.get("rejection_reasons", []):
                line += f"\n  rejected: {r}"
            out.append(line)
        if sv.get("latency_s_p50") is not None:
            out.append(
                f"request latency p50/p95/p99: "
                f"{_fmt_s(sv['latency_s_p50'])} / "
                f"{_fmt_s(sv.get('latency_s_p95'))} / "
                f"{_fmt_s(sv.get('latency_s_p99'))}")
        if sv.get("bucket_histogram"):
            line = "buckets: " + ", ".join(
                f"{b} x{c}" for b, c in sv["bucket_histogram"].items())
            if sv.get("pad_waste_fraction") is not None:
                line += f"   pad waste {sv['pad_waste_fraction']:.1%}"
            if sv.get("batch_fill_p50") is not None:
                line += f"   fill p50 {sv['batch_fill_p50']:.0%}"
            out.append(line)
        if sv.get("queue_depth_p50") is not None:
            cap = sv.get("queue_capacity")
            out.append(
                f"serving queue depth p50/p90: {sv['queue_depth_p50']}/"
                f"{sv['queue_depth_p90']}"
                + (f" (capacity {cap})" if cap is not None else ""))
        gen = sv.get("generate")
        if gen:
            line = (f"generation: {gen['tokens']} tokens over "
                    f"{gen['prefill_ticks']} prefill / "
                    f"{gen['decode_ticks']} decode ticks")
            if gen.get("tokens_per_s") is not None:
                line += f" ({gen['tokens_per_s']:.1f} tok/s while decoding)"
            if gen.get("slot_fill_mean") is not None:
                line += (f"   slot fill {gen['slot_fill_mean']:.0%}"
                         + (f" of {gen['slots']}" if gen.get("slots")
                            else ""))
            out.append(line)
            if gen.get("latency_s_p50") is not None:
                out.append(
                    f"generation latency p50/p99: "
                    f"{_fmt_s(gen['latency_s_p50'])} / "
                    f"{_fmt_s(gen.get('latency_s_p99'))}")
            if gen.get("queue_wait_s_p50") is not None \
                    or gen.get("decode_s_p50") is not None:
                out.append(
                    f"  split: slot-queue wait p50/p99 "
                    f"{_fmt_s(gen.get('queue_wait_s_p50'))} / "
                    f"{_fmt_s(gen.get('queue_wait_s_p99'))}   decode "
                    f"p50/p99 {_fmt_s(gen.get('decode_s_p50'))} / "
                    f"{_fmt_s(gen.get('decode_s_p99'))}")
            if gen.get("traced_sequences"):
                out.append(
                    f"  traced sequences: {gen['traced_sequences']} "
                    f"({gen['traced_tick_rides']} slot-tick rides)")
            kvb = gen.get("kv_blocks")
            if kvb:
                out.append(
                    f"  kv blocks: {kvb['used']} used / "
                    f"{kvb['cached']} cached / {kvb['free']} free "
                    f"of {kvb['total']}"
                    + (f"   ({gen['kv_dtype']} blocks)"
                       if gen.get("kv_dtype") else ""))
            spec = gen.get("speculative")
            if spec:
                line = (f"  speculative: draft k={spec['k']}, "
                        f"{spec['accepted']}/{spec['drafted']} drafts "
                        f"accepted")
                if spec.get("acceptance_rate") is not None:
                    line += f" ({spec['acceptance_rate']:.0%})"
                if spec.get("tokens_per_verify") is not None:
                    line += (f", {spec['tokens_per_verify']:.2f} "
                             f"tokens/verify step")
                out.append(line)
            if gen.get("prefix_hit_tokens"):
                line = (f"  prefix cache: {gen['prefix_hit_tokens']} "
                        f"prompt tokens served from cache "
                        f"({gen.get('prefix_hits', 0)} blocks)")
                if gen.get("prefix_hit_rate") is not None:
                    line += f", hit rate {gen['prefix_hit_rate']:.0%}"
                out.append(line)
    fl = rep.get("fleet")
    if fl:
        line = f"fleet: {len(fl['replicas'])} replica(s)"
        req = fl.get("requests")
        if req:
            line += (f"   requests ok {req.get('ok', 0)} / failed "
                     f"{req.get('failed', 0)} / shed "
                     f"{req.get('shed', 0)}")
            extras = [f"{k} {req[k]}" for k in
                      ("retries", "hedges", "hedge_wins") if req.get(k)]
            if extras:
                line += "   (" + ", ".join(extras) + ")"
        out.append(line)
        for r in fl["replicas"]:
            ln = (f"  replica {r.get('replica')}: {r.get('state', '?')}"
                  + (f", breaker {r['breaker']}" if r.get("breaker")
                     else ""))
            if r.get("deaths"):
                ln += (f", died x{r['deaths']}"
                       + (f" ({r['last_death_reason']})"
                          if r.get("last_death_reason") else ""))
            if r.get("restarts"):
                ln += f", restarted x{r['restarts']}"
            out.append(ln)
        if fl.get("breaker_transitions"):
            out.append("  breaker trail: " + ", ".join(
                f"r{t.get('replica')} {t.get('from')}->{t.get('to')}"
                for t in fl["breaker_transitions"][-8:]))
        for w in fl.get("wire", []):
            ln = (f"  wire {w['verb']}: {w['calls']} call(s), "
                  f"{_fmt_b(w['bytes_sent'])} out / "
                  f"{_fmt_b(w['bytes_recv'])} in")
            if w.get("rtt_p50_ms") is not None:
                ln += (f", rtt p50 {w['rtt_p50_ms']}ms "
                       f"p99 {w['rtt_p99_ms']}ms")
            out.append(ln)
    tr = rep.get("tracing")
    if tr:
        line = (f"tracing: {tr['traces']} trace(s) / {tr['records']} "
                f"spans  ({tr['errors']} error, {tr['shed']} shed, "
                f"{tr['retried']} ok-after-retry)")
        if tr.get("hedged"):
            line += (f"   hedged {tr['hedged']} (won {tr['hedge_won']},"
                     f" hedge_lost spans {tr['hedge_lost_spans']})")
        if tr.get("cross_process"):
            line += f"   cross-process {tr['cross_process']}"
        out.append(line)
        for c in tr.get("slowest", [])[:5]:
            ln = (f"  {c['trace'][:16]} {c.get('op')} "
                  f"{c.get('status')} {_fmt_s(c.get('total_s'))}")
            stages = c.get("stages") or {}
            if stages:
                ln += "  (" + ", ".join(
                    f"{k.replace('_s', '')} {_fmt_s(v)}"
                    for k, v in stages.items()) + ")"
            out.append(ln)
    slo = rep.get("slo")
    if slo:
        for o in slo["objectives"]:
            state = "STILL BREACHED at end of run" \
                if o["breached_at_end"] else "recovered"
            out.append(
                f"SLO [{o['objective']}] {o.get('slo')}: "
                f"{o['breaches']} breach(es), {state} "
                f"(policy {o.get('policy')})")
    mem = rep.get("memory")
    if mem:
        last = mem.get("last")
        if last:
            rows = []
            for name in sorted(last.get("subsystems") or {}):
                rec = last["subsystems"][name]
                b = rec.get("bytes") if isinstance(rec, dict) else rec
                rows.append(f"{name} {_fmt_b(b)}")
            if last.get("residual_bytes") is not None:
                rows.append(f"residual {_fmt_b(last['residual_bytes'])}")
            line = "memory: " + " / ".join(rows)
            if last.get("live_bytes") is not None:
                line += (f"   (live {_fmt_b(last['live_bytes'])} of "
                         f"{_fmt_b(last.get('limit_bytes'))}, headroom "
                         f"{_fmt_b(last.get('headroom_bytes'))})")
            out.append(line)
            kv = (last.get("subsystems") or {}).get("kv_cache")
            if isinstance(kv, dict) and kv.get("blocks_total"):
                out.append(
                    f"  kv pool: {kv.get('blocks_active', 0)} active / "
                    f"{kv.get('blocks_cached', 0)} cached / "
                    f"{kv.get('blocks_free', 0)} free of "
                    f"{kv['blocks_total']} blocks")
        if mem.get("residual_last_bytes") is not None \
                and mem.get("snapshots", 0) > 1:
            out.append(
                f"  residual trajectory: "
                f"{_fmt_b(mem['residual_first_bytes'])} -> "
                f"{_fmt_b(mem['residual_last_bytes'])} over "
                f"{mem['snapshots']} snapshots "
                f"(max {_fmt_b(mem['residual_max_bytes'])})")
        for d in mem.get("dumps", []):
            out.append(
                f"MEMORY DUMP [{d.get('reason')}]"
                + (f": {d['error']}" if d.get("error") else "")
                + f"  ({d.get('last_ticks', 0)} ticks of context; "
                  f"replay with tools/mem_report.py)")
        cb = mem.get("compiled_budget")
        if cb:
            out.append(
                f"  compiled budget: args {_fmt_b(cb.get('argument_bytes'))}"
                f" + out {_fmt_b(cb.get('output_bytes'))} + temp "
                f"{_fmt_b(cb.get('temp_bytes'))} "
                f"(~{_fmt_b(cb.get('peak_bytes'))} peak)")
    rc = rep.get("recovery")
    if rc:
        for e in rc.get("reshards", [])[-6:]:
            mb = (e.get("host_bytes") or 0) / 1e6
            out.append(
                f"reshard [{e.get('what')}]: {e.get('src')} -> "
                f"{e.get('dst')} ({e.get('planes')} planes, "
                f"{mb:.1f} MB host, {e.get('wall_s', 0):.3f}s)")
    if rc and rc.get("restarts"):
        cause_str = ", ".join(f"{c} x{n}" for c, n in
                              sorted(rc["causes"].items()))
        line = f"recovery: {rc['restarts']} restart(s) ({cause_str})"
        if rc.get("steps_replayed_total") is not None:
            line += f"   steps replayed {rc['steps_replayed_total']}"
        line += f"   backoff total {rc['backoff_s_total']:.2f}s"
        out.append(line)
        for e in rc["events"][-6:]:
            ln = (f"  restart {e.get('restart')} [{e.get('cause')}] at "
                  f"step {e.get('at_step')}")
            if e.get("snapshot"):
                ln += (f" <- {os.path.basename(str(e['snapshot']))} "
                       f"(step {e.get('snapshot_step')}")
                if e.get("steps_replayed") is not None:
                    ln += f", {e['steps_replayed']} replayed"
                ln += ")"
            else:
                ln += " <- scratch"
            out.append(ln)
    wd = rep.get("watchdogs") or {}
    if wd.get("recompile_steps"):
        out.append("RECOMPILES after warmup at steps: "
                   + ", ".join(str(r["step"])
                               for r in wd["recompile_steps"]))
    if wd.get("memory_growth"):
        out.append("MEMORY GROWTH flagged at steps: "
                   + ", ".join(str(g["step"]) for g in wd["memory_growth"]))
    for v in rep.get("validations", [])[-4:]:
        out.append(f"validation @ step {v.get('step')}: "
                   f"{v.get('method')} = {v.get('value'):.6f}")
    if rep.get("host_spans"):
        out.append("host spans (total sec):")
        for sp in rep["host_spans"][:8]:
            out.append(f"  {sp['name']:<20} {sp['sec']:>10.4f}s "
                       f"x{sp['count']}")
    dev = rep.get("device")
    if dev:
        out.append(f"device plane '{dev['plane']}': span {dev['span_sec']:.4f}s, "
                   f"busy {dev['busy_event_sec']:.4f}s "
                   f"({dev.get('busy_fraction', 0):.2%} busy)")
    if rep.get("top_ops"):
        out.append("top HLO ops by device time:")
        for op in rep["top_ops"]:
            name = op["name"]
            out.append(f"  {op['pct']:>6.2f}%  {op['sec']:.6f}s  "
                       f"x{op['count']:<5} {name[:90]}")
    return "\n".join(out)


def _json_safe(obj):
    """Non-finite floats -> null, recursively: the --format json output
    is strictly valid JSON (NaN grad norms are real data in telemetry
    .jsonl, but machine consumers get null + the explicit
    first_nonfinite_step field instead of a parser error)."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    if isinstance(obj, dict):
        return {k: _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    return obj


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("run_dir", help="directory holding telemetry.jsonl")
    ap.add_argument("--xplane", default=None,
                    help="xplane trace dir (default: RUN_DIR/xplane)")
    ap.add_argument("--top", type=int, default=10,
                    help="how many HLO ops to list")
    ap.add_argument("--format", choices=("text", "json"), default=None,
                    help="text (default) or json -- the same dict the "
                         "text renderer uses, strictly-valid JSON")
    ap.add_argument("--json", action="store_true",
                    help="alias for --format json")
    args = ap.parse_args(argv)
    fmt = args.format or ("json" if args.json else "text")
    try:
        rep = build_report(args.run_dir, xplane_dir=args.xplane,
                           top=args.top)
    except FileNotFoundError as e:
        print(f"obs_report: {e}", file=sys.stderr)
        return 2
    if rep["n_steps"] == 0 and not any(
            rep.get(k) for k in ("serving", "recovery", "health",
                                 "validations", "slo", "fleet",
                                 "tracing", "memory")):
        # an empty/truncated JSONL must FAIL in scripts, not render a
        # hollow report: zero step events and nothing else to show
        # means the run recorded nothing (broken telemetry hookup, or
        # the wrong directory).  A memory-events-only artifact (the
        # OOM dump a crashed run left behind) is NOT hollow -- it is
        # exactly the artifact a post-mortem runs this tool on.
        print(f"obs_report: {args.run_dir} contains zero step events "
              f"and no serving/recovery/health/validation/memory "
              f"events -- nothing to report (is this the right run "
              f"dir, and was telemetry actually attached?)",
              file=sys.stderr)
        return 2
    if fmt == "json":
        print(json.dumps(_json_safe(rep), indent=2, allow_nan=False))
    else:
        print(format_report(rep))
    return 0


if __name__ == "__main__":
    sys.exit(main())
