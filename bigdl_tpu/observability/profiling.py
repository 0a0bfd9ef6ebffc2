"""Trusted timing: blocking step timers and MFU triangulation.

The measurement layer must be unable to lie before any step-time claim
can land (a round-2 record published a 2.74 "MFU" that was an
async-dispatch artifact -- the host clocked dispatches, not execution --
and was judged down 20x).  Two pieces enforce that here:

- ``BlockingStepTimer`` -- serial-dependency, ``block_until_ready``-
  fenced per-step timing.  ``step_blocked_s`` (the fenced time from
  just before dispatch to the step's outputs being READY on device) is
  the ONLY number the MFU math in tools/obs_report.py publishes.  The fence defeats async dispatch and pipelining, so it is
  a measurement mode, not a throughput mode.

- ``TimingAuditor`` -- triangulates three INDEPENDENT estimates of the
  same quantity (blocking wall-clock x cost-analysis FLOPs, the trace's
  own device-busy time, and the chained dispatch-loop throughput) and
  stamps a machine-readable ``trust`` verdict on the measurement:

  =========================  ============================================
  verdict                    meaning
  =========================  ============================================
  ``trusted``                the estimates agree within tolerance
  ``suspect:async_dispatch`` the published per-step time is SHORTER than
                             the device's own busy time per step, or
                             shorter than the serial dispatch-chain time
                             -- pipelining leaked through the fence
                             (exactly the round-2 failure)
  ``invalid:off_tpu``        the run never reached the accelerator (CPU
                             fallback); MFU is not chip-meaningful
  ``invalid:impossible``     the published MFU is outside (0, 1] -- the
                             measurement or the flops/peak model is
                             broken, not the chip fast
  =========================  ============================================

Training runs under ``set_blocking_timing(True)`` record a
``kind: "timing_audit"`` telemetry event that obs_report's Profiling
section surfaces.

No top-level jax import: ``tools/obs_report.py`` (which must run
anywhere the artifacts were copied) can load this module standalone,
and ``BlockingStepTimer`` imports jax lazily only when fencing.

Audit a record file from the command line::

    python -m bigdl_tpu.observability.profiling record.json
"""

import json
import time

#: the four trust verdicts (docs/observability.md)
TRUSTED = "trusted"
SUSPECT_ASYNC_DISPATCH = "suspect:async_dispatch"
INVALID_OFF_TPU = "invalid:off_tpu"
INVALID_IMPOSSIBLE = "invalid:impossible"


def percentile(sorted_vals, q):
    """Nearest-rank percentile over a pre-sorted list -- THE one
    definition: ``tools/obs_report.py`` aliases this function (by
    spec-load, no package import), so a timer's summary and its run
    report can never disagree on a p50."""
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(q / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


class BlockingStepTimer:
    """Serial-dependency, ``block_until_ready``-fenced per-step timer.

    >>> timer = BlockingStepTimer()
    >>> for batch in batches:
    ...     timer.begin()
    ...     out = compiled(params, batch)      # dispatch
    ...     timer.end(out)                     # fence: out READY on device
    >>> timer.p50()                            # sec/step, fenced

    ``end(payload)`` blocks until every array in ``payload`` is ready on
    device, so the recorded span covers dispatch + the full device
    execution the payload depends on -- no async dispatch, no
    pipelining, no device->host transfer of the values themselves
    (``block_until_ready`` fences readiness without fetching).  The
    samples land in ``self.samples`` (seconds per step).
    """

    def __init__(self):
        self.samples = []
        self._t0 = None

    def begin(self):
        """Open a step window (call immediately before dispatch)."""
        self._t0 = time.perf_counter()

    def end(self, payload):
        """Fence ``payload`` (any pytree of device arrays) and close the
        window; returns this step's blocked seconds."""
        import jax

        jax.block_until_ready(payload)
        dt = time.perf_counter() - self._t0
        self._t0 = None
        self.samples.append(dt)
        return dt

    def time_step(self, fn, *args, **kwargs):
        """Convenience: run ``fn`` as one fenced step; returns its
        output (the payload that was fenced)."""
        self.begin()
        out = fn(*args, **kwargs)
        self.end(out)
        return out

    def p50(self):
        return percentile(sorted(self.samples), 50)

    def p90(self):
        return percentile(sorted(self.samples), 90)

    def summary(self):
        """``{"steps", "step_blocked_s_p50", "step_blocked_s_p90",
        "step_blocked_s_p10", "total_s"}`` over the recorded samples
        (None when no step was timed)."""
        if not self.samples:
            return None
        s = sorted(self.samples)
        return {
            "steps": len(s),
            "step_blocked_s_p10": percentile(s, 10),
            "step_blocked_s_p50": percentile(s, 50),
            "step_blocked_s_p90": percentile(s, 90),
            "total_s": sum(s),
        }


class TimingAuditor:
    """Triangulate independent MFU estimates and stamp a trust verdict.

    ``tolerance`` is the relative disagreement the checks allow (default
    10%): a published step time more than ``tolerance`` SHORTER than
    either the trace's device-busy time per step or the chained
    dispatch-loop time is flagged ``suspect:async_dispatch`` -- both are
    lower bounds a genuinely fenced measurement cannot undercut.
    """

    def __init__(self, tolerance=0.10, require_tpu=True):
        self.tolerance = float(tolerance)
        self.require_tpu = bool(require_tpu)

    def audit(self, *, platform, step_blocked_s=None, flops_per_step=None,
              peak_flops=None, dispatch_s_per_step=None,
              device_busy_s_per_step=None, step_blocked_mean_s=None):
        """Audit one measurement; returns the machine-readable verdict.

        - ``step_blocked_s``: the PUBLISHED per-step time (blocking,
          fenced -- ``BlockingStepTimer``); the only basis MFU may use.
        - ``flops_per_step`` / ``peak_flops``: the cost-analysis flops
          of the compiled step and the device's assumed peak.
        - ``dispatch_s_per_step``: chained dispatch-loop sec/step (N
          donated-chain dispatches then one value fetch, total/N) -- a
          serial device-side dependency chain, so a LOWER bound on true
          step time.
        - ``device_busy_s_per_step``: the profiler trace's own device-
          busy seconds per step over the same window -- the device
          cannot have been busy longer than a fenced step lasted.
        - ``step_blocked_mean_s``: the blocked MEAN, when the caller
          has it.  The two bounds above are means over their windows, so
          the cross-checks compare against this mean-to-mean (one
          straggler step then inflates both sides alike) and fall back
          to ``step_blocked_s`` (a median) when absent.

        Returns ``{"trust", "published", "estimates", "checks"}`` where
        ``published.mfu`` is the only MFU a record may print and
        ``checks`` is the human-readable evidence trail.
        """
        tol = self.tolerance
        checks = []
        est = {}
        # the reference the mean-valued bounds are compared against
        blocked_ref = step_blocked_mean_s or step_blocked_s

        def mfu(sec):
            if sec and sec > 0 and flops_per_step and peak_flops:
                return flops_per_step / sec / peak_flops
            return None

        mfu_blocked = mfu(step_blocked_s)
        mfu_dispatch = mfu(dispatch_s_per_step)
        if mfu_blocked is not None:
            est["mfu_blocked"] = round(mfu_blocked, 4)
        if mfu_dispatch is not None:
            est["mfu_dispatch"] = round(mfu_dispatch, 4)
        if device_busy_s_per_step and blocked_ref:
            # against the SAME reference the suspect check below uses,
            # so the displayed fraction can never contradict the verdict
            est["device_busy_fraction_of_blocked"] = round(
                device_busy_s_per_step / blocked_ref, 4)

        trust = TRUSTED
        if self.require_tpu and platform != "tpu":
            trust = INVALID_OFF_TPU
            checks.append(
                f"run executed on {platform!r}, not the TPU: MFU against a "
                f"nominal peak is not chip-meaningful")
        elif step_blocked_s is None or step_blocked_s <= 0:
            trust = INVALID_IMPOSSIBLE
            checks.append(
                "no blocking per-step measurement (step_blocked_s): nothing "
                "trustworthy was published")
        elif mfu_blocked is not None and not (0.0 < mfu_blocked <= 1.0):
            trust = INVALID_IMPOSSIBLE
            checks.append(
                f"published MFU {mfu_blocked:.4f} outside (0, 1]: the "
                f"measurement or the flops/peak model is broken, not the "
                f"chip fast")
        else:
            if (device_busy_s_per_step
                    and device_busy_s_per_step
                    > blocked_ref * (1.0 + tol)):
                trust = SUSPECT_ASYNC_DISPATCH
                checks.append(
                    f"published step time {blocked_ref:.4f}s < trace "
                    f"device-busy {device_busy_s_per_step:.4f}s/step: the "
                    f"device was busy longer than the published step lasted "
                    f"-- async dispatch leaked through the fence")
            if (dispatch_s_per_step
                    and dispatch_s_per_step
                    > blocked_ref * (1.0 + tol)):
                trust = SUSPECT_ASYNC_DISPATCH
                checks.append(
                    f"chained dispatch-loop {dispatch_s_per_step:.4f}s/step "
                    f"> fenced blocked {blocked_ref:.4f}s/step: a serial "
                    f"dependency chain cannot be slower than a truly "
                    f"fenced step -- the fence did not hold")
            if trust == TRUSTED:
                # NOTE the checks are one-sided by design: they catch a
                # published time that is too SHORT (the direction a
                # measurement lies in).  Blocked time LONGER than the
                # bounds (per-step RTT through a proxied transport) makes
                # the published MFU conservative, not wrong.
                bounds = [k for k in ("mfu_dispatch",
                                      "device_busy_fraction_of_blocked")
                          if k in est]
                if mfu_blocked is None:
                    checks.append(
                        "no MFU published (flops or peak unavailable); the "
                        "blocked timing itself shows no contradiction")
                elif bounds:
                    checks.append(
                        "published step time undercuts no independent "
                        f"lower bound (within {tol:.0%} tolerance): "
                        f"{', '.join(bounds)}")
                else:
                    checks.append(
                        "no independent estimate available to cross-check "
                        "(no trace witness, no dispatch chain); blocked "
                        "timing is self-consistent")

        return {
            "trust": trust,
            "published": {
                "basis": "step_blocked_s",
                "sec_per_step": step_blocked_s,
                "mfu": None if mfu_blocked is None else round(mfu_blocked, 4),
            },
            "estimates": est,
            "checks": checks,
        }

    def audit_record(self, record):
        """Audit a record dict.  Reads the published timing fields
        from ``record["extra"]`` (or ``record`` itself when no extra
        nesting): ``platform``, ``sec_per_step_blocked`` (falling back
        to ``sec_per_step``), ``sec_per_step_chained``,
        ``flops_per_step``, ``peak_flops_assumed``, ``steps`` and the
        ``trace_witness.device_plane.busy_event_sec`` trace evidence."""
        extra = record.get("extra", record) or {}
        busy = None
        witness = extra.get("trace_witness") or {}
        plane = witness.get("device_plane") or {}
        steps = extra.get("steps")
        if plane.get("busy_event_sec") and steps:
            busy = plane["busy_event_sec"] / steps
        return self.audit(
            platform=extra.get("platform"),
            step_blocked_s=(extra.get("sec_per_step_blocked")
                            or extra.get("sec_per_step")),
            step_blocked_mean_s=extra.get("sec_per_step_blocked_mean"),
            flops_per_step=extra.get("flops_per_step"),
            peak_flops=extra.get("peak_flops_assumed"),
            dispatch_s_per_step=extra.get("sec_per_step_chained"),
            device_busy_s_per_step=busy)


def main(argv=None):
    """Audit a record file: print the TimingAuditor verdict."""
    import argparse

    ap = argparse.ArgumentParser(
        description="stamp a trust verdict on a timing record")
    ap.add_argument("record", help="path to a record's JSON file")
    ap.add_argument("--tolerance", type=float, default=0.10)
    args = ap.parse_args(argv)
    with open(args.record) as f:
        record = json.load(f)
    audit = TimingAuditor(tolerance=args.tolerance).audit_record(record)
    print(json.dumps(audit, indent=2))
    return 0 if audit["trust"] == TRUSTED else 1


if __name__ == "__main__":
    raise SystemExit(main())
