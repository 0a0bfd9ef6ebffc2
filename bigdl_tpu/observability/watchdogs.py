"""Silent-failure watchdogs: recompiles, memory growth, bad numerics.

Things go wrong on an accelerator without any exception being raised:
the jitted step silently recompiles every iteration (a shape or
static-arg leak -- each "step" is now a multi-second XLA compile),
device memory creeps up until an OOM hundreds of steps later, a
gradient goes non-finite and poisons the params long before the loss
shows it, or the loss spikes off its trend.  All are invisible in loss
curves at the moment they start; all are cheap to detect on the host.

``RecompileWatchdog`` counts backend compiles per step window via
``jax.monitoring``'s duration listener (every real XLA compile emits
``/jax/core/compile/backend_compile_duration``); where that API is
unavailable it falls back to polling the jit cache size of explicitly
``watch()``-ed functions.  Any compile after the warmup steps logs a
WARNING with the offending step number.

``MemoryWatchdog`` tracks per-device ``bytes_in_use`` and flags a
monotonic increase sustained across N consecutive observations.

``NonFiniteWatchdog`` / ``LossSpikeWatchdog`` ride the sampled numerics
stream (``health.HealthMonitor`` feeds them each ``health`` event) and
back the warn/dump/halt anomaly policy -- see docs/observability.md.
"""

import logging
import math
import threading

from bigdl_tpu.observability.spans import COMPILE_EVENT as _COMPILE_EVENT

log = logging.getLogger("bigdl_tpu.observability")

_counter_lock = threading.Lock()
_compile_count = 0
_listener_state = None  # None = not tried, True = active, False = unavailable


def _on_duration(name, duration_secs=None, **kwargs):
    global _compile_count
    if name == _COMPILE_EVENT:
        with _counter_lock:
            _compile_count += 1


def _ensure_listener():
    """Register the (process-global, permanent) compile listener once."""
    global _listener_state
    if _listener_state is not None:
        return _listener_state
    try:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_duration)
        _listener_state = True
    except Exception:  # pragma: no cover - jax without monitoring
        _listener_state = False
    return _listener_state


def backend_compile_count():
    """Process-wide count of backend compiles seen by the listener."""
    _ensure_listener()
    with _counter_lock:
        return _compile_count


class RecompileWatchdog:
    """Flags backend compiles that happen after warmup.

    Drive it with ``step_begin(step)`` / ``step_end(step)`` around the
    window where NO compile is expected (dispatch + loss sync in the
    driver loop; validation/checkpoint compiles stay outside the window
    and are never false-flagged).  The first ``warmup_steps`` completed
    steps are exempt -- that is where the train step legitimately
    compiles.
    """

    def __init__(self, warmup_steps=1):
        self.warmup_steps = warmup_steps
        self.events = []          # [{"step", "compiles"}] -- one per firing
        self._watched = []        # jitted fns for the cache-size fallback
        self._begin = None
        self._steps_seen = 0
        self._use_monitoring = _ensure_listener()

    def watch(self, fn):
        """Register a jitted function whose cache size becomes the
        compile signal.  Preferred over the process-global monitoring
        counter: cache growth is PER-FUNCTION, so a concurrent thread
        compiling something else (e.g. a serving request with a new
        shape) can never be misattributed to the training step."""
        if hasattr(fn, "_cache_size"):
            self._watched.append(fn)
        return fn

    def _signal(self):
        if self._watched:
            return sum(f._cache_size() for f in self._watched)
        if self._use_monitoring:
            return backend_compile_count()
        return 0

    def step_begin(self, step):
        self._begin = self._signal()

    def step_end(self, step):
        """Close the step window; returns the number of compiles seen
        inside it (0 when clean), WARNING-logging post-warmup compiles."""
        if self._begin is None:
            return 0
        delta = self._signal() - self._begin
        self._begin = None
        self._steps_seen += 1
        if delta > 0 and self._steps_seen > self.warmup_steps:
            self.events.append({"step": step, "compiles": delta})
            log.warning(
                "recompile detected at step %d (%d backend compile%s inside "
                "the step window): a shape or static argument is changing "
                "per step -- every such step pays a full XLA compile",
                step, delta, "s" if delta > 1 else "")
        return delta


class MemoryWatchdog:
    """Flags monotonic device-memory growth sustained over ``window``
    consecutive observations (a leak signature: steady-state training
    should plateau after the first steps)."""

    def __init__(self, window=25):
        self.window = window
        self.events = []          # [{"step", "device", "bytes_in_use"}]
        self._last = {}
        self._streak = {}

    def observe(self, step, bytes_in_use_by_device):
        """Feed ``{device_label: bytes_in_use}`` for one step; returns
        the devices flagged this call (usually empty)."""
        flagged = []
        for dev, used in (bytes_in_use_by_device or {}).items():
            prev = self._last.get(dev)
            self._last[dev] = used
            if prev is not None and used > prev:
                self._streak[dev] = self._streak.get(dev, 0) + 1
            else:
                self._streak[dev] = 0
            if self._streak[dev] >= self.window:
                self._streak[dev] = 0      # re-arm: fire again after N more
                self.events.append(
                    {"step": step, "device": dev, "bytes_in_use": used})
                flagged.append(dev)
                log.warning(
                    "device %s memory grew monotonically for %d consecutive "
                    "steps (now %.1f MiB in use) at step %d -- possible "
                    "leak (host-retained device arrays, growing cache, or "
                    "per-step constants)",
                    dev, self.window, used / 2**20, step)
        return flagged


class NonFiniteWatchdog:
    """Flags the first (and every) health sample carrying non-finite
    numerics: NaN/Inf in gradients, in the updated params, or in the
    loss itself.  Because the stats are sampled every ``stats_every``
    steps INSIDE the compiled step, the firing step bounds when the
    numerics went bad to one sampling window -- versus the many-steps-
    later NaN loss that is otherwise the first visible symptom."""

    def __init__(self):
        self.events = []
        self.first_step = None        # first sampled step seen non-finite

    def observe(self, step, event):
        """Feed one ``health`` event dict; returns a finding dict when
        the sample carries non-finite values, else None."""
        nf_g = int(event.get("nonfinite_grads", 0))
        nf_p = int(event.get("nonfinite_params", 0))
        loss = event.get("loss")
        loss_bad = loss is not None and not math.isfinite(loss)
        gn = event.get("grad_norm")
        gn_bad = gn is not None and not math.isfinite(gn)
        if not (nf_g or nf_p or loss_bad or gn_bad):
            return None
        if self.first_step is None:
            self.first_step = step
        worst = event.get("worst_layer")
        finding = {
            "watchdog": "nonfinite", "step": step,
            "nonfinite_grads": nf_g, "nonfinite_params": nf_p,
            "loss_finite": not loss_bad, "worst_layer": worst,
            "reason": "non-finite numerics (layer %s)" % worst,
        }
        self.events.append(finding)
        log.warning(
            "non-finite numerics at step %d: %d grad / %d param elements "
            "non-finite%s, worst layer %s -- the divergence started within "
            "the last sampling window",
            step, nf_g, nf_p, "" if not loss_bad else " (loss non-finite)",
            worst)
        return finding


class LossSpikeWatchdog:
    """Flags a loss that jumps ``sigma`` standard deviations above its
    exponential moving average (EMA of the loss + EMA of its squared
    deviation, bias-corrected).  The first ``warmup`` samples only train
    the EMAs -- early training legitimately moves fast."""

    def __init__(self, sigma=6.0, beta=0.9, warmup=5):
        self.sigma = float(sigma)
        self.beta = float(beta)
        self.warmup = int(warmup)
        self.events = []
        self._mean = 0.0
        self._var = 0.0
        self._n = 0

    def observe(self, step, loss):
        """Feed one sampled loss; returns a finding dict on a spike,
        else None.  Non-finite losses are NonFiniteWatchdog's business
        and only reset nothing here (the EMAs ignore them)."""
        if loss is None or not math.isfinite(loss):
            return None
        finding = None
        if self._n >= self.warmup:
            bc = 1.0 - self.beta ** self._n      # bias correction
            mean = self._mean / bc
            sd = math.sqrt(max(self._var / bc, 0.0))
            # absolute + relative floor: a perfectly flat loss stream
            # must not flag numeric dust as a "spike"
            sd = max(sd, 1e-8, 1e-3 * abs(mean))
            threshold = mean + self.sigma * sd
            if loss > threshold:
                finding = {
                    "watchdog": "loss_spike", "step": step,
                    "loss": float(loss), "ema": mean, "sd": sd,
                    "sigma": self.sigma,
                    "reason": "loss %.6g > EMA %.6g + %g sigma (%.6g)"
                              % (loss, mean, self.sigma, threshold),
                }
                self.events.append(finding)
                log.warning(
                    "loss spike at step %d: %.6g vs EMA %.6g (+%.1f sigma "
                    "threshold %.6g)", step, loss, mean, self.sigma,
                    threshold)
        # the spiked value still feeds the EMAs: a persistent new level
        # re-normalizes instead of firing forever
        self._mean = self.beta * self._mean + (1 - self.beta) * loss
        # _mean now aggregates n+1 samples -- correct with beta**(n+1):
        # a stale beta**n here seeds phantom variance on a flat stream,
        # masking real spikes for dozens of samples after warmup
        bc = 1.0 - self.beta ** (self._n + 1)
        dev = loss - self._mean / bc
        self._var = self.beta * self._var + (1 - self.beta) * dev * dev
        self._n += 1
        return finding
