"""Single-device training orchestration.

Reference: optim/LocalOptimizer.scala:45 (replica threads + lock-free grad
aggregation) and the Optimizer facade (optim/Optimizer.scala:47: builder
setters for validation/checkpoint/summary/clipping/end-trigger).

TPU-native: no replica threads -- one jitted step fuses fwd/bwd/update and
saturates the chip; the host loop only feeds batches and evaluates triggers.
"""

import logging
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.dataset.dataset import AbstractDataSet
from bigdl_tpu.observability.spans import span
from bigdl_tpu.optim.metrics import Metrics
from bigdl_tpu.optim.optim_method import OptimMethod, SGD
from bigdl_tpu.optim.train_step import make_train_step
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.optim.validation import ValidationMethod, ValidationResult
from bigdl_tpu.utils import file_io
from bigdl_tpu.utils.errors import (CheckpointCorruptionError,
                                    ConfigurationError,
                                    TrainingHaltedError,
                                    UnsupportedFeatureError)
from bigdl_tpu.utils.random_generator import RNG
from bigdl_tpu.utils.shape import spec_of

log = logging.getLogger("bigdl_tpu.optim")


#: staging sentinel: the end trigger is PREDICTED to fire after this step
#: (vs None = staging deferred, fetch synchronously after the state update)
PREDICTED_END = object()


def _device_batch(batch):
    """ONE async ``jax.device_put`` over the whole ``(input, target)``
    tree -- a single dispatch that the runtime overlaps with in-flight
    compute, replacing the old per-leaf blocking ``jnp.asarray`` walk.
    The batch is never donated (``donate_argnums`` on the train step
    covers params/mstate/opt_state only), so donation is unaffected."""
    return jax.device_put(batch.tree())


class BaseOptimizer:
    """Builder facade shared by Local/Distri optimizers
    (reference: optim/Optimizer.scala:47)."""

    def __init__(self, model, dataset: AbstractDataSet, criterion,
                 optim_method: Optional[OptimMethod] = None):
        self.model = model
        self.dataset = dataset
        self.criterion = criterion
        self.optim_method = optim_method or SGD()
        self.end_trigger = Trigger.max_epoch(1)
        self.validation_trigger = None
        self.validation_dataset = None
        self.validation_methods: List[ValidationMethod] = []
        self.checkpoint_path = None
        self.sharded_checkpoint_path = None
        self.checkpoint_trigger = None
        self.train_summary = None
        self.validation_summary = None
        self.compute_dtype = None
        self.clip_value = None
        self.clip_norm = None
        self.telemetry = None
        self.health_monitor = None
        self.grad_transform = None
        self.sync_every = 1
        self.blocking_timing = False
        #: host-side counters: data_wait_s vs device_s per step (the
        #: reference's Metrics accumulators, optim/Metrics.scala:31)
        self.metrics = Metrics()
        self.driver_state: Dict = {"epoch": 1, "neval": 1,
                                   "record_count": 0,
                                   "batches_consumed": 0}
        #: mid-epoch dataset position restored from a snapshot, consumed
        #: by _resume_data_stream at the top of the next optimize
        self._resume_position = None

    # ----- builder setters (names mirror the reference) ------------------- #
    def set_end_when(self, trigger: Trigger):
        self.end_trigger = trigger
        return self

    def set_validation(self, trigger: Trigger, dataset: AbstractDataSet,
                       methods: List[ValidationMethod]):
        self.validation_trigger = trigger
        self.validation_dataset = dataset
        self.validation_methods = methods
        return self

    def set_checkpoint(self, path: str, trigger: Trigger):
        if self.sharded_checkpoint_path is not None:
            raise ConfigurationError(
                "set_checkpoint and set_sharded_checkpoint share one "
                "trigger/write slot; configure ONE checkpoint kind")
        self.checkpoint_path = path
        self.checkpoint_trigger = trigger
        return self

    #: subclasses with sharded (orbax) snapshot writers flip this
    _supports_sharded_checkpoint = False

    def set_sharded_checkpoint(self, path, trigger):
        """Orbax sharded snapshots: every device/host writes its own
        shards of the layout-native params and optimizer state, no
        gather to one host (SURVEY.md hard-parts: the big-model
        checkpoint story).  DistriOptimizer snapshots the flat plane;
        StrategyOptimizer the strategy-native trees."""
        if not self._supports_sharded_checkpoint:
            raise UnsupportedFeatureError(
                f"{type(self).__name__} keeps whole-model state on one "
                "host; use set_checkpoint (sharded snapshots are for the "
                "distributed layouts)")
        if self.checkpoint_path is not None:
            raise ConfigurationError(
                "set_checkpoint and set_sharded_checkpoint share one "
                "trigger/write slot; configure ONE checkpoint kind")
        self.sharded_checkpoint_path = file_io.abs_local(path)
        self.checkpoint_trigger = trigger
        return self

    def resume_from_sharded_checkpoint(self, path=None):
        if path is None and self.sharded_checkpoint_path is None:
            raise ConfigurationError(
                "no sharded checkpoint path: call set_sharded_checkpoint "
                "first or pass path=")
        base = file_io.abs_local(path or self.sharded_checkpoint_path)
        # verified resolution: a crash between the orbax finalize and the
        # driver-state sidecar write leaves an unusable snapshot (skipped);
        # a truncated / digest-mismatched one is QUARANTINED -- resume
        # lands on the last intact snapshot or fails loudly, never loads
        # garbage (docs/robustness.md)
        intact, quarantined = file_io.scan_sharded_snapshots(base)
        if not intact:
            if quarantined:
                raise CheckpointCorruptionError(
                    f"every sharded snapshot under {base} failed "
                    f"verification; quarantined: {quarantined} -- a fresh "
                    "start here would silently discard the run (move the "
                    "*.corrupt files away to force one)")
            return self
        self._resume_sharded = intact[0]
        log.info("Resuming from sharded snapshot %s", self._resume_sharded)
        return self

    def set_train_summary(self, summary):
        self.train_summary = summary
        return self

    def set_telemetry(self, telemetry):
        """Attach a ``StepTelemetry`` recorder: one structured JSONL
        event per step, host-span chrome trace, and the recompile /
        memory watchdogs, all driven by the shared driver loop
        (``bigdl_tpu/observability/``, docs/observability.md)."""
        self.telemetry = telemetry
        return self

    def set_health_monitor(self, monitor=None, **kw):
        """Sampled on-device numerics telemetry + anomaly watchdogs
        (``observability/health.py``, docs/observability.md):

            opt.set_health_monitor(stats_every=10, policy="dump")

        Every ``stats_every``-th step the jitted train step additionally
        returns loss, global + per-layer grad norms, update-to-weight
        ratios and non-finite counts (``jax.lax.cond``: non-sample steps
        pay nothing); the monitor records them as ``health`` telemetry
        events / TB scalars and drives the NonFinite + LossSpike
        watchdogs under the warn/dump/halt policy.  Pass a prebuilt
        ``HealthMonitor`` or its keyword arguments; ``None`` with no
        kwargs disables."""
        if monitor is not None and kw:
            raise ConfigurationError(
                "pass EITHER a HealthMonitor instance OR its keyword "
                f"arguments, not both (got monitor + {sorted(kw)})")
        if monitor is None and kw:
            from bigdl_tpu.observability.health import HealthMonitor
            monitor = HealthMonitor(**kw)
        self.health_monitor = monitor
        return self

    def set_blocking_timing(self, enabled=True):
        """Serial-dependency step timing (docs/observability.md,
        "Profiling & trusted timing"): fence every dispatch with
        ``jax.block_until_ready`` and stamp ``step_blocked_s`` -- the
        fenced dispatch-to-outputs-ready time -- on every step event.
        ``step_blocked_s`` is the ONLY number the MFU math in
        ``tools/obs_report.py`` publishes; un-fenced wall clocks
        measure dispatch, not execution.  The fence defeats the
        async pipelining ``set_sync_every`` exists to exploit, so this
        is a MEASUREMENT mode for timing audits, not a
        production throughput default.  At the end of the run a
        ``kind: "timing_audit"`` event records the ``TimingAuditor``
        trust verdict for the run's blocked timing."""
        self.blocking_timing = bool(enabled)
        return self

    def set_grad_transform(self, fn):
        """Arbitrary pure gradient transform applied inside the jitted
        step after aggregation, before clipping (fault injection,
        custom scaling, ...).  LocalOptimizer only: the distributed
        layouts transform chunked/sharded planes where a user tree
        function has no meaning."""
        self.grad_transform = fn
        return self

    def set_validation_summary(self, summary):
        self.validation_summary = summary
        return self

    def set_gradient_clipping_by_value(self, min_value, max_value):
        """Reference: Optimizer.setConstantGradientClipping."""
        self.clip_value = (min_value, max_value)
        return self

    def set_gradient_clipping_by_l2_norm(self, max_norm):
        """Reference: Optimizer.setGradientClippingByl2Norm."""
        self.clip_norm = max_norm
        return self

    def set_compute_dtype(self, dtype):
        """bf16 mixed precision (TPU-native; no reference analogue)."""
        self.compute_dtype = dtype
        return self

    def set_sync_every(self, k: int):
        """Block on the device loss only every ``k``-th step (default 1 =
        the classic per-step sync).  With ``k > 1`` the host loop keeps
        dispatching ahead of the device, so XLA's async dispatch actually
        pipelines steps; loss/throughput in logs and telemetry are then
        fresh only at sync points (``sync_skew`` in the step event counts
        the staleness).  Output-reading triggers (min_loss/max_score)
        force ``k = 1``, and a validation or checkpoint firing forces a
        point sync, so Plateau schedules always see a fresh loss
        (docs/performance.md, Input pipeline)."""
        if int(k) < 1:
            raise ConfigurationError(f"sync_every must be >= 1, got {k}")
        self.sync_every = int(k)
        return self

    def set_optim_methods(self, methods):
        """One OptimMethod per named submodule (reference:
        Optimizer.setOptimMethods, optim/Optimizer.scala:377).  Names
        resolve anywhere in the module tree; together the subtrees must
        cover every trainable parameter.  Resolved against the built
        model at optimize() time (LocalOptimizer and the sp strategy;
        the flat-chunk dp step, the pipeline restructured layouts and
        the sharded-state tp/ep paths refuse loudly)."""
        self._optim_methods_map = dict(methods)
        return self

    def _resolve_optim_methods(self, params_tree):
        if getattr(self, "_optim_methods_map", None):
            from bigdl_tpu.optim.optim_method import build_composite_method
            sched = getattr(self.optim_method, "schedule", None)
            if sched is not None and hasattr(sched, "record"):
                raise ConfigurationError(
                    "set_optim_methods replaces the constructor's "
                    "optim_method, whose Plateau-style schedule would "
                    "silently never fire; drop one of the two")
            self.optim_method = build_composite_method(
                self.model, params_tree, self._optim_methods_map)

    def _apply_driver_state(self, snap_state):
        """Restore loop counters, the RNG stream position (so a resumed
        run draws the same key sequence -- dropout masks etc. -- as the
        uninterrupted one) AND the mid-epoch dataset position (consumed
        by ``_resume_data_stream`` before the loop starts)."""
        d = dict(snap_state)
        rng_state = d.pop("rng_state", None)
        self._resume_position = d.pop("data_position", None)
        # file_io.save numpy-ified the snapshot: loop counters come back
        # as 0-d ndarrays, which would poison every later step event's
        # JSON encode -- coerce scalars back to python types
        for k, v in d.items():
            if isinstance(v, (np.ndarray, np.generic)) and \
                    getattr(v, "ndim", 1) == 0:
                d[k] = v.item()
        self.driver_state.update(d)
        if rng_state is not None:
            RNG.set_state(rng_state)

    def _resume_data_stream(self, train_iter, first_batch):
        """After a resume restored the driver counters: put the dataset
        back at the snapshot's mid-epoch position and fast-forward a
        FRESH iterator past the batches the checkpointed steps already
        consumed, so the post-restart sample stream is bit-identical to
        the uninterrupted run's (docs/robustness.md).  No-op without a
        restored position.  The drivers call this after their resume
        blocks, before the loop; the pre-resume ``first_batch`` (drawn
        only for shapes/model build) is discarded."""
        pos, self._resume_position = self._resume_position, None
        if pos is None:
            return train_iter, first_batch
        consumed = int(pos.get("batches_consumed", 0))
        ds_state = pos.get("dataset")
        if ds_state is None:
            if consumed or pos.get("reshuffle_pending"):
                log.warning(
                    "snapshot carries a mid-epoch position (%d batches "
                    "into epoch %d) but %s exposes no position_state(); "
                    "resuming from the top of the epoch -- the resumed "
                    "sample stream will NOT match the uninterrupted run",
                    consumed, self.driver_state.get("epoch", 1),
                    type(self.dataset).__name__)
            return train_iter, first_batch
        self.dataset.restore_position(ds_state)
        if pos.get("reshuffle_pending"):
            # the uninterrupted run's DEFERRED epoch-boundary reshuffle
            # (exotic-trigger fetch path) would have run before its next
            # fetch; replay it now that the shuffle RNG is restored
            self.dataset.shuffle()
        train_iter = self.dataset.data(train=True)
        for i in range(consumed):
            try:
                next(train_iter)
            except StopIteration:
                raise CheckpointCorruptionError(
                    f"dataset exhausted {i}/{consumed} batches into the "
                    "mid-epoch fast-forward: the snapshot's position does "
                    "not fit this dataset (changed size or batch "
                    "shape?)") from None
        log.info("resumed dataset position: epoch %d, fast-forwarded %d "
                 "consumed batches", self.driver_state.get("epoch", 1),
                 consumed)
        return train_iter, next(train_iter)

    def _capture_data_position(self):
        """The mid-epoch position block stamped into every snapshot's
        driver state: batches consumed by COMPLETED steps this epoch,
        whether an epoch-boundary reshuffle is still pending, and the
        dataset's own order/RNG state (None when unsupported)."""
        # getattr-guarded: duck-typed datasets (anything with
        # data/size/shuffle) stay supported, they just resume from the
        # top of the epoch
        pos_fn = getattr(self.dataset, "position_state", None)
        return {
            "batches_consumed": int(
                self.driver_state.get("batches_consumed", 0)),
            "reshuffle_pending": bool(
                getattr(self, "_reshuffle_pending", False)),
            "dataset": pos_fn() if callable(pos_fn) else None,
        }

    def _log_learning_rates(self, opt_state, state):
        """LearningRate summary scalars: one per submodule for composite
        methods, a single scalar otherwise (shared by the Local and
        Strategy extra_summaries callbacks)."""
        rates = getattr(self.optim_method, "learning_rates", None)
        if rates is not None:
            for name, lr in rates(opt_state).items():
                self.train_summary.add_scalar(
                    f"LearningRate/{name}", float(lr), state["neval"])
        else:
            self.train_summary.add_scalar(
                "LearningRate",
                float(self.optim_method.get_learning_rate(opt_state)),
                state["neval"])

    def resume_from_checkpoint(self, path: Optional[str] = None):
        """Reference resume semantics: Module.load + OptimMethod.load
        (models/lenet/Train.scala:48-69); iteration-accurate via driver
        state.  Verified resolution (docs/robustness.md): truncated /
        digest-mismatched snapshots are quarantined and resume lands on
        the newest intact one; "nothing to resume" (fresh start) is
        distinguished from "every snapshot corrupt" (raises, listing
        the quarantined files)."""
        base = path or self.checkpoint_path
        snap, quarantined = None, []
        while True:
            # the scan verifies newest-first and stops at the first
            # intact candidate; after a post-verification load failure
            # (quarantined below) the rescan resolves the next one
            intact, q = file_io.scan_checkpoints(base)
            quarantined.extend(q)
            if not intact:
                break
            ckpt_file = intact[0]
            try:
                snap = file_io.load(ckpt_file)
                break
            except Exception:
                # verification passed but the unpickle did not (a saver
                # bug, not an IO truncation): same quarantine treatment
                log.exception("snapshot %s verified but failed to load",
                              ckpt_file)
                quarantined.extend(file_io.quarantine_snapshot(ckpt_file))
        if snap is None:
            if quarantined:
                raise CheckpointCorruptionError(
                    f"every snapshot under {base} failed verification; "
                    f"quarantined: {quarantined} -- a fresh start here "
                    "would silently discard the run (move the *.corrupt "
                    "files away to force one)")
            return self
        self._resume = snap
        self._resume_path = ckpt_file
        ds = snap["driver_state"]
        log.info("Resuming from %s (epoch %s, neval %s)", ckpt_file,
                 ds.get("epoch"), ds.get("neval"))
        return self

    # ----- shared helpers -------------------------------------------------- #
    def _check_plateau_monitor(self):
        """Fail fast (before the failure-retry loop) on a Plateau monitor
        that the configured validation methods can never produce --
        otherwise the deterministic config error would burn
        BIGDL_FAILURE_RETRY_TIMES full validation intervals re-hitting
        itself (reference require-fails at the same mismatch,
        SGD.scala:571)."""
        sched = getattr(self.optim_method, "schedule", None)
        if (sched is None or not hasattr(sched, "record")
                or self.validation_trigger is None):
            return
        monitor = getattr(sched, "monitor", "score")
        available = [m.name for m in self.validation_methods]
        if any(n in ("Top1Accuracy", "Top5Accuracy") for n in available):
            available.append("score")
        available.append("loss")      # training loss is always in state
        if monitor not in available:
            raise ValueError(
                f"Plateau schedule requires monitored value {monitor!r}, "
                f"which the validation methods will never produce "
                f"(available: {available})")

    def _feed_plateau(self, state, opt_state):
        """Feed the monitored validation metric to a Plateau schedule
        (reference: SGD.Plateau consumes the score via the optimizer's
        state Table).  Only an explicitly monitored value is fed -- no
        silent fallback to the training loss, whose direction would not
        match the schedule's mode."""
        sched = getattr(self.optim_method, "schedule", None)
        if sched is None or not hasattr(sched, "record"):
            return opt_state
        monitor = getattr(sched, "monitor", "score")
        # a custom monitor must match exactly -- feeding a different metric
        # (wrong direction for the schedule's mode) would silently decay
        # the LR on healthy training
        value = state.get(monitor)
        if value is None:
            # the monitor is producible (checked fail-fast in optimize());
            # its absence here means THIS validation interval was skipped
            # (e.g. no full batches) -- a transient, not the config error
            # the reference require-fails on (SGD.scala:571)
            log.warning(
                "Plateau schedule: monitored value %r absent this "
                "validation interval; LR factor unchanged", monitor)
            return opt_state
        return sched.record(value, opt_state)

    def _record_validation(self, results, state):
        """Log each validation result and record it in the driver state
        (state[method.name] is addressable by a Plateau monitor; 'score'
        aliases accuracy for the default monitor)."""
        for method, res in zip(self.validation_methods, results):
            if res is None:
                log.warning(
                    "validation dataset produced no full batches; skipping "
                    "%s (reduce batch size or grow the validation split)",
                    method.name)
                continue
            value, _ = res.result()
            log.info("Validation %s: %s", method.name, res)
            state[method.name] = value
            if method.name in ("Top1Accuracy", "Top5Accuracy"):
                state["score"] = value
            if self.validation_summary is not None:
                self.validation_summary.add_scalar(
                    method.name, value, state["neval"])
            if self.telemetry is not None:
                self.telemetry.record("validation", step=state["neval"],
                                      epoch=state["epoch"],
                                      method=method.name, value=float(value))
        return results

    def _stage_next_batch(self, train_iter, state, n, epoch_size,
                          force=False):
        """Prefetch the next batch while the device executes the current
        step (call between dispatch and the loss sync).  Returns
        (next_batch, train_iter); next_batch is PREDICTED_END when the end
        trigger is predicted to fire after this step, so with the
        stateless count-based triggers a stream-fed dataset is never
        touched past the end of training.  Stateful triggers must not be
        probed with a predicted state (they would mutate -- the while
        condition is their single per-step evaluation), and output-reading
        triggers (min_loss/max_score) cannot be predicted before the loss
        sync; for those staging returns None and the fetch is DEFERRED to
        the top of the next loop iteration, after the trigger has decided
        training continues.  Deferral trades the prefetch/compute overlap
        (exotic triggers only; count-based triggers keep it) for liveness:
        an eager fetch one batch past the end would block forever on a
        queue-fed stream dataset whose producer stops at the end of
        training (round-3 advisor finding)."""
        if not force:
            if (getattr(self.end_trigger, "stateful", False)
                    or getattr(self.end_trigger, "uses_outputs", False)):
                return None, train_iter
            predicted = dict(state)
            predicted["neval"] = state["neval"] + 1
            predicted["record_count"] = state["record_count"] + n
            if predicted["record_count"] >= epoch_size:
                predicted["epoch"] = state["epoch"] + 1
            if self.end_trigger(predicted):
                return PREDICTED_END, train_iter
        if getattr(self, "_reshuffle_pending", False):
            # deferred-fetch path: the epoch rolled over (and record_count
            # was reset) before this force fetch ran
            self._reshuffle_pending = False
            self.dataset.shuffle()
            train_iter = self.dataset.data(train=True)
        elif state["record_count"] + n >= epoch_size:
            self.dataset.shuffle()
            train_iter = self.dataset.data(train=True)
        try:
            return next(train_iter), train_iter
        except StopIteration:
            # finite iterator shorter than size() (e.g. drop_remainder):
            # epoch boundary -- reshuffle like the rollover path
            self.dataset.shuffle()
            train_iter = self.dataset.data(train=True)
            return next(train_iter), train_iter

    def optimize(self):
        """Run training with the reference's failure-retry semantics: on an
        exception, reload the latest checkpoint and continue, at most
        BIGDL_FAILURE_RETRY_TIMES times (reference: DistriOptimizer's
        retryNum loop, optim/DistriOptimizer.scala:862-908)."""
        from bigdl_tpu.utils import config
        self._check_plateau_monitor()
        retries_left = config.failure_retry_times()
        while True:
            try:
                return self._optimize_impl()
            except KeyboardInterrupt:
                raise
            except (ConfigurationError, UnsupportedFeatureError,
                    TrainingHaltedError):
                # deterministic configuration/capability errors: a retry
                # replays the identical failure after burning a restore
                # cycle (and masks the message when no checkpoint exists
                # yet) -- fail fast, mirroring _check_plateau_monitor.
                # TrainingHaltedError is the health watchdogs' halt
                # policy: retrying replays the same numerics blow-up.
                # Plain ValueError/RuntimeError stay retryable: a flaky
                # remote read mid-epoch is exactly what the loop is for.
                raise
            except Exception:
                sharded = getattr(self, "sharded_checkpoint_path", None)
                if retries_left <= 0 or (self.checkpoint_path is None
                                         and not sharded):
                    raise
                retries_left -= 1
                log.exception(
                    "training failed; restoring last checkpoint and "
                    "retrying (%d retries left)", retries_left)
                if sharded:
                    self.resume_from_sharded_checkpoint()
                else:
                    self.resume_from_checkpoint()

    def _init_model(self, example_batch):
        x, _ = _device_batch(example_batch)
        if not self.model.is_built():
            self.model.build(spec_of(x))
        # engine seam (reference: DistriOptimizer calls
        # ConversionUtils.convert before training): BIGDL_ENGINE_TYPE=ir
        # routes the model through the IR lowering, ir-quantized through
        # the int8 engine; the default xla engine is the identity
        from bigdl_tpu.utils.config import engine_type
        engine = engine_type()
        if engine not in ("xla", "direct"):
            if "quantized" in engine:
                raise ValueError(
                    "the int8 engine is inference-only (reference: "
                    "nn.quantized.Quantization quantizes for serving); "
                    "train with BIGDL_ENGINE_TYPE=xla or ir, then "
                    "convert(model, engine='ir-quantized') for serving")
            from bigdl_tpu.utils.intermediate import convert
            self.model = convert(self.model, input_spec=spec_of(x))
        return self.model.parameters()[0], self.model.state()

    def _checkpoint(self, params, mstate, opt_state):
        file_io.save_checkpoint(
            self.checkpoint_path, self.driver_state["neval"], params, mstate,
            opt_state, self.driver_state)

    def _histograms(self, params, state):
        """Parameter/gradient histograms per summary trigger (reference:
        AbstractOptimizer.saveSummary, optim/AbstractOptimizer.scala:47-91)."""
        getter = getattr(self.train_summary, "get_summary_trigger", None)
        if getter is None:
            return
        trig = getter("Parameters")
        if trig is not None and trig(state):
            from jax.tree_util import tree_flatten_with_path, keystr

            leaves, _ = tree_flatten_with_path(params)
            for path, leaf in leaves:
                self.train_summary.add_histogram(
                    "Parameters" + keystr(path), np.asarray(leaf),
                    state["neval"])

    def _log_progress(self, loss, throughput, data_wait_s=0.0, sync_skew=0):
        s = self.driver_state
        shown = "%.6f" % loss
        if sync_skew:   # deferred sync: the loss is sync_skew steps stale
            shown += " [%d-step-old sync]" % sync_skew
        log.info(
            "Epoch %d [iteration %d] loss %s, %.1f records/s "
            "(data-wait %.1f ms)",
            s["epoch"], s["neval"], shown, throughput, data_wait_s * 1e3)

    def _effective_sync_every(self):
        """``sync_every`` collapsed to 1 when a configured trigger reads
        step OUTPUTS (min_loss/max_score): those predicates consult
        state["loss"]/["score"] on every evaluation, which a deferred
        sync would leave stale.  Count-based triggers keep the deferred
        cadence -- validation/checkpoint firings force a point sync in
        the loop instead, so Plateau schedules (including monitor="loss")
        always record against a fresh value."""
        k = max(1, int(getattr(self, "sync_every", 1)))
        if k == 1:
            return 1
        for t in (self.end_trigger, self.validation_trigger,
                  self.checkpoint_trigger):
            if t is not None and getattr(t, "uses_outputs", False):
                log.info(
                    "sync_every=%d forced to 1: a configured trigger "
                    "reads step outputs (loss/score) every step", k)
                return 1
        return k

    def _run_driver_loop(self, train_iter, first_batch, *, dispatch,
                        stage_device=None, records_of=None,
                        extra_summaries=None, validate_cb=None,
                        feed_plateau=None, checkpoint_cb=None,
                        health_cb=None, event_fields=None, state_cb=None):
        """The ONE training driver loop shared by Local/Distri/Strategy
        optimizers (they differ only in the step signature and how
        batches reach the devices, injected via the callbacks).

        Encodes the staging/trigger choreography that must not diverge:
        the next batch is prefetched while the device executes the
        current step, its host->device transfer is started immediately
        (double buffering: batch k+1 rides the wire while step k
        executes), the end trigger is evaluated exactly once per
        completed step, and the fetch is DEFERRED past the trigger
        decision for stateful / output-reading triggers (round-3
        liveness fix -- an eager fetch one batch past the end blocks
        forever on a stream dataset).

        - ``dispatch(staged) -> device loss``: runs the step on the
          device-staged payload; owns the params/opt_state closure.
        - ``stage_device(batch) -> staged``: start the batch's
          host->device move (async; placed on the step's sharding).
          Default identity for drivers that stage inside dispatch.
        - ``records_of(batch)``: global records this step (default
          ``batch.size()``).
        - ``extra_summaries(state)``: extra train-summary scalars
          (called only when a summary is set, after Loss/Throughput).
        - ``validate_cb() -> results``: validation results (recorded via
          _record_validation); ``feed_plateau(state)`` then lets the
          caller thread the Plateau schedule through its opt_state.
        - ``checkpoint_cb(state)``: write a checkpoint.
        - ``health_cb() -> host stats tree``: fetch the current step's
          on-device numerics stats (drivers stash the device tree in
          their dispatch closure).  Called only on sampled steps (the
          attached ``HealthMonitor`` decides the cadence); a sample
          forces a loss point sync like a validation firing, and the
          monitor handles event recording + watchdog policy.
        - ``event_fields``: a static dict merged into every step event
          (e.g. the dp driver's ``wire_bytes`` / ``compression_ratio``
          communication footprint).
        - ``state_cb() -> model state``: read for a model that declares
          ``state_spans = {state key: attribute names}`` (an expert
          layer's routing counts): right after each loss sync, when the
          step's outputs are ready, every such key is fetched once and
          recorded as a span of its name with those attributes.

        The per-step loss sync (``float(loss)``) runs every
        ``sync_every``-th step only (default 1 = classic behavior; see
        ``set_sync_every``): between syncs the host stays ahead of the
        device and ``sync_skew`` in the step event counts the staleness
        of the reported loss.  A validation or checkpoint firing forces
        a point sync so downstream consumers (Plateau schedules,
        checkpointed driver state) always see a fresh loss.

        Timing is split, not conflated: ``data_wait_s`` is ALL host
        input work this step -- the deferred fetch at the top of the
        iteration, the in-loop fetch/transform of the next batch, and
        both batches' device staging -- while ``device_s`` (= wall -
        data_wait) covers dispatch + loss sync, the device-bound
        remainder.  A synchronous transformer chain therefore shows up
        as data-wait even though the device computes concurrently: that
        host time bounds how far the loop can run ahead, and it is
        exactly what ``PrefetchDataSet`` moves off the critical path.
        Both timers go to ``self.metrics`` and, when a ``StepTelemetry``
        is attached, into one structured JSONL event per step that the
        TensorBoard scalars are also derived from (single source of
        truth); a prefetching dataset additionally contributes its
        ``queue_depth``/``queue_capacity`` occupancy to each event.
        """
        self._reshuffle_pending = False   # no stale flag from a prior run
        epoch_size = self.dataset.size()
        state = self.driver_state
        batch = first_batch
        dev = None                        # device-staged payload for `batch`
        records_of = records_of or (lambda b: b.size())
        stage_device = stage_device or (lambda b: b)
        queue_stats = getattr(self.dataset, "queue_stats", None)
        sync_every = self._effective_sync_every()
        loss = float("nan")               # last synced loss value
        # primed so the FIRST step always syncs: every published loss is
        # a real (at worst stale) value, never the NaN placeholder, and
        # the warmup compile lands in a synced step
        sync_skew = sync_every - 1        # steps since the last loss sync
        loss_dev = None
        tel = self.telemetry
        mon = self.health_monitor
        health_on = (mon is not None and mon.enabled
                     and health_cb is not None)
        # model state that the model asks to be recorded as spans
        state_spans = {} if state_cb is None else {
            k: v for k, v in getattr(self.model, "state_spans", {}).items()
            if k in state_cb()}
        timer = None
        if getattr(self, "blocking_timing", False):
            # trusted-timing mode (set_blocking_timing): every dispatch
            # is block_until_ready-fenced and step_blocked_s becomes the
            # step event's published timing basis
            from bigdl_tpu.observability.profiling import BlockingStepTimer
            timer = BlockingStepTimer()
            if tel is not None:
                tel.set_timing_mode("blocking")   # no-op if already set
        step_blocked = None

        def point_sync(reason):
            """Force a loss sync outside the cadence (validation/
            checkpoint firing): consumers there need a fresh value."""
            nonlocal loss, sync_skew
            with span("loss_sync", step=state["neval"], forced=reason):
                loss = float(loss_dev)
            sync_skew = 0
            state["loss"] = loss

        try:
            while not self.end_trigger(state):
                with span("step", step=state["neval"]):
                    t0 = time.perf_counter()
                    if batch is None:  # exotic trigger defeated the prediction
                        with span("data_wait", step=state["neval"]):
                            batch, train_iter = self._stage_next_batch(
                                train_iter, state, 0, epoch_size, force=True)
                    if dev is None:    # first iteration / deferred-fetch path
                        with span("device_stage", step=state["neval"]):
                            dev = stage_device(batch)
                    data_wait = time.perf_counter() - t0
                    if tel is not None:   # open the no-compile watchdog window
                        tel.step_begin(state["neval"])
                    with span("dispatch", step=state["neval"]):
                        if timer is not None:
                            timer.begin()
                        loss_dev = dispatch(dev)
                        if timer is not None:
                            # fence: the loss is an output of the step's one
                            # XLA program, so its readiness is the step's
                            step_blocked = timer.end(loss_dev)
                    n = records_of(batch)
                    qdepth = queue_stats() if queue_stats is not None else None
                    t_fetch = time.perf_counter()
                    with span("stage_next_batch", step=state["neval"]):
                        next_batch, train_iter = self._stage_next_batch(
                            train_iter, state, n, epoch_size)
                    next_dev = None
                    if next_batch is not None \
                            and next_batch is not PREDICTED_END:
                        # double buffering: batch k+1's host->device transfer
                        # overlaps step k's execution
                        with span("device_stage", step=state["neval"] + 1):
                            next_dev = stage_device(next_batch)
                    # the in-loop fetch runs while the device executes, but it
                    # is still host time the loop cannot dispatch through --
                    # the input-pipeline cost prefetch workers are there to
                    # take off this path
                    data_wait += time.perf_counter() - t_fetch
                    health_due = health_on and mon.due(state["neval"])
                    if sync_skew + 1 >= sync_every or health_due:
                        # a health sample forces a point sync (same contract
                        # as validation triggers): the published event pairs
                        # the stats with a FRESH loss
                        with span("loss_sync", step=state["neval"]):
                            loss = float(loss_dev)
                        sync_skew = 0
                        for key, fields in state_spans.items():
                            with span(key, step=state["neval"]) as sp:
                                sp.set(**dict(zip(fields, np.asarray(
                                    state_cb()[key]).tolist())))
                    else:
                        sync_skew += 1    # deferred: host runs ahead of device
                    wall = time.perf_counter() - t0
                    device_s = wall - data_wait
                    state["loss"] = loss
                    state["record_count"] += n
                    # batches consumed by COMPLETED steps this epoch -- the
                    # prefetched-but-not-dispatched next batch is NOT counted,
                    # so a snapshot's position replays it after resume
                    state["batches_consumed"] = \
                        state.get("batches_consumed", 0) + 1
                    state["throughput"] = n / max(wall, 1e-9)
                    self.metrics.add("data_wait_s", data_wait)
                    self.metrics.add("device_s", device_s)
                    event = {"step": state["neval"], "epoch": state["epoch"],
                             "wall_s": wall, "data_wait_s": data_wait,
                             "device_s": device_s, "loss": loss, "records": n,
                             "records_per_s": state["throughput"],
                             "sync_skew": sync_skew}
                    if timer is not None:
                        event["step_blocked_s"] = step_blocked
                    if qdepth is not None:
                        event["queue_depth"], event["queue_capacity"] = qdepth
                    if event_fields:
                        event.update(event_fields)
                    if tel is not None:
                        tel.record_step(event)
                    self._log_progress(loss, state["throughput"], data_wait,
                                       sync_skew)
                    if self.train_summary is not None:
                        # scalars derive from the SAME event dict the JSONL
                        # records -- the two channels cannot disagree
                        add_event = getattr(
                            self.train_summary, "add_step_event", None)
                        if add_event is not None:
                            add_event(event)
                        else:   # duck-typed summary: raw scalars
                            self.train_summary.add_scalar(
                                "Loss", loss, state["neval"])
                            self.train_summary.add_scalar(
                                "Throughput", state["throughput"],
                                state["neval"])
                        if extra_summaries is not None:
                            extra_summaries(state)
                    if health_on and mon.policy != "warn":
                        # incident-bundle event ring (kind-tagged like the
                        # JSONL); only dump_incident ever reads it, so a
                        # warn-policy or disabled monitor pays nothing
                        mon.note_event({"kind": "step", **event})
                    if health_due:
                        # fetch the on-device stats (blocks on the step, the
                        # point sync above already did) and hand them to the
                        # monitor: health event + watchdogs + warn/dump/halt
                        with span("health_sample", step=state["neval"]):
                            mon.on_sample(state, health_cb(), loss=loss,
                                          batch=batch, telemetry=tel,
                                          summary=self.train_summary)
                    state["neval"] += 1
                    if state["record_count"] >= epoch_size:
                        state["epoch"] += 1
                        state["record_count"] = 0
                        state["batches_consumed"] = 0
                        if next_batch is None:
                            # fetch deferred past the reset
                            self._reshuffle_pending = True

                    if (self.validation_trigger is not None
                            and self.validation_trigger(state)):
                        if sync_skew:
                            point_sync("validation")
                        with span("validation", step=state["neval"]):
                            self._record_validation(validate_cb(), state)
                            if feed_plateau is not None:
                                feed_plateau(state)
                    if (self.checkpoint_trigger is not None
                            and self.checkpoint_trigger(state)):
                        if sync_skew:
                            point_sync("checkpoint")
                        # snapshot the RNG stream position with the counters,
                        # and the mid-epoch dataset position (shuffle state +
                        # consumed-batch count) so resume can fast-forward to
                        # the exact sample-stream position
                        state["rng_state"] = RNG.get_state()
                        state["data_position"] = self._capture_data_position()
                        with span("checkpoint", step=state["neval"]):
                            checkpoint_cb(state)

                    # next_batch None = deferred: the top-of-loop fetch runs
                    # only after the end trigger decided training continues
                    batch = None if next_batch is PREDICTED_END else next_batch
                    dev = next_dev
            if sync_skew and loss_dev is not None:
                # drain: the run's final loss lands in driver_state even
                # when the last steps deferred their sync
                point_sync("drain")
            if timer is not None and timer.samples and tel is not None:
                # end-of-run trust verdict for the blocked timing (no
                # trace witness or dispatch chain in a training loop --
                # the audit covers platform + MFU plausibility)
                from bigdl_tpu.observability.profiling import TimingAuditor
                from bigdl_tpu.observability.telemetry import peak_flops
                dev0 = jax.devices()[0]
                tel.record("timing_audit", **TimingAuditor().audit(
                    platform=dev0.platform,
                    step_blocked_s=timer.p50(),
                    flops_per_step=(tel.cost or {}).get("flops_per_step"),
                    peak_flops=peak_flops(dev0)))
        finally:
            shutdown = getattr(self.dataset, "shutdown", None)
            if callable(shutdown):
                shutdown()    # prefetch workers must not outlive the run
            if tel is not None:
                tel.flush()   # artifacts complete even on an exception


class LocalOptimizer(BaseOptimizer):
    """Reference: optim/LocalOptimizer.scala:45."""

    def _optimize_impl(self):
        train_iter = self.dataset.data(train=True)
        first_batch = next(train_iter)
        params, mstate = self._init_model(first_batch)
        self._resolve_optim_methods(params)
        opt_state = self.optim_method.init_state(params)

        if getattr(self, "_resume", None):
            snap = self._resume
            params = jax.tree.map(jnp.asarray, snap["model_params"])
            mstate = jax.tree.map(jnp.asarray, snap["model_state"])
            opt_state = jax.tree.map(jnp.asarray, snap["opt_state"])
            self._apply_driver_state(snap["driver_state"])
        train_iter, first_batch = self._resume_data_stream(
            train_iter, first_batch)

        mon = self.health_monitor
        use_health = mon is not None and mon.enabled
        step = jax.jit(make_train_step(
            self.model, self.criterion, self.optim_method,
            compute_dtype=self.compute_dtype, clip_value=self.clip_value,
            clip_norm=self.clip_norm, grad_transform=self.grad_transform,
            health_stats=use_health), donate_argnums=(0, 1, 2))

        if self.telemetry is not None:
            self.telemetry.recompile_watchdog.watch(step)
            if self.blocking_timing:
                # before attach_cost's lazy header write, so the header
                # itself carries the run's timing discipline
                self.telemetry.set_timing_mode("blocking")
            # shape/dtype specs only -- lowering for cost_analysis needs
            # avals, not a device copy of the batch
            spec = lambda a: jax.ShapeDtypeStruct(
                np.shape(a), jax.dtypes.canonicalize_dtype(
                    np.asarray(a).dtype))
            xc = jax.tree.map(spec, first_batch.get_input())
            tgt = first_batch.get_target()
            tc = None if tgt is None else jax.tree.map(spec, tgt)
            cost_args = (params, mstate, opt_state, xc, tc,
                         jax.random.key(0))
            labels = ("params", "mstate", "opt_state", "input", "target",
                      "rng")
            if use_health:
                cost_args += (jax.ShapeDtypeStruct((), jnp.bool_),)
                labels += ("sample",)
            self.telemetry.attach_cost(
                step, *cost_args, records_per_step=first_batch.size(),
                arg_labels=labels)

        stats_holder = [None]         # device stats tree of the live step

        def dispatch(staged):
            nonlocal params, mstate, opt_state
            x, target = staged
            if use_health:
                params, mstate, opt_state, loss, stats = step(
                    params, mstate, opt_state, x, target, RNG.next_key(),
                    mon.due(self.driver_state["neval"]))
                stats_holder[0] = stats
            else:
                params, mstate, opt_state, loss = step(
                    params, mstate, opt_state, x, target, RNG.next_key())
            return loss

        if use_health:
            from bigdl_tpu.observability.health import layer_labels
            mon.bind(
                layer_labels(params),
                params_fn=lambda: jax.device_get(
                    {"params": params, "mstate": mstate,
                     "opt_state": opt_state}))

        def extra_summaries(state):
            self._log_learning_rates(opt_state, state)
            self._histograms(params, state)

        def feed_plateau(state):
            nonlocal opt_state
            opt_state = self._feed_plateau(state, opt_state)

        self._run_driver_loop(
            train_iter, first_batch, dispatch=dispatch,
            stage_device=_device_batch,
            extra_summaries=extra_summaries,
            validate_cb=lambda: validate(
                self.model, params, mstate, self.validation_dataset,
                self.validation_methods, self.compute_dtype),
            feed_plateau=feed_plateau,
            checkpoint_cb=lambda state: self._checkpoint(
                params, mstate, opt_state),
            health_cb=(lambda: jax.device_get(stats_holder[0]))
            if use_health else None,
            state_cb=lambda: mstate)

        self.model.set_parameters(params)
        self.model.set_state(mstate)
        return self.model


def validate(model, params, mstate, dataset, methods, compute_dtype=None):
    """Shared evaluation loop (reference: optim/Evaluator.scala /
    DistriValidator).

    The jitted eval step is cached per (model, dtype) in
    ``validation.compiled_eval_step`` -- a fresh ``jax.jit`` wrapper per
    call would silently recompile on EVERY validation interval."""
    from bigdl_tpu.optim.validation import compiled_eval_step
    eval_step = compiled_eval_step(model, compute_dtype)
    totals: List[Optional[ValidationResult]] = [None] * len(methods)
    for batch in dataset.data(train=False):
        x, target = jax.device_put((batch.get_input(), batch.get_target()))
        out = eval_step(params, mstate, x)
        for i, m in enumerate(methods):
            r = m(out, target)
            totals[i] = r if totals[i] is None else totals[i] + r
    return totals


class Optimizer:
    """Factory mirroring the reference (optim/Optimizer.scala:476,602-676):
    picks Local vs Distri based on the dataset/devices; ``strategy=``
    additionally routes to the model-parallel engines (tensor/pipeline/
    sequence/expert parallelism) with the same builder surface:

        Optimizer(model, ds, crit, method, strategy="tp", mesh=mesh)
        Optimizer(model, ds, crit, method, strategy="pp", mesh=mesh,
                  n_microbatches=4)
    """

    def __new__(cls, model=None, dataset=None, criterion=None,
                optim_method=None, distributed: Optional[bool] = None,
                strategy: Optional[str] = None, **strategy_kw):
        from bigdl_tpu.dataset.dataset import DistributedDataSet
        from bigdl_tpu.optim.distri_optimizer import DistriOptimizer

        if strategy is not None and strategy != "dp":
            from bigdl_tpu.optim.strategy_optimizer import StrategyOptimizer
            return StrategyOptimizer(model, dataset, criterion, optim_method,
                                     strategy=strategy, **strategy_kw)
        if strategy == "dp":
            # dp options (mesh, axis, grad_compression, sync_bn) forward to
            # DistriOptimizer; unknown names fail in its constructor
            return DistriOptimizer(model, dataset, criterion, optim_method,
                                   **strategy_kw)
        if strategy_kw:
            raise TypeError(
                f"unexpected arguments {sorted(strategy_kw)}; pass "
                "strategy= ('dp', 'tp', 'pp', 'sp' or 'ep') to route them")
        if distributed is None:
            distributed = isinstance(dataset, DistributedDataSet)
        klass = DistriOptimizer if distributed else LocalOptimizer
        return klass(model, dataset, criterion, optim_method)
