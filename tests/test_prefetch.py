"""Async input pipeline (ISSUE 2): prefetch workers, device double
buffering, and the K-step deferred loss sync.

The contracts under test:

- determinism: the prefetched batch sequence is IDENTICAL to the
  synchronous path for a fixed seed, including epoch-boundary reshuffles
  with workers in flight;
- liveness/cleanup: worker exceptions propagate to the training loop
  (never a silent hang), and ending training -- including the
  PREDICTED_END early-staging path -- leaves no live pipeline threads;
- ``sync_every=1`` (default) is bit-identical in loss trajectory to the
  classic per-step sync; larger values defer the sync but output-reading
  triggers force it back and validation firings see a fresh loss;
- ``validate()`` no longer recompiles its eval step per invocation.
"""

import json
import logging
import os
import threading
import time

import numpy as np
import pytest

import jax

import bigdl_tpu.nn as nn
from bigdl_tpu import optim
from bigdl_tpu.dataset import (FnTransformer, Normalizer, PrefetchDataSet,
                               SampleToMiniBatch, array_dataset)
from bigdl_tpu.dataset.prefetch import decompose, split_parallel
from bigdl_tpu.observability import StepTelemetry
from bigdl_tpu.optim.validation import compiled_eval_step
from bigdl_tpu.utils.random_generator import RNG


def _prefetch_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("bigdl-prefetch")]


def _pipeline(seed=0, n=96, batch=32, workers=0, queue_depth=4):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 8)).astype("float32")
    y = rng.integers(0, 4, n).astype("int32")
    ds = (array_dataset(x, y) >> Normalizer(0.0, 1.0)
          >> SampleToMiniBatch(batch))
    if workers:
        ds = ds.prefetch(num_workers=workers, queue_depth=queue_depth)
    return ds


def _model():
    RNG.set_seed(0)
    return (nn.Sequential().add(nn.Linear(8, 16)).add(nn.ReLU())
            .add(nn.Linear(16, 4)))


def _fit(ds, iterations=8, run_dir=None, sync_every=1, end_trigger=None,
         **setters):
    model = _model()
    opt = optim.LocalOptimizer(model, ds, nn.CrossEntropyCriterion(),
                               optim.SGD(learning_rate=0.1))
    opt.set_end_when(end_trigger or optim.Trigger.max_iteration(iterations))
    if sync_every != 1:
        opt.set_sync_every(sync_every)
    tel = None
    if run_dir is not None:
        tel = StepTelemetry(run_dir, trace=False)
        opt.set_telemetry(tel)
    for name, arg in setters.items():
        getattr(opt, name)(*arg)
    opt.optimize()
    if tel is not None:
        tel.close()
    return opt


def _step_events(run_dir):
    with open(os.path.join(run_dir, "telemetry.jsonl")) as f:
        return [e for e in map(json.loads, f) if e["kind"] == "step"]


class TestChainDecomposition:
    def test_decompose_walks_nested_wrappers_in_order(self):
        ds = _pipeline()
        source, stages = decompose(ds)
        assert [type(t).__name__ for t in stages] == [
            "Normalizer", "SampleToMiniBatch"]
        assert source.size() == 96

    def test_split_at_first_order_dependent_stage(self):
        _, stages = decompose(_pipeline())
        fns, suffix = split_parallel(stages)
        assert len(fns) == 1                      # Normalizer.apply_one
        assert [type(t).__name__ for t in suffix] == ["SampleToMiniBatch"]

    def test_chained_transformer_flattens(self):
        chain = Normalizer(0.0, 1.0) >> FnTransformer(lambda s: s) \
            >> SampleToMiniBatch(4)
        base = array_dataset(np.zeros((8, 2), "float32"))
        _, stages = decompose(base >> chain)
        fns, suffix = split_parallel(stages)
        assert len(fns) == 2 and len(suffix) == 1

    def test_parallel_safe_false_stays_serial(self):
        """A stateful per-element fn opts out of the worker fan-out and
        runs in source order on the serial suffix path."""
        seen = []
        stateful = FnTransformer(lambda s: (seen.append(s), s)[1],
                                 parallel_safe=False)
        chain = [Normalizer(0.0, 1.0), stateful, SampleToMiniBatch(4)]
        base = array_dataset(np.arange(32, dtype="float32").reshape(8, 4))
        ds = base
        for t in chain:
            ds = ds >> t
        _, stages = decompose(ds)
        fns, suffix = split_parallel(stages)
        assert len(fns) == 1                 # only the Normalizer
        assert stages[1] in suffix           # stateful fn stays serial
        pre = ds.prefetch(num_workers=3, queue_depth=2)
        it = pre.data(train=True)
        for _ in range(4):                   # > one epoch of batches
            next(it)
        pre.shutdown()
        # serial path saw elements in exact source order
        feats = [float(np.asarray(s.feature)[0]) for s in seen[:8]]
        assert feats == sorted(feats)


class TestDeterminism:
    def test_batch_sequence_matches_synchronous_path(self):
        """Epoch-boundary reshuffle with workers in flight: the
        prefetched sequence equals the synchronous one, seed-for-seed."""
        sync_ds = _pipeline(workers=0)
        pre_ds = _pipeline(workers=3, queue_depth=2)

        def collect(ds, epochs=3, steps_per_epoch=3):
            out = []
            for _ in range(epochs):
                it = ds.data(train=True)
                for _ in range(steps_per_epoch):
                    out.append(next(it))
                # reshuffle while prefetch workers are still in flight
                ds.shuffle()
            shutdown = getattr(ds, "shutdown", None)
            if shutdown:
                shutdown()
            return out

        a = collect(sync_ds)
        b = collect(pre_ds)
        assert len(a) == len(b) == 9
        for ba, bb in zip(a, b):
            np.testing.assert_array_equal(ba.get_input(), bb.get_input())
            np.testing.assert_array_equal(ba.get_target(), bb.get_target())
        assert _prefetch_threads() == []

    def test_training_loss_trajectory_identical(self, tmp_path):
        d1, d2 = str(tmp_path / "sync"), str(tmp_path / "pre")
        _fit(_pipeline(workers=0), run_dir=d1)
        _fit(_pipeline(workers=4, queue_depth=3), run_dir=d2)
        sync_losses = [e["loss"] for e in _step_events(d1)]
        pre_losses = [e["loss"] for e in _step_events(d2)]
        assert len(sync_losses) == 8
        assert sync_losses == pre_losses      # bit-identical


class TestLifecycle:
    def test_worker_exception_propagates(self):
        def boom(sample):
            if float(np.sum(np.asarray(sample.feature))) > -1e18:
                raise ValueError("transform exploded")
            return sample

        ds = (array_dataset(np.ones((16, 4), "float32"),
                            np.zeros(16, "int32"))
              >> FnTransformer(boom) >> SampleToMiniBatch(4))
        pre = ds.prefetch(num_workers=2, queue_depth=2)
        it = pre.data(train=True)
        with pytest.raises(ValueError, match="transform exploded"):
            next(it)
        pre.shutdown()
        assert _prefetch_threads() == []

    def test_worker_exception_surfaces_in_optimize(self):
        calls = {"n": 0}
        lock = threading.Lock()

        def boom_later(sample):
            with lock:
                calls["n"] += 1
                n = calls["n"]
            if n > 40:
                raise RuntimeError("mid-epoch transform failure")
            return sample

        raw = _pipeline(workers=0).base.base   # the raw array dataset
        ds = raw >> FnTransformer(boom_later) >> SampleToMiniBatch(32)
        pre = ds.prefetch(num_workers=2, queue_depth=2)
        with pytest.raises(RuntimeError, match="mid-epoch transform"):
            _fit(pre, iterations=50)
        assert _prefetch_threads() == []

    def test_shutdown_after_predicted_end_leaves_no_threads(self):
        """max_iteration is a count-based trigger, so the loop predicts
        the end (PREDICTED_END) and never over-fetches; the driver's
        finally-shutdown must still join every pipeline thread."""
        pre = _pipeline(workers=3, queue_depth=4)
        _fit(pre, iterations=5)
        assert _prefetch_threads() == []

    def test_reorder_buffer_bounded_under_slow_consumer(self):
        """Workers that outpace the consumer must wait: a stalled
        training loop bounds host memory at queue_depth batches + the
        reorder window, instead of freewheeling the infinite source."""
        import time

        pre = _pipeline(n=960, batch=32, workers=4, queue_depth=2)
        it = pre.data(train=True)
        next(it)                      # start the pipeline, then stall
        time.sleep(1.0)               # cheap transform: workers race ahead
        live = pre._live
        # reorder buffer: at most the window + one in-flight per worker
        # (before the backpressure fix this was tens of thousands)
        assert len(live._ready) <= live._window + 4, len(live._ready)
        assert live._out.qsize() <= 2      # queue_depth batches
        pre.shutdown()
        assert _prefetch_threads() == []

    def test_queue_stats_live_and_retired(self):
        pre = _pipeline(workers=2, queue_depth=3)
        assert pre.queue_stats() is None      # nothing live yet
        it = pre.data(train=True)
        next(it)
        depth, cap = pre.queue_stats()
        assert cap == 3 and 0 <= depth <= 3
        pre.shutdown()
        assert pre.queue_stats() is None

    def test_zero_workers_is_synchronous_passthrough(self):
        pre = _pipeline(workers=0)
        assert not isinstance(pre, PrefetchDataSet)
        pre = PrefetchDataSet(_pipeline(), num_workers=0)
        it = pre.data(train=True)
        assert next(it).size() == 32
        assert _prefetch_threads() == []

    def test_eval_stream_stays_synchronous(self):
        pre = _pipeline(workers=2)
        batches = list(pre.data(train=False))
        assert len(batches) == 3
        assert _prefetch_threads() == []

    def test_bad_knobs_raise(self):
        with pytest.raises(ValueError, match="num_workers"):
            PrefetchDataSet(_pipeline(), num_workers=-1)
        with pytest.raises(ValueError, match="queue_depth"):
            PrefetchDataSet(_pipeline(), queue_depth=0)


class TestDeferredLossSync:
    def test_sync_every_default_matches_deferred_at_sync_points(self, tmp_path):
        d1, d2 = str(tmp_path / "s1"), str(tmp_path / "s4")
        o1 = _fit(_pipeline(), iterations=8, run_dir=d1)
        o4 = _fit(_pipeline(), iterations=8, run_dir=d2, sync_every=4)
        e1, e4 = _step_events(d1), _step_events(d2)
        assert all(e["sync_skew"] == 0 for e in e1)
        # step 1 always syncs (no NaN placeholder ever published), then
        # the cadence defers k-1 steps at a time
        skews = [e["sync_skew"] for e in e4]
        assert skews == [0, 1, 2, 3, 0, 1, 2, 3]
        assert all(np.isfinite(e["loss"]) for e in e4)
        # at sync points the deferred run reports the IDENTICAL loss
        for a, b in zip(e1, e4):
            if b["sync_skew"] == 0:
                assert a["loss"] == b["loss"]
        assert o1.driver_state["loss"] == o4.driver_state["loss"]

    def test_final_loss_drains_even_mid_window(self, tmp_path):
        d1, d2 = str(tmp_path / "s1"), str(tmp_path / "s5")
        o1 = _fit(_pipeline(), iterations=7, run_dir=d1)
        o5 = _fit(_pipeline(), iterations=7, run_dir=d2, sync_every=5)
        # 7 steps with sync_every=5: the last sync cadence point is step
        # 5; the end-of-run drain must still surface step 7's loss
        assert o5.driver_state["loss"] == o1.driver_state["loss"]

    def test_output_reading_trigger_forces_per_step_sync(self, tmp_path):
        d = str(tmp_path / "minloss")
        end = optim.Trigger.or_(optim.Trigger.max_iteration(6),
                                optim.Trigger.min_loss(1e-9))
        _fit(_pipeline(), run_dir=d, sync_every=4, end_trigger=end)
        assert all(e["sync_skew"] == 0 for e in _step_events(d))

    def test_validation_firing_sees_fresh_loss(self, tmp_path):
        """A Plateau-style schedule monitoring the training loss must
        record against a FRESH value even under a deferred sync cadence
        (the validation firing forces a point sync)."""
        recorded = []

        class RecordingSchedule:
            monitor = "loss"
            stateful = False

            def __call__(self, step, base_lr):
                return base_lr

            def record(self, value, opt_state):
                recorded.append(float(value))
                return opt_state

        # golden per-step losses from an identical run with the classic
        # per-step sync (validation/schedule do not touch the RNG stream)
        ref_dir = str(tmp_path / "ref")
        _fit(_pipeline(), iterations=6, run_dir=ref_dir)
        ref_losses = [e["loss"] for e in _step_events(ref_dir)]

        model = _model()
        method = optim.SGD(learning_rate=0.1,
                           learning_rate_schedule=RecordingSchedule())
        opt = optim.LocalOptimizer(model, _pipeline(),
                                   nn.CrossEntropyCriterion(), method)
        opt.set_end_when(optim.Trigger.max_iteration(6))
        opt.set_sync_every(4)
        opt.set_validation(optim.Trigger.several_iteration(3),
                           _pipeline(seed=1, n=32), [optim.Top1Accuracy()])
        opt.optimize()
        # validation fired after steps 2 and 5 (neval 3 and 6): the
        # recorded monitor values are exactly those steps' true losses,
        # even though the sync cadence alone would have left them stale
        assert recorded == [ref_losses[1], ref_losses[4]]

    def test_sync_every_validates(self):
        opt = optim.LocalOptimizer(_model(), _pipeline(),
                                   nn.CrossEntropyCriterion())
        with pytest.raises(Exception, match="sync_every"):
            opt.set_sync_every(0)


class TestMnistBitIdentity:
    def test_default_and_deferred_sync_bit_identical_on_mnist(self, tmp_path):
        """ISSUE-2 acceptance on the MNIST example: prefetch +
        ``sync_every=1`` (default) is bit-identical in loss trajectory
        to the classic loop, and ``sync_every>1`` matches it exactly at
        every sync point."""
        from bigdl_tpu.dataset.mnist import synthetic_mnist
        from bigdl_tpu.models.lenet import LeNet5

        def run(d, sync_every=1, wrap=False):
            RNG.set_seed(0)
            x, y = synthetic_mnist(128)
            ds = array_dataset(x, y) >> SampleToMiniBatch(32)
            if wrap:
                ds = ds.prefetch(num_workers=2, queue_depth=2)
            opt = optim.LocalOptimizer(LeNet5(), ds, nn.ClassNLLCriterion(),
                                       optim.SGD(learning_rate=0.1))
            opt.set_end_when(optim.Trigger.max_iteration(6))
            if sync_every != 1:
                opt.set_sync_every(sync_every)
            tel = StepTelemetry(d, trace=False)
            opt.set_telemetry(tel)
            opt.optimize()
            tel.close()
            return [e["loss"] for e in _step_events(d)]

        base = run(str(tmp_path / "a"))
        prefetched = run(str(tmp_path / "b"), wrap=True)
        deferred = run(str(tmp_path / "c"), sync_every=3, wrap=True)
        assert base == prefetched                 # bit-identical
        for i, loss in enumerate(deferred):
            if i % 3 == 0:                        # sync points: steps 1, 4
                assert loss == base[i]


class TestEvalStepCache:
    def test_compiled_eval_step_cached_per_model_and_dtype(self):
        import jax.numpy as jnp

        model = _model()
        a = compiled_eval_step(model, None)
        assert compiled_eval_step(model, None) is a
        b = compiled_eval_step(model, jnp.bfloat16)
        assert b is not a
        assert compiled_eval_step(_model(), None) is not a

    def test_dropped_model_releases_compiled_steps(self):
        """The cache lives ON the model (a side table -- even weak-keyed
        -- would be pinned by the jitted closure's model reference), so
        dropping the model drops its executables."""
        import gc
        import weakref

        model = _model()
        compiled_eval_step(model, None)
        assert "_compiled_eval_steps" in model.__dict__
        ref = weakref.ref(model)
        del model
        gc.collect()
        assert ref() is None

    def test_validate_twice_compiles_once(self):
        model = _model()
        val = _pipeline(seed=1, n=64)
        opt = optim.LocalOptimizer(model, _pipeline(), nn.CrossEntropyCriterion(),
                                   optim.SGD(learning_rate=0.1))
        opt.set_end_when(optim.Trigger.max_iteration(1))
        opt.optimize()
        optim.validate(model, model.parameters()[0], model.state(), val,
                       [optim.Top1Accuracy()])
        step_fn = compiled_eval_step(model, None)
        n_before = step_fn._cache_size()
        optim.validate(model, model.parameters()[0], model.state(), val,
                       [optim.Top1Accuracy()])
        assert step_fn._cache_size() == n_before == 1

    def test_no_recompile_warnings_across_two_validation_intervals(
            self, tmp_path, caplog):
        d = str(tmp_path / "run")
        with caplog.at_level(logging.WARNING,
                             logger="bigdl_tpu.observability"):
            _fit(_pipeline(workers=2), iterations=6, run_dir=d,
                 set_validation=(optim.Trigger.several_iteration(3),
                                 _pipeline(seed=1, n=32),
                                 [optim.Top1Accuracy()]))
        events = _step_events(d)
        assert not any("recompiles" in e for e in events)
        assert not any("recompile detected" in r.message
                       for r in caplog.records)
        validations = 0
        with open(os.path.join(d, "telemetry.jsonl")) as f:
            validations = sum(1 for e in map(json.loads, f)
                              if e["kind"] == "validation")
        assert validations == 2


class TestDeviceStaging:
    def test_device_batch_is_single_tree_transfer(self):
        from bigdl_tpu.dataset.minibatch import MiniBatch
        from bigdl_tpu.optim.local_optimizer import _device_batch

        b = MiniBatch(np.ones((4, 3), "float32"), np.zeros(4, "int32"))
        x, t = _device_batch(b)
        assert isinstance(x, jax.Array) and isinstance(t, jax.Array)
        b2 = MiniBatch((np.ones((2, 2), "float32"),
                        np.zeros((2, 1), "float32")))
        x2, t2 = _device_batch(b2)
        assert t2 is None and isinstance(x2[0], jax.Array)

    def test_donation_still_works_with_device_put_staging(self):
        """The staged batch is NOT in donate_argnums (those cover
        params/mstate/opt_state): it must stay readable after the step,
        and the donated train state must keep updating normally."""
        import jax.numpy as jnp

        from bigdl_tpu.dataset.minibatch import MiniBatch
        from bigdl_tpu.optim.local_optimizer import _device_batch
        from bigdl_tpu.optim.train_step import make_train_step
        from bigdl_tpu.utils.shape import spec_of

        model = _model()
        batch = MiniBatch(np.ones((4, 8), "float32"),
                          np.zeros(4, "int32"))
        x, t = _device_batch(batch)
        model.build(spec_of(x))
        params, mstate = model.parameters()[0], model.state()
        method = optim.SGD(learning_rate=0.1)
        opt_state = method.init_state(params)
        step = jax.jit(make_train_step(model, nn.CrossEntropyCriterion(),
                                       method),
                       donate_argnums=(0, 1, 2))
        key = jax.random.key(0)
        for _ in range(2):   # donated chain: outputs re-feed inputs
            params, mstate, opt_state, loss = step(
                params, mstate, opt_state, x, t, key)
        np.testing.assert_array_equal(np.asarray(x),
                                      np.ones((4, 8), "float32"))
        assert np.isfinite(float(loss))

    def test_queue_depth_fields_in_step_events(self, tmp_path):
        d = str(tmp_path / "run")
        _fit(_pipeline(workers=2, queue_depth=3), run_dir=d)
        events = _step_events(d)
        assert all("queue_depth" in e and e["queue_capacity"] == 3
                   for e in events)
        assert all(0 <= e["queue_depth"] <= 3 for e in events)


class TestSlowTransformLegs:
    def test_data_wait_share_and_queue_capacity(self, tmp_path):
        """A transform that sleeps 0.5 ms a sample, read synchronously
        and through two prefetch workers: the data-wait share the
        optimizer's own counters give (``data_wait_s`` over
        ``data_wait_s + device_s`` of ``Optimizer.metrics``, counted
        once a step) lies in [0, 1] on both legs, and the prefetch leg's
        step events name the queue's capacity."""
        steps, batch = 3, 8
        rng = np.random.default_rng(0)
        x = rng.standard_normal((batch * 8, 16)).astype("float32")
        y = rng.integers(0, 4, batch * 8).astype("int32")

        def slow_identity(sample):
            time.sleep(0.0005)
            return sample

        for workers in (0, 2):
            ds = (array_dataset(x, y) >> FnTransformer(slow_identity)
                  >> SampleToMiniBatch(batch))
            if workers:
                ds = ds.prefetch(num_workers=workers, queue_depth=8)
            model = (nn.Sequential().add(nn.Linear(16, 64)).add(nn.ReLU())
                     .add(nn.Linear(64, 64)).add(nn.ReLU())
                     .add(nn.Linear(64, 4)))
            run_dir = str(tmp_path / f"w{workers}")
            tel = StepTelemetry(run_dir, trace=False)
            opt = optim.LocalOptimizer(model, ds, nn.CrossEntropyCriterion(),
                                       optim.SGD(learning_rate=0.05))
            opt.set_end_when(optim.Trigger.max_iteration(steps))
            opt.set_telemetry(tel)
            opt.optimize()
            tel.close()
            m = opt.metrics.to_dict()
            assert m["data_wait_s"]["count"] == m["device_s"]["count"] == steps
            wait, device = m["data_wait_s"]["sum"], m["device_s"]["sum"]
            assert wait > 0 and 0 <= wait / (wait + device) <= 1
            if workers:
                assert all(e["queue_capacity"] == 8
                           for e in _step_events(run_dir))
        assert not _prefetch_threads()
