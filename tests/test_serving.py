"""Dynamic-batched inference serving (ISSUE 5): bucket ladder, request
coalescing, precompiled closed executable set, sharded multi-device
predict, Predictor ragged-tail padding, PredictionService failure
semantics, serving telemetry + obs_report section, and the coalesced
engine against the serial service under live metrics and an SLO drill."""

import json
import logging
import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu import optim
from bigdl_tpu.dataset import SampleToMiniBatch, array_dataset
from bigdl_tpu.dataset.minibatch import MiniBatch, Sample
from bigdl_tpu.observability import StepTelemetry
from bigdl_tpu.observability.watchdogs import (RecompileWatchdog,
                                               backend_compile_count)
from bigdl_tpu.optim.predictor import PredictionService, Predictor
from bigdl_tpu.optim.validation import compiled_eval_step
from bigdl_tpu.serving import BucketLadder, ServingEngine
from bigdl_tpu.serving.buckets import (ladder_or_default, pad_batch_axis,
                                       pad_length_axis, slice_batch_axis)
from bigdl_tpu.utils.engine import Engine
from bigdl_tpu.utils.random_generator import RNG

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp(hidden=32, out=10, seed=0):
    RNG.set_seed(seed)
    m = (nn.Sequential().add(nn.Linear(16, hidden)).add(nn.ReLU())
         .add(nn.Linear(hidden, out)))
    m.build(jax.ShapeDtypeStruct((2, 16), jnp.float32))
    return m


def _xs(n, seed=0):
    return np.random.default_rng(seed).standard_normal(
        (n, 16)).astype(np.float32)


class TestBucketLadder:
    def test_default_geometric_rungs(self):
        assert BucketLadder(8).rungs == [1, 2, 4, 8]
        assert BucketLadder(10).rungs == [1, 2, 4, 8, 10]
        assert BucketLadder(1).rungs == [1]

    def test_bucket_for_rounds_up(self):
        lad = BucketLadder(16)
        assert [lad.bucket_for(n) for n in (1, 2, 3, 5, 8, 9, 16)] == \
            [1, 2, 4, 8, 8, 16, 16]
        assert lad.bucket_for(17) is None

    def test_alignment_for_sharded_predict(self):
        lad = BucketLadder(32, align=8)
        assert lad.rungs == [8, 16, 32]
        assert lad.bucket_for(1) == 8 and lad.bucket_for(9) == 16

    def test_add_and_contains(self):
        lad = BucketLadder(8)
        assert lad.add(6) == 6 and 6 in lad
        assert lad.rungs == [1, 2, 4, 6, 8]
        lad2 = BucketLadder(8, align=4)
        assert lad2.add(6) == 8          # aligned insert dedups

    def test_copy_is_independent(self):
        lad = BucketLadder(8, align=2)
        cp = lad.copy()
        assert cp.rungs == lad.rungs and cp.align == lad.align
        cp.add(6)
        assert 6 in cp and 6 not in lad  # growth stays on the copy

    def test_ladder_or_default_validates_alignment(self):
        with pytest.raises(ValueError, match="not divisible"):
            ladder_or_default(BucketLadder(8), max_size=8, align=4)
        lad = ladder_or_default(None, max_size=8, align=4)
        assert all(r % 4 == 0 for r in lad)

    def test_rejects_bad_parameters(self):
        with pytest.raises(ValueError):
            BucketLadder(0)
        with pytest.raises(ValueError):
            BucketLadder(8, min_size=9)
        with pytest.raises(ValueError):
            BucketLadder(8, growth=1)

    def test_pad_and_slice_roundtrip(self):
        x = (np.arange(6, dtype=np.float32).reshape(3, 2),
             np.ones((3,), np.int32))
        padded = pad_batch_axis(x, 8)
        assert padded[0].shape == (8, 2) and padded[1].shape == (8,)
        assert (padded[0][3:] == 0).all()
        back = slice_batch_axis(padded, 3)
        np.testing.assert_array_equal(back[0], x[0])

    def test_pad_length_axis_grows_ladder_past_max(self):
        """An over-max length becomes a REUSED rung (like the batch
        path's ladder.add) instead of silently passing through unpadded
        -- which would compile one executable per distinct length."""
        lad = BucketLadder(8)
        a11 = pad_length_axis(np.ones((1, 11, 3), np.float32), lad)
        assert a11.shape == (1, 11, 3) and 11 in lad
        a10 = pad_length_axis(np.ones((1, 10, 3), np.float32), lad)
        assert a10.shape == (1, 11, 3)       # reuses the grown rung

    def test_pad_length_axis(self):
        lad = BucketLadder(8)
        a = np.ones((2, 5, 3), np.float32)
        out = pad_length_axis(a, lad)
        assert out.shape == (2, 8, 3)
        assert (out[:, 5:] == 0).all()
        # rank-1 leaves (labels) untouched
        assert pad_length_axis(np.ones((4,)), lad).shape == (4,)

    def test_concurrent_add_keeps_rungs_sorted(self):
        """The dispatcher thread grows the ladder (over-max lengths)
        while caller threads read it: interleaved unlocked inserts
        could leave rungs unsorted, after which bucket_for's bisect
        returns a rung SMALLER than n and padding raises mid-tick."""
        import threading

        lad = BucketLadder(4)
        errs = []

        def grow(base):
            try:
                for k in range(200):
                    n = base + (k % 37)
                    b = lad.bucket_for(n)
                    if b is None:
                        b = lad.add(n)
                    assert b >= n
            except Exception as e:       # pragma: no cover - the bug
                errs.append(e)

        threads = [threading.Thread(target=grow, args=(base,))
                   for base in (5, 19, 41, 67)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        assert lad.rungs == sorted(set(lad.rungs))


class TestMiniBatchPad:
    def test_pad_to_pads_input_and_target(self):
        mb = MiniBatch(np.ones((3, 4), np.float32), np.ones((3,), np.int32))
        p = mb.pad_to(8)
        assert p.size() == 8 and p.get_target().shape == (8,)
        assert (p.get_input()[3:] == 0).all()
        assert mb.pad_to(3) is mb            # identity fast path

    def test_pad_to_rejects_shrink(self):
        mb = MiniBatch(np.ones((4, 2), np.float32))
        with pytest.raises(ValueError, match="cannot shrink"):
            mb.pad_to(2)

    def test_pad_to_tuple_inputs(self):
        mb = MiniBatch((np.ones((2, 3)), np.ones((2, 5))), None)
        p = mb.pad_to(4)
        assert p.get_input()[0].shape == (4, 3)
        assert p.get_input()[1].shape == (4, 5)

    def test_pad_to_can_skip_target(self):
        """pad_target=False (the predict path): the target is neither
        copied nor allowed to veto padding the input -- an object-dtype
        label tree must not force the recompiling unpadded fallback."""
        labels = np.empty((3,), object)
        labels[:] = [{"id": i} for i in range(3)]
        mb = MiniBatch(np.ones((3, 4), np.float32), labels)
        p = mb.pad_to(8, pad_target=False)
        assert p.size() == 8
        assert p.get_target() is labels          # untouched passthrough
        with pytest.raises(TypeError, match="target leaves"):
            mb.pad_to(8)                         # default still refuses


class TestCompiledEvalStepCache:
    """Satellite: cache keying -- same model + dtype + two bucket shapes
    -> 2 executables; re-predict -> 0 new compiles; bound respected."""

    def test_two_buckets_two_executables_then_stable(self):
        model = _mlp()
        step = compiled_eval_step(model, None)
        params, mstate = model.parameters()[0], model.state()
        x4, x8 = _xs(4), _xs(8)
        step(params, mstate, x4)
        step(params, mstate, x8)
        assert step.executables() == 2
        before = backend_compile_count()
        step(params, mstate, x4)
        step(params, mstate, x8)
        assert step.executables() == 2
        assert backend_compile_count() == before     # 0 new compiles

    def test_precompile_warms_the_ladder(self):
        model = _mlp(seed=1)
        step = compiled_eval_step(model, None)
        params, mstate = model.parameters()[0], model.state()
        n = step.precompile(params, mstate, np.zeros((16,), np.float32),
                            buckets=[1, 2, 4])
        assert n == step.executables() == 3
        before = backend_compile_count()
        for b in (1, 2, 4):
            step(params, mstate, _xs(b))
        assert backend_compile_count() == before
        # warm shapes re-precompile for free
        assert step.precompile(params, mstate,
                               np.zeros((16,), np.float32),
                               buckets=[2, 4]) == 0

    def test_eviction_free_bound_warns_not_evicts(self, caplog):
        model = _mlp(seed=2)
        step = compiled_eval_step(model, None)
        step.max_executables = 1
        params, mstate = model.parameters()[0], model.state()
        with caplog.at_level(logging.WARNING, "bigdl_tpu.optim"):
            step(params, mstate, _xs(2))
            step(params, mstate, _xs(3))
        assert any("leaking past the bucket ladder" in r.message
                   for r in caplog.records)
        assert step.executables() == 2       # warned, NOT evicted

    def test_shared_with_predictor_and_validate(self):
        model = _mlp(seed=3)
        assert Predictor(model)._eval is compiled_eval_step(model, None)


def _hold_ticks(eng, gate):
    """Make every serving tick wait (at most 10 s) for ``gate`` before its
    device call: the dispatcher sits inside the tick, its requests
    claimed."""
    evaluate = eng._backend.eval

    def held(*a, **kw):
        gate.wait(10)
        return evaluate(*a, **kw)

    eng._backend.eval = held


class TestServingEngine:
    def test_burst_coalesces_into_one_full_tick(self, tmp_path):
        model = _mlp(seed=4)
        tel = StepTelemetry(str(tmp_path / "run"), trace=False)
        eng = ServingEngine(model, max_batch_size=8, max_wait_ms=200.0,
                            telemetry=tel)
        try:
            eng.precompile()
            xs = _xs(8)
            futs = [eng.submit(x) for x in xs]
            ys = [f.result(30) for f in futs]
        finally:
            eng.close()
            tel.close()
        assert {f.bucket for f in futs} == {8}
        assert all(f.latency_s > 0 for f in futs)
        events = [json.loads(ln) for ln in open(tel.jsonl_path)]
        inf = [e for e in events if e["kind"] == "inference"]
        assert len(inf) == 1                 # ONE dispatch for 8 callers
        e = inf[0]
        assert e["records"] == 8 and e["bucket"] == 8
        assert e["batch_fill"] == 1.0 and e["pad_waste"] == 0.0
        assert len(e["request_latency_s"]) == 8
        assert "queue_depth" in e and e["queue_capacity"] == 1024
        # per-request rows match the unbatched bucketed reference
        ref = Predictor(model, batch_size=8).predict(
            [Sample(x) for x in xs])
        np.testing.assert_allclose(np.stack(ys), np.stack(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_deadline_flushes_partial_batch(self, tmp_path):
        model = _mlp(seed=5)
        tel = StepTelemetry(str(tmp_path / "run"), trace=False)
        eng = ServingEngine(model, max_batch_size=8, max_wait_ms=30.0,
                            telemetry=tel)
        try:
            eng.precompile()
            t0 = time.perf_counter()
            futs = [eng.submit(x) for x in _xs(3)]
            [f.result(30) for f in futs]
            waited = time.perf_counter() - t0
        finally:
            eng.close()
            tel.close()
        # dispatched by the deadline, not by a full batch: every request
        # rode a sub-max bucket and nobody waited anywhere near forever
        assert all(f.bucket in (1, 2, 4) for f in futs)
        assert waited < 10.0
        events = [json.loads(ln) for ln in open(tel.jsonl_path)]
        inf = [ev for ev in events if ev["kind"] == "inference"]
        assert sum(e["records"] for e in inf) == 3
        if len(inf) == 1:        # the common single-tick coalescing case
            e = inf[0]
            assert e["records"] == 3 and e["bucket"] == 4
            assert abs(e["pad_waste"] - 0.25) < 1e-9

    def test_bit_exact_within_bucket(self):
        """The identical-outputs contract: a request's logits are
        bit-exact whether it shares the bucket with other requests or
        rides alone, padded to the same bucket."""
        model = _mlp(seed=6)
        eng = ServingEngine(model, max_batch_size=8, max_wait_ms=100.0)
        try:
            eng.precompile()
            xs = _xs(6)
            futs = [eng.submit(x) for x in xs]
            ys = [f.result(30) for f in futs]
            bucket = futs[0].bucket
            for x, y in zip(xs, ys):
                np.testing.assert_array_equal(y, eng.predict_at(x, bucket))
        finally:
            eng.close()

    def test_zero_recompiles_after_precompile_mixed_sizes(self):
        """Acceptance: steady-state serving performs zero recompiles
        across mixed request sizes, asserted via RecompileWatchdog."""
        model = _mlp(seed=7)
        eng = ServingEngine(model, max_batch_size=8, max_wait_ms=5.0)
        try:
            eng.precompile()
            wd = RecompileWatchdog(warmup_steps=0)
            wd.watch(eng._backend.step)
            wd.step_begin(1)
            for k in (3, 8, 1, 5, 2, 7, 4, 6):
                eng.predict_many(_xs(k), timeout=30)
            compiles = wd.step_end(1)
        finally:
            eng.close()
        assert compiles == 0 and not wd.events

    def test_tick_failure_surfaces_and_engine_recovers(self):
        model = _mlp(seed=8)
        eng = ServingEngine(model, max_batch_size=4, max_wait_ms=20.0)
        try:
            eng.precompile()
            orig, state = eng._backend.eval, {"calls": 0}

            def flaky(x, tick=0):
                state["calls"] += 1
                if state["calls"] == 1:
                    raise RuntimeError("injected failing batch")
                return orig(x, tick)

            eng._backend.eval = flaky
            xs = _xs(4)
            futs = [eng.submit(x) for x in xs]
            failed = 0
            for f in futs:
                try:
                    f.result(30)
                except RuntimeError:
                    failed += 1
            assert failed >= 1               # the poisoned tick's callers
            # the dispatcher survived: subsequent requests are served
            ys = eng.predict_many(xs, timeout=30)
            assert len(ys) == 4
        finally:
            eng.close()

    def test_cancelled_future_does_not_kill_dispatcher(self):
        """A caller cancelling its pending future must not crash the
        dispatcher (set_result on a CANCELLED future raises
        InvalidStateError): the cancelled request is skipped and every
        later request is still served."""
        model = _mlp(seed=21)
        eng = ServingEngine(model, max_batch_size=4, max_wait_ms=20.0)
        try:
            eng.precompile()
            victim = eng.submit(_xs(1)[0])
            assert victim.cancel()
            # the dispatcher survived the cancelled tick-mate
            ys = eng.predict_many(_xs(3), timeout=30)
            assert len(ys) == 3
            assert victim.cancelled()
        finally:
            eng.close()

    def test_telemetry_failure_does_not_kill_dispatcher(self):
        model = _mlp(seed=22)

        class Boom:
            def record(self, *a, **k):
                raise RuntimeError("telemetry sink is broken")

        eng = ServingEngine(model, max_batch_size=4, max_wait_ms=20.0,
                            telemetry=Boom())
        try:
            eng.precompile()
            ys = eng.predict_many(_xs(4), timeout=30)
            assert len(ys) == 4
            ys = eng.predict_many(_xs(2), timeout=30)   # still serving
            assert len(ys) == 2
        finally:
            eng.close()

    def test_length_ladder_precompile_warms_all_rungs(self):
        """precompile() with a length ladder warms every (batch bucket
        x length rung) combo: mixed-length traffic after warmup does
        ZERO compiles (the documented contract, previously only the
        example's own rung was warmed)."""
        RNG.set_seed(23)
        model = nn.Linear(16, 4)
        model.build(jax.ShapeDtypeStruct((2, 8, 16), jnp.float32))
        eng = ServingEngine(model, max_batch_size=4, max_wait_ms=200.0,
                            length_ladder=BucketLadder(8))
        try:
            rng = np.random.default_rng(1)
            eng.precompile(
                example_feature=rng.standard_normal(
                    (3, 16)).astype(np.float32))
            wd = RecompileWatchdog(warmup_steps=0)
            wd.watch(eng._backend.step)
            wd.step_begin(1)
            for L in (3, 5, 2, 7, 8, 1):       # every length rung's basin
                eng.predict_many(
                    [rng.standard_normal((L, 16)).astype(np.float32)],
                    timeout=30)
            compiles = wd.step_end(1)
        finally:
            eng.close()
        assert compiles == 0 and not wd.events

    def test_close_then_submit_raises(self):
        model = _mlp(seed=9)
        eng = ServingEngine(model, max_batch_size=4, max_wait_ms=5.0)
        eng.close()
        with pytest.raises(RuntimeError, match="closed"):
            eng.submit(_xs(1)[0])

    def test_predict_timeout_bounds_full_queue_admission(self):
        """predict(timeout=) must bound the WHOLE call: with the queue
        full, admission used to wait on _not_full with no timeout, so a
        1s-timeout caller hung until the backlog drained."""
        import concurrent.futures

        gate = threading.Event()
        model = _mlp(seed=28)
        eng = ServingEngine(model, max_batch_size=2, max_wait_ms=5.0,
                            queue_capacity=1)
        try:
            eng.precompile()
            # blocks the dispatcher inside its first tick so the queue
            # behind it stays full for the duration of the assertion
            _hold_ticks(eng, gate)
            fut1 = eng.submit(_xs(1)[0])
            deadline = time.perf_counter() + 5
            while not fut1.running():    # wait until the tick claims it
                assert time.perf_counter() < deadline
                time.sleep(0.005)
            fut2 = eng.submit(_xs(1)[0])     # fills the 1-slot queue
            t0 = time.perf_counter()
            with pytest.raises(concurrent.futures.TimeoutError,
                               match="queue full"):
                eng.predict(_xs(1)[0], timeout=0.2)
            assert time.perf_counter() - t0 < 5.0
        finally:
            gate.set()
            eng.close()              # drains + serves the queued request
        assert fut1.result(5).shape == (10,)
        assert fut2.result(5).shape == (10,)

    def test_small_queue_does_not_stall_ticks(self):
        """queue_capacity below max_batch_size caps tick fill: the
        dispatcher must dispatch at capacity instead of waiting out the
        whole max_wait_ms deadline on every tick (pending can never
        reach max_batch_size when submitters block at capacity)."""
        model = _mlp(seed=29)
        eng = ServingEngine(model, max_batch_size=32, max_wait_ms=5_000.0,
                            queue_capacity=2)
        try:
            eng.precompile()
            t0 = time.perf_counter()
            ys = eng.predict_many(_xs(2), timeout=30)
            wall = time.perf_counter() - t0
            assert len(ys) == 2
            assert wall < 2.0, f"tick stalled {wall:.1f}s on its deadline"
        finally:
            eng.close()

    def test_predict_timeout_cancels_pending_request(self):
        """A timed-out predict() cancels its request: a timeout/retry
        caller must not fill the queue with zombie requests that still
        occupy capacity and batch slots."""
        import concurrent.futures

        gate = threading.Event()
        model = _mlp(seed=30)
        eng = ServingEngine(model, max_batch_size=2, max_wait_ms=5.0,
                            queue_capacity=4)
        try:
            eng.precompile()
            _hold_ticks(eng, gate)
            first = eng.submit(_xs(1)[0])
            deadline = time.perf_counter() + 5
            while not first.running():
                assert time.perf_counter() < deadline
                time.sleep(0.005)
            # times out waiting for a RESULT (queue has room), so the
            # request is still pending -- the timeout must cancel it
            # AND free its queue slot immediately (a zombie left in
            # _pending would count toward capacity until a tick
            # drained it, blocking the caller's own retry)
            with pytest.raises(concurrent.futures.TimeoutError):
                eng.predict(_xs(1)[0], timeout=0.1)
            assert len(eng._pending) == 0      # slot freed right away
            gate.set()
        finally:
            gate.set()
            eng.close()
        assert first.result(5).shape == (10,)

    def test_nonpositive_queue_capacity_rejected(self):
        """queue_capacity=0 would make the first submit() wait on
        _not_full forever (nothing can ever notify it)."""
        model = _mlp(seed=24)
        with pytest.raises(ValueError, match="queue_capacity"):
            ServingEngine(model, queue_capacity=0)

    def test_oversized_min_rung_rejected(self):
        """A ladder whose smallest rung exceeds max_batch_size would
        silently pad EVERY tick past the largest batch a tick can hold
        (>= 2x wasted device compute, visible only as pad_waste)."""
        model = _mlp(seed=33)
        with pytest.raises(ValueError, match="smallest rung"):
            ServingEngine(model, max_batch_size=4,
                          ladder=BucketLadder(8, min_size=8))

    def test_flush_after_foreign_close_is_safe(self, tmp_path):
        """The driver's finally-path tel.flush() must not raise when
        another owner (a serving engine's run) closed the file first --
        that ValueError would mask the original training exception."""
        tel = StepTelemetry(str(tmp_path / "run"), trace=False)
        tel.record("step", step=1)
        tel.close()
        tel.flush()                              # must be a clean no-op

    def test_length_select_excludes_fixed_side_input(self):
        """A multi-input model with a fixed-width rank>=2 side input:
        length_select keeps the side leaf's feature dimension out of
        the ladder (padding 10 -> rung 16 would break Linear(10))."""
        RNG.set_seed(25)
        model = nn.ParallelTable().add(nn.Linear(16, 4)).add(nn.Linear(10, 4))
        model.build((jax.ShapeDtypeStruct((2, 8, 16), jnp.float32),
                     jax.ShapeDtypeStruct((2, 10), jnp.float32)))
        eng = ServingEngine(
            model, max_batch_size=2, max_wait_ms=50.0,
            length_ladder=BucketLadder(8),
            length_select=lambda i, a: i == 0)   # only the token leaf
        try:
            eng.precompile(example_feature=(
                np.zeros((3, 16), np.float32), np.zeros(10, np.float32)))
            before = backend_compile_count()
            y_tok, y_side = eng.predict(
                (np.ones((5, 16), np.float32), np.ones(10, np.float32)),
                timeout=30)
            assert np.asarray(y_tok).shape == (8, 4)   # time rung
            assert np.asarray(y_side).shape == (4,)    # 10 NOT padded to 16
            assert backend_compile_count() == before
        finally:
            eng.close()

    def test_shape_based_length_select_warms_same_leaves(self):
        """length_select sees the leaf at BATCHED rank in precompile()
        too, so an ndim-based predicate (pick the (batch, time, feat)
        token leaf) warms exactly the shapes traffic will hit -- zero
        compiles after warmup (previously precompile passed sample-rank
        leaves, the predicate selected nothing, and the first real
        request paid an XLA compile)."""
        RNG.set_seed(26)
        model = nn.ParallelTable().add(nn.Linear(16, 4)).add(nn.Linear(10, 4))
        model.build((jax.ShapeDtypeStruct((2, 8, 16), jnp.float32),
                     jax.ShapeDtypeStruct((2, 10), jnp.float32)))
        eng = ServingEngine(
            model, max_batch_size=2, max_wait_ms=50.0,
            length_ladder=BucketLadder(8),
            length_select=lambda i, a: a.ndim >= 3)   # shape, not index
        try:
            eng.precompile(example_feature=(
                np.zeros((3, 16), np.float32), np.zeros(10, np.float32)))
            before = backend_compile_count()
            y_tok, y_side = eng.predict(
                (np.ones((5, 16), np.float32), np.ones(10, np.float32)),
                timeout=30)
            assert np.asarray(y_tok).shape == (8, 4)
            assert np.asarray(y_side).shape == (4,)
            assert backend_compile_count() == before
        finally:
            eng.close()

    def test_executable_bound_fits_warmed_ladder(self, caplog):
        """A legitimately large closed shape set (batch rungs x length
        rungs past the default bound) must NOT log the shape-leak
        warning: the engine sizes the shared step's bound from its own
        ladder.  An explicit max_executables= stays the caller's."""
        RNG.set_seed(27)
        model = nn.Linear(16, 4)
        model.build(jax.ShapeDtypeStruct((2, 8, 16), jnp.float32))
        eng = ServingEngine(model, max_batch_size=64, max_wait_ms=50.0,
                            length_ladder=BucketLadder(256))
        try:
            combos = len(eng.ladder) * len(eng.length_ladder)
            assert eng._backend.step.max_executables >= combos
            with caplog.at_level("WARNING", logger="bigdl_tpu.optim"):
                eng.precompile(
                    example_feature=np.zeros((3, 16), np.float32))
            assert not [r for r in caplog.records if "leaking" in r.message]
        finally:
            eng.close()
        eng2 = ServingEngine(model, max_batch_size=64, max_wait_ms=50.0,
                             length_ladder=BucketLadder(256),
                             max_executables=5)
        try:
            assert eng2._backend.step.max_executables == 5
        finally:
            eng2.close()

    def test_telemetry_closed_by_owner_does_not_poison_ticks(self, tmp_path):
        """The owner thread can close a shared StepTelemetry while the
        dispatcher is still serving: record() must drop events cleanly
        instead of raising 'I/O operation on closed file' into every
        subsequent tick (which the tick handler logs as a failure)."""
        model = _mlp(seed=31)
        tel = StepTelemetry(str(tmp_path / "run"), trace=False)
        eng = ServingEngine(model, max_batch_size=4, max_wait_ms=5.0,
                            telemetry=tel)
        try:
            eng.precompile()
            assert eng.predict(_xs(1)[0], timeout=30).shape == (10,)
            tel.close()                       # owner exits its run first
            y = eng.predict(_xs(1)[0], timeout=30)   # still serves fine
            assert y.shape == (10,)
        finally:
            eng.close()

    def test_requires_built_model(self):
        with pytest.raises(ValueError, match="build the model"):
            ServingEngine(nn.Linear(4, 2))

    def test_length_ladder_closes_sequence_shapes(self):
        """Sequence models: mixed request lengths bucket on the TIME
        axis too, so the executable key set stays closed."""
        RNG.set_seed(10)
        model = nn.Linear(16, 4)
        model.build(jax.ShapeDtypeStruct((2, 8, 16), jnp.float32))
        eng = ServingEngine(model, max_batch_size=4, max_wait_ms=200.0,
                            length_ladder=BucketLadder(8))
        try:
            rng = np.random.default_rng(0)
            feats = [rng.standard_normal((L, 16)).astype(np.float32)
                     for L in (3, 5, 2, 7)]
            ys = eng.predict_many(feats, timeout=30)
            assert all(y.shape == (8, 4) for y in ys)    # padded length
            n_exec = eng._backend.step.executables()
            # another mixed-length burst adds NO new shapes
            eng.predict_many(feats[::-1], timeout=30)
            assert eng._backend.step.executables() == n_exec
            # real time steps match the unbucketed forward
            ref = model.forward(feats[0][None])[0]
            np.testing.assert_allclose(ys[0][:3], np.asarray(ref),
                                       rtol=1e-5, atol=1e-6)
        finally:
            eng.close()


class TestShardedServing:
    def test_mesh_predict_matches_single_device(self):
        model = _mlp(seed=11)
        mesh = Engine.mesh()
        n_dev = int(mesh.shape["data"])
        assert n_dev == 8                    # conftest's virtual devices
        eng = ServingEngine(model, max_batch_size=16, max_wait_ms=100.0,
                            mesh=mesh)
        try:
            assert eng._backend.kind == "sharded"
            assert all(r % n_dev == 0 for r in eng.ladder)
            eng.precompile()
            wd = RecompileWatchdog(warmup_steps=0)
            wd.watch(eng._backend.step)
            xs = _xs(11)
            wd.step_begin(1)
            futs = [eng.submit(x) for x in xs]
            ys = [f.result(30) for f in futs]
            compiles = wd.step_end(1)
        finally:
            eng.close()
        assert compiles == 0
        assert futs[0].bucket == 16          # 11 -> aligned rung
        ref = Predictor(model, batch_size=16).predict(
            [Sample(x) for x in xs])
        np.testing.assert_allclose(np.stack(ys), np.stack(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_single_axis_mesh_falls_back_to_local(self):
        from jax.sharding import Mesh

        model = _mlp(seed=12)
        mesh1 = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("data",))
        eng = ServingEngine(model, max_batch_size=4, mesh=mesh1)
        try:
            assert eng._backend.kind == "local"
        finally:
            eng.close()

    def test_explicit_precompile_buckets_validated_against_alignment(self):
        """precompile(buckets=[2]) on an 8-way mesh must fail with the
        same clear alignment ValueError as the ladder= path -- not an
        opaque jax sharding error mid-warmup."""
        model = _mlp(seed=32)
        eng = ServingEngine(model, max_batch_size=16, mesh=Engine.mesh())
        try:
            with pytest.raises(ValueError, match="device alignment"):
                eng.precompile(buckets=[2])
        finally:
            eng.close()


class TestRoundRobinServing:
    def test_round_robin_matches_reference(self):
        model = _mlp(seed=13)
        eng = ServingEngine(model, max_batch_size=4, max_wait_ms=50.0,
                            round_robin=True)
        try:
            assert eng._backend.kind == "round_robin"
            assert len(eng._backend.devices) == 8
            eng.precompile(buckets=[4])
            xs = _xs(4)
            ref = Predictor(model, batch_size=4).predict(
                [Sample(x) for x in xs])     # own (uncommitted-input) exe
            before = backend_compile_count()
            for _ in range(3):               # ticks rotate across devices
                ys = eng.predict_many(xs, timeout=30)
                np.testing.assert_allclose(np.stack(ys), np.stack(ref),
                                           rtol=1e-5, atol=1e-6)
            assert backend_compile_count() == before
        finally:
            eng.close()

    def test_refresh_params_repicks_new_weights(self):
        """refresh_params() must rebuild the per-device clone pool --
        previously it was a silent no-op for round_robin and stale
        weights were served after retraining."""
        model = _mlp(seed=20)
        eng = ServingEngine(model, max_batch_size=4, max_wait_ms=50.0,
                            round_robin=True)
        try:
            xs = _xs(4)
            before = np.stack(eng.predict_many(xs, timeout=30))
            model.set_parameters(
                jax.tree.map(jnp.zeros_like, model.parameters()[0]))
            eng.refresh_params()
            after = np.stack(eng.predict_many(xs, timeout=30))
            assert not np.allclose(before, after)
            np.testing.assert_allclose(after, 0.0, atol=1e-6)
        finally:
            eng.close()


class TestPredictorRaggedTail:
    """Satellite: the last partial minibatch must NOT compile a second
    executable -- it pads to the bucket and the result is sliced."""

    def test_dataset_tail_single_compile(self):
        model = _mlp(seed=14)
        ds = array_dataset(_xs(40), np.zeros(40, np.int32)) \
            >> SampleToMiniBatch(16, drop_remainder=False)  # 16, 16, 8
        p = Predictor(model, batch_size=16)
        wd = RecompileWatchdog(warmup_steps=1)
        wd.watch(p._eval)
        wd.step_begin(1)
        outs = p.predict(ds)
        assert wd.step_end(1) == 1           # the ONE warmup compile
        assert len(outs) == 40
        assert p._eval.executables() == 1    # tail reused the batch-16 exe
        wd.step_begin(2)
        before = backend_compile_count()
        p.predict(ds)                        # repredict: fully warm
        assert wd.step_end(2) == 0 and not wd.events
        # ZERO backend programs of any kind -- the tail unpad happens in
        # numpy after the host sync, not as a device slice executable
        assert backend_compile_count() == before

    def test_sample_list_tail_matches_per_sample(self):
        model = _mlp(seed=15)
        xs = _xs(21)
        p = Predictor(model, batch_size=8)   # 8, 8, 5 -> 5 pads to 8
        outs = p.predict([Sample(x) for x in xs])
        assert len(outs) == 21
        assert p._eval.executables() == 1
        ref = [np.asarray(model.forward(x[None]))[0] for x in xs]
        np.testing.assert_allclose(np.stack(outs), np.stack(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_explicit_ladder_buckets_the_tail(self):
        model = _mlp(seed=16)
        p = Predictor(model, batch_size=8, ladder=BucketLadder(8))
        outs = p.predict([Sample(x) for x in _xs(10)])   # 8 + 2
        assert len(outs) == 10
        assert p._eval.executables() == 2    # rungs 8 and 2

    def test_caller_ladder_not_mutated(self):
        """Consumers COPY a caller-supplied ladder: Predictor grows its
        ladder past max (an oversized dataset batch becomes a rung) and
        ServingEngine adds its max_batch_size rung -- neither may leak
        into a ladder the caller shares with other consumers, whose
        precompile() would then warm executables they can never use."""
        lad = BucketLadder(8)
        model = _mlp(seed=16)
        p = Predictor(model, batch_size=16, ladder=lad)
        p.predict([Sample(x) for x in _xs(10)])    # one 10-row batch
        assert 10 in p.ladder                      # grown on the COPY
        assert lad.rungs == [1, 2, 4, 8]
        with ServingEngine(model, max_batch_size=32, ladder=lad) as eng:
            assert eng.ladder.max == 32
        assert lad.rungs == [1, 2, 4, 8]

    def test_table_output_model_yields_per_sample_trees(self):
        """A ConcatTable model returns a TUPLE per sample -- one list
        entry per sample row, not one per branch (and the padded tail
        is sliced off every leaf)."""
        RNG.set_seed(23)
        model = (nn.Sequential().add(nn.Linear(16, 8)).add(
            nn.ConcatTable().add(nn.Linear(8, 10)).add(nn.Linear(8, 3))))
        model.build(jax.ShapeDtypeStruct((2, 16), jnp.float32))
        xs = _xs(11)
        p = Predictor(model, batch_size=8)         # 8 + 3 -> pads to 8
        outs = p.predict([Sample(x) for x in xs])
        assert len(outs) == 11
        assert all(isinstance(o, tuple) and len(o) == 2 for o in outs)
        assert outs[0][0].shape == (10,) and outs[0][1].shape == (3,)
        ref = model.forward(xs)
        for i, (a, b) in enumerate(outs):
            np.testing.assert_allclose(a, np.asarray(ref[0])[i],
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(b, np.asarray(ref[1])[i],
                                       rtol=1e-5, atol=1e-6)

    def test_inference_events_carry_bucket_fields(self, tmp_path):
        model = _mlp(seed=17)
        tel = StepTelemetry(str(tmp_path / "infer"), trace=False)
        p = Predictor(model, batch_size=16, telemetry=tel)
        p.predict([Sample(x) for x in _xs(24)])          # 16 + 8->16
        tel.close()
        inf = [json.loads(ln) for ln in open(tel.jsonl_path)]
        inf = [e for e in inf if e["kind"] == "inference"]
        assert [e["records"] for e in inf] == [16, 8]
        assert [e["bucket"] for e in inf] == [16, 16]
        assert inf[1]["batch_fill"] == 0.5
        assert inf[1]["pad_waste"] == 0.5


class TestPredictionService:
    def test_failure_releases_semaphore_and_surfaces(self):
        """Satellite: a worker exception must release the permit AND
        reach the caller -- with a leaked permit this num_threads=1
        service would deadlock every later request."""
        model = _mlp(seed=18)
        svc = PredictionService(model, num_threads=1)
        x = _xs(1)[0]
        svc.predict(x)                       # warm
        orig, state = svc.predictor._eval, {"calls": 0}

        def flaky(params, mstate, inp):
            state["calls"] += 1
            if state["calls"] == 1:
                raise RuntimeError("injected eval failure")
            return orig(params, mstate, inp)

        svc.predictor._eval = flaky
        with pytest.raises(RuntimeError, match="injected eval failure"):
            svc.predict(x)
        results = {}

        def worker(i):
            results[i] = svc.predict(x)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads), \
            "permit leaked: requests after the failure deadlocked"
        assert len(results) == 4

    def test_coalesced_service_failing_batch_concurrent(self):
        """Satellite (coalesced path): an injected failing batch fails
        only its own tick's callers; the service keeps serving."""
        model = _mlp(seed=19)
        svc = PredictionService(model, coalesce=True, max_batch_size=4,
                                max_wait_ms=30.0)
        try:
            svc.precompile()
            orig, state = svc.engine._backend.eval, {"calls": 0}

            def flaky(x, tick=0):
                state["calls"] += 1
                if state["calls"] == 1:
                    raise RuntimeError("injected failing batch")
                return orig(x, tick)

            svc.engine._backend.eval = flaky
            xs = _xs(4)
            outcomes = {}

            def worker(i):
                try:
                    outcomes[i] = ("ok", svc.predict(xs[i]))
                except RuntimeError as e:
                    outcomes[i] = ("err", e)

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            assert not any(t.is_alive() for t in threads)
            assert sum(1 for k, _ in outcomes.values() if k == "err") >= 1
            # service still alive after the poisoned batch
            y = svc.predict(xs[0])
            assert y.shape == (10,)
        finally:
            svc.close()

    def test_coalesced_matches_serial(self):
        model = _mlp(seed=20)
        x = _xs(1)[0]
        serial = PredictionService(model, num_threads=2)
        with PredictionService(model, coalesce=True, max_batch_size=4,
                               max_wait_ms=5.0) as svc:
            np.testing.assert_allclose(svc.predict(x), serial.predict(x),
                                       rtol=1e-5, atol=1e-6)

    def test_engine_kwargs_require_coalesce(self):
        with pytest.raises(TypeError, match="coalesce=True"):
            PredictionService(_mlp(seed=21), queue_capacity=4)


class TestObsReportServing:
    """Satellite: the report's Serving section, text + strict JSON."""

    def _obs_report(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "obs_report_serving", os.path.join(REPO, "tools",
                                               "obs_report.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def _serve_run(self, run_dir):
        model = _mlp(seed=22)
        tel = StepTelemetry(run_dir, run_name="serve", trace=False)
        eng = ServingEngine(model, max_batch_size=4, max_wait_ms=100.0,
                            telemetry=tel)
        try:
            eng.precompile()
            for k in (4, 2, 4, 1, 3):
                eng.predict_many(_xs(k), timeout=30)
        finally:
            eng.close()
            tel.close()

    def test_serving_section_fields(self, tmp_path):
        d = str(tmp_path / "run")
        self._serve_run(d)
        rep = self._obs_report().build_report(d)
        sv = rep["serving"]
        assert sv["ticks"] == 5 and sv["requests"] == 14
        assert 0 < sv["latency_s_p50"] <= sv["latency_s_p99"]
        assert sv["latency_s_p95"] is not None
        assert sv["queue_capacity"] == 1024
        assert sv["queue_depth_trajectory"]
        hist = sv["bucket_histogram"]
        assert hist == {"1": 1, "2": 1, "4": 3}
        rows = 4 + 2 + 4 + 1 + 4
        assert abs(sv["pad_waste_fraction"] - (rows - 14) / rows) < 1e-9
        assert 0 < sv["batch_fill_p50"] <= 1.0

    def test_text_and_json_formats(self, tmp_path):
        d = str(tmp_path / "run")
        self._serve_run(d)
        mod = self._obs_report()
        rep = mod.build_report(d)
        text = mod.format_report(rep)
        assert "serving: 5 ticks / 14 requests" in text
        assert "request latency p50/p95/p99" in text
        assert "buckets:" in text and "pad waste" in text
        # strict JSON: dumps with allow_nan=False must round-trip
        js = json.dumps(mod._json_safe(rep), allow_nan=False)
        assert json.loads(js)["serving"]["ticks"] == 5


class TestCoalescedAgainstSerialLive:
    def test_concurrent_clients_scrape_and_slo_drill(self, tmp_path):
        """Four concurrent clients, twelve requests, through the
        semaphore-serial service and the coalescing engine: outputs
        agree to float rounding (buckets differ), the engine compiles
        nothing after ``precompile()``; the engine's run is scraped
        over a real socket, and an objective no request can meet flips
        ``/healthz`` to degraded with a durable ``kind: "slo"`` event."""
        import urllib.request
        from concurrent.futures import ThreadPoolExecutor

        from bigdl_tpu.observability.metrics import (MetricsExporter,
                                                     MetricsRegistry,
                                                     SloTracker)

        model = _mlp()
        xs = _xs(12)
        serial = PredictionService(model, num_threads=4)
        with ThreadPoolExecutor(4) as clients:
            want = list(clients.map(serial.predict, xs))

        run_dir = str(tmp_path)
        tel = StepTelemetry(run_dir, run_name="serve", trace=False)
        registry = MetricsRegistry()
        tel.attach_metrics(registry)
        tracker = SloTracker(registry=registry)
        tracker.bind(tel)

        def get(path):
            return urllib.request.urlopen(exporter.url + path,
                                          timeout=10).read().decode()

        with MetricsExporter(
                registry, port=0,
                health_sources=[tracker.health_status]) as exporter, \
                ServingEngine(model, max_batch_size=4, max_wait_ms=5.0,
                              telemetry=tel) as eng:
            eng.precompile()
            before = backend_compile_count()
            with ThreadPoolExecutor(4) as clients:
                got = list(clients.map(eng.predict, xs))
            assert backend_compile_count() - before == 0
            assert sum(line.startswith("bigdl_serving_")
                       for line in get("/metrics").splitlines()) > 0
            assert json.loads(get("/healthz"))["status"] == "ok"
            tracker.add(name="injected_breach", kind="inference",
                        field="request_latency_s", threshold=0.0,
                        target=0.999, alerts=((5.0, 10.0, 1.0),),
                        min_samples=1)
            for x in xs[:4]:
                eng.predict(x)
            assert json.loads(get("/healthz"))["status"] == "degraded"
        tel.close()
        for y, ref in zip(got, want):
            np.testing.assert_allclose(y, ref, rtol=1e-5, atol=1e-6)
        with open(os.path.join(run_dir, "telemetry.jsonl")) as f:
            assert any(json.loads(line).get("kind") == "slo" for line in f)
