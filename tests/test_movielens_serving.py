"""MovieLens recommender end-to-end (ISSUE 13 satellite): the
``dataset/movielens.py`` + ``nn/sparse.py`` path through training,
``Predictor`` (sparse MiniBatch = the unpadded dispatch path, recompile
behavior pinned), ``ServingEngine`` (zero steady-state recompiles), and
the deploy rollout loop -- item 5's BigDL-native second workload."""

import os
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu import optim
from bigdl_tpu.dataset import (SampleToMiniBatch, Sample, SparseMiniBatch,
                               array_dataset, movielens)
from bigdl_tpu.dataset.dataset import AbstractDataSet
from bigdl_tpu.nn.sparse import SparseTensor, sparse_recommender
from bigdl_tpu.observability.watchdogs import backend_compile_count
from bigdl_tpu.optim.predictor import Predictor
from bigdl_tpu.serving import (ModelRegistry, RolloutController,
                               ServingEngine)
from bigdl_tpu.utils import file_io
from bigdl_tpu.utils.random_generator import RNG


@pytest.fixture()
def ml(tmp_path):
    folder = str(tmp_path / "ml-mini")
    movielens.write_ratings(folder, n_users=20, n_items=30, n=400, seed=0)
    pairs, ratings = movielens.get_id_pairs(folder)
    n_users = int(pairs[:, 0].max())
    n_ids = n_users + int(pairs[:, 1].max())
    x = movielens.to_id_features(pairs, n_users)
    y = (ratings - 1).astype("int32")
    return n_ids, x, y


def _model(n_ids, seed=3):
    RNG.set_seed(seed)
    m = sparse_recommender(n_ids)
    m.build(jax.ShapeDtypeStruct((2, 2), jnp.float32))
    return m


class TestMovieLensData:
    def test_write_read_round_trip(self, tmp_path):
        folder = str(tmp_path / "ml")
        movielens.write_ratings(folder, n_users=10, n_items=12, n=50,
                                seed=1)
        data = movielens.read_data_sets(folder)
        assert data.shape == (50, 3) and data.dtype == np.int32
        pairs, ratings = movielens.get_id_pairs(folder)
        assert pairs[:, 0].min() >= 1 and pairs[:, 0].max() <= 10
        assert pairs[:, 1].min() >= 1 and pairs[:, 1].max() <= 12
        assert set(np.unique(ratings)) <= {1, 2, 3, 4, 5}
        # deterministic: same seed, same bytes
        movielens.write_ratings(str(tmp_path / "ml2"), n_users=10,
                                n_items=12, n=50, seed=1)
        assert open(os.path.join(folder, "ratings.dat")).read() == \
            open(str(tmp_path / "ml2" / "ratings.dat")).read()

    def test_to_id_features_shared_id_space(self):
        pairs = np.array([[1, 1], [3, 7]], np.int32)
        feats = movielens.to_id_features(pairs, n_users=10)
        assert feats.dtype == np.float32
        np.testing.assert_array_equal(feats, [[1, 11], [3, 17]])


class TestMovieLensTrainingAndServing:
    def test_recommender_trains_and_serves_zero_recompiles(self, ml,
                                                           tmp_path):
        """The second workload end-to-end: train a few supervised
        steps, hot-swap the trained checkpoint into a serving engine,
        serve mixed batch sizes with ZERO steady-state recompiles, and
        pin padded-row inertness (a bucket's zero rows contribute no
        sparse entries)."""
        n_ids, x, y = ml
        model = _model(n_ids)
        ds = array_dataset(x, y, seed=0) >> SampleToMiniBatch(32)
        opt = optim.LocalOptimizer(
            model, ds, nn.CrossEntropyCriterion(),
            optim.SGD(learning_rate=0.1, momentum=0.9, dampening=0.0))
        opt.set_checkpoint(str(tmp_path / "ckpt"),
                           optim.Trigger.several_iteration(4))
        opt.set_end_when(optim.Trigger.max_iteration(8))
        opt.optimize()

        serve = _model(n_ids)                 # fresh replica, same seed
        with ServingEngine(serve, max_batch_size=4,
                           max_wait_ms=1.0) as eng:
            eng.precompile(example_feature=x[0])
            before = np.asarray(eng.predict_at(x[0], 4))
            eng.refresh_from_snapshot(str(tmp_path / "ckpt"))
            execs0 = eng._executables()
            after = np.asarray(eng.predict_at(x[0], 4))
            assert not np.array_equal(before, after)
            # padded-row inertness: the engine's bucket-4 result for one
            # request equals the refreshed model's own forward on the
            # same row padded with zero rows (no valid sparse entries)
            # (jitted like the engine's step: op-by-op eager execution is
            # not bit-equal to a fused program on every XLA CPU backend)
            forward = jax.jit(lambda p, s, rows: serve.apply(
                p, s, rows, training=False)[0])
            np.testing.assert_array_equal(
                after,
                np.asarray(forward(
                    serve._params, serve._state,
                    jnp.asarray(np.vstack([x[:1], np.zeros((3, 2),
                                                           np.float32)])))[0]))
            outs = [np.asarray(eng.predict(r)) for r in x[:10]]
            assert all(o.shape == (5,) for o in outs)
            # coalesced vs unbatched reference at the same bucket:
            # bit-exact (padded zero rows add no valid sparse entries)
            burst = [eng.submit(r) for r in x[:4]]
            got = [np.asarray(f.result(30)) for f in burst]
            bucket = burst[0].bucket
            for r, g in zip(x[:4], got):
                np.testing.assert_array_equal(
                    g, np.asarray(eng.predict_at(r, bucket)))
            assert eng._executables() - execs0 == 0

    def test_sparse_minibatch_predictor_unpadded_dispatch_pin(self, ml):
        """The sparse MiniBatch path through ``Predictor.predict``
        takes the UNPADDED dispatch (``pad_to`` refuses object-dtype
        SparseTensor leaves): its recompile contract is one executable
        per DISTINCT batch shape -- the ragged tail compiles once more
        (unlike the padded dense path's single executable), and a
        re-predict compiles nothing."""
        n_ids, x, y = ml
        RNG.set_seed(5)
        model = (nn.Sequential()
                 .add(nn.LookupTableSparse(n_ids, 8, combiner="sum"))
                 .add(nn.Linear(8, 5)))
        cap = 2 * 4                       # 4-row batches, 2 ids per row
        sp0 = SparseTensor.from_dense(x[:4], capacity=cap)
        model.build(sp0)

        class _Batches(AbstractDataSet):
            def __init__(self, batches):
                self.batches = batches

            def data(self, train=False):
                return iter(self.batches)

            def size(self):
                return sum(b.size() for b in self.batches)

        def sparse_batches():
            # 3 full 4-row batches + one ragged 2-row tail
            out = []
            for i in range(0, 14, 4):
                rows = x[i:min(i + 4, 14)]
                samples = [Sample(r) for r in rows]
                out.append(SparseMiniBatch.of(
                    samples, capacity=2 * len(rows)))
            return out

        pred = Predictor(model, batch_size=4)
        # warm the 4-row shape (the first-ever dispatch additionally
        # pays one-time transfer-program compiles we do not pin)
        full = sparse_batches()[0]
        pred.predict_minibatch(full)
        before = backend_compile_count()
        pred.predict_minibatch(full)
        assert backend_compile_count() - before == 0
        outs = pred.predict(_Batches(sparse_batches()))
        first = backend_compile_count() - before
        assert len(outs) == 14
        # the unpadded dispatch compiles ONE more executable for the
        # ragged 2-row tail (the padded dense path would reuse the
        # 4-row one); the three full batches reuse the warm executable
        assert first == 1, first
        again = pred.predict(_Batches(sparse_batches()))
        assert backend_compile_count() - before == first, \
            "re-predict must reuse both executables"
        for a, b in zip(outs, again):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_rollout_loop_on_movielens(self, ml, tmp_path):
        """The deploy loop on the second workload: a trained MovieLens
        candidate walks shadow -> canary -> cutover under live traffic
        (the tier-1 sibling of the slow serve_live movielens demo)."""
        from bigdl_tpu.observability import StepTelemetry

        n_ids, x, y = ml
        model = _model(n_ids)
        tel = StepTelemetry(str(tmp_path / "serve"), run_name="serve",
                            trace=False)
        eng = ServingEngine(model, max_batch_size=4, max_wait_ms=1.0,
                            telemetry=tel)
        eng.precompile(example_feature=x[0])
        execs0 = eng._executables()
        reg = ModelRegistry(str(tmp_path / "registry.json"))
        ctl = RolloutController(eng, reg, str(tmp_path / "ckpt"),
                                telemetry=tel, shadow_fraction=1.0,
                                shadow_min_rows=8, min_top1_agreement=None,
                                max_logit_rmse=100.0, canary_fraction=0.5,
                                canary_min_ticks=3, stage_timeout_s=30.0)
        ctl.baseline()
        stop, stats = threading.Event(), {"ok": 0, "fail": 0}

        def client():
            i = 0
            while not stop.is_set():
                try:
                    eng.predict(x[i % len(x)], timeout=10.0)
                    stats["ok"] += 1
                except Exception:
                    if not stop.is_set():
                        stats["fail"] += 1
                i += 1

        t = threading.Thread(target=client, daemon=True)
        t.start()
        try:
            trained = _model(n_ids)
            dsb = array_dataset(x, y, seed=0) >> SampleToMiniBatch(32)
            opt = optim.LocalOptimizer(
                trained, dsb, nn.CrossEntropyCriterion(),
                optim.SGD(learning_rate=0.1))
            opt.set_checkpoint(str(tmp_path / "ckpt"),
                               optim.Trigger.several_iteration(6))
            opt.set_end_when(optim.Trigger.max_iteration(6))
            opt.optimize()
            v = ctl.poll_once()
            assert v is not None and v.stage == "live"
            assert reg.live.version == v.version
        finally:
            stop.set()
            t.join(5)
            eng.close()
            tel.close()
        assert stats["fail"] == 0
        assert eng._executables() - execs0 == 0
