"""Numerical equivalence of tp/pp/ep train steps vs the single-device step.

Round-2 VERDICT (ask #4): sp already has an equivalence test
(test_ring_attention.py); these give tp/pp/ep the same treatment -- one
optimizer step on identical params/batch must produce the same loss and the
same updated parameters as a plain single-device jit step, because the
parallel forms only re-layout the computation (GSPMD partitioning, GPipe
scheduling), not the math.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu import optim
from bigdl_tpu.nn.attention import TransformerLM
from bigdl_tpu.nn.moe import MoETransformerLM
from bigdl_tpu.utils.random_generator import RNG

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs the 8-device virtual CPU mesh")


def _tree_allclose(a, b, rtol=5e-4, atol=1e-5):
    flat_a = jax.tree_util.tree_flatten_with_path(a)[0]
    flat_b = jax.tree.leaves(b)
    assert len(flat_a) == len(flat_b)
    for (path, x), y in zip(flat_a, flat_b):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=rtol, atol=atol,
            err_msg=jax.tree_util.keystr(path))


def _baseline_step(model, criterion, method, params, x, y):
    """Plain single-device fused step (the semantics tp/pp/ep must match)."""

    def step(p, opt_state):
        def loss_fn(q):
            out, _ = model.apply(q, (), x, training=True,
                                 rng=jax.random.key(0))
            return criterion.apply(out.astype(jnp.float32), y)

        loss, grads = jax.value_and_grad(loss_fn)(p)
        new_p, new_opt = method.update(grads, opt_state, p)
        return new_p, new_opt, loss

    return jax.jit(step)(params, method.init_state(params))


class TestTPEquivalence:
    @pytest.mark.slow
    def test_one_step_matches_single_device(self):
        # slow tier (ISSUE-9 re-tier): ~9s, and the tp-vs-local
        # equivalence stays tier-1 via test_tp.py's
        # test_tp_train_step_matches_local
        from bigdl_tpu.parallel.tp import (init_opt_state_sharded,
                                           make_tp_train_step, shard_params)

        RNG.set_seed(0)
        mesh = jax.sharding.Mesh(
            np.asarray(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
        model = TransformerLM(64, 32, 4, 2, max_len=32)
        model.build(jax.ShapeDtypeStruct((4, 16), jnp.int32))
        crit = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion())
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.integers(0, 64, (4, 16)), jnp.int32)
        y = jnp.asarray(rng.integers(0, 64, (4, 16)), jnp.int32)

        ref_p, _, ref_loss = _baseline_step(
            model, crit, optim.SGD(learning_rate=0.1, momentum=0.9,
                                   dampening=0.0),
            jax.tree.map(jnp.copy, model._params), x, y)

        method = optim.SGD(learning_rate=0.1, momentum=0.9, dampening=0.0)
        step = make_tp_train_step(model, crit, method, mesh)(model._params)
        sharded = shard_params(jax.tree.map(jnp.copy, model._params), mesh)
        opt_state = init_opt_state_sharded(method, sharded, mesh)
        tp_p, _, tp_loss = step(sharded, opt_state, x, y, jax.random.key(0))

        np.testing.assert_allclose(float(tp_loss), float(ref_loss),
                                   rtol=1e-5)
        _tree_allclose(tp_p, ref_p)


class TestPPEquivalence:
    @pytest.mark.slow
    def test_one_step_matches_single_device(self):
        # slow tier (ISSUE-9 re-tier): ~10s, and the pp-vs-local
        # equivalence stays tier-1 via test_pp.py's
        # Test1F1BSchedule::test_matches_single_device_and_gpipe
        from bigdl_tpu.parallel.pp import (init_pp_opt_state,
                                           make_pp_train_step, pp_shardings,
                                           stack_stage_params,
                                           unstack_stage_params)

        RNG.set_seed(0)
        n_stages = 2
        mesh = jax.sharding.Mesh(
            np.asarray(jax.devices()[:4]).reshape(2, 2), ("data", "pipe"))
        model = TransformerLM(64, 32, 4, num_layers=n_stages, max_len=32)
        model.build(jax.ShapeDtypeStruct((4, 16), jnp.int32))
        crit = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion())
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.integers(0, 64, (4, 16)), jnp.int32)
        y = jnp.asarray(rng.integers(0, 64, (4, 16)), jnp.int32)

        ref_p, _, ref_loss = _baseline_step(
            model, crit, optim.SGD(learning_rate=0.1, momentum=0.9,
                                   dampening=0.0),
            jax.tree.map(jnp.copy, model._params), x, y)

        method = optim.SGD(learning_rate=0.1, momentum=0.9, dampening=0.0)
        pp = stack_stage_params(model, n_stages)
        pp = jax.tree.map(jax.device_put, pp, pp_shardings(pp, mesh))
        opt_state = init_pp_opt_state(method, pp, mesh)
        step = make_pp_train_step(model, crit, method, mesh,
                                  n_microbatches=2, data_axis="data")
        pp_new, _, pp_loss = step(pp, opt_state, x, y, jax.random.key(0))

        np.testing.assert_allclose(float(pp_loss), float(ref_loss),
                                   rtol=1e-5)
        _tree_allclose(unstack_stage_params(model, pp_new), ref_p)


class Test3DComposition:
    def test_pp_tp_dp_one_step_matches_single_device(self):
        """3-D mesh (data x pipe x model): GPipe shard_map manual on
        data/pipe, Megatron shardings on the model axis left to GSPMD
        (VERDICT r2 ask #4: composed parallelism dryrun + equivalence)."""
        from bigdl_tpu.parallel.pp import (make_pp_train_step,
                                           pp_tp_shardings,
                                           stack_stage_params,
                                           unstack_stage_params)
        from bigdl_tpu.parallel.zero import shard_opt_state

        RNG.set_seed(0)
        mesh = jax.sharding.Mesh(
            np.asarray(jax.devices()[:8]).reshape(2, 2, 2),
            ("data", "pipe", "model"))
        model = TransformerLM(64, 32, 4, num_layers=2, max_len=32)
        model.build(jax.ShapeDtypeStruct((4, 16), jnp.int32))
        crit = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion())
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.integers(0, 64, (4, 16)), jnp.int32)
        y = jnp.asarray(rng.integers(0, 64, (4, 16)), jnp.int32)

        ref_p, _, ref_loss = _baseline_step(
            model, crit, optim.SGD(learning_rate=0.1, momentum=0.9,
                                   dampening=0.0),
            jax.tree.map(jnp.copy, model._params), x, y)

        method = optim.SGD(learning_rate=0.1, momentum=0.9, dampening=0.0)
        pp = stack_stage_params(model, 2)
        sh = pp_tp_shardings(pp, mesh)
        pp = jax.tree.map(jax.device_put, pp, sh)
        opt_state = shard_opt_state(method, pp, sh, mesh)
        step = make_pp_train_step(model, crit, method, mesh,
                                  n_microbatches=2, data_axis="data",
                                  manual_axes=("data", "pipe"))
        pp_new, _, loss = step(pp, opt_state, x, y, jax.random.key(0))

        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        _tree_allclose(unstack_stage_params(model, pp_new), ref_p)


class TestEPEquivalence:
    # the old-jax skip is retired: PR 7's opt_state_shardings pin fixed
    # the ep donation-alias failure this used to hit, and the step now
    # passes on the compat fallback too.  Slow tier like its tp/pp
    # siblings (heavy MoE shard_map compile); the tier-1 ep gate is
    # test_strategy_facade's test_ep_facade_loss_matches.
    @pytest.mark.slow
    def test_one_step_matches_single_device(self):
        from bigdl_tpu.parallel.ep import (ep_shard_params,
                                           init_ep_opt_state,
                                           make_ep_train_step)

        RNG.set_seed(0)
        mesh = jax.sharding.Mesh(
            np.asarray(jax.devices()[:4]).reshape(2, 2), ("data", "expert"))
        model = MoETransformerLM(64, 32, 4, 2, num_experts=2, max_len=32,
                                 capacity_factor=4.0)
        model.build(jax.ShapeDtypeStruct((2, 8), jnp.int32))
        crit = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion())
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.integers(0, 64, (4, 8)), jnp.int32)
        y = jnp.asarray(rng.integers(0, 64, (4, 8)), jnp.int32)
        aux_weight = 0.01

        method_ref = optim.SGD(learning_rate=0.1, momentum=0.9,
                               dampening=0.0)

        def base_step(p, opt_state):
            def loss_fn(q):
                logits, st = model.apply(q, (), x, training=True,
                                         rng=jax.random.key(0))
                task = crit.apply(logits.astype(jnp.float32), y)
                return task + aux_weight * st["aux_loss"], task

            (_, task), grads = jax.value_and_grad(loss_fn, has_aux=True)(p)
            new_p, new_opt = method_ref.update(grads, opt_state, p)
            return new_p, new_opt, task

        ref_p, _, ref_task = jax.jit(base_step)(
            jax.tree.map(jnp.copy, model._params),
            method_ref.init_state(model._params))

        method = optim.SGD(learning_rate=0.1, momentum=0.9,
                           dampening=0.0)
        step = make_ep_train_step(model, crit, method, mesh,
                                  aux_weight=aux_weight)(model._params)
        params = ep_shard_params(
            jax.tree.map(jnp.copy, model._params), mesh)
        opt_state = init_ep_opt_state(method, params, mesh)
        ep_p, _, ep_task = step(params, opt_state, x, y, jax.random.key(0))

        np.testing.assert_allclose(float(ep_task), float(ref_task),
                                   rtol=1e-5)
        _tree_allclose(ep_p, ref_p)


class TestSyncBatchNorm:
    """Round-5 SyncBN (VERDICT r4 ask #5): with cross-replica statistics
    the dp+ZeRO-1 step matches single-device full-batch BN tightly; the
    default per-shard mode (reference per-replica semantics) stays loose."""

    def _one_step(self, sync, seed=0):
        import bigdl_tpu.nn as nn
        from bigdl_tpu import optim
        from bigdl_tpu.dataset import SampleToMiniBatch, array_dataset
        from bigdl_tpu.models.resnet import ResNetCifar
        from bigdl_tpu.optim import DistriOptimizer, Trigger
        from bigdl_tpu.utils.random_generator import RNG

        mesh = jax.sharding.Mesh(
            np.asarray(jax.devices()[:8]).reshape(8,), ("data",))
        rng = np.random.default_rng(seed)
        x = rng.standard_normal((16, 16, 16, 3)).astype(np.float32)
        y = rng.integers(0, 10, 16).astype(np.int32)
        RNG.set_seed(seed)
        model = ResNetCifar(depth=8, class_num=10)
        opt = DistriOptimizer(
            model, array_dataset(x, y) >> SampleToMiniBatch(16),
            nn.CrossEntropyCriterion(),
            optim.SGD(learning_rate=0.1, momentum=0.9, dampening=0.0),
            mesh=mesh, sync_bn=sync)
        opt.set_end_when(Trigger.max_iteration(1))
        opt.optimize()
        return model, float(opt.driver_state["loss"]), (x, y)

    def _local_step(self, x, y, seed=0):
        import bigdl_tpu.nn as nn
        from bigdl_tpu import optim
        from bigdl_tpu.dataset import SampleToMiniBatch, array_dataset
        from bigdl_tpu.models.resnet import ResNetCifar
        from bigdl_tpu.optim import LocalOptimizer, Trigger
        from bigdl_tpu.utils.random_generator import RNG

        RNG.set_seed(seed)
        model = ResNetCifar(depth=8, class_num=10)
        opt = LocalOptimizer(
            model, array_dataset(x, y) >> SampleToMiniBatch(16),
            nn.CrossEntropyCriterion(),
            optim.SGD(learning_rate=0.1, momentum=0.9, dampening=0.0))
        opt.set_end_when(Trigger.max_iteration(1))
        opt.optimize()
        return model, float(opt.driver_state["loss"])

    # heavy 8-device shard_map compile: full/slow CI tier (tier-1 keeps a
    # cheaper gate for this path)
    @pytest.mark.slow
    def test_sync_bn_matches_single_device_tightly(self):
        model_d, loss_d, (x, y) = self._one_step(sync=True)
        model_l, loss_l = self._local_step(x, y)
        assert abs(loss_d - loss_l) / abs(loss_l) < 1e-3
        # updated params agree too (the backward stat sync is also exact)
        for a, b in zip(jax.tree.leaves(model_d._params),
                        jax.tree.leaves(model_l._params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-4)
        # running statistics pooled identically
        for a, b in zip(jax.tree.leaves(model_d.state()),
                        jax.tree.leaves(model_l.state())):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-4)

    # heavy 8-device shard_map compile: full/slow CI tier (tier-1 keeps a
    # cheaper gate for this path)
    @pytest.mark.slow
    def test_per_shard_default_drifts(self):
        """Default per-shard stats (reference per-replica semantics) give a
        CLOSE but not tight loss -- documents why sync is opt-in."""
        model_d, loss_d, (x, y) = self._one_step(sync=False, seed=1)
        _, loss_l = self._local_step(x, y, seed=1)
        assert abs(loss_d - loss_l) / abs(loss_l) < 0.05
