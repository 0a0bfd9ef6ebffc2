"""MoE layer + expert-parallel training tests on the 8-device mesh."""

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import bigdl_tpu.nn as nn
from bigdl_tpu import optim
from bigdl_tpu.nn.moe import MoE, MoETransformerLM
from bigdl_tpu.parallel.ep import (ep_shard_params, ep_sharding_for_params,
                                   init_ep_opt_state, make_ep_train_step)
from bigdl_tpu.utils.random_generator import RNG


def ep_mesh():
    return Mesh(np.asarray(jax.devices()).reshape(2, 4), ("data", "expert"))


class TestMoELayer:
    def test_single_expert_matches_dense_mlp(self):
        # E=1, k=1, ample capacity: MoE must equal its one expert's MLP.
        RNG.set_seed(0)
        moe = MoE(16, num_experts=1, k=1, mlp_ratio=2, capacity_factor=8.0)
        x = jnp.asarray(
            np.random.default_rng(0).standard_normal((2, 8, 16)),
            jnp.float32)
        moe.build(jax.ShapeDtypeStruct(x.shape, jnp.float32))
        out, st = moe.apply(moe._params, (), x)
        p = moe._params
        ref = jax.nn.gelu(x @ p["w1"][0] + p["b1"][0]) @ p["w2"][0] + p["b2"][0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
        assert np.isclose(float(st["aux_loss"]), 1.0, atol=1e-5)

    def test_topk_routing_preserves_scale(self):
        RNG.set_seed(1)
        moe = MoE(16, num_experts=4, k=2, capacity_factor=4.0)
        x = jnp.asarray(
            np.random.default_rng(1).standard_normal((2, 16, 16)),
            jnp.float32)
        moe.build(jax.ShapeDtypeStruct(x.shape, jnp.float32))
        out, st = moe.apply(moe._params, (), x)
        assert out.shape == x.shape
        assert np.isfinite(float(st["aux_loss"]))
        # with generous capacity nothing is dropped -> nonzero output rows
        assert float(jnp.abs(out).sum()) > 0

    def test_capacity_drops_overflow(self):
        # capacity_factor tiny -> most tokens dropped -> near-zero output
        RNG.set_seed(2)
        moe = MoE(8, num_experts=2, k=1, capacity_factor=1e-6)
        x = jnp.asarray(
            np.random.default_rng(2).standard_normal((1, 32, 8)), jnp.float32)
        moe.build(jax.ShapeDtypeStruct(x.shape, jnp.float32))
        out, _ = moe.apply(moe._params, (), x)
        kept_rows = int((jnp.abs(out[0]).sum(-1) > 1e-7).sum())
        assert kept_rows <= 2  # k * capacity(=1) rows per expert


class TestExpertParallel:
    def test_ep_sharding_rules(self):
        RNG.set_seed(3)
        model = MoETransformerLM(64, 32, 4, 2, num_experts=4, max_len=32)
        model.build(jax.ShapeDtypeStruct((2, 8), jnp.int32))
        sh = ep_sharding_for_params(model._params, ep_mesh())
        assert sh["block0"]["moe"]["w1"].spec == P("expert", None, None)
        assert sh["block0"]["moe"]["gate"].spec == P()
        assert sh["wte"].spec == P()

    def test_ep_forward_matches_replicated(self):
        RNG.set_seed(4)
        model = MoETransformerLM(64, 32, 4, 2, num_experts=4, max_len=32,
                                 capacity_factor=4.0)
        model.build(jax.ShapeDtypeStruct((2, 8), jnp.int32))
        x = jnp.asarray(
            np.random.default_rng(4).integers(0, 64, (4, 8)), jnp.int32)
        ref, _ = model.apply(model._params, (), x)

        mesh = ep_mesh()
        sharded = ep_shard_params(
            jax.tree.map(jnp.copy, model._params), mesh)
        with mesh:
            got, _ = jax.jit(
                lambda p, xx: model.apply(p, (), xx))(sharded, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_ep_train_step_descends(self):
        RNG.set_seed(5)
        model = MoETransformerLM(64, 32, 4, 2, num_experts=4, max_len=32,
                                 capacity_factor=4.0)
        model.build(jax.ShapeDtypeStruct((2, 8), jnp.int32))
        mesh = ep_mesh()
        crit = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion())
        method = optim.Adam(learning_rate=1e-2)
        step = make_ep_train_step(model, crit, method, mesh)(model._params)
        params = ep_shard_params(
            jax.tree.map(jnp.copy, model._params), mesh)
        opt_state = init_ep_opt_state(method, params, mesh)
        r = np.random.default_rng(5)
        x = jnp.asarray(r.integers(0, 64, (8, 8)), jnp.int32)
        y = jnp.asarray(r.integers(0, 64, (8, 8)), jnp.int32)
        rng = jax.random.key(0)
        losses = []
        for _ in range(5):
            params, opt_state, loss = step(params, opt_state, x, y, rng)
            losses.append(float(loss))
        assert losses[-1] < losses[0], losses
        leaf = params["block0"]["moe"]["w1"]
        assert "expert" in str(leaf.sharding.spec), leaf.sharding
