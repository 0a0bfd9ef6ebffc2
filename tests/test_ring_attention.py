"""Ring attention + sequence parallelism correctness on the 8-device mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import bigdl_tpu.nn as nn
from bigdl_tpu import optim
from bigdl_tpu.nn.attention import (MultiHeadAttention, TransformerLM,
                                    dot_product_attention)
from bigdl_tpu.parallel.ring_attention import sequence_shard_attention
from bigdl_tpu.parallel.sequence import make_sp_train_step, shard_tokens
from bigdl_tpu.utils.random_generator import RNG


def seq_mesh(n=8):
    return Mesh(np.asarray(jax.devices()[:n]), ("seq",))


def rand_qkv(b=2, t=32, h=4, d=8):
    r = np.random.default_rng(0)
    mk = lambda: jnp.asarray(r.standard_normal((b, t, h, d)), jnp.float32)
    return mk(), mk(), mk()


class TestRingAttention:
    def test_matches_plain_full(self):
        q, k, v = rand_qkv()
        want = dot_product_attention(q, k, v, causal=False)
        got = sequence_shard_attention(q, k, v, seq_mesh(), causal=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_matches_plain_causal(self):
        q, k, v = rand_qkv()
        want = dot_product_attention(q, k, v, causal=True)
        got = sequence_shard_attention(q, k, v, seq_mesh(), causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_bf16_inputs(self):
        q, k, v = rand_qkv()
        q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
        want = dot_product_attention(q, k, v, causal=True)
        got = sequence_shard_attention(q, k, v, seq_mesh(), causal=True)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=0.1, atol=0.05)

    # heavy 8-device shard_map compile: full/slow CI tier (tier-1 keeps a
    # cheaper gate for this path)
    @pytest.mark.slow
    def test_grads_flow_through_ring(self):
        q, k, v = rand_qkv(t=16)
        mesh = seq_mesh()

        def loss_ring(q, k, v):
            return jnp.sum(
                sequence_shard_attention(q, k, v, mesh, causal=True) ** 2)

        def loss_plain(q, k, v):
            return jnp.sum(dot_product_attention(q, k, v, causal=True) ** 2)

        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g_plain = jax.grad(loss_plain, argnums=(0, 1, 2))(q, k, v)
        for gr, gp in zip(g_ring, g_plain):
            np.testing.assert_allclose(np.asarray(gr), np.asarray(gp),
                                       rtol=1e-4, atol=1e-4)


class TestSequenceParallelTransformer:
    def _tokens(self, b=2, t=32, vocab=50):
        r = np.random.default_rng(1)
        return (r.integers(0, vocab, (b, t)).astype(np.int32),
                r.integers(0, vocab, (b, t)).astype(np.int32))

    def test_sp_forward_matches_local(self):
        x, _ = self._tokens()
        RNG.set_seed(3)
        local = TransformerLM(50, 32, 4, 2, max_len=64)
        local.build(jax.ShapeDtypeStruct(x.shape, jnp.int32))
        RNG.set_seed(3)
        sp = TransformerLM(50, 32, 4, 2, max_len=64, seq_axis_name="seq")
        sp._params = local._params  # same weights

        y_local = local.forward(jnp.asarray(x))

        mesh = seq_mesh()
        fn = jax.jit(shard_map(
            lambda p, xx: sp.apply(p, (), xx, training=False)[0],
            mesh=mesh, in_specs=(P(), P(None, "seq")),
            out_specs=P(None, "seq"), check_vma=False))
        y_sp = fn(local._params, jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(y_sp), np.asarray(y_local),
                                   rtol=2e-4, atol=2e-4)

    # heavy 8-device shard_map compile: full/slow CI tier (tier-1 keeps a
    # cheaper gate for this path)
    @pytest.mark.slow
    def test_sp_train_step_matches_local_step(self):
        x, y = self._tokens()
        mesh = seq_mesh()
        RNG.set_seed(5)
        model_sp = TransformerLM(50, 32, 4, 2, max_len=64,
                                 seq_axis_name="seq")
        model_sp.build(jax.ShapeDtypeStruct((2, 4), jnp.int32))  # T_local spec
        params = model_sp._params
        crit = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion())
        method = optim.SGD(learning_rate=0.1)

        step = make_sp_train_step(model_sp, crit, method, mesh)
        opt_state = method.init_state(params)
        p_sp, _, loss_sp = step(params, opt_state,
                                shard_tokens(x, mesh), shard_tokens(y, mesh),
                                jax.random.key(0))

        # local reference step with identical init
        RNG.set_seed(5)
        model_l = TransformerLM(50, 32, 4, 2, max_len=64)
        model_l.build(jax.ShapeDtypeStruct((2, 4), jnp.int32))

        def loss_fn(p):
            out, _ = model_l.apply(p, (), jnp.asarray(x), training=True,
                                   rng=None)
            return crit.apply(out, jnp.asarray(y))

        loss_l, grads = jax.value_and_grad(loss_fn)(model_l._params)
        p_l, _ = method.update(grads, method.init_state(model_l._params),
                               model_l._params)

        assert abs(float(loss_sp) - float(loss_l)) < 1e-4
        flat_sp = jax.flatten_util.ravel_pytree(p_sp)[0]
        flat_l = jax.flatten_util.ravel_pytree(p_l)[0]
        np.testing.assert_allclose(np.asarray(flat_sp), np.asarray(flat_l),
                                   rtol=5e-4, atol=5e-4)

    def test_dp_x_sp_mesh(self):
        """2-D mesh: data x sequence."""
        x, y = self._tokens(b=4, t=16)
        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("data", "seq"))
        RNG.set_seed(9)
        model = TransformerLM(50, 32, 4, 1, max_len=32, seq_axis_name="seq")
        model.build(jax.ShapeDtypeStruct((2, 4), jnp.int32))
        crit = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion())
        method = optim.SGD(learning_rate=0.1)
        step = make_sp_train_step(model, crit, method, mesh,
                                  data_axis="data")
        opt_state = method.init_state(model._params)
        p2, _, loss = step(model._params, opt_state,
                           shard_tokens(x, mesh, data_axis="data"),
                           shard_tokens(y, mesh, data_axis="data"),
                           jax.random.key(0))
        assert np.isfinite(float(loss))
