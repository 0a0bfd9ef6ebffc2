"""Ulysses all-to-all sequence parallelism: numerical equivalence with
plain attention and with the ring strategy (parallel/ulysses.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import bigdl_tpu.nn as nn
from bigdl_tpu import optim
from bigdl_tpu.nn.attention import TransformerLM, dot_product_attention
from bigdl_tpu.parallel.sequence import make_sp_train_step, shard_tokens
from bigdl_tpu.parallel.ulysses import ulysses_self_attention
from bigdl_tpu.utils.random_generator import RNG

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs the 8-device virtual CPU mesh")


def _mesh(n=4):
    return Mesh(np.asarray(jax.devices()[:n]), ("seq",))


def _rand_qkv(b=2, t=32, h=4, d=8):
    r = np.random.default_rng(0)
    mk = lambda: jnp.asarray(r.standard_normal((b, t, h, d)), jnp.float32)
    return mk(), mk(), mk()


def _sharded(q, k, v, mesh, causal):
    fn = shard_map(
        lambda a, b, c: ulysses_self_attention(a, b, c, "seq",
                                               causal=causal),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"), check_vma=False)
    return fn(q, k, v)


class TestUlyssesAttention:
    def test_matches_plain_full(self):
        q, k, v = _rand_qkv()
        want = dot_product_attention(q, k, v, causal=False)
        got = _sharded(q, k, v, _mesh(), causal=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_matches_plain_causal(self):
        q, k, v = _rand_qkv()
        want = dot_product_attention(q, k, v, causal=True)
        got = _sharded(q, k, v, _mesh(), causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_grads_match_plain(self):
        q, k, v = _rand_qkv(t=16)
        mesh = _mesh()

        def loss_u(q, k, v):
            return jnp.sum(_sharded(q, k, v, mesh, True) ** 2)

        def loss_p(q, k, v):
            return jnp.sum(dot_product_attention(q, k, v, causal=True) ** 2)

        gu = jax.grad(loss_u, argnums=(0, 1, 2))(q, k, v)
        gp = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gu, gp):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_heads_not_divisible_raises(self):
        q, k, v = _rand_qkv(h=3)
        with pytest.raises(Exception, match="divisible"):
            _sharded(q, k, v, _mesh(4), causal=False)


class TestUlyssesTrainStep:
    def test_sp_step_matches_single_device(self):
        """Full TransformerLM sp step with seq_mode='ulysses' must match
        the unsharded step (the same bar ring attention clears)."""
        RNG.set_seed(0)
        mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                    ("data", "seq"))
        model = TransformerLM(64, 32, 4, 2, max_len=64, seq_axis_name="seq",
                              seq_mode="ulysses")
        model.build(jax.ShapeDtypeStruct((2, 8), jnp.int32))
        crit = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion())

        RNG.set_seed(0)
        plain = TransformerLM(64, 32, 4, 2, max_len=64)
        plain.build(jax.ShapeDtypeStruct((2, 8), jnp.int32))

        rng = np.random.default_rng(0)
        x = rng.integers(0, 64, (4, 32)).astype(np.int32)
        y = rng.integers(0, 64, (4, 32)).astype(np.int32)

        method = optim.SGD(learning_rate=0.1)
        step = make_sp_train_step(model, crit, method, mesh,
                                  data_axis="data")
        _, _, loss = step(model._params, method.init_state(model._params),
                          shard_tokens(x, mesh, data_axis="data"),
                          shard_tokens(y, mesh, data_axis="data"),
                          jax.random.key(0))

        def base(p):
            out, _ = plain.apply(p, (), jnp.asarray(x), training=True,
                                 rng=jax.random.key(0))
            return crit.apply(out.astype(jnp.float32), jnp.asarray(y))

        ref = jax.jit(base)(plain._params)
        np.testing.assert_allclose(float(loss), float(ref), rtol=1e-5)
