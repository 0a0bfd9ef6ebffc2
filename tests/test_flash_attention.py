"""Pallas flash-attention kernel vs plain attention (interpret mode on CPU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.attention import dot_product_attention
from bigdl_tpu.ops import flash_attention


def rand(b=2, t=64, h=4, d=16, seed=0, dtype=jnp.float32):
    r = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(r.standard_normal((b, t, h, d)), dtype)
    return mk(), mk(), mk()


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_plain(self, causal):
        q, k, v = rand()
        want = dot_product_attention(q, k, v, causal=causal)
        got = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_single_block(self):
        q, k, v = rand(t=16)
        want = dot_product_attention(q, k, v, causal=True)
        got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_uneven_blocks(self):
        q, k, v = rand(t=96)
        want = dot_product_attention(q, k, v, causal=True)
        got = flash_attention(q, k, v, causal=True, block_q=32, block_k=16,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


#: how far the kernel may lie from ``dot_product_attention`` on inputs of
#: unit variance (outputs of order 1).  float32: both sum in float32, in
#: another order.  bfloat16: both round the weights to bfloat16 before
#: ``p @ v`` (the plain path after normalising, the kernel before) and the
#: result to bfloat16, whose spacing below 4 is 2**-6.
TOLERANCE = {jnp.float32: 2e-5, jnp.bfloat16: 3e-2}

#: T -> (batch, heads, head_dim, [(block_q, block_k), ...]); None: the
#: kernel's own choice.  64 is one block; 1024 is the benchmark cell's
#: sequence with its 16 heads of 64.
SHAPES = {
    64: (2, 4, 64, [(None, None)]),
    256: (2, 4, 64, [(None, None), (128, 64), (64, 128), (128, 128)]),
    1024: (1, 16, 64, [(None, None), (512, 256), (256, 512)]),
}


class TestKernelAgainstPlain:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bfloat16"])
    @pytest.mark.parametrize("causal", [True, False],
                             ids=["causal", "full"])
    @pytest.mark.parametrize(
        "t,block_q,block_k",
        [(t, bq, bk) for t, (_, _, _, tiles) in SHAPES.items()
         for bq, bk in tiles],
        ids=lambda x: "auto" if x is None else str(x))
    def test_forward(self, dtype, causal, t, block_q, block_k):
        b, h, d, _ = SHAPES[t]
        q, k, v = rand(b, t, h, d, seed=t, dtype=dtype)
        want = dot_product_attention(q, k, v, causal=causal)
        got = flash_attention(q, k, v, causal=causal, block_q=block_q,
                              block_k=block_k, interpret=True)
        assert got.dtype == dtype and got.shape == q.shape
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=0, atol=TOLERANCE[dtype])

    @pytest.mark.parametrize("causal", [True, False],
                             ids=["causal", "full"])
    def test_masked_blocks_are_not_read(self, causal):
        """Keys a causal query block cannot see may hold anything: blocks
        wholly above the diagonal are skipped and the straddling ones
        masked, so NaN there never reaches the result.  Without the mask
        every key is read and the NaN shows."""
        q, k, v = rand(1, 256, 2, 64, seed=3)
        first = flash_attention(q[:, :64], k[:, :64], v[:, :64],
                                causal=causal, interpret=True)
        k = k.at[:, 64:].set(jnp.nan)
        v = v.at[:, 64:].set(jnp.nan)
        got = flash_attention(q, k, v, causal=causal, block_q=64,
                              block_k=64, interpret=True)[:, :64]
        if causal:
            np.testing.assert_allclose(np.asarray(got), np.asarray(first),
                                       rtol=0, atol=2e-6)
        else:
            assert np.isnan(np.asarray(got)).all()

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bfloat16"])
    def test_gradient_is_the_plain_paths(self, dtype):
        """The backward rule recomputes through plain attention on the
        saved q/k/v, so the cotangents are the plain path's own."""
        q, k, v = rand(1, 256, 2, 64, seed=5, dtype=dtype)
        w = rand(1, 256, 2, 64, seed=6)[0]

        def through(attend):
            return jax.grad(
                lambda *a: (attend(*a).astype(jnp.float32) * w).sum(),
                argnums=(0, 1, 2))(q, k, v)

        got = through(lambda *a: flash_attention(*a, causal=True,
                                                 interpret=True))
        want = through(lambda *a: dot_product_attention(*a, causal=True))
        for g, p in zip(got, want):
            assert g.dtype == dtype
            np.testing.assert_array_equal(np.asarray(g, np.float32),
                                          np.asarray(p, np.float32))


class TestFlashBlockAlignment:
    """ISSUE-7 satellite: 'auto' mode must accept block-alignable SHORT
    sequences (the kernel's call site handles block_q = t for t < 128);
    the old ``t % 128`` test rejected all of them."""

    def test_short_sequences_block_alignable(self):
        from bigdl_tpu.nn.attention import MultiHeadAttention

        ok = MultiHeadAttention._flash_block_ok
        # sublane-aligned short sequences are flash-able now
        assert ok(8) and ok(24) and ok(64) and ok(120)
        # unaligned short sequences are not
        assert not ok(7) and not ok(20) and not ok(127)
        # long sequences still need exact 128-tiling
        assert ok(128) and ok(256) and ok(1024)
        assert not ok(129) and not ok(192)

    def test_auto_routes_through_block_check(self, monkeypatch):
        """_flash_ok('auto') accepts an aligned short T wherever the
        platform check passes -- pin the predicate chain by faking the
        platform probe."""
        import bigdl_tpu.nn.attention as attention

        mha = attention.MultiHeadAttention(32, 4, causal=True,
                                           use_flash="auto")

        class _Dev:
            platform = "tpu"

        monkeypatch.setattr(attention.jax, "devices", lambda: [_Dev()])
        assert mha._flash_ok(24)
        assert mha._flash_ok(256)
        assert not mha._flash_ok(20)

    def test_short_seq_flash_matches_plain_interpret(self):
        """Numerical agreement at a short, previously-rejected T (the
        wiring the TPU auto mode now takes), kernel in interpret mode."""
        import jax
        import jax.numpy as jnp

        from bigdl_tpu.nn.attention import MultiHeadAttention
        from bigdl_tpu.utils.random_generator import RNG

        t = 24                      # < 128, t % 8 == 0, t % 128 != 0
        RNG.set_seed(0)
        plain = MultiHeadAttention(32, 4, causal=True, use_flash="never")
        plain.build(jax.ShapeDtypeStruct((2, t, 32), jnp.float32))
        RNG.set_seed(0)
        flash = MultiHeadAttention(32, 4, causal=True,
                                   use_flash="interpret")
        flash.build(jax.ShapeDtypeStruct((2, t, 32), jnp.float32))
        x = jnp.asarray(
            np.random.default_rng(1).standard_normal((2, t, 32)),
            jnp.float32)
        np.testing.assert_allclose(np.asarray(flash.forward(x)),
                                   np.asarray(plain.forward(x)),
                                   rtol=2e-5, atol=2e-5)


class TestMHAFlashWiring:
    def test_mha_flash_matches_plain(self):
        """MultiHeadAttention(use_flash='interpret') must match the plain
        path (the wiring the TPU 'auto' mode takes)."""
        import numpy as np

        import jax
        import jax.numpy as jnp

        from bigdl_tpu.nn.attention import MultiHeadAttention
        from bigdl_tpu.utils.random_generator import RNG

        RNG.set_seed(0)
        plain = MultiHeadAttention(32, 4, causal=True, use_flash="never")
        plain.build(jax.ShapeDtypeStruct((2, 16, 32), jnp.float32))
        RNG.set_seed(0)
        flash = MultiHeadAttention(32, 4, causal=True,
                                   use_flash="interpret")
        flash.build(jax.ShapeDtypeStruct((2, 16, 32), jnp.float32))

        x = jnp.asarray(
            np.random.default_rng(0).standard_normal((2, 16, 32)),
            jnp.float32)
        y_plain = plain.forward(x)
        y_flash = flash.forward(x)
        np.testing.assert_allclose(np.asarray(y_flash),
                                   np.asarray(y_plain),
                                   rtol=2e-5, atol=2e-5)
