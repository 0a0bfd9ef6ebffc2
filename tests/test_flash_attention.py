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


def grads(attend, q, k, v, w):
    """dq, dk, dv of ``sum(attend(q, k, v) * w)`` (``w`` is the output's
    cotangent), as float32."""
    got = jax.grad(lambda *a: (attend(*a).astype(jnp.float32) * w).sum(),
                   argnums=(0, 1, 2))(q, k, v)
    assert all(g.dtype == x.dtype for g, x in zip(got, (q, k, v)))
    return [np.asarray(g, np.float32) for g in got]


def plain_float32(q, k, v, w, causal):
    """The truth: the plain path on the same values in float32."""
    return grads(lambda *a: dot_product_attention(*a, causal=causal),
                 *(x.astype(jnp.float32) for x in (q, k, v)), w)


class TestBackwardKernel:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bfloat16"])
    @pytest.mark.parametrize("causal", [True, False],
                             ids=["causal", "full"])
    @pytest.mark.parametrize("t,block_q,block_k",
                             [(64, 64, 64), (256, 128, 128), (256, 128, 64),
                              (256, 64, 128)])
    def test_gradient_matches_plain_float32(self, dtype, causal, t, block_q,
                                            block_k):
        """dq, dk, dv of the ``attention_bwd`` kernel against the plain
        path's in float32 (cotangents of order 1, so absolute errors).
        float32: both sum in float32, in another order, 2e-5.  bfloat16:
        no further from the float32 truth than the plain bfloat16 path
        is, times 1.5: the kernel rounds ``p`` and ``ds`` once each where
        the plain path rounds the scores, the weights and their
        cotangents."""
        q, k, v = rand(1, t, 2, 64, seed=5, dtype=dtype)
        w = rand(1, t, 2, 64, seed=6)[0]
        got = grads(lambda *a: flash_attention(
            *a, causal=causal, block_q=block_q, block_k=block_k,
            interpret=True), q, k, v, w)
        truth = plain_float32(q, k, v, w, causal)
        if dtype == jnp.float32:
            limits = [2e-5] * 3
        else:
            plain = grads(lambda *a: dot_product_attention(
                *a, causal=causal), q, k, v, w)
            limits = [1.5 * np.abs(p - t_).max()
                      for p, t_ in zip(plain, truth)]
        for g, t_, limit in zip(got, truth, limits):
            assert np.abs(g - t_).max() <= limit

    @pytest.mark.parametrize("causal", [True, False],
                             ids=["causal", "full"])
    def test_masked_tiles_are_not_read(self, causal):
        """The backward skips what the forward skips.  Keys past the
        first block may hold NaN and the first query block's dq is that
        of its own 64 keys; queries (and their cotangents) before the
        last block may hold NaN and the last key block's dk and dv are
        what they were.  Without the mask every tile is read and the NaN
        shows."""
        q, k, v = rand(1, 256, 2, 64, seed=3)
        w = rand(1, 256, 2, 64, seed=4)[0]
        attend = lambda *a: flash_attention(
            *a, causal=causal, block_q=64, block_k=64, interpret=True)
        first = grads(attend, q[:, :64], k[:, :64], v[:, :64], w[:, :64])
        clean = grads(attend, q, k, v, w)
        dq = grads(attend, q, k.at[:, 64:].set(jnp.nan),
                   v.at[:, 64:].set(jnp.nan), w)[0][:, :64]
        _, dk, dv = grads(attend, q.at[:, :192].set(jnp.nan), k, v,
                          w.at[:, :192].set(jnp.nan))
        if causal:
            np.testing.assert_allclose(dq, first[0], rtol=0, atol=2e-6)
            np.testing.assert_array_equal(dk[:, 192:], clean[1][:, 192:])
            np.testing.assert_array_equal(dv[:, 192:], clean[2][:, 192:])
        else:
            assert np.isnan(dq).all()
            assert np.isnan(dk[:, 192:]).all() and np.isnan(dv[:, 192:]).all()

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bfloat16"])
    def test_the_kernels_own_tiles(self, dtype):
        """No tiles given: the forward takes 1024 x 1024 at T = 1024 and
        the backward its own 512 x 512, three of four under the mask."""
        q, k, v = rand(1, 1024, 1, 64, seed=7, dtype=dtype)
        w = rand(1, 1024, 1, 64, seed=8)[0]
        got = grads(lambda *a: flash_attention(*a, interpret=True),
                    q, k, v, w)
        truth = plain_float32(q, k, v, w, True)
        limit = 2e-5 if dtype == jnp.float32 else 6e-2
        for g, t_ in zip(got, truth):
            assert np.abs(g - t_).max() <= limit


def nested_jaxprs(eqn):
    """The jaxprs an equation carries (scan, remat, pjit, cond ...)."""
    for value in eqn.params.values():
        for sub in value if isinstance(value, (tuple, list)) else [value]:
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield sub


def arrays_outside_kernels(jaxpr):
    """(primitive, aval) of every value a jaxpr computes outside its
    ``pallas_call``s, the jaxprs nested in its equations included."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield eqn.primitive.name, var.aval
        if eqn.primitive.name != "pallas_call":
            for sub in nested_jaxprs(eqn):
                yield from arrays_outside_kernels(sub)


def kernels(jaxpr):
    """``(name, output avals)`` of every ``pallas_call`` in a jaxpr."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params["name"],
                          [v.aval for v in eqn.outvars]))
        else:
            for sub in nested_jaxprs(eqn):
                found += kernels(sub)
    return found


def two_sequence_axes(jaxpr, t):
    return [(name, aval) for name, aval in arrays_outside_kernels(jaxpr)
            if list(getattr(aval, "shape", ())).count(t) >= 2]


class TestWhatTheProgramsHold:
    T = 256     # no other axis of these programs has this length

    def test_plain_path_is_seen_by_the_check(self):
        q, k, v = rand(1, self.T, 2, 64)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda *a: dot_product_attention(*a, causal=True).sum()))(q, k, v)
        assert two_sequence_axes(jaxpr.jaxpr, self.T)

    @pytest.mark.parametrize("causal", [True, False],
                             ids=["causal", "full"])
    def test_differentiated_program_has_no_score_matrix(self, causal):
        """Outside the two kernels no array has two axes of length T: no
        scores, weights or cotangents of them in HBM."""
        q, k, v = rand(1, self.T, 2, 64, dtype=jnp.bfloat16)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda *a: flash_attention(*a, causal=causal, interpret=True)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2)))(q, k, v)
        assert not two_sequence_axes(jaxpr.jaxpr, self.T)
        names = [name for name, _ in kernels(jaxpr.jaxpr)]
        assert names == ["flash_attention", "attention_bwd"]

    def test_primal_writes_no_lse(self):
        """The undifferentiated call (generation's prefill, ``predict``)
        is the forward kernel with its one output; differentiated, it
        also writes the log-sum-exp, narrow: 4 bytes a position."""
        q, k, v = rand(2, self.T, 4, 64, dtype=jnp.bfloat16)
        attend = lambda *a: flash_attention(*a, interpret=True)
        (name, outs), = kernels(jax.make_jaxpr(attend)(q, k, v).jaxpr)
        assert name == "flash_attention"
        assert [(o.shape, o.dtype) for o in outs] == [
            ((8, self.T, 64), jnp.bfloat16)]
        (name, outs), _ = kernels(jax.make_jaxpr(jax.grad(
            lambda *a: attend(*a).astype(jnp.float32).sum()))(q, k, v).jaxpr)
        assert name == "flash_attention"
        assert [(o.shape, o.dtype) for o in outs] == [
            ((8, self.T, 64), jnp.bfloat16), ((8, 1, self.T), jnp.float32)]

    def test_lm_step_under_remat(self):
        """A ``TransformerLM(scan_layers=True)`` step (each layer under
        ``jax.checkpoint``), flash against plain: the loss, every leaf's
        gradient norm, and no ``(T, T)`` array outside the kernels,
        which the plain path's step has."""
        from bigdl_tpu.nn.attention import TransformerLM
        from bigdl_tpu.utils.random_generator import RNG

        t = self.T
        x = jnp.asarray(np.random.default_rng(0).integers(0, 96, (2, t)),
                        jnp.int32)

        def step(mode):
            RNG.set_seed(0)
            model = TransformerLM(96, 32, 2, 2, max_len=t, scan_layers=True)
            for block in model.blocks:
                block.attn.use_flash = mode
            model.build(jax.ShapeDtypeStruct((2, t), jnp.int32))

            def loss(p):
                logits, _ = model.apply(p, (), x, training=True,
                                        rng=jax.random.key(0))
                return jnp.mean(jnp.square(logits))

            params = model.parameters()[0]
            fn = jax.value_and_grad(loss)
            return fn(params), jax.make_jaxpr(fn)(params).jaxpr

        (loss_plain, grads_plain), jaxpr_plain = step("never")
        (loss_flash, grads_flash), jaxpr_flash = step("interpret")
        np.testing.assert_allclose(loss_flash, loss_plain, rtol=1e-5)
        for got, want in zip(jax.tree.leaves(grads_flash),
                             jax.tree.leaves(grads_plain)):
            np.testing.assert_allclose(jnp.linalg.norm(got),
                                       jnp.linalg.norm(want), rtol=1e-4)
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-6)
        assert two_sequence_axes(jaxpr_plain, t)
        assert not two_sequence_axes(jaxpr_flash, t)
        assert "attention_bwd" in [n for n, _ in kernels(jaxpr_flash)]

    def test_grouped_query_attention_gradients(self):
        """``GroupedQueryAttention`` runs the kernel inside ``lax.map``
        (``kv_heads_per_call``) over K and V broadcast to a group's query
        heads: the gradients of its weights and input, dk and dv summed
        over the group by XLA, against the plain path's."""
        from bigdl_tpu.nn import GroupedQueryAttention

        x = jnp.asarray(np.random.default_rng(1).standard_normal((2, 64, 64)),
                        jnp.float32)

        def run(mode):
            layer = GroupedQueryAttention(64, 8, 2, kv_heads_per_call=2,
                                          use_flash=mode)
            params, _ = layer.setup(jax.random.key(0), None)

            def loss(p, x):
                y, _ = layer.apply(p, (), x, training=True)
                return jnp.sum(jnp.square(y))

            return jax.value_and_grad(loss, argnums=(0, 1))(params, x)

        loss_plain, grads_plain = run("never")
        loss_flash, grads_flash = run("interpret")
        np.testing.assert_allclose(loss_flash, loss_plain, rtol=1e-5)
        for got, want in zip(jax.tree.leaves(grads_flash),
                             jax.tree.leaves(grads_plain)):
            scale = float(jnp.abs(want).max())
            np.testing.assert_allclose(got, want, rtol=0, atol=1e-4 * scale)


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
