"""Multi-head latent attention (MLA, as published for DeepSeek-V2) without
a query latent: keys and values of every head are read off ONE low-rank
latent a token, and that latent, not the heads' keys and values, is what
generation caches.

For a token ``x`` at position ``p``::

    q          = RMSNorm_head(W_q x)          (H, nope + rope); RoPE on the last ``rope``
    [c, k_r]   = W_kva x                      (rank), (rope)
    c, k_r     = RMSNorm(c), RoPE_p(RMSNorm(k_r))
    [k_n, v]_h = W_kvb c                      (H, nope + v_dim)
    k_h        = [k_n_h, k_r]                 k_r shared by all heads
    o_h        = softmax(q_h . k_h / sqrt(nope + rope)) v      causal
    out        = W_o (sigmoid(W_gate x)_h * o_h)

The layer is told what it is: ``gate`` (the head-wise output gate),
``qk_norm`` (the norms on ``q`` and ``k_r``; the one on ``c`` is MLA's own
and always there) and ``rope_interleave`` (the rotary part turns the pairs
``(x_2i, x_2i+1)`` by angle ``i``, DeepSeek-V3's convention, where
rotate-half turns ``(x_i, x_i+rope/2)``).  The defaults are Ling's; with
all three off and the last on it is DeepSeek-V3's layer.  The interleaved
rotation is done as the published code does it: the pairs are pulled
apart (evens, then odds) and turned as halves, so the rotated columns come
out in that order, in ``q`` and in the cached ``k_r`` alike, and every
score is what turning the pairs in place gives (tests/test_kanana.py).

The cache row of a token is ``[c, k_r]`` (``rank + rope`` values, a
``"block"`` leaf of ``nn/generation_state.py``).  Two paths that must
agree: EXPANDED (the full forward and a prefill chunk: ``W_kvb`` applied
to every context token of the row, then plain attention over heads) and
ABSORBED (decode: ``W_kvb`` folded into the query and the output,
attention over the latent rows themselves, no per-head key or value ever
formed; on a TPU the Pallas kernel ``latent_paged_decode_attention``,
which fetches a slot's blocks through its table up to its length, elsewhere
an XLA gather of the whole table).

The block leaf is ``(NB, bs, rank + rope)`` for a layer by itself, or the
layer-stacked ``(L, NB, bs, rank + rope)`` of a model that scans its
layers, with ``layer`` an int32 scalar traced inside the loop: rows are
written ``.at[layer, block, offset]`` and read at ``(layer, block)``, and
``leaf[layer]`` is never formed (``MultiHeadAttention._apply_paged``).
"""

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.generation_state import BLOCK, StateSpec
from bigdl_tpu.nn.initialization import Xavier
from bigdl_tpu.nn.module import Module, child_rng

#: context tokens a prefill chunk expands at a time
CONTEXT_BLOCK = 1024


def rotary_at(x, positions, theta: float, interleave: bool = False):
    """Rotary positions on ``x (N, T, H, Dh)`` at ``positions (N, T)``,
    rotate-half convention; float32 inside, ``x``'s dtype out.
    ``interleave``: the pairs are ``(x_2i, x_2i+1)``; they are pulled
    apart first (evens, then odds) and the result stays in that order."""
    dh = x.shape[-1]
    if interleave:
        # by a 0/1 matrix on the MXU (exact: one term a column): strided
        # slices of the 64 lanes lower to a gather on the chip
        order = jnp.concatenate([jnp.arange(0, dh, 2), jnp.arange(1, dh, 2)])
        pick = (jnp.arange(dh)[:, None] == order[None, :]).astype(x.dtype)
        x = jnp.einsum("...d,de->...e", x, pick, precision=(
            "highest" if x.dtype == jnp.float32 else None))
    inv_freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _rms(x, weight, eps):
    sq = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return x * jax.lax.rsqrt(sq + eps).astype(x.dtype) * weight.astype(x.dtype)


def _widened(x, width):
    """``x`` with noughts behind its last axis up to ``width``."""
    pad = width - x.shape[-1]
    return x if pad == 0 else jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


class LatentAttention(Module):
    """``(N, T, D) -> (N, T, D)``, causal."""

    def __init__(self, hidden_size: int, num_heads: int, kv_rank: int = 512,
                 nope_dim: int = 128, rope_dim: int = 64, v_dim: int = 128,
                 rope_theta: float = 10000.0, norm_eps: float = 1e-6,
                 gate: bool = True, qk_norm: bool = True,
                 rope_interleave: bool = False, row_align: int = 1,
                 use_kernel: str = "auto", name=None):
        super().__init__(name)
        assert use_kernel in ("auto", "never", "interpret")
        #: the cache row, ``rank + rope`` values, is stored in the next
        #: multiple of this many columns, the rest nought
        self.row_align = row_align
        self.gate, self.qk_norm = gate, qk_norm
        self.rope_interleave = rope_interleave
        self.use_kernel = use_kernel
        self.hidden_size = hidden_size
        self.num_heads = num_heads
        self.kv_rank, self.nope_dim = kv_rank, nope_dim
        self.rope_dim, self.v_dim = rope_dim, v_dim
        self.rope_theta = float(rope_theta)
        self.norm_eps = norm_eps
        self.scale = (nope_dim + rope_dim) ** -0.5

    def setup(self, rng, input_spec):
        d, h = self.hidden_size, self.num_heads
        r, qd = self.kv_rank, self.nope_dim + self.rope_dim
        kvd = self.nope_dim + self.v_dim
        init = Xavier()
        params = {
            "q_weight": init.init(child_rng(rng, 0), (h * qd, d), d, h * qd),
            "q_norm": jnp.ones((qd,), jnp.float32),
            "kva_weight": init.init(child_rng(rng, 1),
                                    (r + self.rope_dim, d), d, r),
            "kv_norm": jnp.ones((r,), jnp.float32),
            "kr_norm": jnp.ones((self.rope_dim,), jnp.float32),
            "kvb_weight": init.init(child_rng(rng, 2), (h * kvd, r), r,
                                    h * kvd),
            "gate_weight": init.init(child_rng(rng, 3), (h, d), d, h),
            "out_weight": init.init(child_rng(rng, 4), (d, h * self.v_dim),
                                    h * self.v_dim, d),
        }
        absent = (() if self.qk_norm else ("q_norm", "kr_norm")) \
            + (() if self.gate else ("gate_weight",))
        return {k: v for k, v in params.items() if k not in absent}, ()

    # ----- generation state ------------------------------------------------- #
    def state_spec(self, dtype):
        width = self.kv_rank + self.rope_dim
        width += -width % self.row_align
        return {"latent": StateSpec(BLOCK, (width,), dtype)}

    # ----- the layer's parts ------------------------------------------------ #
    def _query_and_latent(self, params, x, positions):
        """``q (N, T, H, nope + rope)`` with its rotary part turned, and the
        cache rows ``[c, RoPE(k_r)] (N, T, rank + rope)``."""
        n, t, _ = x.shape
        dt = x.dtype
        h, rope = self.num_heads, self.rope_dim
        turn = lambda a: rotary_at(a, positions, self.rope_theta,
                                   self.rope_interleave)
        q = (x @ params["q_weight"].astype(dt).T).reshape(n, t, h, -1)
        if self.qk_norm:
            q = _rms(q, params["q_norm"], self.norm_eps)
        q = jnp.concatenate([q[..., :-rope], turn(q[..., -rope:])], -1)
        kva = x @ params["kva_weight"].astype(dt).T
        c = _rms(kva[..., :self.kv_rank], params["kv_norm"], self.norm_eps)
        k_r = kva[..., self.kv_rank:]
        if self.qk_norm:
            k_r = _rms(k_r, params["kr_norm"], self.norm_eps)
        return q, jnp.concatenate([c, turn(k_r[:, :, None, :])[:, :, 0]], -1)

    def _expand(self, params, rows):
        """Cache rows ``(..., rank + rope)`` -> ``(k (..., H, nope + rope),
        v (..., H, v_dim))``."""
        h = self.num_heads
        c = rows[..., :self.kv_rank]
        k_r = rows[..., self.kv_rank:self.kv_rank + self.rope_dim]
        kv = (c @ params["kvb_weight"].astype(c.dtype).T).reshape(
            c.shape[:-1] + (h, self.nope_dim + self.v_dim))
        k_r = jnp.broadcast_to(k_r[..., None, :],
                               c.shape[:-1] + (h, self.rope_dim))
        return (jnp.concatenate([kv[..., :self.nope_dim], k_r], -1),
                kv[..., self.nope_dim:])

    def _output(self, params, o, x):
        """``o (N, T, H, v_dim)`` -> ``(N, T, D)``, gated head by head
        where the layer has a gate."""
        n, t = o.shape[:2]
        dt = x.dtype
        y = o.astype(dt)
        if self.gate:
            gate = jax.nn.sigmoid(x @ params["gate_weight"].astype(dt).T)
            y = y * gate[..., None]
        return y.reshape(n, t, -1) @ params["out_weight"].astype(dt).T

    # ----- forward (expanded) ------------------------------------------------ #
    def apply(self, params, state, input, *, training=False, rng=None):
        from bigdl_tpu.nn.attention import dot_product_attention

        n, t, _ = input.shape
        positions = jnp.broadcast_to(jnp.arange(t)[None], (n, t))
        q, rows = self._query_and_latent(params, input, positions)
        k, v = self._expand(params, rows)
        o = dot_product_attention(q, k, v, causal=True, scale=self.scale)
        return self._output(params, o, input), state

    # ----- generation ------------------------------------------------------- #
    def _kernel_ok(self, leaf):
        """Whether decode goes through ``latent_paged_decode_attention``:
        in ``auto`` on a TPU, when a block of the leaf is whole tiles of
        its dtype: 8 rows of float32 or 16 of bfloat16, by 128 columns
        (the chip stores a 576-wide row in 640 columns whatever the leaf
        says, and a DMA takes whole tiles only: ``row_align`` 128 is a
        leaf that says so, at no byte more)."""
        if self.use_kernel != "auto":
            return self.use_kernel == "interpret"
        from bigdl_tpu.nn.attention import _on_tpu

        bs, width = leaf.shape[-2:]
        return _on_tpu() and width % 128 == 0 \
            and bs % (32 // leaf.dtype.itemsize) == 0

    def apply_paged(self, params, input, pool, tables, pos, lengths=None,
                    layer=None):
        """A chunk (``lengths`` given; expanded) or one token a row
        (absorbed) against the block leaf ``pool["latent"]``, a layer's own
        or, with ``layer``, the stacked one; see
        ``MultiHeadAttention._apply_paged`` for the table contract.
        Returns ``(out, new pool)``."""
        n, t, _ = input.shape
        leaf = pool["latent"]
        at = () if layer is None else (layer,)
        bs, max_blocks = leaf.shape[-2], tables.shape[1]
        trash = leaf.shape[-3] - 1
        gpos = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        q, rows = self._query_and_latent(params, input, gpos)
        rows = _widened(rows, leaf.shape[-1])
        logical = jnp.clip(gpos // bs, 0, max_blocks - 1)
        phys = jnp.take_along_axis(tables, logical, axis=1)
        if lengths is not None:
            valid = jnp.arange(t, dtype=jnp.int32)[None, :] < lengths[:, None]
            phys = jnp.where(valid, phys, trash)
        leaf = leaf.at[at + (phys.reshape(-1), (gpos % bs).reshape(-1))].set(
            rows.reshape(n * t, -1).astype(leaf.dtype))
        if lengths is not None:
            o = self._chunk_attention(params, q, leaf, tables, gpos, lengths,
                                      layer)
        else:
            o = self._absorbed_attention(params, q[:, 0], leaf, tables, pos,
                                         layer)[:, None]
        return self._output(params, o, input), {"latent": leaf}

    @staticmethod
    def _blocks(leaf, ids, layer):
        """``leaf``'s blocks ``ids``, of ``layer`` on a stacked leaf."""
        if layer is None:
            return jnp.take(leaf, ids, axis=0, mode="clip")
        return leaf.at[layer, ids].get(mode="clip")

    def _absorbed_attention(self, params, q, leaf, tables, pos, layer=None):
        """``q (N, H, nope + rope)`` at positions ``pos`` over each row's
        mapped latent rows; ``(N, H, v_dim)``.  ``W_kvb`` is folded into
        the query before and into the output after; between them the
        kernel, or the gather of every row's whole table through XLA (the
        CPU's and the tests' path, which the kernel is held to)."""
        n, h = q.shape[:2]
        dt = q.dtype
        r, nope = self.kv_rank, self.nope_dim
        w = params["kvb_weight"].astype(dt).reshape(h, nope + self.v_dim, r)
        kernel = self._kernel_ok(leaf)
        if not kernel:
            # gathered before the query is folded: the order in which
            # Ling's decode program has always lowered (tests/test_ling.py)
            ctx = self._blocks(leaf, tables, layer).reshape(
                n, -1, leaf.shape[-1]).astype(dt)
        q_abs = _widened(jnp.concatenate(
            [jnp.einsum("nhd,hdr->nhr", q[..., :nope], w[:, :nope]),
             q[..., nope:]], -1), leaf.shape[-1])
        if kernel:
            from bigdl_tpu.ops.flash_attention import \
                latent_paged_decode_attention

            o_lat = latent_paged_decode_attention(
                q_abs, leaf, tables, pos, layer, rank=r, scale=self.scale,
                interpret=self.use_kernel == "interpret").astype(dt)
        else:
            scores = jnp.einsum("nhr,ncr->nhc", q_abs, ctx).astype(jnp.float32)
            seen = jnp.arange(ctx.shape[1])[None, None, :] \
                <= pos[:, None, None]
            p = jax.nn.softmax(
                jnp.where(seen, scores * self.scale, -jnp.inf), axis=-1)
            o_lat = jnp.einsum("nhc,ncr->nhr", p.astype(dt), ctx[..., :r])
        return jnp.einsum("nhr,hdr->nhd", o_lat, w[:, nope:])

    def _chunk_attention(self, params, q, leaf, tables, gpos, lengths,
                         layer=None):
        """``q (N, T, H, nope + rope)`` at positions ``gpos (N, T)`` over
        each row's mapped context, expanded ``CONTEXT_BLOCK`` tokens at a
        time with a running softmax.  The loop over context blocks ENDS at
        the block of the row's last query (``gpos`` at ``lengths - 1``; a
        loop whose bound is read on the device), so a chunk's work follows
        its context and not the table's width; a row of padding
        (``lengths`` 0) runs no block and comes back nought.  One row after
        the other (``lax.map``): a row's scores are ``(H, T,
        CONTEXT_BLOCK)`` float32.  ``(N, T, H, v_dim)``."""
        n, t, h, _ = q.shape
        dt = q.dtype
        bs, max_blocks = leaf.shape[-2], tables.shape[1]
        per = max(1, min(CONTEXT_BLOCK // bs, max_blocks))
        steps = -(-max_blocks // per)
        tables = jnp.pad(tables, ((0, 0), (0, steps * per - max_blocks)),
                         constant_values=leaf.shape[-3] - 1)
        last = jnp.take_along_axis(
            gpos, jnp.clip(lengths - 1, 0, t - 1)[:, None], axis=1)[:, 0]
        ends = jnp.where(lengths > 0, last // (per * bs) + 1, 0)
        f32 = jnp.float32

        def one_row(args):
            q_row, table, at, end = args           # (T, H, d), (steps*per,), (T,)
            q_row = q_row * jnp.asarray(self.scale, dt)

            def block(j, carry):
                m, l, acc = carry
                ids = jax.lax.dynamic_slice_in_dim(table, j * per, per)
                rows = self._blocks(leaf, ids, layer) \
                    .reshape(per * bs, -1).astype(dt)
                k, v = self._expand(params, rows)
                s = jnp.einsum("qhd,khd->hqk", q_row, k).astype(f32)
                kpos = j * per * bs + jnp.arange(per * bs)
                s = jnp.where(kpos[None, None, :] <= at[None, :, None],
                              s, -jnp.inf)
                m_new = jnp.maximum(m, s.max(-1))
                # a block whose keys all lie ahead of a query leaves that
                # query's maximum at -inf: exp(-inf - -inf) is NaN
                safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
                p = jnp.exp(s - safe[..., None])
                fix = jnp.exp(jnp.where(jnp.isfinite(m), m - safe, -jnp.inf))
                l = l * fix + p.sum(-1)
                acc = acc * fix[..., None] + jnp.einsum(
                    "hqk,khd->hqd", p.astype(dt), v).astype(f32)
                return m_new, l, acc

            init = (jnp.full((h, t), -jnp.inf, f32), jnp.zeros((h, t), f32),
                    jnp.zeros((h, t, self.v_dim), f32))
            _, l, acc = jax.lax.fori_loop(0, jnp.minimum(end, steps), block,
                                          init)
            return jnp.moveaxis(acc / jnp.maximum(l, 1e-30)[..., None],
                                0, 1)              # (T, H, v)

        return jax.lax.map(one_row, (q, tables, gpos, ends))
