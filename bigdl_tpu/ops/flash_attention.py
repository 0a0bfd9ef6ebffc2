"""Fused flash-attention forward kernel in Pallas (Mosaic/TPU).

The TPU-native analogue of the reference's hand-tuned native kernels
(bigdl-core MKL-DNN primitives, SURVEY.md section 2.8): where XLA's fusion
isn't enough, drop to Pallas.  Attention is the one op where manual tiling
pays -- the (T, T) score matrix never materialises in HBM; each (block_q,
block_k) tile lives in VMEM with a flash-style online softmax.

Layout: q/k/v (BH, T, D) fp32/bf16; softmax state fp32.  Causal masking by
global position.  Grid: (BH, T/block_q); the k-loop is a lax.fori_loop
inside the kernel.  ``interpret=True`` runs on CPU for tests.

All three kernels keep one head's whole K and V (or pool plane) resident
in VMEM, so what the TPU compiler accepts is bounded by bytes, not only
by tile alignment: ``kv_blocks_fit`` is that bound, and the ``auto``
gates in nn/attention.py ask it before selecting a kernel.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: VMEM the resident K/V blocks may take.  The compiler's default scoped
#: limit on a v5e is 16 MiB (compiling T=8192 fp32 there is refused with
#: "16.25M and limit 16.00M"); the rest is left to the q/o blocks and the
#: (block_q, block_k) score tiles.
_VMEM_KV_BUDGET = 12 * 2 ** 20


def _vmem_block_bytes(rows: int, cols: int, dtype) -> int:
    """Bytes one (rows, cols) block occupies in VMEM: lanes pad to 128,
    sublanes to the dtype's tile (8 fp32, 16 bf16, 32 int8)."""
    item = jnp.dtype(dtype).itemsize
    sub = 8 * max(1, 4 // item)
    return -(-rows // sub) * sub * -(-cols // 128) * 128 * item


def kv_blocks_fit(rows: int, head_dim: int, dtype,
                  quantized: bool = False) -> bool:
    """Whether a kernel of this file compiles with ``rows`` K/V positions
    per head resident: the sequence for ``flash_attention``, the cache
    length for ``flash_decode_attention``, the whole pool plane
    (``num_blocks * block_size``) for ``flash_paged_decode_attention``.
    K and V are each double-buffered by the pipeline; an int8 pool adds
    two fp32 scale columns, which pad to full 128-lane rows."""
    need = 4 * _vmem_block_bytes(rows, head_dim, dtype)
    if quantized:
        need += 4 * _vmem_block_bytes(rows, 1, jnp.float32)
    return need <= _VMEM_KV_BUDGET


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, causal: bool,
                 scale: float):
    block_q, d = q_ref.shape
    t = k_ref.shape[0]
    iq = pl.program_id(1)

    q = q_ref[:].astype(jnp.float32) * scale
    nk = t // block_k

    def body(j, carry):
        acc, m, l = carry
        kblk = k_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        vblk = v_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = q @ kblk.T  # (block_q, block_k)
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = kpos <= qpos
            s = jnp.where(mask, s, -jnp.inf)
        bm = jnp.max(s, axis=1)
        new_m = jnp.maximum(m, bm)
        safe_m = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
        p = jnp.exp(s - safe_m[:, None])
        if causal:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l = l * corr + jnp.sum(p, axis=1)
        acc = acc * corr[:, None] + p @ vblk
        return acc, new_m, l

    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, nk, body, (acc0, m0, l0))
    o_ref[:] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def _flash_forward(q, k, v, causal, block_q, block_k, interpret):
    b, t, h, d = q.shape
    scale = 1.0 / math.sqrt(d)

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)

    qb, kb, vb = to_bh(q), to_bh(k), to_bh(v)

    out = pl.pallas_call(
        functools.partial(_attn_kernel, block_k=block_k, causal=causal,
                          scale=scale),
        grid=(b * h, t // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((None, t, d), lambda bh, i: (bh, 0, 0)),
            pl.BlockSpec((None, t, d), lambda bh, i: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda bh, i: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(qb, kb, vb)
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_k, interpret):
    return _flash_forward(q, k, v, causal, block_q, block_k, interpret)


def _flash_fwd_rule(q, k, v, causal, block_q, block_k, interpret):
    return _flash_forward(q, k, v, causal, block_q, block_k,
                          interpret), (q, k, v)


def _flash_bwd_rule(causal, block_q, block_k, interpret, res, g):
    # There is no backward kernel: the cotangents come from re-running
    # plain attention on the saved q/k/v, so only one layer's (T, T)
    # scores exist at a time and the forward saves no score matrix.
    from bigdl_tpu.nn.attention import dot_product_attention

    _, vjp = jax.vjp(
        functools.partial(dot_product_attention, causal=causal), *res)
    return vjp(g)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q, k, v: (B, T, H, D) -> (B, T, H, D).

    T must be a multiple of the block sizes (pad upstream; the reference
    pipeline pads too -- dataset/MiniBatch.scala:523 PaddingParam).
    Differentiable: the forward is the kernel, the backward recomputes
    through ``nn.attention.dot_product_attention``.
    """
    t = q.shape[1]
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    assert t % block_q == 0 and t % block_k == 0, (t, block_q, block_k)
    return _flash(q, k, v, causal, block_q, block_k, interpret)


def _online_softmax_step(q, kblk, vblk, kpos, p, carry):
    """One K/V block of the q_len=1 online softmax shared by the two
    decode kernels: ``q (1, d)``, ``kblk/vblk (n, d)`` fp32, ``kpos (1,
    n)`` the block's logical positions, ``p`` the row's frontier."""
    acc, m, l = carry
    s = jax.lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (1, n)
    mask = kpos <= p
    s = jnp.where(mask, s, -jnp.inf)
    new_m = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    safe_m = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
    pr = jnp.where(mask, jnp.exp(s - safe_m), 0.0)
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
    l = l * corr + jnp.sum(pr, axis=1, keepdims=True)
    acc = acc * corr + jnp.dot(pr, vblk,
                               preferred_element_type=jnp.float32)
    return acc, new_m, l


def _online_softmax_init(d):
    # (1, 1) carries, not (1,): Mosaic keeps vectors 2-D
    return (jnp.zeros((1, d), jnp.float32),
            jnp.full((1, 1), -jnp.inf, jnp.float32),
            jnp.zeros((1, 1), jnp.float32))


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, *, block_k: int,
                   scale: float):
    """q_len=1 decode step: one query row against a K/V cache, masked
    at the per-row frontier ``kpos <= pos``.  The k-loop's trip count is
    DYNAMIC -- ``ceil((pos + 1) / block_k)`` -- so a short sequence in a
    long cache reads only the blocks its mask can see: the O(1)-per-
    token work the cache exists to buy, not O(max_len).  ``pos_ref`` is
    the whole ``(B,)`` frontier vector, scalar-prefetched into SMEM."""
    d = q_ref.shape[-1]
    p = pos_ref[pl.program_id(0)]
    q = q_ref[:].astype(jnp.float32) * scale          # (1, d)
    nk = (p + block_k) // block_k                     # blocks with kpos <= p

    def body(j, carry):
        start = pl.multiple_of(j * block_k, block_k)
        kblk = k_ref[pl.ds(start, block_k), :].astype(jnp.float32)
        vblk = v_ref[pl.ds(start, block_k), :].astype(jnp.float32)
        kpos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        return _online_softmax_step(q, kblk, vblk, kpos, p, carry)

    acc, m, l = jax.lax.fori_loop(0, nk, body, _online_softmax_init(d))
    o_ref[:] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def flash_decode_attention(q, k, v, pos, block_k: int = 128,
                           interpret: bool = False):
    """Single-token decode attention: ``q (B, 1, H, D)`` against a K/V
    cache ``k, v (B, T, H, D)`` with per-row frontier positions ``pos
    (B,)`` (row ``i`` attends ``kpos <= pos[i]``) -> ``(B, 1, H, D)``.

    The decode-shaped sibling of :func:`flash_attention`: same online
    softmax, but the grid is one program per (batch, head) and the
    query block is a single row, so the kernel streams cache blocks
    through VMEM without ever materialising a score matrix.  T must be
    a multiple of ``block_k`` (the cache allocator picks aligned
    ``max_len``).  ``interpret=True`` runs on CPU for tests.
    """
    b, t1, h, d = q.shape
    tk = k.shape[1]
    assert t1 == 1, f"decode takes one query token per row, got {t1}"
    block_k = min(block_k, tk)
    assert tk % block_k == 0, (tk, block_k)
    scale = 1.0 / math.sqrt(d)

    def heads_first(x):
        return x.transpose(0, 2, 1, 3)                # (B, H, T, D)

    def row(t):
        return pl.BlockSpec((None, None, t, d),
                            lambda i, j, pos: (i, j, 0, 0))

    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_k=block_k, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h),
            in_specs=[row(1), row(tk), row(tk)],
            out_specs=row(1)),
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
        interpret=interpret,
        name="flash_decode_attention",
    )(jnp.asarray(pos, jnp.int32), heads_first(q), heads_first(k),
      heads_first(v))
    return out.transpose(0, 2, 1, 3)


def _paged_decode_kernel(pos_ref, table_ref, q_ref, k_ref, v_ref, *rest,
                         block_size: int, scale: float, quantized: bool):
    """Paged decode step: like ``_decode_kernel`` but the K/V blocks
    are INDIRECT -- loop iteration ``j`` covers logical positions
    ``[j*bs, (j+1)*bs)``, whose K/V physically live at pool block
    ``table[j]``; the ``pl.ds`` slice start is the table entry, read
    from SMEM (``pos_ref (B,)`` and ``table_ref (B, max_blocks)`` are
    scalar-prefetched whole).  The trip count is still the dynamic
    frontier count ``ceil((pos + 1) / bs)``, so a short sequence in a
    big pool reads only the blocks it has actually mapped.

    ``quantized=True`` adds two scale refs (per-position-per-head fp32
    absmax scales, one per K/V ``head_dim`` vector): each int8 block
    dequantizes IN-KERNEL -- payload * scale right after the VMEM load,
    so the fp32 K/V context the XLA fallback would materialise in HBM
    never exists and the pool traffic stays at int8 width."""
    if quantized:
        ks_ref, vs_ref, o_ref = rest
    else:
        (o_ref,) = rest
    d = q_ref.shape[-1]
    bs = block_size
    i = pl.program_id(0)
    p = pos_ref[i]
    q = q_ref[:].astype(jnp.float32) * scale          # (1, d)
    nk = (p + bs) // bs                               # mapped, visible blocks

    def body(j, carry):
        start = pl.multiple_of(table_ref[i, j] * bs, bs)  # physical block
        kblk = k_ref[pl.ds(start, bs), :].astype(jnp.float32)
        vblk = v_ref[pl.ds(start, bs), :].astype(jnp.float32)
        if quantized:
            # (bs, 1) scale columns broadcast over head_dim
            kblk = kblk * ks_ref[pl.ds(start, bs), :]
            vblk = vblk * vs_ref[pl.ds(start, bs), :]
        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        return _online_softmax_step(q, kblk, vblk, kpos, p, carry)

    acc, m, l = jax.lax.fori_loop(0, nk, body, _online_softmax_init(d))
    o_ref[:] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_paged_decode_attention(q, k_pool, v_pool, tables, pos,
                                 k_scale=None, v_scale=None,
                                 interpret: bool = False):
    """Single-token decode attention through a PAGED K/V pool:
    ``q (B, 1, H, D)`` against pools ``k_pool, v_pool (NB, bs, H, D)``
    addressed by per-row block tables ``tables (B, max_blocks)`` with
    frontier positions ``pos (B,)`` -> ``(B, 1, H, D)``.

    The paged sibling of :func:`flash_decode_attention`: the same
    one-program-per-(batch, head) online softmax, but K/V blocks are
    fetched by table lookup instead of contiguous stride, so the
    gather that the XLA fallback materialises (``(B, max_blocks*bs,
    H, D)`` per layer per step) never exists -- each program streams
    exactly the ``ceil((pos+1)/bs)`` blocks its row has mapped.

    ``k_scale``/``v_scale`` (both or neither, ``(NB, bs, H, 1)`` fp32)
    select the INT8 pool layout: payloads are int8 and each block
    dequantizes in-kernel against its per-position-per-head scale
    column, so HBM<->VMEM traffic stays at the narrow width end to end.
    ``interpret=True`` runs on CPU for tests.  On a TPU each head's
    whole pool plane ``(NB*bs, D)`` is one VMEM block, so only a pool
    that ``kv_blocks_fit`` admits compiles, and ``bs`` must tile (auto
    mode gates on both, MultiHeadAttention._flash_paged_ok).
    """
    b, t1, h, d = q.shape
    nb, bs = k_pool.shape[0], k_pool.shape[1]
    assert t1 == 1, f"decode takes one query token per row, got {t1}"
    quantized = k_scale is not None
    assert (v_scale is not None) == quantized, \
        "pass both k_scale and v_scale or neither"
    scale = 1.0 / math.sqrt(d)

    # per-head pool planes (H, NB*bs, D): physical block i occupies rows
    # [i*bs, (i+1)*bs) so the kernel's pl.ds(bid*bs, bs) lands on it
    def plane(x):
        return x.transpose(2, 0, 1, 3).reshape(h, nb * bs, x.shape[-1])

    def plane_spec(cols):
        return pl.BlockSpec((None, nb * bs, cols),
                            lambda i, j, pos, tables: (j, 0, 0))

    row = pl.BlockSpec((None, None, 1, d),
                       lambda i, j, pos, tables: (i, j, 0, 0))
    in_specs = [row, plane_spec(d), plane_spec(d)]
    args = [q.transpose(0, 2, 1, 3), plane(k_pool), plane(v_pool)]
    if quantized:
        # fp32 scale planes (H, NB*bs, 1) ride beside the int8 payload
        in_specs += [plane_spec(1), plane_spec(1)]
        args += [plane(k_scale.astype(jnp.float32)),
                 plane(v_scale.astype(jnp.float32))]

    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, block_size=bs, scale=scale,
                          quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h),
            in_specs=in_specs,
            out_specs=row),
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d),
                                       jnp.float32 if quantized else q.dtype),
        interpret=interpret,
        name="flash_paged_decode_attention",
    )(jnp.asarray(pos, jnp.int32), jnp.asarray(tables, jnp.int32), *args)
    return out.transpose(0, 2, 1, 3)
