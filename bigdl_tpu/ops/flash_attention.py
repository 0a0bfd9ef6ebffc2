"""Fused flash-attention kernels in Pallas (Mosaic/TPU): forward, backward
and the two decode-shaped forwards.

The TPU-native analogue of the reference's hand-tuned native kernels
(bigdl-core MKL-DNN primitives, SURVEY.md section 2.8): where XLA's fusion
isn't enough, drop to Pallas.  Attention is the one op where manual tiling
pays -- the (T, T) score matrix never materialises in HBM, forward or
backward; each (block_q, block_k) tile lives in VMEM.

Layout: q/k/v (BH, T, D) fp32/bf16.  Causal masking by global position.
``interpret=True`` runs on CPU for tests.

``flash_attention`` (the forward kernel): grid (BH, T/block_q, T/block_k)
with the key axis last and sequential.  VMEM holds one query block, one
key and one value block (double-buffered by the pipeline, so the next
block's copy overlaps this one's compute) and the fp32 softmax state of
the query block (running maximum, running sum, accumulator) as scratch --
never a head's whole K/V, so no sequence is too long for it.  Under
``causal`` a key block wholly above the diagonal is neither computed nor
fetched, and only the blocks that straddle the diagonal build a mask.
The MXU takes the operands in the dtype they come in (bf16 in training)
and accumulates in fp32.  Where the call is differentiated it also writes
each query row's log-sum-exp, as ``(BH, 1, T)`` fp32.

``attention_bwd`` (the backward kernel; its name must not hold
``flash_attention``, by which the benchmark finds forward calls): grid
(BH, T/block_k, T/block_q) with the query axis last, one fused kernel for
dq, dk and dv from q, k, v, the output's cotangent, the saved log-sum-exp
and ``delta = rowsum(do * o)``: per tile ``s``, ``p = exp(s - lse)``,
``dv += p^T do``, ``dp = do v^T``, ``ds = p (dp - delta)``, ``dk += ds^T
q``, ``dq += ds k`` -- five products where separate dk/dv and dq kernels
take seven.  dk and dv accumulate over the query axis in fp32 scratch; a
head's whole dq (``T x D`` fp32) stays in VMEM across both axes, the one
part that grows with T and what ``_bwd_vmem_bytes`` asks the compiler for.
The same blocks are skipped and masked as in the forward.

``flash_decode_attention`` (the contiguous cache's decode kernel) keeps one
head's whole K and V resident in VMEM, so what the TPU compiler accepts of
it is bounded by bytes, not only by tile alignment: ``kv_blocks_fit`` is
that bound, and its ``auto`` gate in nn/attention.py asks it before
selecting the kernel.

``flash_paged_decode_attention`` (the paged pool's decode kernel) leaves
the pool in HBM as it is stored, ``(NB, bs, H * D)`` a layer or
``(L, NB, bs, H * D)`` with the layers stacked and the layer an argument,
and fetches a slot's blocks through its block table by its own DMAs, a
step's worth at a time into a double buffer, up to the slot's frontier and
no further: VMEM holds two steps whatever the pool's size, so no gate
bounds it by bytes; its gate asks only that a block be whole tiles of the
pool's dtype.

``latent_paged_decode_attention`` (the latent paged pool's decode kernel)
fetches the same way from a leaf of ONE shared row a token, ``(NB, bs, W)``
or ``(L, NB, bs, W)``: every absorbed head scores against the whole row and
sums its first ``rank`` columns, so a slot's rows are fetched once for all
heads and both products are MXU matmuls in the leaf's dtype; a slot whose
table starts on the trash block is skipped.  ``W`` must be whole tiles of
128 columns (the chip stores a row so whatever its logical width, and a
DMA of part of a tile is refused).
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: VMEM the contiguous decode kernel's resident K/V blocks may take.  The compiler's
#: default scoped limit on a v5e is 16 MiB (``flash_decode_attention`` over
#: a cache of 8192 fp32 positions is refused there with "size 16.00M and
#: limit 16.00M exceeded ... by 1.0K"); the rest is left to the q/o rows
#: and the score tiles.
_VMEM_KV_BUDGET = 12 * 2 ** 20


def _vmem_block_bytes(rows: int, cols: int, dtype) -> int:
    """Bytes one (rows, cols) block occupies in VMEM: lanes pad to 128,
    sublanes to the dtype's tile (8 fp32, 16 bf16, 32 int8)."""
    item = jnp.dtype(dtype).itemsize
    sub = 8 * max(1, 4 // item)
    return -(-rows // sub) * sub * -(-cols // 128) * 128 * item


def kv_blocks_fit(rows: int, head_dim: int, dtype) -> bool:
    """Whether ``flash_decode_attention`` compiles with ``rows`` K/V
    positions per head resident (the cache length).  K and V are each
    double-buffered by the pipeline."""
    return 4 * _vmem_block_bytes(rows, head_dim, dtype) <= _VMEM_KV_BUDGET


#: What a masked score is set to: finite, so that no ``inf - inf`` can
#: arise in the online softmax; ``exp`` of it less any row maximum is 0.
_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)

_LANES = 128
_CONTRACT_LAST = (((1,), (1,)), ((), ()))
_MATMUL = (((1,), (0,)), ((), ()))
_CONTRACT_FIRST = (((0,), (0,)), ((), ()))


def _across(x, n):
    """A lane-replicated ``(rows, 128)`` column statistic as ``(rows, n)``:
    whole vregs repeated where ``n`` is a multiple of 128, a slice where
    it is narrower, and a broadcast of lane 0 otherwise."""
    if n % _LANES == 0:
        return jnp.tile(x, (1, n // _LANES))
    if n < _LANES:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _operand_precision(dtype):
    """Operands go to the MXU in the dtype that came in, fp32 comes out.
    Products of bf16 values are exact in fp32, so for them a higher matmul
    precision from the caller's context has no meaning (and Mosaic refuses
    it); fp32 operands keep the context's."""
    return None if dtype == jnp.float32 else jax.lax.Precision.DEFAULT


def _causal_steps(step, causal, ahead, block_q, block_k):
    """Run ``step(straddles)`` for this (query block, key block) pair as
    the mask asks: a pair wholly above the diagonal does nothing (and its
    index map fetched nothing), one wholly below it builds no mask.  Key 0
    is visible to every query, so a query block's first key step is never
    skipped and leaves a finite maximum in every row."""
    if not causal:
        step(False)
        return
    visible = -ahead <= block_q - 1
    below = block_k - 1 <= ahead
    pl.when(below)(functools.partial(step, False))
    pl.when(jnp.logical_and(visible, jnp.logical_not(below)))(
        functools.partial(step, True))


def _as_row(x):
    """A lane-replicated ``(rows, 128)`` column statistic as one row ``(1,
    rows)``: the layout in which it is stored narrow (4 bytes a position)
    and in which the backward, whose tiles have the queries on the lanes,
    broadcasts it over the keys."""
    return x.T[:1]


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *rest, causal: bool,
                 scale: float):
    """One (query block, key block) step of the online softmax.  The key
    axis is the last, sequential grid axis: ``m_ref``/``l_ref`` (running
    maximum and sum, lane-replicated) and ``acc_ref`` carry the state in
    fp32 from the first key step to the last, which writes ``o_ref`` and,
    where the call is differentiated (``rest`` then starts with that
    output), the rows' log-sum-exp as one row ``(1, block_q)``."""
    *lse_ref, m_ref, l_ref, acc_ref = rest
    block_q, d = q_ref.shape
    block_k = k_ref.shape[0]
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        m_ref[:] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[:] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[:] = jnp.zeros(acc_ref.shape, jnp.float32)

    # first query position less first key position: key c of the block is
    # visible to query r where c - r <= ahead
    ahead = iq * block_q - ik * block_k
    precision = _operand_precision(q_ref.dtype)

    def step(straddles):
        s = jax.lax.dot_general(q_ref[:], k_ref[:], _CONTRACT_LAST,
                                precision=precision,
                                preferred_element_type=jnp.float32) * scale
        if straddles:
            shape = (block_q, block_k)
            c_less_r = (jax.lax.broadcasted_iota(jnp.int32, shape, 1)
                        - jax.lax.broadcasted_iota(jnp.int32, shape, 0))
            s = jnp.where(c_less_r <= ahead, s, _MASKED)
        m_prev = m_ref[:]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _across(m_next, block_k))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:] = m_next
        acc_ref[:] = acc_ref[:] * _across(alpha, d) + jnp.dot(
            p.astype(v_ref.dtype), v_ref[:], precision=precision,
            preferred_element_type=jnp.float32)

    _causal_steps(step, causal, ahead, block_q, block_k)

    @pl.when(ik == pl.num_programs(2) - 1)
    def _():
        o_ref[:] = (acc_ref[:] / _across(l_ref[:], d)).astype(o_ref.dtype)
        if lse_ref:
            lse_ref[0][:] = _as_row(m_ref[:] + jnp.log(l_ref[:]))


def _tile(t: int) -> int:
    """The forward's block size, of queries and of keys, for a sequence
    of ``t``: ``t`` itself below 128 (one block), else the largest of
    1024, 512, 256, 128 that divides it.  Large tiles win on a v5e
    although they skip fewer masked tiles: a grid step and a rescale of
    the state cost more than the masked half of a tile (PERF.md section 6,
    PR 28, has the sweep)."""
    if t < 128:
        return t
    return next(b for b in (1024, 512, 256, 128) if t % b == 0)


def _to_bh(x):
    """``(B, T, H, D)`` -> ``(B H, T, D)``: one head's rows contiguous."""
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _from_bh(x, b):
    bh, t, d = x.shape
    return x.reshape(b, bh // b, t, d).transpose(0, 2, 1, 3)


def _flash_forward(qb, kb, vb, causal, block_q, block_k, interpret,
                   save_lse=False):
    """The forward kernel over ``(BH, T, D)`` operands.  ``save_lse``: also
    return each query row's log-sum-exp ``(BH, 1, T)`` fp32, which is all
    the backward needs of the softmax; the undifferentiated call writes
    none."""
    bh, t, d = qb.shape
    scale = 1.0 / math.sqrt(d)

    def q_map(h, i, j):
        return h, i, 0

    def kv_map(h, i, j):
        if causal:
            # past the last block this query block can see, stay on it:
            # an unchanged block index copies nothing
            j = jnp.minimum(j, (i * block_q + block_q - 1) // block_k)
        return h, j, 0

    out_specs = [pl.BlockSpec((None, block_q, d), q_map)]
    out_shape = [jax.ShapeDtypeStruct((bh, t, d), qb.dtype)]
    if save_lse:
        out_specs.append(pl.BlockSpec((None, 1, block_q),
                                      lambda h, i, j: (h, 0, i)))
        out_shape.append(jax.ShapeDtypeStruct((bh, 1, t), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_attn_kernel, causal=causal, scale=scale),
        grid=(bh, t // block_q, t // block_k),
        in_specs=[
            pl.BlockSpec((None, block_q, d), q_map),
            pl.BlockSpec((None, block_k, d), kv_map),
            pl.BlockSpec((None, block_k, d), kv_map),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(qb, kb, vb)
    return out if save_lse else out[0]


def _attn_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                     causal: bool, scale: float):
    """One (key block, query block) step of the backward.  The tiles are
    transposed, keys on the sublanes and queries on the lanes, so that
    ``lse`` and ``delta`` come in as rows ``(1, block_q)`` and four of the
    five products need no transpose: ``s^T = k q^T``, ``dv += p^T do``,
    ``dp^T = v do^T``, ``dk += ds^T q``; the fifth is ``dq^T += k^T ds^T``,
    so dq is summed as ``(D, block_q)`` tiles.

    The query axis is the last grid axis: ``dk_acc``/``dv_acc`` carry one
    key block's sums over it in fp32.  ``dq_acc`` holds a whole head's dq
    (``T x D`` fp32) across both axes; the last key block turns each tile
    over into ``dq_ref``, the head's ``(T, D)`` result.  ``scale``
    multiplies the sums of dq and dk once, in fp32, and not every tile of
    ``ds``."""
    block_q = q_ref.shape[0]
    block_k = k_ref.shape[0]
    ik, iq = pl.program_id(1), pl.program_id(2)

    @pl.when(iq == 0)
    def _():
        dk_acc[:] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[:] = jnp.zeros(dv_acc.shape, jnp.float32)

    @pl.when(ik == 0)
    def _():
        dq_acc[iq] = jnp.zeros(dq_acc.shape[1:], jnp.float32)

    ahead = iq * block_q - ik * block_k
    precision = _operand_precision(q_ref.dtype)
    dot = functools.partial(jax.lax.dot_general, precision=precision,
                            preferred_element_type=jnp.float32)

    def step(straddles):
        q, k, v, do = q_ref[:], k_ref[:], v_ref[:], do_ref[:]
        s = dot(k, q, _CONTRACT_LAST) * scale             # (block_k, block_q)
        if straddles:
            shape = (block_k, block_q)
            c_less_r = (jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                        - jax.lax.broadcasted_iota(jnp.int32, shape, 1))
            s = jnp.where(c_less_r <= ahead, s, _MASKED)
        p = jnp.exp(s - lse_ref[:])
        dv_acc[:] += dot(p.astype(do.dtype), do, _MATMUL)
        dp = dot(v, do, _CONTRACT_LAST)
        ds = (p * (dp - delta_ref[:])).astype(q.dtype)
        dk_acc[:] += dot(ds, q, _MATMUL)
        dq_acc[iq] += dot(k, ds, _CONTRACT_FIRST)         # (D, block_q)

    _causal_steps(step, causal, ahead, block_q, block_k)

    @pl.when(iq == pl.num_programs(2) - 1)
    def _():
        dk_ref[:] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when(ik == pl.num_programs(1) - 1)
    def _():
        rows = pl.ds(pl.multiple_of(iq * block_q, block_q), block_q)
        dq_ref[rows, :] = (dq_acc[iq] * scale).T.astype(dq_ref.dtype)


def _bwd_tile(t: int) -> int:
    """The backward's block size, of queries and of keys: ``t`` itself
    below 128, else the largest of 512, 256, 128 that divides it.  Its
    four fp32 tiles (``s``, ``p``, ``dp``, ``ds``) are 1 MiB each at 512;
    at the forward's 1024 they alone would fill the scoped VMEM."""
    if t < 128:
        return t
    return next(b for b in (512, 256, 128) if t % b == 0)


def _bwd_vmem_bytes(t, d, block_q, block_k, dtype):
    """Scoped VMEM the backward is given: its tiles (``s``, ``p``, ``dp``,
    ``ds`` and a mask's two iotas in 32 bits, ``p`` and ``ds`` once more
    as rounded), the operand and result blocks double-buffered, the
    accumulators, and a head's dq (fp32 sums and the result block): the
    one part that grows with T.  Never under the compiler's own 16 MiB."""
    item = jnp.dtype(dtype).itemsize
    tiles = block_q * block_k * (6 * 4 + 2 * item)
    blocks = (4 * _vmem_block_bytes(block_q, d, dtype)
              + 8 * _vmem_block_bytes(block_k, d, dtype)
              + 2 * _vmem_block_bytes(block_k, d, jnp.float32)
              + 4 * _vmem_block_bytes(8, block_q, jnp.float32))
    head = ((t // block_q) * _vmem_block_bytes(d, block_q, jnp.float32)
            + 2 * _vmem_block_bytes(t, d, dtype))
    return max(tiles + blocks + head + 2 ** 20, 16 * 2 ** 20)


def _flash_backward(qb, kb, vb, ob, lse, dob, causal, block_q, block_k,
                    interpret):
    """dq, dk, dv ``(BH, T, D)`` from the saved operands, output and
    log-sum-exp and the output's cotangent.  No array with two sequence
    axes leaves VMEM."""
    bh, t, d = qb.shape
    nq, nk = t // block_q, t // block_k
    scale = 1.0 / math.sqrt(d)
    delta = jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32),
                    axis=-1)[:, None, :]                  # (BH, 1, T)

    def q_row(i, j):
        if causal:
            # before the first query block this key block is visible to,
            # stay on that one
            j = jnp.maximum(j, i * block_k // block_q)
        return j

    def q_map(h, i, j):
        return h, q_row(i, j), 0

    def row_map(h, i, j):
        return h, 0, q_row(i, j)

    def kv_map(h, i, j):
        return h, i, 0

    q_spec = pl.BlockSpec((None, block_q, d), q_map)
    kv_spec = pl.BlockSpec((None, block_k, d), kv_map)
    row_spec = pl.BlockSpec((None, 1, block_q), row_map)
    return pl.pallas_call(
        functools.partial(_attn_bwd_kernel, causal=causal, scale=scale),
        grid=(bh, nk, nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[pl.BlockSpec((None, t, d), lambda h, i, j: (h, 0, 0)),
                   kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((bh, t, d), x.dtype)
                   for x in (qb, kb, vb)],
        scratch_shapes=[pltpu.VMEM((nq, d, block_q), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_bwd_vmem_bytes(t, d, block_q, block_k,
                                             qb.dtype)),
        interpret=interpret,
        name="attention_bwd",
    )(qb, kb, vb, dob, lse, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_k, interpret):
    out = _flash_forward(_to_bh(q), _to_bh(k), _to_bh(v), causal, block_q,
                         block_k, interpret)
    return _from_bh(out, q.shape[0])


def _flash_fwd_rule(q, k, v, causal, block_q, block_k, interpret):
    qb, kb, vb = _to_bh(q), _to_bh(k), _to_bh(v)
    ob, lse = _flash_forward(qb, kb, vb, causal, block_q, block_k, interpret,
                             save_lse=True)
    return _from_bh(ob, q.shape[0]), (qb, kb, vb, ob, lse)


def _flash_bwd_rule(causal, block_q, block_k, interpret, res, g):
    t = g.shape[1]
    # the tiles the caller gave the forward hold for the backward as far
    # as they fit it (tests pass small ones); the kernel's own are its own
    block_q, block_k = (min(b, _bwd_tile(t)) for b in (block_q, block_k))
    grads = _flash_backward(*res, _to_bh(g), causal, block_q, block_k,
                            interpret)
    return tuple(_from_bh(x, g.shape[0]) for x in grads)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, causal: bool = True,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None, interpret: bool = False):
    """q, k, v: (B, T, H, D) -> (B, T, H, D).

    The kernel chooses its tiles from T (``_tile``); ``block_q`` and
    ``block_k`` override them (tests do).  T must be a multiple of both
    (pad upstream; the reference pipeline pads too --
    dataset/MiniBatch.scala:523 PaddingParam).  The MXU takes q, k, v in
    the dtype they come in and accumulates in fp32; the softmax state is
    fp32, and the weights are rounded to v's dtype before ``p @ v`` as
    ``nn.attention.dot_product_attention`` rounds them.

    Differentiable, and the backward is a kernel too (``attention_bwd``):
    where the call is differentiated the forward also saves each row's
    log-sum-exp, and dq, dk and dv come from q, k, v, the output, that and
    the cotangent tile by tile, in fp32 until the one rounding before each
    product.  The plain call saves nothing.
    """
    t = q.shape[1]
    block_q = min(block_q or _tile(t), t)
    block_k = min(block_k or _tile(t), t)
    assert t % block_q == 0 and t % block_k == 0, (t, block_q, block_k)
    return _flash(q, k, v, causal, block_q, block_k, interpret)


def _online_softmax_step(q, kblk, vblk, kpos, p, carry):
    """One K/V block of ``_decode_kernel``'s q_len=1 online softmax:
    ``q (1, d)``, ``kblk/vblk (n, d)`` fp32, ``kpos (1,
    n)`` the block's logical positions, ``p`` the row's frontier."""
    acc, m, l = carry
    s = jax.lax.dot_general(q, kblk, _CONTRACT_LAST,
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


def _split3(x):
    """An fp32 array as three bf16 arrays whose sum is ``x`` to the last
    bit (8 + 8 + 8 significant bits; each residual is exact in fp32).
    Against a 0/1 matrix the MXU then sums fp32 values at fp32 accuracy in
    three bf16 passes, where ``precision=highest`` takes six."""
    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _paged_decode_kernel(pos_ref, table_ref, layer_ref, q_ref, k_hbm, v_hbm,
                         *rest, block_size: int, blocks_per_step: int,
                         max_blocks: int, head_dim: int, scale: float,
                         quantized: bool):
    """One decode slot a grid step.  The pool stays in HBM in the layout it
    is stored in, seen as ``(L, NB, bs, H * D)``: a block lies at
    ``(layer_ref[0], table entry)``, and the slot's blocks come
    through its row of the table ``blocks_per_step`` at a time, each block
    one DMA of all its heads, into the other half of a double buffer while
    this half is computed on -- and the first step of the NEXT slot while
    this slot's last one is, so no slot waits for its first block.  Only
    blocks up to the frontier ``pos // bs`` are fetched and only steps up
    to it run; rows past ``pos`` (the rest of the frontier block, and what
    an earlier step left in the buffer) are masked.

    The lanes of a K or V row hold ``(kv head, d)``.  The per-head sums
    over ``d`` and the spreading of a head's weight back over its ``d``
    lanes are products with one 0/1 matrix ``seg (H, H * D)`` on the MXU,
    exact in fp32 (``_split3``); scores and softmax state are ``(H, rows)``
    and ``(H, 1)``, positions on the lanes; the rest is fp32 on the VPU.
    Grouped queries (``q_ref (G, Hkv * D)``: row ``j`` holds query head
    ``kv * G + j`` on KV head ``kv``'s lanes) take the block that was
    fetched once through the same arithmetic ``G`` times, each with its
    own softmax state; one group is the multi-head kernel as it was.  An
    int8 pool's scales come gathered as ``(steps, H, rows)`` a slot and
    multiply there: K's the scores, V's the softmax weights -- the
    payload goes from int8 to fp32 and is never multiplied out."""
    bs, g, d = block_size, blocks_per_step, head_dim
    if quantized:
        ks_ref, vs_ref, *rest = rest
    o_ref, k_buf, v_buf, sem, half_ref, m_ref, l_ref, acc_ref = rest
    i, n = pl.program_id(0), pl.num_programs(0)
    rows, (groups, width) = g * bs, q_ref.shape
    heads = width // d

    def frontier(slot):
        return jnp.clip(pos_ref[slot] // bs, 0, max_blocks - 1)

    def copies(slot, step, half):
        """The DMAs of one step of one slot, each with whether its block
        is live (at or before the slot's frontier)."""
        last, layer = frontier(slot), layer_ref[0]
        for j in range(g):
            blk = step * g + j
            phys = table_ref[slot * max_blocks + jnp.minimum(blk, last)]
            for a, (pool, buf) in enumerate(((k_hbm, k_buf),
                                             (v_hbm, v_buf))):
                yield blk <= last, pltpu.make_async_copy(
                    pool.at[layer, phys], buf.at[half, pl.ds(j * bs, bs)],
                    sem.at[half, a])

    def start(slot, step, half):
        for live, dma in copies(slot, step, half):
            pl.when(live)(dma.start)

    def wait(slot, step, half):
        for live, dma in copies(slot, step, half):
            pl.when(live)(dma.wait)

    @pl.when(i == 0)
    def _():
        half_ref[0] = 0
        start(0, 0, 0)

    m_ref[:] = jnp.full_like(m_ref, _MASKED)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)
    p = pos_ref[i]
    steps = frontier(i) // g + 1
    first_half = half_ref[0]
    q = q_ref[:].astype(jnp.float32) * scale              # (G, H * D)
    seg = jax.lax.broadcasted_iota(jnp.int32, (heads, width), 1) // d \
        == jax.lax.broadcasted_iota(jnp.int32, (heads, width), 0)
    seg16 = seg.astype(jnp.bfloat16)

    def spread(x):
        # (H, 1) -> (1, H * D): each head's value on its own lanes
        return jnp.sum(jnp.where(seg, x, 0.0), axis=0, keepdims=True)

    def with_seg(lhs, rhs, dims):
        return jax.lax.dot_general(lhs, rhs, dims,
                                   precision=jax.lax.Precision.DEFAULT,
                                   preferred_element_type=jnp.float32)

    def head_sums(x):
        # (rows, H * D) -> (H, rows): each head's sum over its d lanes
        return sum(with_seg(seg16, part, _CONTRACT_LAST)
                   for part in _split3(x))

    def over_lanes(w):
        # (H, rows) -> (rows, H * D): a head's weight on each of its lanes
        return sum(with_seg(part, seg16, _CONTRACT_FIRST)
                   for part in _split3(w))

    def body(step, _):
        half = (first_half + step) % 2

        @pl.when(step + 1 < steps)
        def _():
            start(i, step + 1, 1 - half)

        @pl.when(jnp.logical_and(step + 1 == steps, i + 1 < n))
        def _():
            start(i + 1, 0, 1 - half)

        wait(i, step, half)
        k = k_buf[half].astype(jnp.float32)                # (rows, H * D)
        v = v_buf[half].astype(jnp.float32)
        seen = step * rows + jax.lax.broadcasted_iota(
            jnp.int32, (1, rows), 1) <= p
        # a row never fetched holds whatever the buffer held: its weight
        # is 0, and 0 * nan must not reach the sum
        fetched = step * rows + jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0) <= p
        for j in range(groups):
            one, of = slice(j, j + 1), slice(j * heads, (j + 1) * heads)
            s = head_sums(k * q[one])                      # (H, rows)
            if quantized:
                s = s * ks_ref[step]
            s = jnp.where(seen, s, _MASKED)
            m = m_ref[of]
            new_m = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            w = jnp.exp(s - new_m)                         # (H, rows)
            corr = jnp.exp(m - new_m)
            m_ref[of] = new_m
            l_ref[of] = l_ref[of] * corr + jnp.sum(w, axis=1, keepdims=True)
            if quantized:
                w = w * vs_ref[step]
            pv = jnp.where(fetched, over_lanes(w) * v, 0.0)
            acc_ref[one] = acc_ref[one] * spread(corr) + jnp.sum(
                pv, axis=0, keepdims=True)
        return _

    jax.lax.fori_loop(0, steps, body, None)
    half_ref[0] = (first_half + steps) % 2
    for j in range(groups):
        one = slice(j, j + 1)
        o_ref[one] = (acc_ref[one] / spread(
            l_ref[j * heads:(j + 1) * heads])).astype(o_ref.dtype)


#: fp32 bytes of K (and as many of V) that one step of the paged decode
#: kernel computes on: 256 rows at 16 heads of 64.  At the serving cell's
#: shape and lengths (PERF.md section 6, PR 32; ms a call) 32 rows a step
#: read 0.34, 64 0.25, 128 0.19, 256 0.17, 512 0.21, the DMAs alone 0.13:
#: fewer, larger steps until a slot's last step is mostly rows past its end.
_PAGED_STEP_BYTES = 2 ** 20


def _paged_blocks_per_step(block_size: int, width: int,
                           max_blocks: int) -> int:
    rows = _PAGED_STEP_BYTES // (4 * width)
    return max(1, min(max_blocks, rows // block_size))


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_paged_decode_attention(q, k_pool, v_pool, tables, pos,
                                 k_scale=None, v_scale=None, layer=None,
                                 interpret: bool = False):
    """Single-token decode attention through a PAGED K/V pool:
    ``q (B, 1, H, D)`` against pools ``k_pool, v_pool`` addressed by
    per-row block tables ``tables (B, max_blocks)`` with frontier positions
    ``pos (B,)`` -> ``(B, 1, H, D)``.

    The pools hold ``Hkv`` KV heads a row, ``Hkv * D`` wide; ``H = Hkv *
    G`` and query head ``g`` reads KV head ``g // G`` (grouped-query
    attention; ``G`` is read off the two widths, and ``G = 1`` is
    multi-head attention).  A block is fetched once for all ``G`` query
    heads of its KV heads.

    Where a block lies is told by the pool's shape.  One layer's leaf is
    ``(NB, bs, Hkv * D)`` and block ``b`` is ``pool[b]`` (the unrolled
    layout).  The layer-stacked leaf of a ``scan_layers`` model is
    ``(L, NB, bs, Hkv * D)``, ``layer`` (an int32 scalar, traced inside the
    layer loop) says which layer is asked for, and block ``b`` is
    ``pool[layer, b]``: the whole leaf is handed over and no layer of it is
    sliced out, so the loop that carries it never copies it.  The first is
    the second with one layer.

    The pool is read where it lies, in the shape ``init_paged_cache``
    stores it in (heads and head_dim on one axis: the TPU compiler lays a
    ``(NB, bs, H, 64)`` array out with the block axis on the lanes, and
    no block of it can be fetched): the kernel fetches a row's blocks
    through its table by its own DMAs and does work in proportion to
    ``pos`` -- the gather that the XLA path materialises
    (``(B, max_blocks * bs, H, D)`` a layer a step, whatever the lengths)
    never exists, and nothing of the pool is copied or transposed before
    the call.  VMEM holds two steps' blocks, whatever the pool's size.

    ``k_scale``/``v_scale`` (both or neither, fp32 ``(NB, bs, H)``, or
    ``(L, NB, bs, H)`` beside a stacked pool) select the INT8 pool layout:
    payloads are int8 and become fp32 in the kernel, so the pool's traffic
    stays at the narrow width; the scales, a sixteenth of the payload at
    D=64 and too narrow for a DMA of their own (Mosaic pads their 16 lanes
    to 128 in HBM and refuses the slice), are gathered by XLA, all
    ``max_blocks`` of a row, by one gather over ``(layer, tables)``.
    ``interpret=True`` runs on the CPU for tests.  On a TPU ``bs`` must be
    a multiple of the pool dtype's sublane tile (8 fp32, 16 bf16, 32 int8)
    and ``Hkv * D`` of 128 (``MultiHeadAttention._flash_paged_ok``).
    """
    b, t1, h, d = q.shape
    max_blocks = tables.shape[1]
    assert t1 == 1, f"decode takes one query token per row, got {t1}"
    quantized = k_scale is not None
    assert (v_scale is not None) == quantized, \
        "pass both k_scale and v_scale or neither"
    if k_pool.ndim == 3:
        assert layer is None, "a single layer's leaf has no layer to ask for"
        layer = 0
        k_pool, v_pool = k_pool[None], v_pool[None]
        if quantized:
            k_scale, v_scale = k_scale[None], v_scale[None]
    assert layer is not None, "a stacked pool needs the layer"
    width = k_pool.shape[3]
    assert width % d == 0 and (h * d) % width == 0, \
        f"a pool row of {width} values is not whole KV heads of {d} that " \
        f"divide the {h} query heads"
    kv_heads = width // d
    groups = h // kv_heads
    layer = jnp.asarray(layer, jnp.int32)
    bs = k_pool.shape[2]
    tables = jnp.asarray(tables, jnp.int32)
    g = _paged_blocks_per_step(bs, width, max_blocks)
    steps = -(-max_blocks // g)

    def slot_block(*shape):
        return pl.BlockSpec(
            (None,) + shape,
            lambda i, pos, tables, layer: (i,) + (0,) * len(shape))

    def gathered(scales):
        # (L, NB, bs, H) -> (B, steps, H, rows): a step's positions on the
        # lanes, as its scores have them
        x = scales.at[layer, tables].get(mode="fill").astype(
            jnp.float32).reshape(b, max_blocks * bs, kv_heads)
        x = jnp.pad(x, ((0, 0), (0, (steps * g - max_blocks) * bs), (0, 0)))
        return x.reshape(b, steps, g * bs, kv_heads).transpose(0, 1, 3, 2)

    # (B, G, Hkv * D): row j holds the query heads kv * G + j, each on its
    # KV head's lanes (one group: the row as it is)
    q = q.reshape(b, 1, width) if groups == 1 else \
        q.reshape(b, kv_heads, groups, d).transpose(0, 2, 1, 3).reshape(
            b, groups, width)
    in_specs = [slot_block(groups, width)] + \
        [pl.BlockSpec(memory_space=pltpu.HBM)] * 2
    args = [q, k_pool, v_pool]
    if quantized:
        in_specs += [slot_block(steps, kv_heads, g * bs)] * 2
        args += [gathered(k_scale), gathered(v_scale)]

    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, block_size=bs,
                          blocks_per_step=g, max_blocks=max_blocks,
                          head_dim=d, scale=1.0 / math.sqrt(d),
                          quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=in_specs,
            out_specs=slot_block(groups, width),
            scratch_shapes=[
                pltpu.VMEM((2, g * bs, width), k_pool.dtype),
                pltpu.VMEM((2, g * bs, width), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((groups, width), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(
            (b, groups, width), jnp.float32 if quantized else q.dtype),
        # slots run in order: each starts the next one's first fetch
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="flash_paged_decode_attention",
    )(jnp.asarray(pos, jnp.int32), tables.reshape(-1), layer.reshape(1),
      *args)
    if groups > 1:
        out = out.reshape(b, groups, kv_heads, d).transpose(0, 2, 1, 3)
    return out.reshape(b, 1, h, d)


#: latent rows one step of the latent paged decode kernel computes on
_LATENT_STEP_ROWS = 512


def _latent_decode_kernel(pos_ref, table_ref, layer_ref, q_ref, pool_hbm,
                          o_ref, buf, sem, chain_ref, m_ref, l_ref, acc_ref,
                          *, block_size: int, blocks_per_step: int,
                          max_blocks: int, rank: int, scale: float,
                          trash: int):
    """One decode slot a grid step, ``_paged_decode_kernel``'s way of
    fetching (the pool in HBM as ``(L, NB, bs, W)``, a block at
    ``(layer_ref[0], table entry)``, a step's blocks by one DMA each into
    the other half of a double buffer, the next slot's first step started
    by this slot's last) over ONE shared row a token: the scores are
    ``q (H, W) . rows (R, W)`` and the output ``p (H, R) . rows[:, :rank]``,
    both on the MXU in the pool's dtype with float32 sums, the running
    softmax ``(H, 1)`` in float32.  A slot whose table starts on the trash
    block is not live: nothing of it is fetched or computed and its output
    is nought.  ``chain_ref``: which half the next step lands in, and
    whether the slot before started this slot's first step."""
    bs, g = block_size, blocks_per_step
    i, n = pl.program_id(0), pl.num_programs(0)
    rows = g * bs

    def is_live(slot):
        return table_ref[slot * max_blocks] != trash

    def frontier(slot):
        return jnp.clip(pos_ref[slot] // bs, 0, max_blocks - 1)

    def copies(slot, step, half):
        last, layer, alive = frontier(slot), layer_ref[0], is_live(slot)
        for j in range(g):
            blk = step * g + j
            phys = table_ref[slot * max_blocks + jnp.minimum(blk, last)]
            yield jnp.logical_and(alive, blk <= last), pltpu.make_async_copy(
                pool_hbm.at[layer, phys], buf.at[half, pl.ds(j * bs, bs)],
                sem.at[half])

    def start(slot, step, half):
        for fetch, dma in copies(slot, step, half):
            pl.when(fetch)(dma.start)

    def wait(slot, step, half):
        for fetch, dma in copies(slot, step, half):
            pl.when(fetch)(dma.wait)

    @pl.when(i == 0)
    def _():
        chain_ref[0] = 0
        chain_ref[1] = 0

    first_half = chain_ref[0]

    @pl.when(chain_ref[1] == 0)
    def _():
        start(i, 0, first_half)

    m_ref[:] = jnp.full_like(m_ref, _MASKED)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)
    p = pos_ref[i]
    steps = jnp.where(is_live(i), frontier(i) // g + 1, 0)
    q = q_ref[:]                                           # (H, W)
    precision = _operand_precision(q.dtype)

    def body(step, _):
        half = (first_half + step) % 2

        @pl.when(step + 1 < steps)
        def _():
            start(i, step + 1, 1 - half)

        @pl.when(jnp.logical_and(step + 1 == steps, i + 1 < n))
        def _():
            start(i + 1, 0, 1 - half)

        wait(i, step, half)
        # a row never fetched holds whatever the buffer held: its weight
        # is 0, and 0 * nan must not reach the sum
        fetched = step * rows + jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0) <= p
        ctx = jnp.where(fetched, buf[half], 0)             # (R, W)
        s = jax.lax.dot_general(q, ctx, _CONTRACT_LAST, precision=precision,
                                preferred_element_type=jnp.float32) * scale
        seen = step * rows + jax.lax.broadcasted_iota(
            jnp.int32, (1, rows), 1) <= p
        s = jnp.where(seen, s, _MASKED)                    # (H, R)
        m = m_ref[:]
        new_m = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        w = jnp.exp(s - new_m)
        corr = jnp.exp(m - new_m)
        m_ref[:] = new_m
        l_ref[:] = l_ref[:] * corr + jnp.sum(w, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jnp.dot(
            w.astype(ctx.dtype), ctx[:, :rank], precision=precision,
            preferred_element_type=jnp.float32)
        return _

    jax.lax.fori_loop(0, steps, body, None)
    chain_ref[0] = (first_half + steps) % 2
    # a slot that ran a step started its successor's first one
    chain_ref[1] = jnp.where(steps > 0, 1, 0)
    o_ref[:] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def latent_paged_decode_attention(q, pool, tables, pos, layer=None, *,
                                  rank: int, scale: float,
                                  interpret: bool = False):
    """Single-token ABSORBED latent attention through a paged pool of
    latent rows: ``q (B, H, W)`` (``W_kvb``'s key half already folded into
    its first ``rank`` columns, the rotary part behind them) against the
    block leaf ``pool`` -- ``(NB, bs, W)`` a layer, or ``(L, NB, bs, W)``
    with ``layer`` an int32 scalar, as ``flash_paged_decode_attention``
    takes them -- through ``tables (B, max_blocks)`` up to ``pos (B,)``;
    ``(B, H, rank)``: the softmax-weighted sum of each row's first
    ``rank`` columns, which the caller takes through ``W_kvb``'s value
    half.  Every head reads the same row a token, so a slot's rows are
    fetched ONCE for all heads, only the blocks up to its frontier, and
    the work follows its length.  A row whose table starts on the trash
    block (the pool's last) is not live and comes back nought."""
    b, h, width = q.shape
    if pool.ndim == 3:
        assert layer is None, "a single layer's leaf has no layer to ask for"
        layer, pool = 0, pool[None]
    assert layer is not None, "a stacked pool needs the layer"
    assert pool.shape[3] == width, (pool.shape, q.shape)
    bs, max_blocks = pool.shape[2], tables.shape[1]
    g = max(1, min(max_blocks, _LATENT_STEP_ROWS // bs))

    def slot_block(cols):
        return pl.BlockSpec((None, h, cols),
                            lambda i, pos, tables, layer: (i, 0, 0))

    return pl.pallas_call(
        functools.partial(_latent_decode_kernel, block_size=bs,
                          blocks_per_step=g, max_blocks=max_blocks,
                          rank=rank, scale=scale, trash=pool.shape[1] - 1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[slot_block(width),
                      pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=slot_block(rank),
            scratch_shapes=[
                pltpu.VMEM((2, g * bs, width), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, rank), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, rank), q.dtype),
        # slots run in order: each starts the next one's first fetch
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_paged_decode_attention",
    )(jnp.asarray(pos, jnp.int32), jnp.asarray(tables, jnp.int32).reshape(-1),
      jnp.asarray(layer, jnp.int32).reshape(1), q.astype(pool.dtype), pool)
