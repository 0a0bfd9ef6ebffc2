"""Fused softmax cross-entropy Pallas kernel for large vocabularies.

The LM-training hot op (pairs with models/transformer.py): computing
``log_softmax(logits)`` then gathering materialises an (N, V) fp32 tensor
in HBM twice (forward activations + backward).  This kernel streams V in
VMEM-sized blocks with an online logsumexp, so the forward writes only two
(N,) vectors; the backward recomputes ``softmax`` blockwise straight into
the gradient buffer.  Same role as the reference's hand-written native
kernels (SURVEY.md 2.8: drop below the compiler only where fusion isn't
enough).

``interpret=True`` runs on CPU for tests (like ops/flash_attention.py).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ce_fwd_kernel(x_ref, y_ref, loss_ref, lse_ref, m_ref, s_ref, xy_ref,
                   *, nv: int):
    """Grid (N/block_n, V/block_v): the vocab axis streams through VMEM one
    (block_n, block_v) tile at a time; the online logsumexp state lives in
    VMEM scratch, which persists across the sequential inner grid axis."""
    n, block_v = x_ref.shape
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full((n, 1), -jnp.inf, jnp.float32)
        s_ref[:] = jnp.zeros((n, 1), jnp.float32)
        xy_ref[:] = jnp.zeros((n, 1), jnp.float32)

    blk = x_ref[:].astype(jnp.float32)
    m = m_ref[:, 0]
    bm = jnp.max(blk, axis=1)
    new_m = jnp.maximum(m, bm)
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - new_m), 0.0)
    s_ref[:, 0] = s_ref[:, 0] * corr + jnp.sum(
        jnp.exp(blk - new_m[:, None]), axis=1)
    cols = j * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (n, block_v), 1)
    xy_ref[:, 0] = xy_ref[:, 0] + jnp.sum(
        jnp.where(cols == y_ref[:], blk, 0.0), axis=1)
    m_ref[:, 0] = new_m

    @pl.when(j == nv - 1)
    def _finish():
        lse = m_ref[:, 0] + jnp.log(jnp.maximum(s_ref[:, 0], 1e-30))
        loss_ref[:, 0] = lse - xy_ref[:, 0]
        lse_ref[:, 0] = lse


def _ce_bwd_kernel(x_ref, y_ref, lse_ref, g_ref, dx_ref):
    n, block_v = dx_ref.shape
    j = pl.program_id(1)
    blk = x_ref[:].astype(jnp.float32)
    p = jnp.exp(blk - lse_ref[:])                    # (n, block_v)
    cols = j * block_v + jax.lax.broadcasted_iota(jnp.int32, (n, block_v), 1)
    onehot = (cols == y_ref[:]).astype(jnp.float32)
    dx_ref[:] = ((p - onehot) * g_ref[:]).astype(dx_ref.dtype)


def _pad_vocab(logits, block_v):
    v = logits.shape[1]
    pad = (-v) % block_v
    if pad:
        logits = jnp.pad(logits, ((0, 0), (0, pad)),
                         constant_values=-1e30)
    return logits, v + pad


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def fused_softmax_cross_entropy(logits, labels, block_n: int = 128,
                                block_v: int = 512,
                                interpret: bool = False):
    """(N, V) logits + (N,) int labels -> per-row loss (N,).

    Differentiable wrt logits via a blockwise Pallas backward.
    """
    loss, _ = _ce_fwd(logits, labels, block_n, block_v, interpret)
    return loss


def _ce_fwd(logits, labels, block_n, block_v, interpret):
    n, v_orig = logits.shape
    block_n = min(block_n, n)
    if n % block_n:
        raise ValueError(f"N={n} must be a multiple of block_n={block_n}")
    x, v = _pad_vocab(logits, block_v)
    bv = min(block_v, v)
    y = labels.astype(jnp.int32).reshape(n, 1)
    loss, lse = pl.pallas_call(
        functools.partial(_ce_fwd_kernel, nv=v // bv),
        grid=(n // block_n, v // bv),
        in_specs=[
            pl.BlockSpec((block_n, bv), lambda i, j: (i, j)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
        ],
        interpret=interpret,
        name="fused_softmax_cross_entropy",
    )(x, y)
    return loss[:, 0], (logits, labels, lse)


def _ce_fwd_rule(logits, labels, block_n, block_v, interpret):
    loss, res = _ce_fwd(logits, labels, block_n, block_v, interpret)
    return loss, res


def _ce_bwd_rule(block_n, block_v, interpret, res, g):
    logits, labels, lse = res
    n, v_orig = logits.shape
    block_n = min(block_n, n)
    x, v = _pad_vocab(logits, block_v)
    bv = min(block_v, v)
    y = labels.astype(jnp.int32).reshape(n, 1)
    gcol = g.astype(jnp.float32).reshape(n, 1)
    dx = pl.pallas_call(
        _ce_bwd_kernel,
        grid=(n // block_n, v // bv),
        in_specs=[
            pl.BlockSpec((block_n, bv), lambda i, j: (i, j)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, bv), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, v), logits.dtype),
        interpret=interpret,
        name="fused_softmax_cross_entropy_grad",
    )(x, y, lse, gcol)
    return dx[:, :v_orig], None


fused_softmax_cross_entropy.defvjp(_ce_fwd_rule, _ce_bwd_rule)
