"""Grouped matrix product in Pallas (Mosaic/TPU): every group of rows of
``lhs`` times its own matrix of ``rhs``.  What a dropless mixture of
experts runs over the rows it routed to each expert (nn/moe.py
``DroplessMoE``); ``jax.experimental.pallas.ops.tpu.megablox`` is the
yardstick.

Layout (the caller builds it; ``group_layout`` says where rows go): the
rows of ``lhs`` are sorted by group and EVERY GROUP STARTS ON A MULTIPLE OF
``block_rows``, so that a tile of rows belongs to one group and no tile is
masked or visited twice.  Group ``g`` holds ``group_sizes[g]`` rows from
``offsets[g]``; the rows between its end and the next multiple of
``block_rows`` are padding (the caller keeps them zero), and the tiles past
the last group are dead: no kernel of this file reads or writes them, so
``lhs`` can be sized for the worst case and only routed rows are computed
(the row axis of the grid is as long as the tiles in use, a scalar the
kernel is handed).  Dead rows of a result are uninitialised memory: read
the rows ``group_layout`` names and no others.

Three products, all grouped:

- ``grouped_matmul(lhs (M, K), rhs (G, K, N)) -> (M, N)``, or with
  ``transpose_rhs`` ``rhs (G, N, K)``: ``out[r] = lhs[r] @ rhs[group(r)]``;
- its backward for ``lhs`` is the same kernel with ``rhs`` read transposed;
- its backward for ``rhs`` is ``_grouped_lhs_t_matmul``:
  ``out[g] = lhs[rows of g].T @ dout[rows of g]``, rows on the last,
  sequential grid axis with an fp32 accumulator.  A group with no rows is
  never visited and its slice is set to zero afterwards.

The MXU takes the operands in the dtype they come in and accumulates in
fp32.  ``interpret=True`` runs on the CPU for tests; off the TPU the plain
``grouped_matmul_reference`` is the layer's path.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows a tile holds on the chip (every group starts on a multiple of it)
BLOCK_ROWS = 512

#: VMEM the kernels may use: the whole contraction axis of a row tile and
#: of a weight tile is resident, double-buffered (about 15 MiB at 2048 x
#: 1792 in bf16, over the compiler's default 16 MiB scoped limit)
_VMEM_LIMIT = 64 * 2 ** 20
#: ... and asked for only by tiles that need it: the limit is a claim on
#: VMEM from the scoped region's start upward, whatever the kernel uses,
#: and the compiler keeps small values of the surrounding program there
#: (generation's tiles of 16 to 256 rows need 3 to 6 MiB; training's 12.5 and over)
_VMEM_DEFAULT_FITS = 10 * 2 ** 20


def _vmem_limit(*tiles, itemsize):
    """None (the compiler's default) where the double-buffered ``tiles``
    fit under it, ``_VMEM_LIMIT`` where they do not."""
    need = 2 * itemsize * sum(int(np.prod(t)) for t in tiles)
    return None if need <= _VMEM_DEFAULT_FITS else _VMEM_LIMIT


def buffer_rows(rows, groups, block_rows):
    """Rows a buffer needs so that ``rows`` rows fit however they fall into
    ``groups`` groups: every group may waste up to a tile."""
    return (-(-rows // block_rows) + groups) * block_rows


def group_layout(group_sizes, total_rows, block_rows):
    """``(offsets (G,), tile_group (tiles,), num_tiles ())`` of the layout
    above: where each group starts, the group of every tile of
    ``block_rows`` rows (past the last tile in use: the last group's id),
    and how many tiles are in use."""
    tiles = total_rows // block_rows
    group_tiles = (group_sizes + block_rows - 1) // block_rows
    ends = jnp.cumsum(group_tiles)
    offsets = (ends - group_tiles) * block_rows
    tile_group = jnp.repeat(jnp.arange(group_sizes.shape[0], dtype=jnp.int32),
                            group_tiles, total_repeat_length=tiles)
    return (offsets.astype(jnp.int32), tile_group,
            ends[-1].astype(jnp.int32))


def _pick(n, choices):
    for c in choices:
        if n % c == 0:
            return c
    return n


def _precision(dtype):
    """bf16 operands go to the MXU as they are (Mosaic refuses a higher
    precision for them, and a process-wide default would ask for one);
    fp32 operands keep whatever the caller's default asks."""
    return jax.lax.Precision.DEFAULT if dtype == jnp.bfloat16 else None


def _matmul_kernel(tile_group_ref, lhs_ref, rhs_ref, out_ref, *,
                   transpose_rhs):
    del tile_group_ref
    dims = (((1,), (1,)), ((), ())) if transpose_rhs \
        else (((1,), (0,)), ((), ()))
    out_ref[...] = jax.lax.dot_general(
        lhs_ref[...], rhs_ref[...], dims, precision=_precision(lhs_ref.dtype),
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


def _grouped_matmul_forward(lhs, rhs, group_sizes, block_rows, transpose_rhs,
                            interpret):
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    assert m % block_rows == 0, (m, block_rows)
    assert rhs.shape[2 if transpose_rhs else 1] == k, (lhs.shape, rhs.shape)
    _, tile_group, num_tiles = group_layout(group_sizes, m, block_rows)
    tn = _pick(n, (1024, 896, 512, 256, 128))

    def rhs_index(j, i, tile_group):
        return (tile_group[i], j, 0) if transpose_rhs \
            else (tile_group[i], 0, j)

    return pl.pallas_call(
        functools.partial(_matmul_kernel, transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            # weight columns outside, row tiles inside: a weight tile
            # stays while the row tiles of its group go by
            grid=(n // tn, num_tiles),
            in_specs=[
                pl.BlockSpec((block_rows, k), lambda j, i, tg: (i, 0)),
                pl.BlockSpec((None, tn, k) if transpose_rhs
                             else (None, k, tn), rhs_index),
            ],
            out_specs=pl.BlockSpec((block_rows, tn),
                                   lambda j, i, tg: (i, j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                (block_rows, k), (k, tn), (block_rows, tn),
                itemsize=lhs.dtype.itemsize)),
        interpret=interpret,
        name="grouped_matmul",
    )(tile_group, lhs, rhs)


def _lhs_t_kernel(tile_group_ref, lhs_ref, dout_ref, out_ref, acc_ref):
    i = pl.program_id(2)
    last = pl.num_programs(2) - 1
    group = tile_group_ref[i]
    first_of_group = jnp.logical_or(
        i == 0, tile_group_ref[jnp.maximum(i - 1, 0)] != group)
    last_of_group = jnp.logical_or(
        i == last, tile_group_ref[jnp.minimum(i + 1, last)] != group)

    @pl.when(first_of_group)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        lhs_ref[...], dout_ref[...], (((0,), (0,)), ((), ())),
        precision=_precision(lhs_ref.dtype),
        preferred_element_type=jnp.float32)

    @pl.when(last_of_group)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _grouped_lhs_t_matmul(lhs, dout, group_sizes, block_rows, interpret):
    """``out[g] = lhs[rows of g].T @ dout[rows of g]``: ``(G, K, N)`` in
    ``lhs``'s dtype from ``lhs (M, K)`` and ``dout (M, N)``."""
    m, k = lhs.shape
    n = dout.shape[1]
    groups = group_sizes.shape[0]
    _, tile_group, num_tiles = group_layout(group_sizes, m, block_rows)
    tk = _pick(k, (1024, 896, 512, 256, 128))
    tn = _pick(n, (1024, 896, 512, 256, 128))
    out = pl.pallas_call(
        _lhs_t_kernel,
        out_shape=jax.ShapeDtypeStruct((groups, k, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(k // tk, n // tn, num_tiles),
            in_specs=[
                pl.BlockSpec((block_rows, tk), lambda a, b, i, tg: (i, a)),
                pl.BlockSpec((block_rows, tn), lambda a, b, i, tg: (i, b)),
            ],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda a, b, i, tg: (tg[i], a, b)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="grouped_matmul_lhs_t",
    )(tile_group, lhs, dout)
    # a group without rows has no tile: its slice was never written
    return jnp.where((group_sizes > 0)[:, None, None], out,
                     jnp.zeros((), out.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _grouped(lhs, rhs, group_sizes, block_rows, transpose_rhs, interpret):
    return _grouped_matmul_forward(lhs, rhs, group_sizes, block_rows,
                                   transpose_rhs, interpret)


def _grouped_fwd(lhs, rhs, group_sizes, block_rows, transpose_rhs, interpret):
    out = _grouped_matmul_forward(lhs, rhs, group_sizes, block_rows,
                                  transpose_rhs, interpret)
    return out, (lhs, rhs, group_sizes)


def _grouped_bwd(block_rows, transpose_rhs, interpret, res, g):
    lhs, rhs, group_sizes = res
    g = g.astype(lhs.dtype)
    dlhs = _grouped_matmul_forward(g, rhs, group_sizes, block_rows,
                                   not transpose_rhs, interpret)
    if transpose_rhs:
        drhs = _grouped_lhs_t_matmul(g, lhs, group_sizes, block_rows,
                                     interpret)
    else:
        drhs = _grouped_lhs_t_matmul(lhs, g, group_sizes, block_rows,
                                     interpret)
    return dlhs, drhs.astype(rhs.dtype), np.zeros(group_sizes.shape,
                                                  jax.dtypes.float0)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


@functools.partial(jax.jit, static_argnames=("block_rows", "transpose_rhs",
                                             "interpret"))
def grouped_matmul(lhs, rhs, group_sizes, block_rows: int = BLOCK_ROWS,
                   transpose_rhs: bool = False, interpret: bool = False):
    """``out[r] = lhs[r] @ rhs[group of r]`` in the layout of this file's
    head: ``lhs (M, K)`` with ``M`` a multiple of ``block_rows``, ``rhs
    (G, K, N)`` (``(G, N, K)`` with ``transpose_rhs``), ``group_sizes (G,)``
    int32.  Differentiable in ``lhs`` and ``rhs``; both backward products
    are grouped kernels too.  Rows outside every group come back
    uninitialised, and so do their cotangents."""
    return _grouped(lhs, rhs.astype(lhs.dtype),
                    group_sizes.astype(jnp.int32), block_rows, transpose_rhs,
                    interpret)


def grouped_matmul_reference(lhs, rhs, group_sizes, block_rows: int,
                             transpose_rhs: bool = False):
    """The same product in plain XLA (every row against every group's
    matrix, all but its own masked away): the path off the TPU, and what
    the tests hold the kernel to.  Rows outside every group come back
    zero."""
    m = lhs.shape[0]
    groups = group_sizes.shape[0]
    offsets, _, _ = group_layout(group_sizes, m, block_rows)
    row = jnp.arange(m)[:, None]
    member = (row >= offsets[None]) & (row < (offsets + group_sizes)[None])
    spec = "mk,gnk,mg->mn" if transpose_rhs else "mk,gkn,mg->mn"
    assert rhs.shape[0] == groups
    return jnp.einsum(spec, lhs, rhs.astype(lhs.dtype),
                      member.astype(lhs.dtype),
                      preferred_element_type=jnp.float32).astype(lhs.dtype)
