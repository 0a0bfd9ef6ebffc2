"""The selective state-space recurrence of Mamba-2 (state-space duality):
a token mixer that keeps, for every head, a ``(P, N)`` float32 state in
place of a cache of keys and values.

One token, head ``h`` (``x (P,)``, ``dt`` after its softplus, ``A < 0``;
``B`` and ``C`` ``(N,)`` are shared by the heads of a group, one group
here)::

    S' = exp(dt A) S + (dt x) (x) B
    y  = S' C + D x

Three forms of it:

- ``ssd_step``: plain ``jax.numpy`` on the state as written, ``(..., H,
  P, N)``: the definition.
- ``ssd_chunk_scan``: the chunked form, for prefill.  Inside a chunk of
  ``Q`` tokens, with ``a_t = dt_t A`` and ``cum_t = sum_{s<=t} a_s``::

      y_t    = sum_{s<=t} exp(cum_t - cum_s) (C_t . B_s) dt_s x_s
               + exp(cum_t) C_t S_prev + D x_t
      S_next = exp(cum_Q) S_prev + sum_s exp(cum_Q - cum_s) dt_s x_s (x) B_s

  matrix products on the MXU (inputs in the dtype ``x`` comes in or, for
  what a chunk adds to the state, float32 at the default precision;
  accumulated in float32), every decay in float32, exponents only of
  numbers that are not positive; between chunks only the state is
  carried.  A token whose ``dt`` is nought leaves the state as it is:
  that is how padding is told.
- ``ssd_decode_step``: one token a row in ONE Pallas kernel: a row's
  state is fetched once, decayed, updated, written back in place and read
  out in one pass.  Rows address the state by slot id; a row whose slot
  id is the last row of the state (the trash slot) is skipped.

The state is STORED with ``N`` on the sublanes and the ``P`` values of
``pack`` heads side by side on the lanes, ``(H / pack, N, pack P)``
(``to_stored``; at 64 heads of 64 a pair of heads fills the 128 lanes):
``dt x`` and ``y`` then lie along the lanes as they come and go, the sum
over ``N`` runs down the sublanes, and the kernel turns nothing but ``B``
and ``C``, once a row.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: stored rows ``(N, pack P)`` one grid step of the kernel takes (16 x 64
#: KiB of state at 64 heads of 64 over 128, in and out, double-buffered:
#: 4 MiB of VMEM)
ROWS_PER_STEP = 16
_LANES = 128


def state_pack(heads: int, head_dim: int) -> int:
    """Heads whose ``P`` values share the lanes of one stored row."""
    return math.gcd(heads, max(1, _LANES // head_dim))


def stored_shape(heads: int, head_dim: int, state_dim: int):
    pack = state_pack(heads, head_dim)
    return (heads // pack, state_dim, pack * head_dim)


def to_stored(state):
    """``(..., H, P, N)`` -> ``(..., H / pack, N, pack P)``."""
    *lead, h, p, n = state.shape
    pack = state_pack(h, p)
    x = state.reshape(*lead, h // pack, pack, p, n)
    return jnp.moveaxis(x, -1, -3).reshape(*lead, h // pack, n, pack * p)


def from_stored(stored, heads: int):
    """``to_stored``'s inverse."""
    *lead, rows, n, width = stored.shape
    pack = heads // rows
    x = stored.reshape(*lead, rows, n, pack, width // pack)
    return jnp.moveaxis(x, -3, -1).reshape(*lead, heads, width // pack, n)


def ssd_step(state, x, dt, A, B, C, D):
    """``state (..., H, P, N)`` float32, ``x (..., H, P)``, ``dt (...,
    H)`` (after its softplus; nought leaves the state as it is), ``A`` and
    ``D`` ``(H,)``, ``B`` and ``C`` ``(..., N)`` -> ``(new state, y (...,
    H, P))``, all float32."""
    f32 = jnp.float32
    x, dt, A, B, C, D = (a.astype(f32) for a in (x, dt, A, B, C, D))
    decay = jnp.exp(dt * A)[..., None, None]
    write = (dt[..., None] * x)[..., None] * B[..., None, None, :]
    new = decay * state + write
    y = (new * C[..., None, None, :]).sum(-1) + D[:, None] * x
    return new, y


def ssd_chunk_scan(state, x, dt, A, B, C, D, chunk: int = 256):
    """The recurrence over ``T`` tokens a row, ``chunk`` at a time:
    ``state (B, H / pack, N, pack P)`` float32 AS STORED (``to_stored``),
    ``x (B, T, H, P)``, ``dt (B, T, H)`` float32 (after its softplus,
    NOUGHT for a padding token), ``A`` and ``D`` ``(H,)``, ``B`` and ``C``
    ``(B, T, N)``.  Returns ``(state after the last token, as stored, y (B,
    T, H, P) float32)``; what a padding token reads out means nothing.
    ``T`` need not be a multiple of ``chunk``.

    The state is taken, carried and handed back in its stored layout and
    every product that touches it writes or reads that layout itself: a
    transpose next to the gather of a slot's rows would have the compiler
    re-lay the whole slot leaf out, a copy of all of it a step."""
    f32 = jnp.float32
    n, t, h, p = x.shape
    rows, k, width = state.shape[1:]
    assert (rows * width, B.shape[-1]) == (h * p, k), (state.shape, x.shape)
    q = min(int(chunk), t)
    nc = -(-t // q)
    mm = x.dtype

    def chunks(a):
        a = jnp.pad(a, ((0, 0), (0, nc * q - t)) + ((0, 0),) * (a.ndim - 2))
        return a.reshape((n, nc, q) + a.shape[2:])

    def lanes(a):
        # (..., H, P) -> (..., H / pack, pack P): a stored row's lanes
        return a.reshape(a.shape[:-2] + (rows, width))

    x, dt, B, C = chunks(x), chunks(dt.astype(f32)), chunks(B), chunks(C)
    A, D = A.astype(f32), D.astype(f32)
    cum = jnp.cumsum(dt * A, axis=2)                      # (n, nc, q, h) <= 0
    # what x_s brings: dt_s x_s, in the products' dtype
    xs = (dt[..., None] * x.astype(f32)).astype(mm)
    # inside a chunk: M[t, s] = exp(cum_t - cum_s) (C_t . B_s), s <= t
    cb = jnp.einsum("nctk,ncsk->ncts", C, B, preferred_element_type=f32)
    by_head = jnp.moveaxis(cum, 3, 2)                     # (n, nc, h, q)
    gap = by_head[..., :, None] - by_head[..., None, :]   # (n, nc, h, t, s)
    seen = jnp.tril(jnp.ones((q, q), bool))
    m = jnp.exp(jnp.where(seen, gap, -jnp.inf)) * cb[:, :, None]
    y = jnp.einsum("nchts,ncshp->ncthp", m.astype(mm), xs,
                   preferred_element_type=f32)
    # what a chunk adds to the state, and what is left of the old one
    left = jnp.exp(cum[:, :, -1:, :] - cum)               # (n, nc, q, h)
    # (float32 operands: at the default matmul precision the MXU rounds them
    # to bfloat16 itself, and XLA's CPU backend runs no bf16 x bf16 -> f32
    # product of this form)
    add = jnp.einsum("ncsrw,ncsk->ncrkw",
                     lanes(left[..., None] * xs.astype(f32)), B.astype(f32),
                     preferred_element_type=f32)
    keep = lanes(jnp.broadcast_to(jnp.exp(cum[:, :, -1, :])[..., None],
                                  (n, nc, h, p)))         # (n, nc, rows, w)

    def carry(s, chunk_c):
        add_c, keep_c = chunk_c
        return keep_c[:, :, None, :] * s + add_c, s

    state, before = jax.lax.scan(
        carry, state.astype(f32),
        (jnp.moveaxis(add, 1, 0), jnp.moveaxis(keep, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                   # (n, nc, rows, k, w)
    # what the state a chunk starts from gives each of its tokens
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "nctk,ncrkw->nctrw", C, before.astype(mm),
        preferred_element_type=f32).reshape(n, nc, q, h, p)
    y = y + D[:, None] * x.astype(f32)
    return state, y.reshape(n, nc * q, h, p)[:, :t]


def _decode_kernel(slots_ref, layer_ref, state_ref, xs_ref, decay_ref,
                   dx_ref, b_ref, c_ref, new_ref, y_ref, *, rows, trash):
    live = slots_ref[pl.program_id(0)] != trash

    @pl.when(jnp.logical_not(live))
    def _():
        # nothing of a skipped row's state is read or written; its output
        # is given a value, because what a dead row computes from it lands
        # in the trash block that live rows' padded tables gather (and
        # weigh with nought: 0 x NaN would still be NaN)
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(live)
    def _():
        k, width = state_ref.shape[-2:]

        def column(v):
            # (1, N) on the lanes -> (N, width) with v[i] along row i
            return jnp.broadcast_to(v, (width, k)).T

        bc, cc = column(b_ref[...]), column(c_ref[...])
        for r in range(rows):
            at = slice(r, r + 1)
            new = state_ref[r] * decay_ref[at, :] + bc * xs_ref[at, :]
            new_ref[r] = new
            y_ref[at, :] = jnp.sum(new * cc, axis=0, keepdims=True) \
                + dx_ref[at, :]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_decode_step(state, slots, x, dt, A, B, C, D, layer=None,
                    interpret=False):
    """One token a row against the slot state, in place.

    ``state (S + 1, H / pack, N, pack P)`` float32 as ``to_stored`` lays
    it out (row ``S`` is the trash slot), or the layer-stacked ``(L, S +
    1, ...)`` with ``layer`` an int32 scalar (traced inside the layer
    loop): the whole leaf is handed over and no layer of it is sliced
    out.  ``slots (n,)`` int32, ``x (n, H, P)``, ``dt (n, H)`` after its
    softplus, ``A`` and ``D`` ``(H,)``, ``B`` and ``C`` ``(n, N)``.
    Returns ``(state, y (n, H, P))``: rows ``slots[i]`` of the state (of
    ``layer``) hold the step's result, every other row is untouched, and
    ``y[i]`` of a row given the trash slot is nought.  The kernel's result
    aliases its state operand: under a step that donates the pool the
    state is updated where it lies."""
    f32 = jnp.float32
    n, h, p = x.shape
    stacked = state.ndim == 5
    if not stacked:
        assert layer is None, "a single layer's leaf has no layer to ask for"
        state, layer = state[None], 0
    assert layer is not None, "a stacked state needs the layer"
    rows_all, k, width = state.shape[2:]
    pack = h // rows_all
    assert (rows_all * pack, pack * p) == (h, width) \
        and state.dtype == f32, (state.shape, x.shape)
    per = ROWS_PER_STEP if rows_all % ROWS_PER_STEP == 0 else rows_all
    x, dt = x.astype(f32), dt.astype(f32)
    lanes = lambda a: a.reshape(n, rows_all, width)       # (n, H, P) as stored
    over_p = lambda a: jnp.broadcast_to(a[..., None], (n, h, p))
    vec = pl.BlockSpec((None, per, width), lambda i, j, *_: (i, j, 0))
    shared = pl.BlockSpec((None, 1, k), lambda i, j, *_: (i, 0, 0))
    mat = pl.BlockSpec(
        (None, None, per, k, width),
        lambda i, j, slots, layer: (layer[0], slots[i], j, 0, 0))
    new, y = pl.pallas_call(
        functools.partial(_decode_kernel, rows=per,
                          trash=state.shape[1] - 1),
        out_shape=(jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((n, rows_all, width), f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n, rows_all // per),
            in_specs=[mat, vec, vec, vec, shared, shared],
            out_specs=(mat, vec),
        ),
        # operands 0 and 1 are the scalar-prefetched slot ids and layer
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssd_decode_step",
    )(slots.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      state, lanes(dt[..., None] * x),
      lanes(over_p(jnp.exp(dt * A.astype(f32)))),
      lanes(D.astype(f32)[:, None] * x),
      B.astype(f32)[:, None, :], C.astype(f32)[:, None, :])
    y = y.reshape(n, h, p)
    return (new if stacked else new[0]), y
