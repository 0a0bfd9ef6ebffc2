"""The delta rule with a per-channel decay (Kimi Delta Attention): the
recurrence of a linear-attention layer that keeps, for every head, a
``(d_k, d_v)`` float32 state in place of a cache of keys and values.

One token::

    S' = Diag(exp(g)) S                   g (d_k,) <= 0, the log-decay
    u  = S'^T k                           what the state holds for k
    S  = S' + beta k (v - u)^T            = (I - beta k k^T) S' + beta k v^T
    o  = S^T q

Four forms of it:

- ``kda_step``: plain ``jax.numpy``; every product is a multiply and a
  sum on the vector unit, so that no float32 operand is rounded on its
  way through the MXU.
- ``kda_scan``: ``kda_step`` over the tokens of a chunk, one after the
  other (``lax.scan``), carrying the state; padding tokens leave it as it
  is.  What prefill runs for a decay per channel (Kimi Delta Attention),
  and the definition the chunked form is held to.
- ``gated_delta_chunk_scan``: the same recurrence for a decay that is ONE
  number a head (``g (d,)`` all equal: the gated delta rule), in the
  chunkwise form of Gated Delta Networks (Yang, Kautz, Hatamizadeh,
  arXiv:2412.06464, section 3): inside a chunk of ``CHUNK`` tokens
  matrix products and one unit lower-triangular solve, from chunk to
  chunk one carried state.
- ``kda_decode_step``: one token a row in ONE Pallas kernel: a row's
  state is fetched once, decayed, corrected, written back in place and
  read out in one pass (plain XLA makes two reads and a write of it).
  Rows address the state by slot id; a row whose slot id is the last row
  of the state (the trash slot) is skipped.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: heads one grid step of the kernel takes (8 x 64 KiB of state, in and
#: out, double-buffered: 2 MiB of VMEM)
HEADS_PER_STEP = 8
#: tokens a chunk of ``gated_delta_chunk_scan`` (its triangular solve is
#: ``CHUNK x CHUNK`` a head)
CHUNK = 64


def kda_step(state, q, k, v, g, beta):
    """``state (..., dk, dv)`` float32, ``q``, ``k``, ``g`` ``(..., dk)``,
    ``v (..., dv)``, ``beta (...,)`` -> ``(new state, o (..., dv))``, all
    float32."""
    f32 = jnp.float32
    q, k, v, g = (a.astype(f32) for a in (q, k, v, g))
    decayed = state * jnp.exp(g)[..., None]
    held = (decayed * k[..., None]).sum(-2)
    write = beta.astype(f32)[..., None] * (v - held)
    new = decayed + k[..., None] * write[..., None, :]
    return new, (new * q[..., None]).sum(-2)


def kda_scan(state, q, k, v, g, beta, valid):
    """The recurrence over a chunk: ``state (B, H, dk, dv)``; ``q``, ``k``,
    ``g`` ``(B, T, H, dk)``, ``v (B, T, H, dv)``, ``beta (B, T, H)``,
    ``valid (B, T)`` bool.  Returns ``(state after the last valid token,
    o (B, T, H, dv))``; the outputs of padding tokens mean nothing."""
    def step(s, xs):
        qt, kt, vt, gt, bt, ok = xs
        new, o = kda_step(s, qt, kt, vt, gt, bt)
        return jnp.where(ok[:, None, None, None], new, s), o

    time_major = lambda a: jnp.moveaxis(a, 1, 0)
    state, o = jax.lax.scan(
        step, state, tuple(time_major(a) for a in (q, k, v, g, beta, valid)))
    return state, jnp.moveaxis(o, 0, 1)


def gated_delta_chunk_scan(state, q, k, v, g, beta, valid):
    """``kda_scan`` for a decay that is one number a head, chunk by chunk.
    ``state (B, H, dk, dv)`` float32, carried in that layout; ``q``, ``k``
    ``(B, T, H, dk)``, ``v (B, T, H, dv)``, ``g`` and ``beta (B, T, H)``,
    ``valid (B, T)`` bool.  Returns ``(state after the last valid token,
    o (B, T, H, dv))``: a token that is not valid decays nothing and
    writes nothing, so it leaves the state as it is; its output means
    nothing.

    A chunk of ``C`` tokens from the state ``S0``, ``gamma`` the running
    sum of the decays inside the chunk (every exponent taken is a
    difference <= 0)::

        A   = tril_-1(exp(gamma_t - gamma_i) k_t . k_i)             (C, C)
        U   = (I + diag(beta) A)^-1 diag(beta) (V - diag(exp(gamma)) K S0)
        O   = diag(exp(gamma)) Q S0 + tril(exp(gamma_t - gamma_i) q_t . k_i) U
        S_C = exp(gamma_C) S0 + K^T diag(exp(gamma_C - gamma)) U

    ``U`` is what each token writes: the solve corrects a token's write by
    those of the tokens before it in the chunk.  Everything that does not
    depend on ``S0`` is formed for all chunks at once; the chunks then
    follow each other (``lax.scan``).  Float32 throughout, products at
    precision ``highest``."""
    f32, hp = jnp.float32, jax.lax.Precision.HIGHEST
    b, t, h, _ = q.shape
    c = min(CHUNK, t)
    n = -(-t // c)
    pad = lambda a, fill=0: jnp.pad(
        a, ((0, 0), (0, n * c - t)) + ((0, 0),) * (a.ndim - 2),
        constant_values=fill)
    valid = pad(valid, False)[:, :, None]                       # (B, nC, 1)
    g = jnp.where(valid, pad(g.astype(f32)), 0.0)
    beta = jnp.where(valid, pad(beta.astype(f32)), 0.0)
    # (B, T, H, ...) -> (N, B, H, C, ...): chunks first, heads before tokens
    chunked = lambda a: jnp.moveaxis(
        a.reshape((b, n, c, h) + a.shape[3:]), (1, 3), (0, 2))
    q, k, v = (chunked(pad(a.astype(f32))) for a in (q, k, v))
    g, beta = chunked(g), chunked(beta)
    gamma = jnp.cumsum(g, axis=-1)                              # (N, B, H, C)
    diff = gamma[..., :, None] - gamma[..., None, :]            # t, i
    causal = jnp.tril(jnp.ones((c, c), bool))
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    decay = lambda mask: jnp.exp(jnp.where(mask, diff, -jnp.inf))
    dot = lambda spec, x, y: jnp.einsum(spec, x, y, precision=hp)
    a = decay(strict) * dot("...td,...id->...ti", k, k)
    scores = decay(causal) * dot("...td,...id->...ti", q, k)
    lower = jnp.eye(c, dtype=f32) + beta[..., :, None] * a
    dv = v.shape[-1]
    w = jax.scipy.linalg.solve_triangular(
        lower, jnp.concatenate([beta[..., None] * v,
                                (beta * jnp.exp(gamma))[..., None] * k], -1),
        lower=True, unit_diagonal=True)
    w_v, w_k = w[..., :dv], w[..., dv:]

    def chunk(s, xs):
        q, k, w_v, w_k, scores, gamma = xs
        u = w_v - dot("bhck,bhkv->bhcv", w_k, s)
        o = jnp.exp(gamma)[..., None] * dot("bhck,bhkv->bhcv", q, s) \
            + dot("bhti,bhiv->bhtv", scores, u)
        last = gamma[..., -1:]
        s = jnp.exp(last)[..., None] * s + dot(
            "bhck,bhcv->bhkv", jnp.exp(last - gamma)[..., None] * k, u)
        return s, o

    state, o = jax.lax.scan(chunk, state.astype(f32),
                            (q, k, w_v, w_k, scores, gamma))
    # (N, B, H, C, dv) -> (B, T, H, dv)
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, n * c, h, dv)
    return state, o[:, :t]


def _decode_kernel(slots_ref, state_ref, q_ref, k_ref, v_ref, g_ref,
                   beta_ref, new_ref, o_ref, *, heads, trash):
    live = slots_ref[pl.program_id(0)] != trash

    @pl.when(jnp.logical_not(live))
    def _():
        # nothing of a skipped row's state is read or written; its output
        # is given a value, because what a dead row computes from it lands
        # in the trash block that live rows' padded tables gather (and
        # weigh with nought: 0 x NaN would still be NaN)
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _():
        dk = state_ref.shape[-2]

        def column(x):
            # (1, dk) on the lanes -> (dk, dv) with x[i] along row i
            return jnp.broadcast_to(x, (dk, x.shape[-1])).T

        for h in range(heads):
            at = slice(h, h + 1)
            kc, qc = column(k_ref[at, :]), column(q_ref[at, :])
            decayed = state_ref[h] * column(jnp.exp(g_ref[at, :]))
            held = jnp.sum(decayed * kc, axis=0, keepdims=True)
            write = beta_ref[at, :] * (v_ref[at, :] - held)
            new = decayed + kc * write
            new_ref[h] = new
            o_ref[at, :] = jnp.sum(new * qc, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_decode_step(state, slots, q, k, v, g, beta, interpret=False):
    """One token a row against the slot state, in place.

    ``state (S + 1, H, d, d)`` float32 (row ``S`` is the trash slot),
    ``slots (N,)`` int32, ``q``, ``k``, ``v``, ``g`` ``(N, H, d)``, ``beta
    (N, H)``.  Returns ``(state, o (N, H, d))``: rows ``slots[i]`` of the
    state hold the step's result, every other row is untouched, and
    ``o[i]`` of a row given the trash slot is nought.  The kernel's
    result aliases its state operand: under a step that donates the pool
    the state is updated where it lies."""
    n, heads, d = q.shape
    assert state.shape[1:] == (heads, d, d) and state.dtype == jnp.float32
    per = HEADS_PER_STEP if heads % HEADS_PER_STEP == 0 else heads
    f32 = jnp.float32
    vec = pl.BlockSpec((None, per, d), lambda i, j, slots: (i, j, 0))
    mat = pl.BlockSpec((None, per, d, d),
                       lambda i, j, slots: (slots[i], j, 0, 0))
    beta = jnp.broadcast_to(beta.astype(f32)[..., None], (n, heads, d))
    new, o = pl.pallas_call(
        functools.partial(_decode_kernel, heads=per,
                          trash=state.shape[0] - 1),
        out_shape=(jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((n, heads, d), f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n, heads // per),
            in_specs=[mat, vec, vec, vec, vec, vec],
            out_specs=(mat, vec),
        ),
        # operand 0 is the scalar-prefetched slot ids
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="kda_decode_step",
    )(slots.astype(jnp.int32), state, q.astype(f32), k.astype(f32),
      v.astype(f32), g.astype(f32), beta)
    return new, o
