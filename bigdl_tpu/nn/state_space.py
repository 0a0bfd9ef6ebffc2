"""The Mamba-2 mixer (selective state space, ``ops/ssd.py``): a token
mixer whose memory is a fixed-size float32 state a head and the last few
rows of a short convolution's input, not a cache that grows with the
sequence.

For a token ``u`` (``d_inner = H P``, one group of ``B`` and ``C``)::

    [z (d_inner), xBC (d_inner + 2 N)] = W_in u;   dt (H) = W_dt u
    xBC         = SiLU(causal depthwise conv(xBC) + conv_bias)
    [x, B, C]   = xBC                        x (H, P); B, C (N,) for all heads
    dt          = softplus(dt + dt_bias);    A = -exp(A_log)
    S           = exp(dt A) S + (dt x) (x) B           (P, N) float32 a head
    y           = S C + D x
    out         = W_out (RMSNorm(y * SiLU(z)) * w)     gate BEFORE the norm

``W_in`` and ``W_dt`` are the rows of the published ``in_proj`` kept as
two leaves, so that ``dt`` comes out of its product in float32: an error
in it is raised to the power of every token the state outlives.  Between
the steps of generation a sequence keeps ``state`` (as ``ops/ssd.py``
stores it: ``(H / pack, N, pack P)`` float32) and ``conv``, the last
``conv_kernel - 1`` rows of ``xBC`` before the convolution: both PER SLOT
(``nn/generation_state.py``), nothing per token.
"""

import math

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.generation_state import SLOT, StateSpec
from bigdl_tpu.nn.initialization import Xavier
from bigdl_tpu.nn.module import Module, child_rng
from bigdl_tpu.ops.ssd import (from_stored, ssd_chunk_scan, ssd_decode_step,
                               ssd_step, stored_shape, to_stored)


def _on_tpu():
    return jax.devices()[0].platform == "tpu"


class Mamba2Mixer(Module):
    """``(N, T, D) -> (N, T, D)``, causal.  ``use_kernel``: ``"auto"`` runs
    the decode step as the Pallas kernel on a TPU and as plain XLA
    elsewhere; ``"interpret"`` runs the kernel in interpreter mode
    (tests); ``"never"`` is plain XLA everywhere."""

    def __init__(self, hidden_size: int, num_heads: int, head_dim: int,
                 state_dim: int = 128, conv_kernel: int = 4,
                 chunk_size: int = 256, norm_eps: float = 1e-5,
                 use_kernel: str = "auto", name=None):
        super().__init__(name)
        assert use_kernel in ("auto", "never", "interpret")
        self.hidden_size = hidden_size
        self.num_heads = num_heads
        self.head_dim = head_dim
        self.state_dim = state_dim
        self.conv_kernel = conv_kernel
        self.chunk_size = chunk_size
        self.norm_eps = norm_eps
        self.use_kernel = use_kernel
        self.inner = num_heads * head_dim
        #: what the convolution runs over: x, B and C
        self.conv_dim = self.inner + 2 * state_dim

    def setup(self, rng, input_spec):
        d, h, c = self.hidden_size, self.num_heads, self.conv_dim
        taps = self.conv_kernel
        init = Xavier()
        wide = self.inner + c
        # a state that remembers from one token to some hundreds: the
        # steps log-uniform over [1e-3, 1e-1], the rates over [1, 16]
        step = jnp.exp(jax.random.uniform(
            child_rng(rng, 3), (h,), jnp.float32, math.log(1e-3),
            math.log(1e-1)))
        rate = jax.random.uniform(child_rng(rng, 4), (h,), jnp.float32,
                                  1.0, 16.0)
        return {
            "in_weight": init.init(child_rng(rng, 0), (wide, d), d, wide),
            "dt_weight": init.init(child_rng(rng, 1), (h, d), d, h),
            "conv_kernel": init.init(child_rng(rng, 2), (taps, c), taps, 1),
            "conv_bias": jnp.zeros((c,), jnp.float32),
            # softplus(dt_bias) = step
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.log(rate),
            "D": jnp.ones((h,), jnp.float32),
            "o_norm": jnp.ones((self.inner,), jnp.float32),
            "out_weight": init.init(child_rng(rng, 5), (d, self.inner),
                                    self.inner, d),
        }, ()

    # ----- generation state ------------------------------------------------- #
    def state_spec(self, dtype):
        """``state``: the recurrent state as ``ops/ssd.py`` stores it,
        float32 whatever is asked; ``conv``: the convolution's tail in
        ``dtype``.  Both per SLOT."""
        return {"state": StateSpec(SLOT, stored_shape(
                    self.num_heads, self.head_dim, self.state_dim),
                    jnp.float32),
                "conv": StateSpec(SLOT, (self.conv_kernel - 1,
                                         self.conv_dim), dtype)}

    # ----- the layer's parts ------------------------------------------------ #
    def _inputs(self, params, u, tail, lengths=None):
        """What the recurrence takes for ``u (N, T, D)`` after the rows
        ``tail (N, taps - 1, conv_dim)``: ``(x (N, T, H, P), dt (N, T, H)
        float32 after its softplus, B, C (N, T, state_dim), z (N, T,
        d_inner), the tail after the last valid token)``."""
        n, t, _ = u.shape
        dtype = u.dtype
        f32 = jnp.float32
        taps, inner, k = self.conv_kernel, self.inner, self.state_dim
        z, xbc = jnp.split(u @ params["in_weight"].astype(dtype).T, [inner],
                           axis=-1)
        padded = jnp.concatenate([tail.astype(dtype), xbc], axis=1)
        kernel = params["conv_kernel"].astype(dtype)
        conv = sum(kernel[j] * jax.lax.slice_in_dim(padded, j, j + t, axis=1)
                   for j in range(taps)) + params["conv_bias"].astype(dtype)
        x, B, C = jnp.split(jax.nn.silu(conv), [inner, inner + k], axis=-1)
        dt = jax.nn.softplus(jnp.einsum(
            "ntd,hd->nth", u, params["dt_weight"].astype(dtype),
            preferred_element_type=f32) + params["dt_bias"].astype(f32))
        if lengths is None:
            new_tail = padded[:, t:]
        else:
            at = lengths[:, None] + jnp.arange(taps - 1)[None]
            new_tail = jnp.take_along_axis(padded, at[..., None], axis=1)
        return (x.reshape(n, t, self.num_heads, self.head_dim), dt, B, C, z,
                new_tail)

    def _rates(self, params):
        f32 = jnp.float32
        return -jnp.exp(params["A_log"].astype(f32)), params["D"].astype(f32)

    def _output(self, params, y, z):
        """``y (N, T, H, P)`` float32 -> ``(N, T, D)`` in ``z``'s dtype."""
        n, t = y.shape[:2]
        dtype = z.dtype
        y = y.reshape(n, t, -1) * jax.nn.silu(z.astype(jnp.float32))
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                              + self.norm_eps) * params["o_norm"]
        return y.astype(dtype) @ params["out_weight"].astype(dtype).T

    # ----- forward ---------------------------------------------------------- #
    def apply(self, params, state, input, *, training=False, rng=None):
        n = input.shape[0]
        h, p, k = self.num_heads, self.head_dim, self.state_dim
        tail = jnp.zeros((n, self.conv_kernel - 1, self.conv_dim),
                         input.dtype)
        x, dt, B, C, z, _ = self._inputs(params, input, tail)
        A, D = self._rates(params)
        _, y = ssd_chunk_scan(
            jnp.zeros((n,) + stored_shape(h, p, k), jnp.float32), x, dt, A,
            B, C, D, self.chunk_size)
        return self._output(params, y, z), state

    def apply_paged(self, params, input, pool, slots, pos, lengths=None,
                    layer=None):
        """A chunk (``lengths`` given: row ``i``'s first ``lengths[i]``
        tokens are real and start at position ``pos[i]``) or one token a
        row, against the slot leaves ``pool``; row ``i`` is slot
        ``slots[i]`` (the trash slot for a row that is not live).  A chunk
        that starts at position 0 starts from a zero state, whatever the
        slot held; a padding token leaves the state as it is.  With
        ``layer`` an int32 scalar (traced inside a layer loop) ``pool``'s
        leaves are layer-STACKED, ``(L, S + 1, ...)``, and this layer's
        rows are ``leaf[layer, slot]``: gathered, written and handed to
        the kernel where they lie, no layer sliced out.  Returns ``(out,
        new pool)``."""
        t = input.shape[1]
        h = self.num_heads
        at = (slots,) if layer is None else (layer, slots)
        tail = pool["conv"][at]
        A, D = self._rates(params)
        if lengths is not None:
            fresh = (pos == 0)[:, None, None]
            tail = jnp.where(fresh, jnp.zeros((), tail.dtype), tail)
            state = jnp.where(fresh[..., None], 0.0, pool["state"][at])
            x, dt, B, C, z, tail = self._inputs(params, input, tail, lengths)
            valid = jnp.arange(t)[None, :] < lengths[:, None]
            dt = jnp.where(valid[..., None], dt, 0.0)
            state, y = ssd_chunk_scan(state, x, dt, A, B, C, D,
                                      self.chunk_size)
            new_state = pool["state"].at[at].set(state)
        else:
            x, dt, B, C, z, tail = self._inputs(params, input, tail)
            one = lambda a: a[:, 0]
            kernel = self.use_kernel == "interpret" or (
                self.use_kernel == "auto" and _on_tpu())
            if kernel:
                new_state, y = ssd_decode_step(
                    pool["state"], slots, one(x), one(dt), A, one(B), one(C),
                    D, layer=layer, interpret=self.use_kernel == "interpret")
            else:
                state, y = ssd_step(from_stored(pool["state"][at], h),
                                    one(x), one(dt), A, one(B), one(C), D)
                new_state = pool["state"].at[at].set(to_stored(state))
            y = y[:, None]
        new_pool = {"state": new_state,
                    "conv": pool["conv"].at[at].set(
                        tail.astype(pool["conv"].dtype))}
        return self._output(params, y, z), new_pool
