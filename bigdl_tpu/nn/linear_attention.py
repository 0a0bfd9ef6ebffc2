"""Delta-rule linear attention with a per-channel decay (KDA, as published
for Kimi Linear): a token mixer whose memory is a fixed-size float32 state
a head, not a cache that grows with the sequence.

For a token ``x``::

    q, k, v = SiLU(conv(W_qkv x))           causal depthwise, ``conv_kernel`` taps
    q, k    = l2norm(q), l2norm(k)  a head;  q *= d^-1/2
    g       = lower_bound * sigmoid(exp(A_log) * (W_a x + dt_bias))   (d,) a head
    beta    = sigmoid(W_b x)                 a head
    S, o    = the delta rule (``ops/kda.py``)
    out     = W_o (sigmoid(W_g x) * RMSNorm_head(o))

Between the steps of generation a sequence keeps ``state`` ``(H, d, d)``
float32 and ``conv``, the last ``conv_kernel - 1`` rows of ``W_qkv x``:
both PER SLOT (``nn/generation_state.py``), nothing per token.
"""

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.generation_state import SLOT, StateSpec
from bigdl_tpu.nn.initialization import Xavier
from bigdl_tpu.nn.module import Module, child_rng
from bigdl_tpu.ops.kda import kda_decode_step, kda_scan, kda_step


def _on_tpu():
    return jax.devices()[0].platform == "tpu"


class KimiDeltaAttention(Module):
    """``(N, T, D) -> (N, T, D)``, causal.  ``use_kernel``: ``"auto"`` runs
    the decode step as the Pallas kernel on a TPU and as plain XLA
    elsewhere; ``"interpret"`` runs the kernel in interpreter mode
    (tests); ``"never"`` is plain XLA everywhere."""

    def __init__(self, hidden_size: int, num_heads: int, head_dim: int = 128,
                 conv_kernel: int = 4, lower_bound: float = -5.0,
                 norm_eps: float = 1e-6, use_kernel: str = "auto", name=None):
        super().__init__(name)
        assert use_kernel in ("auto", "never", "interpret")
        self.hidden_size = hidden_size
        self.num_heads = num_heads
        self.head_dim = head_dim
        self.conv_kernel = conv_kernel
        self.lower_bound = float(lower_bound)
        self.norm_eps = norm_eps
        self.use_kernel = use_kernel

    def setup(self, rng, input_spec):
        d, h = self.hidden_size, self.num_heads
        c = h * self.head_dim
        taps = self.conv_kernel
        init = Xavier()
        return {
            "qkv_weight": init.init(child_rng(rng, 0), (3 * c, d), d, 3 * c),
            "conv_kernel": init.init(child_rng(rng, 1), (taps, 3 * c),
                                     taps, 1),
            # the decay's and the output gate's projections, in that order
            "ag_weight": init.init(child_rng(rng, 2), (2 * c, d), d, 2 * c),
            "dt_bias": jnp.zeros((c,), jnp.float32),
            "A_log": jnp.zeros((h,), jnp.float32),
            "b_weight": init.init(child_rng(rng, 3), (h, d), d, h),
            "o_norm": jnp.ones((self.head_dim,), jnp.float32),
            "out_weight": init.init(child_rng(rng, 4), (d, c), c, d),
        }, ()

    # ----- generation state ------------------------------------------------- #
    def state_spec(self, dtype):
        h, dh = self.num_heads, self.head_dim
        return {"state": StateSpec(SLOT, (h, dh, dh), jnp.float32),
                "conv": StateSpec(SLOT, (self.conv_kernel - 1, 3 * h * dh),
                                  dtype)}

    # ----- the layer's parts ------------------------------------------------ #
    def _inputs(self, params, x, tail, lengths=None):
        """What the recurrence takes for ``x (N, T, D)`` after the rows
        ``tail (N, taps - 1, 3C)``: ``(q, k, v, g (N, T, H, d) float32,
        beta (N, T, H), gate (N, T, C), the tail after the last valid
        token)``."""
        n, t, _ = x.shape
        dt = x.dtype
        h, dh, taps = self.num_heads, self.head_dim, self.conv_kernel
        f32 = jnp.float32
        padded = jnp.concatenate(
            [tail.astype(dt), x @ params["qkv_weight"].astype(dt).T], axis=1)
        kernel = params["conv_kernel"].astype(dt)
        conv = sum(kernel[j] * jax.lax.slice_in_dim(padded, j, j + t, axis=1)
                   for j in range(taps))
        q, k, v = (a.reshape(n, t, h, dh).astype(f32) for a in
                   jnp.split(jax.nn.silu(conv), 3, axis=-1))

        def l2norm(a):
            return a * jax.lax.rsqrt(
                jnp.sum(jnp.square(a), -1, keepdims=True) + self.norm_eps)

        q, k = l2norm(q) * dh ** -0.5, l2norm(k)
        # the decay's logit comes out of its product in float32: an error
        # in it is raised to the power of every token the state outlives
        wide = lambda w: jnp.einsum("ntd,od->nto", x, w.astype(dt),
                                    preferred_element_type=f32)
        a, gate = jnp.split(wide(params["ag_weight"]), 2, axis=-1)
        gate = gate.astype(dt)
        rate = jnp.exp(params["A_log"].astype(f32))[:, None]
        g = self.lower_bound * jax.nn.sigmoid(rate * (
            a + params["dt_bias"].astype(f32)).reshape(n, t, h, dh))
        beta = jax.nn.sigmoid(wide(params["b_weight"]))
        if lengths is None:
            new_tail = padded[:, t:]
        else:
            at = lengths[:, None] + jnp.arange(taps - 1)[None]
            new_tail = jnp.take_along_axis(padded, at[..., None], axis=1)
        return q, k, v, g, beta, gate, new_tail

    def _output(self, params, o, gate):
        """``o (N, T, H, d)`` float32 -> ``(N, T, D)`` in ``gate``'s dtype."""
        n, t = o.shape[:2]
        dt = gate.dtype
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                              + self.norm_eps) * params["o_norm"]
        y = jax.nn.sigmoid(gate) * o.reshape(n, t, -1).astype(dt)
        return y @ params["out_weight"].astype(dt).T

    # ----- forward ---------------------------------------------------------- #
    def apply(self, params, state, input, *, training=False, rng=None):
        n, t, _ = input.shape
        h, dh = self.num_heads, self.head_dim
        tail = jnp.zeros((n, self.conv_kernel - 1, 3 * h * dh), input.dtype)
        q, k, v, g, beta, gate, _ = self._inputs(params, input, tail)
        _, o = kda_scan(jnp.zeros((n, h, dh, dh), jnp.float32), q, k, v, g,
                        beta, jnp.ones((n, t), bool))
        return self._output(params, o, gate), state

    def apply_paged(self, params, input, pool, slots, pos, lengths=None):
        """A chunk (``lengths`` given: row ``i``'s first ``lengths[i]``
        tokens are real and start at position ``pos[i]``) or one token a
        row, against the slot leaves ``pool``; row ``i`` is slot
        ``slots[i]`` (the trash slot for a row that is not live).  A chunk
        that starts at position 0 starts from a zero state, whatever the
        slot held.  Returns ``(out, new pool)``."""
        t = input.shape[1]
        state = pool["state"][slots]
        tail = pool["conv"][slots]
        if lengths is not None:
            fresh = (pos == 0)[:, None, None]
            tail = jnp.where(fresh, jnp.zeros((), tail.dtype), tail)
            state = jnp.where(fresh[..., None], 0.0, state)
            q, k, v, g, beta, gate, tail = self._inputs(
                params, input, tail, lengths)
            valid = jnp.arange(t)[None, :] < lengths[:, None]
            state, o = kda_scan(state, q, k, v, g, beta, valid)
            new_state = pool["state"].at[slots].set(state)
        else:
            q, k, v, g, beta, gate, tail = self._inputs(params, input, tail)
            one = lambda a: a[:, 0]
            kernel = self.use_kernel == "interpret" or (
                self.use_kernel == "auto" and _on_tpu())
            if kernel:
                new_state, o = kda_decode_step(
                    pool["state"], slots, one(q), one(k), one(v), one(g),
                    one(beta), interpret=self.use_kernel == "interpret")
            else:
                state, o = kda_step(state, one(q), one(k), one(v), one(g),
                                    one(beta))
                new_state = pool["state"].at[slots].set(state)
            o = o[:, None]
        new_pool = {"state": new_state,
                    "conv": pool["conv"].at[slots].set(
                        tail.astype(pool["conv"].dtype))}
        return self._output(params, o, gate), new_pool
