"""Delta-rule linear attention: token mixers whose memory is a fixed-size
float32 state a head, not a cache that grows with the sequence.  Two of
them, on one convolution, one state spec and one kernel:

``KimiDeltaAttention`` (KDA, as published for Kimi Linear): a decay PER
CHANNEL and as many key heads as value heads.  For a token ``x``::

    q, k, v = SiLU(conv(W_qkv x))           causal depthwise, ``conv_kernel`` taps
    q, k    = l2norm(q), l2norm(k)  a head;  q *= d^-1/2
    g       = lower_bound * sigmoid(exp(A_log) * (W_a x + dt_bias))   (d,) a head
    beta    = sigmoid(W_b x)                 a head
    S, o    = the delta rule (``ops/kda.py``)
    out     = W_o (sigmoid(W_g x) * RMSNorm_head(o))

``GatedDeltaNet`` (the gated delta rule): a decay that is ONE number a
value head, and ``value_heads`` over ``key_heads`` (value head ``h``
reads key head ``h // (value_heads / key_heads)``).  For a token ``x``::

    q, k, v = SiLU(conv([W_q x; W_k x; W_v x]))     key, key, value widths
    q, k    = l2norm(q), l2norm(k)  a key head;  q *= d^-1/2
    g       = -exp(A_log) * softplus(W_a x + dt_bias)   one a value head
    beta    = sigmoid(W_b x)                            one a value head
    S, o    = the delta rule, q and k read by their value heads
    out     = W_o (RMSNorm_head(o) * (1 + w_o) * gate_scale * sigmoid(W_z x))

The delta rule itself (``ops/kda.py``): prefill runs ``kda_scan`` (KDA)
or the chunked ``gated_delta_chunk_scan`` (a decay a head); decode runs
the Pallas ``kda_decode_step`` for both, the gated delta rule's decay
broadcast over the key channels and its q and k repeated to the value
heads.

Between the steps of generation a sequence keeps ``state`` ``(H_v, d, d)``
float32 and ``conv``, the last ``conv_kernel - 1`` rows of the
convolution's input: both PER SLOT (``nn/generation_state.py``), nothing
per token.
"""

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.generation_state import SLOT, StateSpec
from bigdl_tpu.nn.initialization import Xavier
from bigdl_tpu.nn.module import Module, child_rng
from bigdl_tpu.ops.kda import (gated_delta_chunk_scan, kda_decode_step,
                               kda_scan, kda_step)


def _on_tpu():
    return jax.devices()[0].platform == "tpu"


class _DeltaRuleMixer(Module):
    """What the delta-rule mixers share: the short convolution over the
    projected ``[q; k; v]`` and its tail, the slot state, the full forward
    and the paged step.  A subclass sets ``value_heads``, ``head_dim`` and
    ``conv_width`` (the convolution's channels) and gives ``_inputs``,
    ``_output``, ``_scan`` (prefill) and ``_per_value_head`` (what the
    one-token step takes).  ``use_kernel``: ``"auto"`` runs the decode
    step as the Pallas kernel on a TPU and as plain XLA elsewhere;
    ``"interpret"`` runs the kernel in interpreter mode (tests);
    ``"never"`` is plain XLA everywhere."""

    def __init__(self, hidden_size, conv_kernel, norm_eps, use_kernel,
                 name=None):
        super().__init__(name)
        assert use_kernel in ("auto", "never", "interpret")
        self.hidden_size = hidden_size
        self.conv_kernel = conv_kernel
        self.norm_eps = norm_eps
        self.use_kernel = use_kernel

    # ----- generation state ------------------------------------------------- #
    def state_spec(self, dtype):
        h, dh = self.value_heads, self.head_dim
        return {"state": StateSpec(SLOT, (h, dh, dh), jnp.float32),
                "conv": StateSpec(SLOT, (self.conv_kernel - 1,
                                         self.conv_width), dtype)}

    # ----- the shared parts ------------------------------------------------- #
    def _conv(self, params, x, tail):
        """``(SiLU(conv) (N, T, conv_width), the convolution's input with
        the tail in front)`` for ``x (N, T, D)`` after the rows ``tail (N,
        taps - 1, conv_width)``."""
        t = x.shape[1]
        dt = x.dtype
        padded = jnp.concatenate(
            [tail.astype(dt), x @ params["qkv_weight"].astype(dt).T], axis=1)
        kernel = params["conv_kernel"].astype(dt)
        conv = sum(kernel[j] * jax.lax.slice_in_dim(padded, j, j + t, axis=1)
                   for j in range(self.conv_kernel))
        return jax.nn.silu(conv), padded

    def _l2norm(self, a):
        return a * jax.lax.rsqrt(
            jnp.sum(jnp.square(a), -1, keepdims=True) + self.norm_eps)

    def _tail(self, padded, lengths):
        """The convolution's tail after the last valid token."""
        t = padded.shape[1] - (self.conv_kernel - 1)
        if lengths is None:
            return padded[:, t:]
        at = lengths[:, None] + jnp.arange(self.conv_kernel - 1)[None]
        return jnp.take_along_axis(padded, at[..., None], axis=1)

    def _rms_head(self, o):
        """``o (N, T, H, d)`` float32 over each head's RMS."""
        return o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                                 + self.norm_eps)

    def _per_value_head(self, q, k, v, g, beta):
        """One token's ``_inputs`` as the one-token step takes them: ``q``,
        ``k``, ``v``, ``g`` ``(N, H_v, d)``, ``beta (N, H_v)``."""
        return q, k, v, g, beta

    # ----- forward ---------------------------------------------------------- #
    def apply(self, params, state, input, *, training=False, rng=None):
        n, t, _ = input.shape
        h, dh = self.value_heads, self.head_dim
        tail = jnp.zeros((n, self.conv_kernel - 1, self.conv_width),
                         input.dtype)
        q, k, v, g, beta, gate, _ = self._inputs(params, input, tail)
        _, o = self._scan(jnp.zeros((n, h, dh, dh), jnp.float32), q, k, v, g,
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
            state, o = self._scan(state, q, k, v, g, beta, valid)
            new_state = pool["state"].at[slots].set(state)
        else:
            q, k, v, g, beta, gate, tail = self._inputs(params, input, tail)
            one = self._per_value_head(*(a[:, 0] for a in (q, k, v, g, beta)))
            kernel = self.use_kernel == "interpret" or (
                self.use_kernel == "auto" and _on_tpu())
            if kernel:
                new_state, o = kda_decode_step(
                    pool["state"], slots, *one,
                    interpret=self.use_kernel == "interpret")
            else:
                state, o = kda_step(state, *one)
                new_state = pool["state"].at[slots].set(state)
            o = o[:, None]
        new_pool = {"state": new_state,
                    "conv": pool["conv"].at[slots].set(
                        tail.astype(pool["conv"].dtype))}
        return self._output(params, o, gate), new_pool


class KimiDeltaAttention(_DeltaRuleMixer):
    """``(N, T, D) -> (N, T, D)``, causal; a decay per channel (module
    docstring)."""

    def __init__(self, hidden_size: int, num_heads: int, head_dim: int = 128,
                 conv_kernel: int = 4, lower_bound: float = -5.0,
                 norm_eps: float = 1e-6, use_kernel: str = "auto", name=None):
        super().__init__(hidden_size, conv_kernel, norm_eps, use_kernel, name)
        self.num_heads = self.value_heads = num_heads
        self.head_dim = head_dim
        self.conv_width = 3 * num_heads * head_dim
        self.lower_bound = float(lower_bound)

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

    def _inputs(self, params, x, tail, lengths=None):
        """What the recurrence takes for ``x (N, T, D)`` after the rows
        ``tail (N, taps - 1, 3C)``: ``(q, k, v, g (N, T, H, d) float32,
        beta (N, T, H), gate (N, T, C), the tail after the last valid
        token)``."""
        n, t, _ = x.shape
        dt = x.dtype
        h, dh = self.num_heads, self.head_dim
        f32 = jnp.float32
        act, padded = self._conv(params, x, tail)
        q, k, v = (a.reshape(n, t, h, dh).astype(f32)
                   for a in jnp.split(act, 3, axis=-1))
        q, k = self._l2norm(q) * dh ** -0.5, self._l2norm(k)
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
        return q, k, v, g, beta, gate, self._tail(padded, lengths)

    _scan = staticmethod(kda_scan)

    def _output(self, params, o, gate):
        """``o (N, T, H, d)`` float32 -> ``(N, T, D)`` in ``gate``'s dtype."""
        n, t = o.shape[:2]
        dt = gate.dtype
        o = self._rms_head(o) * params["o_norm"]
        y = jax.nn.sigmoid(gate) * o.reshape(n, t, -1).astype(dt)
        return y @ params["out_weight"].astype(dt).T


class GatedDeltaNet(_DeltaRuleMixer):
    """``(N, T, D) -> (N, T, D)``, causal; a decay a value head and
    ``value_heads`` over ``key_heads`` (module docstring).  The output
    norm's weight is zero-centred (``1 + w_o``) and the output gate is
    ``gate_scale * sigmoid(W_z x)``."""

    def __init__(self, hidden_size: int, key_heads: int, value_heads: int,
                 head_dim: int = 128, conv_kernel: int = 4,
                 norm_eps: float = 1e-6, gate_scale: float = 2.0,
                 use_kernel: str = "auto", name=None):
        super().__init__(hidden_size, conv_kernel, norm_eps, use_kernel, name)
        assert value_heads % key_heads == 0
        self.key_heads, self.value_heads = key_heads, value_heads
        self.head_dim = head_dim
        self.conv_width = (2 * key_heads + value_heads) * head_dim
        self.gate_scale = float(gate_scale)

    def setup(self, rng, input_spec):
        d, hv = self.hidden_size, self.value_heads
        cv, c = hv * self.head_dim, self.conv_width
        taps = self.conv_kernel
        init = Xavier()
        return {
            # [W_q; W_k; W_v]
            "qkv_weight": init.init(child_rng(rng, 0), (c, d), d, c),
            "conv_kernel": init.init(child_rng(rng, 1), (taps, c), taps, 1),
            # the decay's and beta's projections, in that order
            "ab_weight": init.init(child_rng(rng, 2), (2 * hv, d), d, 2 * hv),
            "dt_bias": jnp.zeros((hv,), jnp.float32),
            "A_log": jnp.zeros((hv,), jnp.float32),
            "z_weight": init.init(child_rng(rng, 3), (cv, d), d, cv),
            "o_norm": jnp.zeros((self.head_dim,), jnp.float32),
            "out_weight": init.init(child_rng(rng, 4), (d, cv), cv, d),
        }, ()

    def _inputs(self, params, x, tail, lengths=None):
        """``(q, k (N, T, H_k, d), v (N, T, H_v, d) float32, g, beta (N, T,
        H_v) float32, gate (N, T, H_v d), the tail after the last valid
        token)``."""
        n, t, _ = x.shape
        dt = x.dtype
        hk, hv, dh = self.key_heads, self.value_heads, self.head_dim
        f32 = jnp.float32
        act, padded = self._conv(params, x, tail)
        q, k, v = jnp.split(act, [hk * dh, 2 * hk * dh], axis=-1)
        q, k = (a.reshape(n, t, hk, dh).astype(f32) for a in (q, k))
        v = v.reshape(n, t, hv, dh).astype(f32)
        q, k = self._l2norm(q) * dh ** -0.5, self._l2norm(k)
        # the decay's logit and beta's come out of their product in float32
        a, b = jnp.split(jnp.einsum("ntd,od->nto", x,
                                    params["ab_weight"].astype(dt),
                                    preferred_element_type=f32), 2, axis=-1)
        g = -jnp.exp(params["A_log"].astype(f32)) * jax.nn.softplus(
            a + params["dt_bias"].astype(f32))
        beta = jax.nn.sigmoid(b)
        gate = x @ params["z_weight"].astype(dt).T
        return q, k, v, g, beta, gate, self._tail(padded, lengths)

    def _by_value_head(self, a, axis):
        """Key heads repeated to the value heads that read them."""
        return jnp.repeat(a, self.value_heads // self.key_heads, axis=axis)

    def _scan(self, state, q, k, v, g, beta, valid):
        return gated_delta_chunk_scan(state, self._by_value_head(q, 2),
                                      self._by_value_head(k, 2), v, g, beta,
                                      valid)

    def _per_value_head(self, q, k, v, g, beta):
        return (self._by_value_head(q, 1), self._by_value_head(k, 1), v,
                jnp.broadcast_to(g[..., None], v.shape), beta)

    def _output(self, params, o, gate):
        """``o (N, T, H_v, d)`` float32 -> ``(N, T, D)`` in ``gate``'s
        dtype."""
        n, t = o.shape[:2]
        dt = gate.dtype
        o = self._rms_head(o) * (1.0 + params["o_norm"])
        y = (self.gate_scale * jax.nn.sigmoid(gate)) \
            * o.reshape(n, t, -1).astype(dt)
        return y @ params["out_weight"].astype(dt).T
