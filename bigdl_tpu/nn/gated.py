"""Gated layers of hybrid language models: the gated short convolution
(a token mixer with a three-tap memory in place of attention) and the
gated MLP (SwiGLU).  No reference analogue; no bias anywhere.

Layout (N, T, D), weights ``(out, in)`` as ``nn.Linear`` keeps them.
"""

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.initialization import Xavier
from bigdl_tpu.nn.module import Module, child_rng


class GatedShortConv(Module):
    """``[B, C, X] = split3(u W_in)``; ``z = B * X``; ``c_t = sum_j k_j *
    z_{t-(L-1)+j}`` (a depthwise causal convolution: one ``L``-tap filter a
    channel, zeros to the left); ``out = (C * c) W_out``."""

    def __init__(self, hidden_size: int, kernel_size: int = 3, name=None):
        super().__init__(name)
        self.hidden_size = hidden_size
        self.kernel_size = kernel_size

    def setup(self, rng, input_spec):
        d, taps = self.hidden_size, self.kernel_size
        init = Xavier()
        return {
            "in_weight": init.init(child_rng(rng, 0), (3 * d, d), d, 3 * d),
            "kernel": init.init(child_rng(rng, 1), (taps, d), taps, 1),
            "out_weight": init.init(child_rng(rng, 2), (d, d), d, d),
        }, ()

    def apply(self, params, state, input, *, training=False, rng=None):
        dt = input.dtype
        t = input.shape[1]
        taps = self.kernel_size
        gate_b, gate_c, x = jnp.split(
            input @ params["in_weight"].astype(dt).T, 3, axis=-1)
        z = jnp.pad(gate_b * x, ((0, 0), (taps - 1, 0), (0, 0)))
        kernel = params["kernel"].astype(dt)
        conv = sum(kernel[j] * jax.lax.slice_in_dim(z, j, j + t, axis=1)
                   for j in range(taps))
        return (gate_c * conv) @ params["out_weight"].astype(dt).T, state


class GatedMLP(Module):
    """``W_2(silu(W_1 u) * W_3 u)``."""

    def __init__(self, hidden_size: int, width: int, name=None):
        super().__init__(name)
        self.hidden_size = hidden_size
        self.width = width

    def setup(self, rng, input_spec):
        d, f = self.hidden_size, self.width
        init = Xavier()
        return {
            "w1": init.init(child_rng(rng, 0), (f, d), d, f),
            "w3": init.init(child_rng(rng, 1), (f, d), d, f),
            "w2": init.init(child_rng(rng, 2), (d, f), f, d),
        }, ()

    def apply(self, params, state, input, *, training=False, rng=None):
        dt = input.dtype
        h = jax.nn.silu(input @ params["w1"].astype(dt).T) \
            * (input @ params["w3"].astype(dt).T)
        return h @ params["w2"].astype(dt).T, state
