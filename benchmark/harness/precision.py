"""What a lower-precision control does to a value: the reference's own
arithmetic stays float32, and the control rounds the inputs of its matmuls
and convolutions (and, for bfloat16, the activations it keeps) through the
lower format.  Used by every model file's reference."""

import jax
import jax.numpy as jnp


def rounded(x, fn):
    """``fn(x)`` forward, identity backward: the lower precision rounds
    values, not the cotangents on their way back (a cotangent cast to
    e4m3 underflows to nought)."""
    return x + jax.lax.stop_gradient(fn(x) - x)


def through(x, mode):
    """``x`` as a matmul input sees it in ``mode``: ``f32`` (untouched),
    ``bf16``, or ``fp8`` (e4m3 with a per-tensor scale to the format's
    range, as fp8 recipes do)."""
    if mode == "f32":
        return x
    if mode == "bf16":
        return rounded(x, lambda v: v.astype(jnp.bfloat16).astype(
            jnp.float32))
    if mode == "fp8":
        def e4m3(v):
            scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(v)), 1e-30)
            return (v * scale).astype(jnp.float8_e4m3fn).astype(
                jnp.float32) / scale
        return rounded(x, e4m3)
    raise ValueError(f"unknown precision {mode!r}")
