"""Normalization and regularization layers.

Reference: nn/BatchNormalization.scala:51, nn/SpatialBatchNormalization.scala,
nn/Dropout.scala, nn/SpatialCrossMapLRN.scala, nn/Normalize.scala.
"""

from contextlib import contextmanager

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.nn.module import Module

#: trace-time switch: when a mesh axis name (or tuple of names) is set,
#: training-mode batch statistics are cross-replica (pmean over the axis)
#: -- SyncBN.  Per-shard statistics remain the default, matching the
#: reference's per-replica BN semantics (nn/BatchNormalization.scala
#: normalizes each worker's local batch).
_SYNC_AXIS = None


@contextmanager
def sync_batchnorm(axis):
    """Within this context (at TRACE time, e.g. around ``model.apply``
    inside a shard_map), BatchNormalization layers normalize with
    cross-replica batch statistics over the mesh ``axis`` -- the
    distributed step then matches the single-device full-batch math
    instead of per-shard statistics."""
    global _SYNC_AXIS
    prev, _SYNC_AXIS = _SYNC_AXIS, axis
    try:
        yield
    finally:
        _SYNC_AXIS = prev


class BatchNormalization(Module):
    """Batch norm over (N, C) inputs (reference: nn/BatchNormalization.scala:51).

    Running stats follow the reference/Torch update:
    ``running = (1 - momentum) * running + momentum * batch`` with the
    *unbiased* batch variance feeding the running estimate while the biased
    one normalises the batch.
    """

    reduce_axes = (0,)

    def __init__(self, n_output, eps=1e-5, momentum=0.1, affine=True, name=None):
        super().__init__(name)
        self.n_output = n_output
        self.eps = eps
        self.momentum = momentum
        self.affine = affine

    def setup(self, rng, input_spec):
        params = {}
        if self.affine:
            params = {
                "weight": jnp.ones((self.n_output,), jnp.float32),
                "bias": jnp.zeros((self.n_output,), jnp.float32),
            }
        state = {
            "running_mean": jnp.zeros((self.n_output,), jnp.float32),
            "running_var": jnp.ones((self.n_output,), jnp.float32),
        }
        return params, state

    def apply(self, params, state, input, *, training=False, rng=None):
        # statistics accumulate in fp32 WITHOUT materialising an fp32 copy
        # of the activations: the elementwise cast/square fuse into the
        # reduction, and the normalise runs in the input dtype so it fuses
        # with the surrounding convs (bf16 on TPU).  E[x^2]-E[x]^2 in fp32
        # is the standard fused-BN formulation (post-conv activations are
        # ~zero-mean, so cancellation is benign at fp32).
        if training:
            mean = jnp.mean(input, axis=self.reduce_axes,
                            dtype=jnp.float32)
            sq = jnp.mean(jnp.square(input.astype(jnp.float32)),
                          axis=self.reduce_axes, dtype=jnp.float32)
            n = input.size // input.shape[-1]
            if _SYNC_AXIS is not None:
                # SyncBN: moments pooled across replicas (grad of pmean is
                # pmean, so backward stat reductions sync the same way)
                mean = lax.pmean(mean, _SYNC_AXIS)
                sq = lax.pmean(sq, _SYNC_AXIS)
                n = n * lax.psum(1, _SYNC_AXIS)
            var = jnp.maximum(sq - jnp.square(mean), 0.0)
            unbiased = var * n / jnp.maximum(n - 1, 1)
            m = self.momentum
            state = {
                "running_mean": (1 - m) * state["running_mean"] + m * mean,
                "running_var": (1 - m) * state["running_var"] + m * unbiased,
            }
        else:
            mean, var = state["running_mean"], state["running_var"]
        inv = lax.rsqrt(var + self.eps)
        scale, shift = inv, -mean * inv
        if self.affine:
            scale = scale * params["weight"]
            shift = shift * params["weight"] + params["bias"]
        y = input * scale.astype(input.dtype) + shift.astype(input.dtype)
        return y, state


class SpatialBatchNormalization(BatchNormalization):
    """Batch norm over NHWC images, per-channel (reference: nn/SpatialBatchNormalization.scala)."""

    reduce_axes = (0, 1, 2)


class LayerNorm(Module):
    """Layer norm over the last dim.  Not in the reference (pre-transformer);
    required by the transformer/long-context stack."""

    def __init__(self, n_output, eps=1e-6, name=None):
        super().__init__(name)
        self.n_output = n_output
        self.eps = eps

    def setup(self, rng, input_spec):
        return {
            "weight": jnp.ones((self.n_output,), jnp.float32),
            "bias": jnp.zeros((self.n_output,), jnp.float32),
        }, ()

    def apply(self, params, state, input, *, training=False, rng=None):
        # fp32-accumulated statistics, normalise in the input dtype (fused
        # like BatchNormalization above -- no fp32 activation copy)
        mean = jnp.mean(input, axis=-1, keepdims=True, dtype=jnp.float32)
        sq = jnp.mean(jnp.square(input.astype(jnp.float32)), axis=-1,
                      keepdims=True, dtype=jnp.float32)
        var = jnp.maximum(sq - jnp.square(mean), 0.0)
        inv = lax.rsqrt(var + self.eps)
        dt = input.dtype
        y = (input - mean.astype(dt)) * inv.astype(dt)
        y = y * params["weight"].astype(dt) + params["bias"].astype(dt)
        return y, state


class RMSNorm(Module):
    """RMS norm (transformer stack; not in the reference)."""

    def __init__(self, n_output, eps=1e-6, name=None):
        super().__init__(name)
        self.n_output = n_output
        self.eps = eps

    def setup(self, rng, input_spec):
        return {"weight": jnp.ones((self.n_output,), jnp.float32)}, ()

    def apply(self, params, state, input, *, training=False, rng=None):
        sq = jnp.mean(jnp.square(input.astype(jnp.float32)), -1,
                      keepdims=True, dtype=jnp.float32)
        inv = lax.rsqrt(sq + self.eps).astype(input.dtype)
        return input * inv * params["weight"].astype(input.dtype), state


class ZeroCenteredGatedNorm(RMSNorm):
    """RMS norm whose scale a channel is ``gate * sigmoid(w)``: ``w``
    centred on nought (the scale is 1 there at ``gate`` 2), bounded in
    ``(0, gate)``."""

    def __init__(self, n_output, eps=1e-6, gate=2.0, name=None):
        super().__init__(n_output, eps, name)
        self.gate = float(gate)

    def setup(self, rng, input_spec):
        return {"weight": jnp.zeros((self.n_output,), jnp.float32)}, ()

    def apply(self, params, state, input, *, training=False, rng=None):
        scale = self.gate * jax.nn.sigmoid(
            params["weight"].astype(jnp.float32))
        return super().apply({"weight": scale}, state, input)


class Dropout(Module):
    """Inverted dropout (reference: nn/Dropout.scala -- scales by 1/(1-p) at train)."""

    def __init__(self, init_p=0.5, name=None):
        super().__init__(name)
        self.p = init_p

    def apply(self, params, state, input, *, training=False, rng=None):
        if not training or self.p <= 0.0 or rng is None:
            return input, state
        keep = 1.0 - self.p
        mask = jax.random.bernoulli(rng, keep, jnp.shape(input))
        return jnp.where(mask, input / keep, 0.0).astype(input.dtype), state


class SpatialCrossMapLRN(Module):
    """Local response normalization across channels (reference: nn/SpatialCrossMapLRN.scala).

    NHWC layout: channel window sum via a 1-D reduce_window over the last axis.
    """

    def __init__(self, size=5, alpha=1.0, beta=0.75, k=1.0, data_format="NHWC", name=None):
        super().__init__(name)
        self.size = size
        self.alpha = alpha
        self.beta = beta
        self.k = k
        self.data_format = data_format

    def apply(self, params, state, input, *, training=False, rng=None):
        x = input
        if self.data_format == "NCHW":
            x = jnp.transpose(x, (0, 2, 3, 1))
        half = (self.size - 1) // 2
        sq = jnp.square(x.astype(jnp.float32))
        window_sum = lax.reduce_window(
            sq, 0.0, lax.add,
            (1, 1, 1, self.size), (1, 1, 1, 1),
            ((0, 0), (0, 0), (0, 0), (half, self.size - 1 - half)),
        )
        denom = jnp.power(self.k + self.alpha / self.size * window_sum, self.beta)
        y = (x.astype(jnp.float32) / denom).astype(input.dtype)
        if self.data_format == "NCHW":
            y = jnp.transpose(y, (0, 3, 1, 2))
        return y, state


class Normalize(Module):
    """L_p normalisation over the last dim (reference: nn/Normalize.scala)."""

    def __init__(self, p=2.0, eps=1e-10, name=None):
        super().__init__(name)
        self.p = p
        self.eps = eps

    def apply(self, params, state, input, *, training=False, rng=None):
        if self.p == float("inf"):
            norm = jnp.max(jnp.abs(input), axis=-1, keepdims=True)
        else:
            norm = jnp.power(
                jnp.sum(jnp.power(jnp.abs(input), self.p), axis=-1, keepdims=True),
                1.0 / self.p,
            )
        return input / (norm + self.eps), state
