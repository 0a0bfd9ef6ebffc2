"""Sequence-parallel (and data x sequence) training steps.

The 'scale the sequence' capability (SURVEY.md section 5: greenfield).
Activations are sharded over the ``seq`` mesh axis; attention runs as a
ppermute ring (parallel/ring_attention.py); everything else in the
transformer is position-local, so the only other collectives are the
gradient pmean over the mesh.  Optimizer state is replicated here (the
ZeRO-1 path lives in optim/distri_optimizer.py; they compose in later
rounds via chunking over the data axis).
"""

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from bigdl_tpu.optim.train_step import _cast_params, _cast_tree


def make_sp_train_step(model, criterion, optim_method, mesh,
                       seq_axis: str = "seq",
                       data_axis: Optional[str] = None,
                       compute_dtype=None):
    """-> jitted (params, opt_state, x, y, rng) -> (params, opt_state, loss).

    ``model`` must be built with ``seq_axis_name=seq_axis`` (e.g.
    TransformerLM) so its attention expects per-device sequence blocks.
    ``x``/``y``: (B, T) int token arrays, globally shaped; sharded
    (data_axis, seq_axis).
    """
    from bigdl_tpu.nn.module import has_frozen
    if has_frozen(model):
        raise NotImplementedError(
            "freeze() is honored by make_train_step and the "
            "DistriOptimizer flat-chunk step; this model-parallel engine "
            "does not mask frozen parameters yet -- unfreeze() before "
            "building, or train with LocalOptimizer/DistriOptimizer")
    axes = tuple(a for a in (data_axis, seq_axis) if a is not None)

    def step_body(params, opt_state, x, y, rng):
        for i, a in enumerate(axes):
            rng = jax.random.fold_in(rng, lax.axis_index(a) + i * 131)

        def loss_fn(p):
            cp = _cast_params(p, compute_dtype)
            out, _ = model.apply(cp, (), x, training=True, rng=rng)
            return criterion.apply(out.astype(jnp.float32), y)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = _cast_tree(grads, jnp.float32)
        # equal token counts per shard -> grad of the global mean loss is the
        # mean of shard grads
        grads = jax.tree.map(lambda g: lax.pmean(g, axes), grads)
        with jax.named_scope("optimizer"):
            new_params, new_opt = optim_method.update(grads, opt_state,
                                                      params)
        return new_params, new_opt, lax.pmean(loss, axes)

    batch_spec = P(data_axis, seq_axis)
    return jax.jit(shard_map(
        step_body,
        mesh=mesh,
        in_specs=(P(), P(), batch_spec, batch_spec, P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    ), donate_argnums=(0, 1))


def make_sp_eval_step(model, mesh, seq_axis: str = "seq",
                      data_axis: Optional[str] = None, compute_dtype=None):
    """-> jitted forward (params, x) -> fp32 logits for validation.

    The model's attention binds ``seq_axis`` via lax.axis_index, so plain
    ``jit`` cannot evaluate it -- the eval forward must run under the same
    shard_map topology as the train step."""

    def fwd(params, x):
        cp = _cast_params(params, compute_dtype)
        out, _ = model.apply(cp, (), x, training=False, rng=None)
        return out.astype(jnp.float32)

    batch_spec = P(data_axis, seq_axis)
    return jax.jit(shard_map(
        fwd, mesh=mesh,
        in_specs=(P(), batch_spec),
        out_specs=batch_spec,
        check_vma=False,
    ))


def shard_tokens(x, mesh, seq_axis="seq", data_axis=None):
    """Place a host token array with (data, seq) sharding."""
    import numpy as np

    sharding = NamedSharding(mesh, P(data_axis, seq_axis))
    return jax.make_array_from_process_local_data(sharding, np.asarray(x))
