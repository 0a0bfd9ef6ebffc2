"""Tensor parallelism via GSPMD sharding rules.

No reference analogue (SURVEY.md section 2.4: tensor parallelism absent) --
built the canonical TPU way: annotate parameter shardings over a ``model``
mesh axis with ``NamedSharding`` and let XLA's SPMD partitioner insert the
collectives (all-gather/reduce-scatter on ICI).  Megatron-style layout for
the transformer: column-parallel qkv/fc1 (output dim sharded), row-parallel
out/fc2 (input dim sharded), so each block needs exactly one psum per
sub-layer, which GSPMD derives automatically from these annotations.
"""

import re
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.tree_util import keystr, tree_flatten_with_path, tree_unflatten

from bigdl_tpu.optim.train_step import _cast_params, _cast_tree

#: path-regex -> per-dim sharding over the model axis.  None entries mean
#: replicated.  Applied to TransformerLM parameter paths.
TRANSFORMER_TP_RULES = [
    (r"qkv_weight", ("model", None)),     # column parallel (heads sharded)
    (r"qkv_bias", ("model",)),
    (r"out_weight", (None, "model")),     # row parallel
    (r"fc1'\]\['weight", ("model", None)),
    (r"fc1'\]\['bias", ("model",)),
    (r"fc2'\]\['weight", (None, "model")),
    (r"\['head'\]$", ("model", None)),    # vocab-sharded lm head
]


def sharding_for_params(params, mesh, rules=TRANSFORMER_TP_RULES):
    """-> pytree of NamedSharding matching ``rules`` by parameter path."""
    leaves, treedef = tree_flatten_with_path(params)
    out = []
    for path, leaf in leaves:
        name = keystr(path)
        spec = P()
        for pattern, dims in rules:
            if re.search(pattern, name):
                if len(dims) == getattr(leaf, "ndim", 0):
                    spec = P(*dims)
                break
        out.append(NamedSharding(mesh, spec))
    return tree_unflatten(treedef, out)


def shard_params(params, mesh, rules=TRANSFORMER_TP_RULES):
    shardings = sharding_for_params(params, mesh, rules)
    return jax.tree.map(jax.device_put, params, shardings)


def make_tp_train_step(model, criterion, optim_method, mesh,
                       data_axis: Optional[str] = "data",
                       rules=TRANSFORMER_TP_RULES, compute_dtype=None):
    """-> jitted GSPMD train step with tensor-parallel params.

    ``x``/``y`` batch-sharded over ``data_axis``; params sharded per rules;
    optimizer state inherits the param shardings (each device updates only
    its param shard -- optimizer-state parallelism for free).
    """
    from bigdl_tpu.nn.module import has_frozen
    if has_frozen(model):
        raise NotImplementedError(
            "freeze() is honored by make_train_step and the "
            "DistriOptimizer flat-chunk step; this model-parallel engine "
            "does not mask frozen parameters yet -- unfreeze() before "
            "building, or train with LocalOptimizer/DistriOptimizer")

    def step(params, opt_state, x, y, rng):
        def loss_fn(p):
            cp = _cast_params(p, compute_dtype)
            out, _ = model.apply(cp, (), x, training=True, rng=rng)
            return criterion.apply(out.astype(jnp.float32), y)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        with jax.named_scope("optimizer"):
            grads = _cast_tree(grads, jnp.float32)
            new_params, new_opt = optim_method.update(grads, opt_state,
                                                      params)
        return new_params, new_opt, loss

    def compile_for(params):
        from bigdl_tpu.parallel.zero import opt_state_shardings

        ps = sharding_for_params(params, mesh, rules)
        batch_sh = NamedSharding(mesh, P(data_axis))
        rep = NamedSharding(mesh, P())
        # optimizer-state shardings pinned on BOTH sides: with the
        # output sharding left to propagation, GSPMD occasionally picks
        # a different layout for a moment plane than its donated input
        # carries, and XLA silently drops that buffer's alias -- the
        # plane is then double-buffered (caught by tools/hlo_audit.py)
        opt_sh = opt_state_shardings(optim_method, params, ps, mesh)
        return jax.jit(
            step,
            in_shardings=(ps, opt_sh, batch_sh, batch_sh, rep),
            out_shardings=(ps, opt_sh, rep),
            donate_argnums=(0, 1),
        )

    return compile_for


def init_opt_state_sharded(optim_method, params, mesh,
                           rules=TRANSFORMER_TP_RULES):
    """Optimizer state placed with the same shardings as its params
    (moments shard like weights; scalars replicated)."""
    from bigdl_tpu.parallel.zero import shard_opt_state

    ps = sharding_for_params(params, mesh, rules)
    return shard_opt_state(optim_method, params, ps, mesh)
