"""Expert parallelism: MoE expert-stacked params sharded over an ``expert``
mesh axis via GSPMD annotations.

No reference analogue (SURVEY.md section 2.4: expert parallelism absent).
The MoE layer (nn/moe.py) keeps experts stacked on a leading dimension; here
that dimension is annotated with ``NamedSharding(P("expert", ...))`` and the
batch with ``P("data")``.  XLA's SPMD partitioner then turns the
dispatch/combine einsums (``tec,td->ecd`` / ``tec,ecd->td``) into
all-to-all + local expert matmuls -- the same comm pattern hand-written EP
implementations build with ``lax.all_to_all``, derived automatically.
"""

import re
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.tree_util import keystr, tree_flatten_with_path, tree_unflatten

#: expert-stacked leaves: leading dim sharded over the expert axis.
MOE_EP_RULES = [
    (r"moe'\]\['w1", ("expert", None, None)),
    (r"moe'\]\['w2", ("expert", None, None)),
    (r"moe'\]\['b1", ("expert", None)),
    (r"moe'\]\['b2", ("expert", None)),
]


def ep_sharding_for_params(params, mesh, rules=MOE_EP_RULES):
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


def ep_shard_params(params, mesh, rules=MOE_EP_RULES):
    return jax.tree.map(jax.device_put, params,
                        ep_sharding_for_params(params, mesh, rules))


def make_ep_train_step(model, criterion, optim_method, mesh,
                       data_axis: Optional[str] = "data",
                       aux_weight: float = 0.01, rules=MOE_EP_RULES,
                       compute_dtype=None):
    """-> compile_for(params) -> jitted step with expert-parallel params.

    Task loss + ``aux_weight``  x  router load-balance loss; expert params
    (and their optimizer moments) updated where their shard lives.
    """
    from bigdl_tpu.nn.module import has_frozen
    from bigdl_tpu.optim.train_step import _cast_tree
    if has_frozen(model):
        raise NotImplementedError(
            "freeze() is honored by make_train_step and the "
            "DistriOptimizer flat-chunk step; this model-parallel engine "
            "does not mask frozen parameters yet -- unfreeze() before "
            "building, or train with LocalOptimizer/DistriOptimizer")

    def _cast_ep_params(p):
        """Compute-dtype cast with the stacked-layout correction: expert
        biases are stored stacked as (E, features) -- rank 2 -- but are
        still VPU vector operands per expert, so they keep the fp32
        master treatment the rank rule gives unstacked biases (the MoE
        layer casts them at its use site, nn/moe.py:102-105)."""
        if compute_dtype is None:
            return p
        from jax.tree_util import keystr, tree_flatten_with_path, \
            tree_unflatten
        leaves, treedef = tree_flatten_with_path(p)
        out = []
        for path, leaf in leaves:
            bias_like = re.search(r"\['b[12]'\]$", keystr(path))
            if (jnp.issubdtype(leaf.dtype, jnp.floating)
                    and leaf.ndim >= 2 and not bias_like):
                leaf = leaf.astype(compute_dtype)
            out.append(leaf)
        return tree_unflatten(treedef, out)

    def step(params, opt_state, x, y, rng):
        def loss_fn(p):
            cp = _cast_ep_params(p)
            logits, st = model.apply(cp, (), x, training=True, rng=rng)
            task = criterion.apply(logits.astype(jnp.float32), y)
            return task + aux_weight * st["aux_loss"].astype(jnp.float32), \
                task

        (loss, task), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params)
        with jax.named_scope("optimizer"):
            grads = _cast_tree(grads, jnp.float32)
            new_params, new_opt = optim_method.update(grads, opt_state,
                                                      params)
        return new_params, new_opt, task

    def compile_for(params):
        from bigdl_tpu.parallel.zero import opt_state_shardings

        ps = ep_sharding_for_params(params, mesh, rules)
        batch_sh = NamedSharding(mesh, P(data_axis))
        rep = NamedSharding(mesh, P())
        # optimizer-state shardings pinned on BOTH sides (same fix as
        # parallel/tp.py): with the opt output left to propagation,
        # GSPMD picks an expert-sharded layout for the ROUTER's Adam
        # moments while the donated input plane is replicated, and XLA
        # refuses the alias at dispatch ("Expected aliased input ... to
        # have the same size") -- the 8-device ep dryrun failure
        opt_sh = opt_state_shardings(optim_method, params, ps, mesh)
        return jax.jit(
            step,
            in_shardings=(ps, opt_sh, batch_sh, batch_sh, rep),
            out_shardings=(ps, opt_sh, rep),
            donate_argnums=(0, 1),
        )

    return compile_for


def init_ep_opt_state(optim_method, params, mesh, rules=MOE_EP_RULES):
    """Optimizer moments sharded like their params; scalars replicated."""
    from bigdl_tpu.parallel.zero import shard_opt_state

    ps = ep_sharding_for_params(params, mesh, rules)
    return shard_opt_state(optim_method, params, ps, mesh)
