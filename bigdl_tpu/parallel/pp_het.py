"""Pipeline parallelism for arbitrary (uneven, heterogeneous) Sequential
models -- CNNs included.

Round-5 generalization of parallel/pp.py (VERDICT r4 ask #4): the stacked
GPipe path requires identical per-stage pytrees (homogeneous transformer
blocks).  Real models -- a ResNet-style CNN, a Sequential with mixed layer
types, uneven splits -- have per-stage parameter trees of DIFFERENT
structure and activation shapes that change across stage boundaries, so
neither the stage-stacked parameter layout nor the fixed-shape ppermute
ring applies.

TPU-native design:

- **Stage selection by ``lax.switch``**: every device runs the same SPMD
  program; ``lax.switch(axis_index(pipe), branches, buffer)`` picks the
  device's stage body.  All stage parameters ride in replicated (their
  bytes are small next to CNN activations); activations -- the dominant
  memory term -- are pipelined.
- **Padded flat ring buffer**: ``ppermute`` needs one static shape on
  every hop, so boundary activations are flattened to ``(mb, width)``
  and zero-padded to the widest boundary; each stage body unflattens its
  statically-known input shape, computes, and re-pads.  The pad bytes are
  dead stores XLA sinks into the same fusion as the stage compute.
- **GPipe schedule in one ``lax.scan``** (``n_micro + n_stages - 1``
  ticks), autodiff straight through -- the transpose of ``ppermute`` is
  the reverse-ring ``ppermute``, exactly as in parallel/pp.py.

Composes with data parallelism over a 2-D ``(data, pipe)`` mesh: batch
sharded over ``data``, shard_map's transpose inserts the gradient psums.
"""

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from bigdl_tpu.nn.module import child_rng
from bigdl_tpu.optim.train_step import _cast_params, _cast_tree


def partition_sequential(model, n_stages: int,
                         boundaries: Optional[Sequence[int]] = None):
    """Split a built ``nn.Sequential`` into pipeline stages.

    ``boundaries``: child indices that START stages 1..n-1 (stage 0 starts
    at child 0); len == n_stages - 1.  Omitted -> auto-balance by
    parameter count (greedy prefix split).  Uneven and heterogeneous
    splits are the point: ``[2, 7, 9]`` gives stages of 2/5/2/rest
    children.

    -> (stage_slices, stage_params): per-stage (start, stop) child ranges
    and the per-stage parameter subtrees (different structures allowed).
    """
    n_children = len(model.modules)
    if boundaries is None:
        sizes = [sum(int(np.prod(l.shape))
                     for l in jax.tree.leaves(model._params[str(i)]))
                 for i in range(n_children)]
        total = sum(sizes)
        # greedy: cut whenever the running stage reaches its fair share,
        # leaving enough children for the remaining stages
        boundaries = []
        acc = 0
        for i, s in enumerate(sizes):
            acc += s
            if (len(boundaries) < n_stages - 1
                    and acc >= total / n_stages
                    and n_children - (i + 1) >= n_stages - 1 - len(boundaries)):
                boundaries.append(i + 1)
                acc = 0
        while len(boundaries) < n_stages - 1:   # param-less tails
            boundaries.append(n_children - (n_stages - 1 - len(boundaries)))
    boundaries = list(boundaries)
    if len(boundaries) != n_stages - 1:
        raise ValueError(
            f"need {n_stages - 1} boundaries for {n_stages} stages, got "
            f"{len(boundaries)}")
    cuts = [0] + boundaries + [n_children]
    if any(cuts[i] >= cuts[i + 1] for i in range(n_stages)):
        raise ValueError(f"empty stage in boundaries {boundaries} "
                         f"({n_children} children)")
    slices = [(cuts[i], cuts[i + 1]) for i in range(n_stages)]
    stage_params = [
        {str(j): model._params[str(j)] for j in range(a, b)}
        for a, b in slices
    ]
    return slices, stage_params


def _boundary_specs(model, slices, input_spec):
    """Activation spec entering each stage (index 0 = model input) plus
    the final output spec."""
    specs = [input_spec]
    spec = input_spec
    for i, layer in enumerate(model.modules):
        p, s = model._params[str(i)], model._state[str(i)]
        spec = layer.output_spec(p, s, spec)
        for a, b in slices[1:]:
            if i + 1 == a:
                specs.append(spec)
    return specs, spec


def make_het_pp_train_step(model, criterion, optim_method, mesh,
                           n_microbatches: int, input_spec,
                           boundaries: Optional[Sequence[int]] = None,
                           pipe_axis: str = "pipe",
                           data_axis: Optional[str] = None,
                           compute_dtype=None):
    """-> (step, stage_params) for an arbitrary Sequential.

    ``step(stage_params, opt_state, x, y, rng) -> (params, opt, loss)``
    (the shared strategy-step convention).  ``stage_params`` is the
    list-of-subtrees pytree from partition_sequential -- replicated on
    every device; optimizer state mirrors it.

    ``input_spec``: ShapeDtypeStruct of one MICROBATCH (local to the data
    shard), e.g. ``(mb, H, W, C)`` -- boundary shapes are inferred from
    it, so it must be the true per-device microbatch shape.
    """
    from bigdl_tpu.nn.module import has_frozen
    if has_frozen(model):
        raise NotImplementedError(
            "freeze() is not honored by the pipeline engines; unfreeze() "
            "or train with LocalOptimizer/DistriOptimizer")
    if any(jnp.issubdtype(getattr(l, "dtype", jnp.int32), jnp.floating)
           for l in jax.tree.leaves(model._state)):
        raise NotImplementedError(
            "pipelined Sequential with floating module state (BatchNorm "
            "running stats) is not supported; swap BN for a stateless "
            "normalization or train data-parallel")

    n_stages = mesh.shape[pipe_axis]
    slices, init_stage_params = partition_sequential(
        model, n_stages, boundaries)
    # fresh buffers: the returned step donates its params argument, and the
    # partition subtrees alias model._params -- donating those would leave
    # the model holding deleted arrays
    init_stage_params = jax.tree.map(jnp.array, init_stage_params)
    bspecs, out_spec = _boundary_specs(model, slices, input_spec)
    cdt = compute_dtype or jnp.float32

    mb = input_spec.shape[0]
    widths = [int(np.prod(s.shape[1:])) for s in bspecs]
    out_width = int(np.prod(out_spec.shape[1:]))
    width = max(widths + [out_width])

    def stage_body(s, stage_params, flat_in, rng):
        a, b = slices[s]
        x = flat_in[:, :widths[s]].reshape(
            (mb,) + bspecs[s].shape[1:]).astype(
                bspecs[s].dtype if not jnp.issubdtype(
                    bspecs[s].dtype, jnp.floating) else cdt)
        for j in range(a, b):
            x, _ = model.modules[j].apply(
                stage_params[str(j)], model._state[str(j)], x,
                training=True, rng=child_rng(rng, j))
        flat = x.reshape(mb, -1).astype(cdt)
        pad = width - flat.shape[1]
        return jnp.pad(flat, ((0, 0), (0, pad))) if pad else flat

    def per_device(stage_params_list, x, y, rng):
        # x: (n_micro, mb, ...) local shard; y: (n_micro, mb, ...)
        stage = lax.axis_index(pipe_axis)
        n_micro = x.shape[0]
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        branches = [
            lambda flat, rng, s=s: stage_body(
                s, _cast_params(stage_params_list[s], compute_dtype),
                flat, rng)
            for s in range(n_stages)
        ]

        def embed_input(m_idx):
            flat = x[m_idx].reshape(mb, -1).astype(cdt)
            pad = width - flat.shape[1]
            return jnp.pad(flat, ((0, 0), (0, pad))) if pad else flat

        def tick(carry, tk):
            recv, outs = carry
            m_idx = jnp.clip(tk, 0, n_micro - 1)
            inp = jnp.where(stage == 0, embed_input(m_idx), recv)
            out = lax.switch(stage, branches, inp, child_rng(rng, tk))
            out_idx = tk - (n_stages - 1)
            valid = (stage == n_stages - 1) & (out_idx >= 0)
            widx = jnp.clip(out_idx, 0, n_micro - 1)
            outs = outs.at[widx].set(jnp.where(valid, out, outs[widx]))
            send = lax.ppermute(out, pipe_axis, fwd_perm)
            return (send, outs), None

        init = (jnp.zeros((mb, width), cdt),
                jnp.zeros((n_micro, mb, width), cdt))
        (_, outs), _ = lax.scan(tick, init,
                                jnp.arange(n_micro + n_stages - 1))
        logits = outs[:, :, :out_width].reshape(
            (n_micro * mb,) + out_spec.shape[1:]).astype(jnp.float32)
        yf = y.reshape((n_micro * mb,) + y.shape[2:])
        loss_local = criterion.apply(logits, yf)
        loss = lax.psum(
            jnp.where(stage == n_stages - 1, loss_local, 0.0), pipe_axis)
        if data_axis is not None:
            loss = lax.pmean(loss, data_axis)
        return loss

    batch_spec = P(None, data_axis) if data_axis else P()
    smapped = shard_map(
        per_device, mesh=mesh,
        in_specs=(P(), batch_spec, batch_spec, P()),
        out_specs=P(),
        check_vma=False,
    )

    data_size = mesh.shape[data_axis] if data_axis else 1
    expected_n = n_microbatches * data_size * mb

    def loss_fn(stage_params_list, x, y, rng):
        n = x.shape[0]
        if n != expected_n:
            # the stage bodies bake the microbatch shape from input_spec;
            # a drifting batch (e.g. a short final minibatch) must fail
            # with the cause, not a reshape error inside the scan
            raise ValueError(
                f"batch {n} != the compiled pipeline batch {expected_n} "
                f"({n_microbatches} microbatches x {data_size} data "
                f"shards x microbatch {mb}); use SampleToMiniBatch"
                f"(..., drop_remainder=True) or a batch-preserving "
                f"dataset")
        xm = x.reshape((n_microbatches, n // n_microbatches) + x.shape[1:])
        ym = y.reshape((n_microbatches, n // n_microbatches) + y.shape[1:])
        return smapped(stage_params_list, xm, ym, rng)

    def step(stage_params_list, opt_state, x, y, rng):
        loss, grads = jax.value_and_grad(loss_fn)(stage_params_list, x, y,
                                                  rng)
        with jax.named_scope("optimizer"):
            grads = _cast_tree(grads, jnp.float32)
            new_params, new_opt = optim_method.update(grads, opt_state,
                                                      stage_params_list)
        return new_params, new_opt, loss

    return jax.jit(step, donate_argnums=(0, 1)), init_stage_params


def merge_stage_params(model, stage_params_list):
    """Fold per-stage subtrees back into the Sequential's params dict."""
    out = {}
    for sub in stage_params_list:
        out.update(sub)
    return out
