"""Pipeline parallelism (GPipe schedule) over a ``pipe`` mesh axis.

No reference analogue (SURVEY.md section 2.4: pipeline parallelism absent) --
built the canonical TPU way: transformer blocks are split into ``n_stages``
contiguous stages whose parameters are *stacked* on a leading stage dimension
and sharded over the ``pipe`` mesh axis.  Inside ``shard_map`` every device
runs its own stage; activations move stage->stage with a single
``lax.ppermute`` hop per schedule tick (nearest-neighbour on the ICI ring,
the cheapest collective there is).  The schedule is the classic GPipe loop:
``n_micro + n_stages - 1`` ticks, each device computing every tick (bubble
ticks compute garbage that is masked out), microbatch *t* entering stage 0 at
tick *t* and leaving the last stage at tick ``t + n_stages - 1``.

Autodiff runs straight through the schedule: the transpose of ``ppermute`` is
the reverse-ring ``ppermute``, so ``jax.grad`` of the shard_map'd loss *is*
the 1F1B-ish backward pipeline -- no hand-written backward schedule.

Embedding and the LM head are computed replicated (they are cheap relative
to the blocks); only the block stack is pipelined.  Composes with data
parallelism via a 2-D ``(data, pipe)`` mesh: the batch is sharded over
``data`` and shard_map's transpose machinery inserts the gradient psums.
"""

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from bigdl_tpu.nn.module import child_rng


def stack_stage_params(model, n_stages: int):
    """Split a built TransformerLM's blocks into ``n_stages`` stacked stages.

    -> dict with
       ``embed``:  {wte, wpe}                       (replicated)
       ``stages``: {layer{j}: block-params-stacked} (leading dim = stage)
       ``tail``:   {ln_f, head}                     (replicated)
    """
    params = model._params
    n_layers = len(model.blocks)
    assert n_layers % n_stages == 0, (n_layers, n_stages)
    lps = n_layers // n_stages
    stages = {}
    for j in range(lps):
        per_stage = [params[f"block{s * lps + j}"] for s in range(n_stages)]
        stages[f"layer{j}"] = jax.tree.map(
            lambda *xs: jnp.stack(xs), *per_stage)
    return {
        "embed": {"wte": params["wte"], "wpe": params["wpe"]},
        "stages": stages,
        "tail": {"ln_f": params["ln_f"], "head": params["head"]},
    }


def unstack_stage_params(model, pp_params):
    """Inverse of stack_stage_params -> plain TransformerLM params dict."""
    out = {"wte": pp_params["embed"]["wte"], "wpe": pp_params["embed"]["wpe"],
           "ln_f": pp_params["tail"]["ln_f"],
           "head": pp_params["tail"]["head"]}
    stages = pp_params["stages"]
    lps = len(stages)
    n_stages = jax.tree.leaves(stages["layer0"])[0].shape[0]
    for s in range(n_stages):
        for j in range(lps):
            out[f"block{s * lps + j}"] = jax.tree.map(
                lambda a: a[s], stages[f"layer{j}"])
    return out


def pp_shardings(pp_params, mesh, pipe_axis="pipe"):
    """NamedShardings: stage-stacked leaves sharded on dim 0, rest replicated."""
    rep = NamedSharding(mesh, P())
    staged = NamedSharding(mesh, P(pipe_axis))
    return {
        "embed": jax.tree.map(lambda _: rep, pp_params["embed"]),
        "stages": jax.tree.map(lambda _: staged, pp_params["stages"]),
        "tail": jax.tree.map(lambda _: rep, pp_params["tail"]),
    }


def pp_tp_shardings(pp_params, mesh, pipe_axis="pipe", model_axis="model",
                    rules=None):
    """3-D composition shardings: stage-stacked leaves sharded over
    ``pipe`` on dim 0 AND Megatron-style over ``model`` on their weight
    dims (TRANSFORMER_TP_RULES shifted by the stage dimension); embed/tail
    replicated.  Use with make_pp_train_step(..., manual_axes=
    ("data", "pipe")) so the model axis stays automatic (GSPMD)."""
    import re

    from jax.tree_util import keystr, tree_flatten_with_path, tree_unflatten

    from bigdl_tpu.parallel.tp import TRANSFORMER_TP_RULES

    rules = rules if rules is not None else TRANSFORMER_TP_RULES
    rep = NamedSharding(mesh, P())

    def stage_shardings(tree):
        leaves, treedef = tree_flatten_with_path(tree)
        out = []
        for path, leaf in leaves:
            name = keystr(path)
            spec = [pipe_axis] + [None] * (leaf.ndim - 1)
            for pattern, dims in rules:
                if re.search(pattern, name):
                    if len(dims) == leaf.ndim - 1:
                        spec = [pipe_axis] + [
                            d if d is None else model_axis for d in dims]
                    break
            out.append(NamedSharding(mesh, P(*spec)))
        return tree_unflatten(treedef, out)

    return {
        "embed": jax.tree.map(lambda _: rep, pp_params["embed"]),
        "stages": stage_shardings(pp_params["stages"]),
        "tail": jax.tree.map(lambda _: rep, pp_params["tail"]),
    }


def make_pp_loss_fn(model, criterion, mesh, n_microbatches: int,
                    pipe_axis: str = "pipe",
                    data_axis: Optional[str] = None,
                    manual_axes: Optional[tuple] = None,
                    compute_dtype=None):
    """-> loss(pp_params, x_tokens, y_tokens) with the GPipe schedule inside.

    ``x``/``y``: int32 (batch, T); batch must divide n_microbatches (times
    the data-axis size when present).

    ``manual_axes``: mesh axes handled manually by this shard_map; axes NOT
    listed (e.g. a ``model`` tensor-parallel axis on a 3-D mesh) stay
    automatic -- GSPMD partitions the per-stage math over them from the
    argument shardings (pp_tp_shardings).  Default: all mesh axes manual
    (the 2-D data x pipe case).
    """
    n_stages = mesh.shape[pipe_axis]
    lps = len(model.blocks) // n_stages

    def stage_fn(stage_params, x, rng):
        for j in range(lps):
            x, _ = model.blocks[0].apply(
                stage_params[f"layer{j}"], (), x, training=True,
                rng=child_rng(rng, j))
        return x

    def per_device(pp_params, x, y, rng):
        # x, y: (n_micro, mb_local, T) on this device
        from bigdl_tpu.optim.train_step import _cast_params
        cdt = compute_dtype or jnp.float32
        stage = lax.axis_index(pipe_axis)
        # slice the stage dim off BEFORE the compute-dtype cast, so the
        # rank>=2 rule sees the true per-leaf ranks (a stacked bias is
        # (n_stages, C) -- rank 2 -- but is still a VPU vector operand
        # that must stay an fp32 master)
        sp = _cast_params(jax.tree.map(lambda a: a[0],
                                       pp_params["stages"]), compute_dtype)
        emb = _cast_params(pp_params["embed"], compute_dtype)
        tailp = _cast_params(pp_params["tail"], compute_dtype)
        n_micro, mb, t = x.shape

        def embed(tok):
            h = jnp.take(emb["wte"], tok, axis=0)
            return h + emb["wpe"][:t][None]

        d = emb["wte"].shape[1]
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, tk):
            recv, outs = carry
            mb_idx = jnp.clip(tk, 0, n_micro - 1)
            inp = jnp.where(stage == 0, embed(x[mb_idx]), recv)
            out = stage_fn(sp, inp, child_rng(child_rng(rng, 7), tk))
            out_idx = tk - (n_stages - 1)
            valid = (stage == n_stages - 1) & (out_idx >= 0)
            widx = jnp.clip(out_idx, 0, n_micro - 1)
            outs = outs.at[widx].set(jnp.where(valid, out, outs[widx]))
            send = lax.ppermute(out, pipe_axis, fwd_perm)
            return (send, outs), None

        init = (jnp.zeros((mb, t, d), cdt),
                jnp.zeros((n_micro, mb, t, d), cdt))
        (_, outs), _ = lax.scan(tick, init,
                                jnp.arange(n_micro + n_stages - 1))
        # replicated tail on the collected last-stage activations
        h = outs.reshape(n_micro * mb, t, d)
        h, _ = model.ln_f.apply(tailp["ln_f"], (), h)
        logits = h @ tailp["head"].astype(h.dtype).T
        loss_local = criterion.apply(logits.astype(jnp.float32),
                                     y.reshape(n_micro * mb, t))
        loss = lax.psum(
            jnp.where(stage == n_stages - 1, loss_local, 0.0), pipe_axis)
        if data_axis is not None:
            loss = lax.pmean(loss, data_axis)
        return loss

    batch_spec = P(None, data_axis) if data_axis else P()
    smap_kwargs = {}
    if manual_axes is not None:
        smap_kwargs["axis_names"] = frozenset(manual_axes)
    smapped = shard_map(
        per_device, mesh=mesh,
        in_specs=({"embed": P(), "stages": P(pipe_axis), "tail": P()},
                  batch_spec, batch_spec, P()),
        out_specs=P(),
        check_vma=False,
        **smap_kwargs,
    )

    def loss_fn(pp_params, x, y, rng=None):
        n, t = x.shape
        assert n % n_microbatches == 0, (n, n_microbatches)
        if data_axis is not None:
            mb = n // n_microbatches
            assert mb % mesh.shape[data_axis] == 0, (
                f"microbatch size {mb} must divide over the "
                f"'{data_axis}' axis ({mesh.shape[data_axis]} devices)")
        xm = x.reshape(n_microbatches, n // n_microbatches, t)
        ym = y.reshape(n_microbatches, n // n_microbatches, t)
        if rng is None:
            rng = jax.random.key(0)
        return smapped(pp_params, xm, ym, rng)

    return loss_fn


def make_pp_train_step(model, criterion, optim_method, mesh,
                       n_microbatches: int, pipe_axis: str = "pipe",
                       data_axis: Optional[str] = None,
                       manual_axes: Optional[tuple] = None,
                       compute_dtype=None):
    """-> jitted step(pp_params, opt_state, x, y, rng) -> (params', opt', loss).

    Stage-stacked params (and their optimizer moments) live sharded over the
    ``pipe`` axis; the update runs where the shard lives (optimizer-state
    parallelism, the pipeline analogue of the reference's chunk ownership in
    parameters/AllReduceParameter.scala:84).  ``manual_axes``: see
    make_pp_loss_fn -- pass ("data", "pipe") on a 3-D data x pipe x model
    mesh to compose with GSPMD tensor parallelism.
    """
    from bigdl_tpu.nn.module import has_frozen
    if has_frozen(model):
        raise NotImplementedError(
            "freeze() is honored by make_train_step and the "
            "DistriOptimizer flat-chunk step; this model-parallel engine "
            "does not mask frozen parameters yet -- unfreeze() before "
            "building, or train with LocalOptimizer/DistriOptimizer")
    loss_fn = make_pp_loss_fn(model, criterion, mesh, n_microbatches,
                              pipe_axis, data_axis, manual_axes,
                              compute_dtype)

    def step(pp_params, opt_state, x, y, rng):
        loss, grads = jax.value_and_grad(loss_fn)(pp_params, x, y, rng)
        with jax.named_scope("optimizer"):
            new_params, new_opt = optim_method.update(grads, opt_state,
                                                      pp_params)
        return new_params, new_opt, loss

    return jax.jit(step, donate_argnums=(0, 1))


def make_pp_1f1b_train_step(model, criterion, optim_method, mesh,
                            n_microbatches: int, pipe_axis: str = "pipe",
                            data_axis: Optional[str] = None,
                            manual_axes: Optional[tuple] = None,
                            compute_dtype=None):
    """GPipe-equivalent gradients with the 1F1B (PipeDream-flush) schedule
    and a BOUNDED activation stash.

    The GPipe path (make_pp_train_step) differentiates straight through
    its scan, so autodiff stashes one residual set per tick -- memory
    grows with ``n_microbatches``.  Here the schedule is hand-written in
    ONE scan of ``M + 2S - 1`` ticks: device ``d`` runs the forward of
    microbatch ``t - d`` and the backward of microbatch ``t - (2S-1-d)``
    in the same tick (one-forward-one-backward steady state).  Backward
    uses per-stage ``jax.vjp`` with the stage INPUT rematerialised from a
    ring stash of ``2S`` slots -- the in-flight window of the 1F1B
    schedule -- so activation memory is O(S), independent of M.  Weights
    update once at the flush, so gradients are numerically the GPipe/
    single-device gradients (asserted in tests), not the PipeDream
    weight-stashing approximation.

    Activations ride the forward ring (+1 ppermute) and gradients the
    reverse ring (-1 ppermute), one hop each per tick -- both
    nearest-neighbour on the ICI.

    Same model scope as make_pp_loss_fn: a built TransformerLM with
    stage-stacked block params (embed/tail replicated).
    """
    n_stages = mesh.shape[pipe_axis]
    lps = len(model.blocks) // n_stages
    M = n_microbatches
    S = n_stages
    W = 2 * S                     # stash slots >= max residual lifetime 2S-1

    def stage_fn(stage_params, x, rng):
        for j in range(lps):
            x, _ = model.blocks[0].apply(
                stage_params[f"layer{j}"], (), x, training=True,
                rng=child_rng(rng, j))
        return x

    def per_device(pp_params, x, y, rng):
        # x, y: (M, mb, T) int tokens on this device's data shard
        from bigdl_tpu.optim.train_step import _cast_params
        cdt = compute_dtype or jnp.float32
        stage = lax.axis_index(pipe_axis)
        # slice the stage dim BEFORE the cast so the rank>=2 rule sees
        # true per-leaf ranks (stacked biases stay fp32 masters)
        sp = _cast_params(jax.tree.map(lambda a: a[0],
                                       pp_params["stages"]), compute_dtype)
        emb = _cast_params(pp_params["embed"], compute_dtype)
        tail = _cast_params(pp_params["tail"], compute_dtype)
        n_micro, mb, t = x.shape
        d_model = emb["wte"].shape[1]
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]
        bwd_perm = [(i, (i - 1) % S) for i in range(S)]

        def embed_fn(e, tok):
            h = jnp.take(e["wte"], tok, axis=0)
            return h + e["wpe"][:t][None]

        def tail_loss(tl, h, tok_y):
            hn, _ = model.ln_f.apply(tl["ln_f"], (), h)
            logits = hn @ tl["head"].astype(hn.dtype).T
            # mean over this microbatch; the flush divides by M so the
            # total equals the criterion's full-batch mean
            return criterion.apply(logits.astype(jnp.float32), tok_y)

        def mrng(m):
            # keyed like the GPipe path's forward tick tk = m + stage
            # (make_pp_loss_fn), so (a) each stage draws distinct dropout
            # masks and (b) 1F1B gradients equal GPipe's under dropout;
            # the backward recompute reuses the same key by construction
            return child_rng(child_rng(rng, 7), m + stage)

        # fp32 gradient accumulators shaped like the UNCAST master params
        # (the per-tick vjp cotangents arrive in the compute dtype and are
        # upcast on accumulation -- the same master-grad semantics the
        # GPipe path gets from differentiating through its cast)
        zeros_g = {
            "embed": jax.tree.map(jnp.zeros_like, pp_params["embed"]),
            "stages": jax.tree.map(
                lambda a: jnp.zeros_like(a[0]), pp_params["stages"]),
            "tail": jax.tree.map(jnp.zeros_like, pp_params["tail"]),
        }

        def tick(carry, tk):
            fwd_recv, bwd_recv, stash, seeds, gacc, loss_acc = carry

            # ---- forward leg: microbatch mf = tk - stage ------------- #
            mf = tk - stage
            mf_ok = (mf >= 0) & (mf < M)
            mf_i = jnp.clip(mf, 0, M - 1)
            fwd_in = jnp.where(stage == 0,
                               embed_fn(emb, x[mf_i]), fwd_recv)
            out = stage_fn(sp, fwd_in, mrng(mf_i))
            stash = stash.at[mf_i % W].set(
                jnp.where(mf_ok, fwd_in, stash[mf_i % W]))

            # last stage: loss + seed gradient + tail grads via one vjp
            def tail_both(tl, h):
                return tail_loss(tl, h, y[mf_i])
            loss_m, tail_vjp = jax.vjp(tail_both, tail, out)
            dtail_m, seed_m = tail_vjp(jnp.ones((), jnp.float32))
            is_last = stage == S - 1
            take_loss = mf_ok & is_last
            loss_acc = loss_acc + jnp.where(take_loss, loss_m, 0.0)
            gacc = dict(gacc)
            gacc["tail"] = jax.tree.map(
                lambda a, g: a + jnp.where(take_loss, g, 0.0).astype(a.dtype),
                gacc["tail"], dtail_m)
            seeds = seeds.at[mf_i % 2].set(
                jnp.where(take_loss, seed_m, seeds[mf_i % 2]))

            # ---- backward leg: microbatch mbk = tk - (2S-1-stage) ---- #
            mbk = tk - (2 * S - 1 - stage)
            mb_ok = (mbk >= 0) & (mbk < M)
            mb_i = jnp.clip(mbk, 0, M - 1)
            xin = stash[mb_i % W]
            gin = jnp.where(stage == S - 1, seeds[mb_i % 2], bwd_recv)

            def stage_both(p, xi):
                return stage_fn(p, xi, mrng(mb_i))
            _, stage_vjp = jax.vjp(stage_both, sp, xin)
            dsp, dx = stage_vjp(gin)
            gacc["stages"] = jax.tree.map(
                lambda a, g: a + jnp.where(mb_ok, g, 0.0).astype(a.dtype),
                gacc["stages"], dsp)

            # stage 0 consumes dx into the embedding instead of the ring
            def embed_only(e):
                return embed_fn(e, x[mb_i])
            _, emb_vjp = jax.vjp(embed_only, emb)
            (demb,) = emb_vjp(dx)
            take_emb = mb_ok & (stage == 0)
            gacc["embed"] = jax.tree.map(
                lambda a, g: a + jnp.where(take_emb, g, 0.0).astype(a.dtype),
                gacc["embed"], demb)

            fwd_recv = lax.ppermute(out, pipe_axis, fwd_perm)
            bwd_recv = lax.ppermute(dx, pipe_axis, bwd_perm)
            return (fwd_recv, bwd_recv, stash, seeds, gacc, loss_acc), None

        init = (
            jnp.zeros((mb, t, d_model), cdt),
            jnp.zeros((mb, t, d_model), cdt),
            jnp.zeros((W, mb, t, d_model), cdt),
            jnp.zeros((2, mb, t, d_model), cdt),
            zeros_g,
            jnp.zeros((), jnp.float32),
        )
        (_, _, _, _, gacc, loss_acc), _ = lax.scan(
            tick, init, jnp.arange(M + 2 * S - 1))

        # flush: per-microbatch means -> full-batch mean
        loss = lax.psum(loss_acc, pipe_axis) / M
        grads = {
            "embed": jax.tree.map(
                lambda g: lax.psum(g, pipe_axis) / M, gacc["embed"]),
            # stage grads live where the stage lives; restack the leading
            # stage dim so the tree matches pp_params["stages"]
            "stages": jax.tree.map(
                lambda g: g[None] / M, gacc["stages"]),
            "tail": jax.tree.map(
                lambda g: lax.psum(g, pipe_axis) / M, gacc["tail"]),
        }
        if data_axis is not None:
            loss = lax.pmean(loss, data_axis)
            grads = jax.tree.map(lambda g: lax.pmean(g, data_axis), grads)
        return loss, grads

    batch_spec = P(None, data_axis) if data_axis else P()
    smap_kwargs = {}
    if manual_axes is not None:
        # axes not listed (a tensor-parallel "model" axis on a 3-D mesh)
        # stay automatic: GSPMD partitions the per-stage math and the
        # per-stage vjp from the argument shardings (pp_tp_shardings),
        # exactly as on the GPipe path
        smap_kwargs["axis_names"] = frozenset(manual_axes)
    smapped = shard_map(
        per_device, mesh=mesh,
        in_specs=({"embed": P(), "stages": P(pipe_axis), "tail": P()},
                  batch_spec, batch_spec, P()),
        out_specs=(P(), {"embed": P(), "stages": P(pipe_axis), "tail": P()}),
        check_vma=False,
        **smap_kwargs,
    )

    def step(pp_params, opt_state, x, y, rng):
        n, t = x.shape
        assert n % n_microbatches == 0, (n, n_microbatches)
        if data_axis is not None:
            mbs = n // n_microbatches
            assert mbs % mesh.shape[data_axis] == 0, (
                f"microbatch size {mbs} must divide over the "
                f"'{data_axis}' axis ({mesh.shape[data_axis]} devices)")
        xm = x.reshape(n_microbatches, n // n_microbatches, t)
        ym = y.reshape(n_microbatches, n // n_microbatches, t)
        loss, grads = smapped(pp_params, xm, ym, rng)
        with jax.named_scope("optimizer"):
            new_params, new_opt = optim_method.update(grads, opt_state,
                                                      pp_params)
        return new_params, new_opt, loss

    return jax.jit(step, donate_argnums=(0, 1))


def init_pp_opt_state(optim_method, pp_params, mesh, pipe_axis="pipe"):
    """Optimizer state device_put with the same shardings as its params."""
    from bigdl_tpu.parallel.zero import shard_opt_state

    ps = pp_shardings(pp_params, mesh, pipe_axis)
    return shard_opt_state(optim_method, pp_params, ps, mesh)
