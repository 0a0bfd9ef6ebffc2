"""Distributed synchronous training over a TPU mesh.

Reference: optim/DistriOptimizer.scala:52 -- two Spark jobs per iteration
(fwd/bwd with BlockManager weight fetch; then chunk-owner gradient
aggregation + optimize + weight republish).

TPU-native redesign (SURVEY.md section 7): ONE jitted, shard_map'd XLA
program per step over the ICI mesh:

    local fwd/bwd on the device's batch shard
      -> reduce_scatter(grad)   [replaces putGradients/aggregateGradientPartition]
      -> OptimMethod on own chunk (ZeRO-1 state sharding, as the reference
         shards OptimMethod state per node)
      -> all_gather(weights)    [replaces sendWeightPartition/getWeights]

The collectives' WIRE FORMAT is first-class (``grad_compression=``,
``ops/quantization.py``): narrow-float casts, or blockwise int8 over an
``all_to_all`` with per-block scales and an optional EF-SGD residual
plane -- the generalization of the reference's FP16CompressedTensor
(docs/performance.md, "Gradient compression").

Straggler dropping (optim/DistriOptimizer.scala:177-186) has no analogue:
ICI collectives are synchronous and chips don't straggle; per-step wall-time
metrics are kept instead (SURVEY.md section 5).
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from bigdl_tpu.ops.quantization import (CompressionSpec,
                                        dequantize_blockwise,
                                        quantize_blockwise,
                                        quantized_reduce_chunks,
                                        uncompressed_wire_summary)
from bigdl_tpu.optim.local_optimizer import BaseOptimizer, validate
from bigdl_tpu.optim.optim_method import clip_by_value
from bigdl_tpu.optim.train_step import _cast_params, _cast_tree
from bigdl_tpu.parallel.reshard import LayoutSpec, redistribute
from bigdl_tpu.parallel.zero import (FlatParamSpace, refit_flat_plane,
                                     repartition_ef_residual)
from bigdl_tpu.utils import file_io
from bigdl_tpu.utils.engine import Engine
from bigdl_tpu.utils.random_generator import RNG

log = logging.getLogger("bigdl_tpu.optim")


def make_distri_train_step(model, criterion, optim_method, flat_space,
                           mesh, axis="data", compute_dtype=None,
                           clip_value=None, clip_norm=None,
                           grad_compression=None, sync_bn=False,
                           health_stats=False):
    """Build the per-device step body and its shard_map wrapper.

    ``grad_compression``: the wire format of the data-plane collectives
    -- any spelling ``CompressionSpec.parse`` accepts (the legacy
    ``jnp.bfloat16`` / ``jnp.float16`` dtypes, ``"bf16"``-style strings,
    or a full ``CompressionSpec``) -- the TPU analogue of the
    reference's fp16 on-the-wire compression
    (parameters/FP16CompressedTensor.scala:26,173-199), generalized:

    - ``"bf16"`` / ``"fp16"``: the historical cast path -- gradients
      ride ``psum_scatter`` in the narrow dtype and the reduction output
      converts back to fp32 before the optimizer update, exactly like
      the reference decompresses after aggregation.  Parity guarantee
      (pinned by tests/test_quant_collectives.py): on an MLP-scale
      model the cast step's loss trajectory tracks the fp32 step's
      within ~1e-2 relative after tens of steps -- the wire rounds each
      gradient element to ~8 (bf16) / ~11 (fp16) mantissa bits, a
      zero-mean perturbation the optimizer averages out; it does NOT
      change convergence class.  fp16's narrow exponent (max ~65504)
      can saturate pathological gradients where bf16 cannot -- prefer
      bf16 unless reproducing the reference bit-for-bit.
    - ``"int8"`` (``CompressionSpec(wire="int8", ...)``): blockwise
      quantized wire (ops/quantization.py).  The ``psum_scatter``
      becomes quantize -> ``all_to_all`` of int8 payload + per-block
      scales over the data axis -> local dequant-and-sum in fp32 ->
      own ZeRO-1 chunk; ~4x less wire than fp32.  With
      ``error_feedback=True`` the step carries an EF-SGD residual
      plane (one fp32 local-gradient buffer per device, sharded over
      the data axis like the optimizer state): each device adds its
      accumulated quantization error to the next step's local gradient
      before quantizing, so the applied updates telescope to the fp32
      trajectory.  ``compress_weight_gather=True`` additionally rides
      the weight ``all_gather`` in the same block format as a
      quantized DELTA applied to the replicated fp32 master vector
      (masters never drop to int8 precision; replicas stay
      bit-identical because every device applies the same dequantized
      bytes).

    ``health_stats=True`` appends two traced args (``sample`` bool,
    ``seg_ids`` = this plane's layer-id map sharded like the flat
    vector) and a final output: the per-layer numerics tree of
    ``observability.health.flat_health_stats``, computed from each
    device's chunk via ``segment_sum`` + ``psum`` under ``lax.cond`` --
    replica-consistent stats of the GLOBAL mean gradient, so device 0
    suffices and non-sample steps pay nothing.  Under a compressed wire
    the sampled branch re-reduces the raw gradient in fp32
    (one extra reduce-scatter on sampled steps only): the stats read
    the PRE-quantization gradient, so per-layer norms stay comparable
    across compression settings.

    Step signature (positional, after the fixed six): ``ef_residual``
    (when the spec has error feedback), then ``sample, seg_ids`` (when
    ``health_stats``).  Outputs append in the same order.
    """
    spec = CompressionSpec.parse(grad_compression)
    use_ef = spec is not None and spec.error_feedback
    n_chunks = flat_space.num_chunks
    if spec is not None and spec.quantized \
            and flat_space.chunk_size % spec.block_size != 0:
        raise ValueError(
            f"ZeRO-1 chunk size {flat_space.chunk_size} is not a "
            f"multiple of the quantization block "
            f"({spec.block_size}); build the FlatParamSpace with "
            f"block_size={spec.block_size}")

    from bigdl_tpu.nn.module import frozen_param_mask, has_frozen
    from bigdl_tpu.optim.regularizer import (has_regularizers,
                                             regularization_loss)
    use_reg = has_regularizers(model)
    n_layers = len(jax.tree.leaves(model.parameters()[0]))
    # freeze() support on the flat parameter plane: the static bool mask
    # flattens to a 0/1 vector laid out exactly like the params (padding
    # = 0, i.e. held), chunked per device below
    if has_frozen(model):
        mask_tree = frozen_param_mask(model)
        freeze_mask_flat = flat_space.flatten(jax.tree.map(
            lambda _, keep: jnp.full(_.shape, 1.0 if keep else 0.0,
                                     jnp.float32),
            model.parameters()[0], mask_tree))
    else:
        freeze_mask_flat = None

    def step_body(params_flat, mstate, opt_state, x, target, rng, *extra):
        # optional traced args ride positionally after the fixed six:
        # [ef_residual] (wire spec has error feedback), [sample, seg_ids]
        # (health_stats) -- mirrored by wrap()'s in_specs
        i = 0
        ef = None
        if use_ef:
            ef, i = extra[0], 1
        sample, seg_ids = (extra[i], extra[i + 1]) if health_stats \
            else (None, None)
        # per-device view: params_flat replicated, x/target = this device's shard
        rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))

        def loss_fn(pflat):
            params = flat_space.unflatten(pflat)
            cp = _cast_params(params, compute_dtype)
            cx = _cast_tree(x, compute_dtype)
            # sync_bn: cross-replica BN statistics -- the distributed step
            # then matches single-device full-batch math (~1e-6) instead
            # of per-shard stats (~1e-2 drift); one extra pmean per BN
            # layer on the ICI
            from contextlib import nullcontext
            from bigdl_tpu.nn.normalization import sync_batchnorm
            with sync_batchnorm(axis) if sync_bn else nullcontext():
                out, new_mstate = model.apply(cp, mstate, cx,
                                              training=True, rng=rng)
            out32 = _cast_tree(out, jnp.float32)
            data_loss = criterion.apply(out32, target)
            total = data_loss
            if use_reg:
                # per-layer wRegularizer/bRegularizer gradient contributions
                # enter via autodiff; the REPORTED loss stays the bare
                # criterion value like the reference (accGradParameters
                # touches gradients only)
                total = total + regularization_loss(model, params)
            return total, (data_loss, new_mstate)

        (_, (loss, new_mstate)), gflat = jax.value_and_grad(
            loss_fn, has_aux=True)(params_flat)
        n_dev = jax.lax.psum(1, axis)
        raw_gflat = gflat            # pre-wire, pre-EF: the stats source
        new_ef = None
        # mean-reduce gradients; each device keeps only its chunk (ZeRO-1)
        if spec is None:
            gchunk = jax.lax.psum_scatter(gflat, axis, tiled=True)
        elif spec.quantized:
            if use_ef:
                # EF-SGD: fold the residual (this device's accumulated
                # quantization error) into the local gradient BEFORE
                # quantizing; the new residual is exactly what this
                # step's wire dropped
                gflat = gflat + ef[0]
            gchunk, err = quantized_reduce_chunks(
                gflat, n_chunks, axis, spec,
                jax.random.fold_in(rng, 0x5149))
            if use_ef:
                new_ef = err[None, :]
        else:
            wire = gflat.astype(spec.wire_dtype)
            gchunk = jax.lax.psum_scatter(wire, axis,
                                          tiled=True).astype(gflat.dtype)
        gchunk = gchunk / n_dev
        mchunk = flat_space.chunk(freeze_mask_flat,
                                  jax.lax.axis_index(axis)) \
            if freeze_mask_flat is not None else None
        # stats gradient: post-freeze (a frozen layer's raw NaN is
        # harmless -- it never updates params -- and must not trip the
        # watchdogs), PRE-clip (clip hides explosions); matches
        # make_train_step's capture point exactly
        raw_gchunk = gchunk if mchunk is None else gchunk * mchunk
        if clip_value is not None:
            gchunk = clip_by_value(gchunk, *clip_value)
        if clip_norm is not None:
            # global norm across chunks (reference: L2NormClippingProcessor,
            # parameters/ParameterOperations.scala:71-89)
            sq = jax.lax.psum(jnp.sum(jnp.square(gchunk)), axis)
            scale = jnp.minimum(1.0, clip_norm / jnp.maximum(jnp.sqrt(sq), 1e-12))
            gchunk = gchunk * scale
        pchunk = flat_space.chunk(params_flat, jax.lax.axis_index(axis))
        if mchunk is not None:
            gchunk = gchunk * mchunk
        with jax.named_scope("optimizer"):
            new_pchunk, new_opt_state = optim_method.update(
                gchunk, opt_state, pchunk)
        if freeze_mask_flat is not None:
            # restore frozen positions so weight decay cannot leak in
            new_pchunk = mchunk * new_pchunk + (1.0 - mchunk) * pchunk
        if spec is not None and spec.compress_weight_gather:
            # the weight all_gather rides the same block format -- as a
            # quantized DELTA on top of the replicated fp32 master
            # vector: gathering raw int8 weights would clamp the
            # masters to int8 precision every step, whereas the delta's
            # error is bounded by the UPDATE's block absmax/127 (second
            # order in the learning rate).  Frozen positions have delta
            # exactly 0 and quantize to exactly 0.
            delta = new_pchunk - pchunk
            dq, ds = quantize_blockwise(
                delta, spec.block_size, stochastic=spec.stochastic,
                rng=jax.random.fold_in(rng, 0x5157),
                scale_dtype=spec.scale_dtype)
            dqf = jax.lax.all_gather(dq, axis, tiled=True)
            dsf = jax.lax.all_gather(ds, axis, tiled=True)
            new_flat = params_flat + dequantize_blockwise(
                dqf, dsf, spec.block_size)
        else:
            new_flat = jax.lax.all_gather(new_pchunk, axis, tiled=True)
        # average replicated floating state (BN running stats) across shards
        new_mstate = jax.tree.map(
            lambda s: jax.lax.pmean(s, axis)
            if jnp.issubdtype(s.dtype, jnp.floating) else s,
            new_mstate)
        loss = jax.lax.pmean(loss, axis)
        out = (new_flat, new_mstate, new_opt_state, loss)
        if new_ef is not None:
            out = out + (new_ef,)
        if sample is None:
            return out
        from bigdl_tpu.observability.health import (empty_health_stats,
                                                    flat_health_stats)

        def sampled_stats():
            if spec is None:
                stats_chunk = raw_gchunk
            else:
                # PRE-quantization gradient: re-reduce the raw local
                # gradients in fp32 (sampled steps only, inside the
                # cond) so per-layer norms stay comparable across
                # compression settings
                c = jax.lax.psum_scatter(raw_gflat, axis,
                                         tiled=True) / n_dev
                stats_chunk = c if mchunk is None else c * mchunk
            return flat_health_stats(stats_chunk, pchunk, new_pchunk,
                                     loss, seg_ids, n_layers, axis)

        stats = jax.lax.cond(sample, sampled_stats,
                             lambda: empty_health_stats(n_layers))
        return out + (stats,)

    def opt_spec(leaf):
        return P(axis) if getattr(leaf, "ndim", 0) >= 1 else P()

    #: every health-stats leaf is replicated (psum'd post-collective)
    _HEALTH_SPECS = {
        "loss": P(), "grad_norm": P(), "layer_grad_norms": P(),
        "layer_update_ratios": P(), "layer_nonfinite_grads": P(),
        "layer_nonfinite_params": P(), "sampled": P(),
    }

    def wrap(opt_state_eval):
        opt_specs = jax.tree.map(opt_spec, opt_state_eval)
        in_specs = [P(), P(), opt_specs, P(axis), P(axis), P()]
        out_specs = [P(), P(), opt_specs, P()]
        donate = [0, 1, 2]
        if use_ef:
            # the EF residual plane: global (n_dev, padded), one row --
            # this device's full local-gradient error -- per device;
            # donated like the opt state it lives beside
            in_specs.append(P(axis))
            out_specs.append(P(axis))
            donate.append(6)
        if health_stats:
            in_specs += [P(), P(axis)]
            out_specs.append(dict(_HEALTH_SPECS))
        return jax.jit(
            shard_map(
                step_body,
                mesh=mesh,
                in_specs=tuple(in_specs),
                out_specs=tuple(out_specs),
                check_vma=False,
            ),
            donate_argnums=tuple(donate),
        )

    return step_body, wrap


class DistriOptimizer(BaseOptimizer):
    """Mesh data-parallel optimizer with ZeRO-1 state sharding
    (reference: optim/DistriOptimizer.scala:52)."""

    def __init__(self, model, dataset, criterion, optim_method=None,
                 mesh=None, axis="data", grad_compression=None,
                 sync_bn=False):
        super().__init__(model, dataset, criterion, optim_method)
        self.mesh = mesh or Engine.mesh()
        self.axis = axis
        # parse eagerly: a bad spec fails HERE, not steps into training
        CompressionSpec.parse(grad_compression)
        self.grad_compression = grad_compression
        self.sync_bn = sync_bn

    def set_sync_batchnorm(self, enabled=True):
        """Cross-replica BatchNorm statistics (SyncBN).  Default off: the
        reference normalizes each worker's local batch
        (nn/BatchNormalization.scala), and per-shard stats are also the
        cheaper TPU form (no extra collective).  Enable to make the
        distributed step numerically match single-device full-batch BN --
        the small-per-device-batch regime where per-shard stats hurt."""
        self.sync_bn = enabled
        return self

    def set_gradient_compression(self, spec=jnp.bfloat16):
        """Choose the data-plane wire format (the analogue of the
        reference's fp16 compression for slow/DCN-crossing axes,
        parameters/FP16CompressedTensor.scala:26), generalized to any
        ``CompressionSpec.parse`` spelling:

        - legacy dtypes / strings -- ``jnp.bfloat16`` (default),
          ``jnp.float16``, ``"bf16"``, ``"fp16"``: the plain cast path
        - ``"int8"`` or ``CompressionSpec(wire="int8", block_size=256,
          stochastic=..., error_feedback=..., ...)``: blockwise
          quantized collectives, optionally with the EF-SGD residual
          plane (docs/performance.md, "Gradient compression")
        """
        CompressionSpec.parse(spec)       # fail fast on a bad spelling
        self.grad_compression = spec
        return self

    #: flat-plane orbax snapshots (set_sharded_checkpoint on BaseOptimizer)
    _supports_sharded_checkpoint = True

    def _sharded_save(self, neval, params_flat, mstate, opt_state, state,
                      ef_state=None, layout=None):
        import orbax.checkpoint as ocp

        d = file_io.join(self.sharded_checkpoint_path, f"snap_{neval}")
        payload = {"params_flat": params_flat, "mstate": mstate,
                   "opt_state": opt_state}
        if ef_state is not None:
            # the error-feedback residual plane is part of the training
            # state: dropping it on resume would replay the accumulated
            # quantization error into the wire uncompensated
            payload["ef_residual"] = ef_state
        # crash-safe commit protocol (docs/robustness.md) shared with
        # the Strategy saver: file_io.write_sharded_snapshot.  The
        # manifest additionally carries the flat-plane LAYOUT the N->M
        # resume reads.
        def save_dir(path):
            with ocp.StandardCheckpointer() as ckptr:
                ckptr.save(path, payload, force=True)

        file_io.write_sharded_snapshot(
            d, save_dir, state,
            manifest_meta={"layout": layout} if layout else None,
            direct=(file_io.is_remote(self.sharded_checkpoint_path)
                    or jax.process_count() > 1),
            write_manifest=jax.process_index() == 0)

    def _sharded_layout_mismatch(self, flat_space, n_dev):
        """True when the pending sharded snapshot's manifest records a
        flat-plane layout (padded size / chunk count) differing from
        the live one -- the N->M restart path.  Manifest-less legacy
        snapshots answer False and take the strict same-layout path."""
        layout = (file_io.read_manifest(self._resume_sharded)
                  or {}).get("layout")
        if not layout:
            return False
        return (int(layout.get("padded_size", flat_space.padded_size))
                != flat_space.padded_size
                or int(layout.get("num_chunks", n_dev)) != n_dev)

    def _shard_batch(self, batch, sharding):
        # the staging path is shared with the sharded serving engine
        # (bigdl_tpu/serving): one definition of "host batch -> global
        # array on the data axis" for training and inference
        from bigdl_tpu.parallel.zero import stage_batch_global

        return (stage_batch_global(batch.get_input(), sharding),
                stage_batch_global(batch.get_target(), sharding))

    def _optimize_impl(self):
        from bigdl_tpu.utils.errors import UnsupportedFeatureError
        if self.grad_transform is not None:
            raise UnsupportedFeatureError(
                "set_grad_transform operates on the model's gradient "
                "TREE; the dp+ZeRO-1 step reduces into per-device chunks "
                "of the flat plane -- use LocalOptimizer for gradient "
                "transforms")
        if getattr(self, "_optim_methods_map", None):
            raise UnsupportedFeatureError(
                "set_optim_methods is incompatible with the dp+ZeRO-1 "
                "step: its chunks slice the FLAT parameter vector across "
                "devices, not per-submodule subtrees (reference "
                "DistriOptimizer keeps per-submodule aggregation instead "
                "of chunk ownership for this case); train with "
                "LocalOptimizer or a model-parallel strategy")
        if jax.process_count() > 1:
            # record accounting multiplies the local batch by the process
            # count, which is only correct for host-sharded datasets whose
            # size() reports the GLOBAL count (PartitionedDataSet /
            # DistributedDataSet expose local_size as the marker)
            base = self.dataset
            while hasattr(base, "base"):
                base = base.base
            if not hasattr(base, "local_size"):
                raise ValueError(
                    "multi-host DistriOptimizer requires a host-sharded "
                    "dataset (PartitionedDataSet or DistributedDataSet) "
                    "whose size() is the GLOBAL record count; got "
                    f"{type(base).__name__}, whose per-host size would "
                    "corrupt epoch accounting")
        n_dev = int(np.prod([self.mesh.shape[a] for a in self.mesh.axis_names
                             if a == self.axis]))
        train_iter = self.dataset.data(train=True)
        first_batch = next(train_iter)
        global_batch = first_batch.size() * jax.process_count()
        if global_batch % n_dev != 0:
            raise ValueError(
                f"global batch {global_batch} (local "
                f"{first_batch.size()} x {jax.process_count()} processes) "
                f"not divisible by {n_dev} devices on axis '{self.axis}'")

        params_tree, mstate = self._init_model(first_batch)
        spec = CompressionSpec.parse(self.grad_compression)
        use_ef = spec is not None and spec.error_feedback
        # the chunk layout rounds to the quantization block so a block
        # never straddles a device boundary on the wire
        flat_space = FlatParamSpace(
            params_tree, n_dev,
            block_size=spec.block_size
            if spec is not None and spec.quantized else 1)
        params_flat = flat_space.flatten(params_tree)

        # ZeRO-1: optimizer state over the full flat vector, sharded on the
        # data axis => each device holds state for its chunk only.
        vec_sharding = NamedSharding(self.mesh, P(self.axis))
        # P(), not P(None): replicated at every rank, scalars included
        rep_sharding = NamedSharding(self.mesh, P())

        opt_state_eval = jax.eval_shape(
            self.optim_method.init_state,
            jax.ShapeDtypeStruct((flat_space.padded_size,), jnp.float32))
        opt_shardings = jax.tree.map(
            lambda l: vec_sharding if l.ndim >= 1 else rep_sharding,
            opt_state_eval)
        opt_state = jax.jit(
            self.optim_method.init_state, out_shardings=opt_shardings,
        )(jnp.zeros((flat_space.padded_size,), jnp.float32))

        # EF-SGD residual plane: one fp32 local-gradient buffer per
        # device (row i = device i's accumulated quantization error),
        # sharded over the data axis beside the ZeRO-1 opt state
        ef_state = None
        if use_ef:
            ef_state = jax.jit(
                lambda: jnp.zeros((n_dev, flat_space.padded_size),
                                  jnp.float32),
                out_shardings=vec_sharding)()

        def refit(a, old_padded):
            # an N->M device-count restart, or a compression-spec change,
            # changes the CHUNK ROUNDING of the flat plane; the layouts
            # differ only in trailing padding (never read by the model
            # math), so flat-plane leaves resize by zero-pad /
            # tail-truncate (parallel/zero.refit_flat_plane).  Leaves
            # that are not flat planes (scalar counters) pass through.
            a = jnp.asarray(a)
            if a.ndim >= 1 and a.shape[-1] == old_padded:
                return refit_flat_plane(a, flat_space.padded_size,
                                        flat_space.true_size)
            return a

        def restore_ef(ef_saved):
            # same device count: each row is still that device's own
            # accumulated error -- trailing pad/truncate is exact.
            # Different count: re-partition the summed residual by
            # global flat offset so no accumulated correction is
            # dropped (parallel/zero.repartition_ef_residual).
            ef_np = np.asarray(ef_saved)
            if ef_np.shape == (n_dev, flat_space.padded_size):
                return jax.device_put(jnp.asarray(ef_np), vec_sharding)
            if ef_np.shape[0] == n_dev:
                return jax.device_put(
                    refit_flat_plane(ef_np, flat_space.padded_size,
                                     flat_space.true_size), vec_sharding)
            log.info(
                "re-partitioning the EF residual plane %s -> (%d, %d) "
                "for the new device count", ef_np.shape, n_dev,
                flat_space.padded_size)
            return jax.device_put(
                jnp.asarray(repartition_ef_residual(
                    ef_np, flat_space.true_size, n_dev,
                    flat_space.padded_size)), vec_sharding)

        #: the flat-plane layout this run writes snapshots under (and
        #: the REDISTRIBUTION TARGET of any cross-layout resume) --
        #: stamped into every snapshot manifest so a restart on a
        #: different device count can re-chunk instead of refusing
        live_layout = LayoutSpec.dp(
            n_dev, flat_space.padded_size, flat_space.true_size,
            flat_space.block_size,
            ef_shape=([n_dev, flat_space.padded_size] if use_ef
                      else None),
            axis=self.axis)

        if getattr(self, "_resume", None):
            snap = self._resume
            # save_checkpoint nests the 3rd argument under "model_params"
            old_padded = int(np.shape(
                snap["model_params"]["model_params_flat"])[0])
            src_layout = LayoutSpec.from_manifest(
                (file_io.read_manifest(getattr(self, "_resume_path", None)
                                       or "") or {}).get("layout"))
            if src_layout is not None and src_layout != live_layout:
                # restore-under-own-layout, then redistribute
                # (parallel/reshard.py): the pickle payload is already
                # host arrays in the snapshot's own chunk layout; the
                # redistribution emits the durable kind:"reshard" event
                payload = {"params_flat":
                           snap["model_params"]["model_params_flat"],
                           "opt_state": snap["opt_state"]}
                if "ef_residual" in snap["model_params"]:
                    payload["ef_residual"] = \
                        snap["model_params"]["ef_residual"]
                payload = redistribute(payload, src_layout, live_layout,
                                       telemetry=self.telemetry,
                                       what="dp-resume(pickle)")
                params_flat = payload["params_flat"]
                opt_state = jax.tree.map(
                    lambda l, s: jax.device_put(jnp.asarray(l), s),
                    payload["opt_state"], opt_shardings)
                if use_ef:
                    if "ef_residual" in payload:
                        ef_state = jax.device_put(
                            jnp.asarray(payload["ef_residual"]),
                            vec_sharding)
                    else:
                        log.warning(
                            "checkpoint snapshot has no ef_residual "
                            "plane; starting error feedback from a "
                            "zero residual")
            else:
                # same layout, or a legacy manifest-less snapshot: the
                # shape-observing refit walk (exact for same-layout)
                params_flat = refit(
                    snap["model_params"]["model_params_flat"], old_padded)
                opt_state = jax.tree.map(
                    lambda l, s: jax.device_put(refit(l, old_padded), s),
                    snap["opt_state"], opt_shardings)
                if use_ef:
                    if "ef_residual" in snap["model_params"]:
                        ef_state = restore_ef(
                            snap["model_params"]["ef_residual"])
                    else:
                        log.warning(
                            "checkpoint snapshot has no ef_residual "
                            "plane; starting error feedback from a "
                            "zero residual")
            mstate = jax.tree.map(jnp.asarray, snap["model_state"])
            self._apply_driver_state(snap["driver_state"])

        if getattr(self, "_resume_sharded", None) and \
                self._sharded_layout_mismatch(flat_space, n_dev):
            # N->M data-parallel restart (docs/robustness.md): the
            # snapshot was written under a DIFFERENT chunk layout
            # (device count and/or block rounding).  Restore every
            # flat-plane leaf under the SNAPSHOT's own shapes,
            # replicated on the new mesh -- no cross-layout resharding
            # for orbax/jax to be strict about -- then re-chunk on host:
            # trailing-pad/truncate for params + optimizer planes,
            # offset-preserving re-partition for the EF residual.
            import orbax.checkpoint as ocp

            d = self._resume_sharded
            layout = (file_io.read_manifest(d) or {})["layout"]
            old_padded = int(layout["padded_size"])

            def sds(shape, dtype):
                return jax.ShapeDtypeStruct(tuple(shape), dtype,
                                            sharding=rep_sharding)

            abstract = {
                "params_flat": sds((old_padded,),
                                   jnp.asarray(params_flat).dtype),
                "mstate": jax.tree.map(
                    lambda l: sds(l.shape, l.dtype), mstate),
                "opt_state": jax.tree.map(
                    lambda l: sds((old_padded,) if l.ndim >= 1
                                  else l.shape, l.dtype), opt_state_eval),
            }
            ef_shape = layout.get("ef_shape")
            if ef_shape:
                abstract["ef_residual"] = sds(ef_shape, jnp.float32)
            with ocp.StandardCheckpointer() as ckptr:
                restored = ckptr.restore(d, abstract)
            # restore-under-own-layout done; redistribute onto the live
            # chunk layout (parallel/reshard.py -- subsumes the PR 8
            # refit/re-partition closures and emits the durable
            # kind:"reshard" audit event)
            src_layout = LayoutSpec.from_manifest(layout)
            restored = redistribute(restored, src_layout, live_layout,
                                    telemetry=self.telemetry,
                                    what="dp-resume(sharded)")
            params_flat = restored["params_flat"]
            mstate = restored["mstate"]
            opt_state = jax.tree.map(
                lambda l, s: jax.device_put(jnp.asarray(l), s),
                restored["opt_state"], opt_shardings)
            if use_ef:
                if ef_shape:
                    ef_state = jax.device_put(
                        jnp.asarray(restored["ef_residual"]), vec_sharding)
                else:
                    log.warning(
                        "sharded snapshot %s has no ef_residual plane; "
                        "starting error feedback from a zero residual", d)
            elif ef_shape:
                log.warning(
                    "sharded snapshot %s carries an ef_residual plane "
                    "the current grad_compression does not use; "
                    "discarding it (error feedback restarts from zero "
                    "if re-enabled later)", d)
            log.info(
                "re-chunked sharded snapshot %s: padded %d -> %d, "
                "%s -> %d device chunks", d, old_padded,
                flat_space.padded_size, layout.get("num_chunks", "?"),
                n_dev)
            self._apply_driver_state(file_io.load(d + ".driver"))
            # consumed: a later failure-retry must re-resolve the LATEST
            # snapshot, not replay this one
            self._resume_sharded = None

        if getattr(self, "_resume_sharded", None):
            import orbax.checkpoint as ocp

            d = self._resume_sharded
            abstract = {
                "params_flat": jax.ShapeDtypeStruct(
                    np.shape(params_flat), jnp.asarray(params_flat).dtype,
                    sharding=rep_sharding),
                "mstate": jax.tree.map(
                    lambda l: jax.ShapeDtypeStruct(
                        l.shape, l.dtype, sharding=rep_sharding), mstate),
                "opt_state": jax.tree.map(
                    lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                                      sharding=s),
                    opt_state, opt_shardings),
            }
            if use_ef:
                abstract["ef_residual"] = jax.ShapeDtypeStruct(
                    ef_state.shape, ef_state.dtype, sharding=vec_sharding)
            def _layout_error(first_err):
                # both attempts failing means the snapshot's FLAT
                # LAYOUT differs in a way this orbax will not reshape
                # (int8 block rounding changes padded_size)
                return ValueError(
                    f"cannot restore {d} under the current "
                    f"grad_compression: the flat-plane layout (padded "
                    f"size {flat_space.padded_size}, block "
                    f"{flat_space.block_size}) does not match the "
                    f"snapshot's -- resume under the snapshot's "
                    f"original compression spec or restart training")

            with ocp.StandardCheckpointer() as ckptr:
                try:
                    restored = ckptr.restore(d, abstract)
                except Exception as first_err:
                    if use_ef:
                        # snapshot predates error feedback (taken
                        # before the EF spec was turned on): retry
                        # without the residual plane and keep the
                        # zeros init, matching the non-sharded path's
                        # graceful degrade
                        abstract.pop("ef_residual")
                        try:
                            restored = ckptr.restore(d, abstract)
                        except Exception:
                            raise _layout_error(first_err) from first_err
                        restored["ef_residual"] = ef_state
                        log.warning(
                            "sharded snapshot %s has no ef_residual "
                            "plane; starting error feedback from a "
                            "zero residual", d)
                    else:
                        # the snapshot may carry an ef_residual plane
                        # the current (EF-off) spec does not use:
                        # restore it alongside and discard, instead of
                        # surfacing orbax's raw key-mismatch error
                        abstract["ef_residual"] = jax.ShapeDtypeStruct(
                            (n_dev, flat_space.padded_size), jnp.float32,
                            sharding=vec_sharding)
                        try:
                            restored = ckptr.restore(d, abstract)
                        except Exception:
                            raise _layout_error(first_err) from first_err
                        restored.pop("ef_residual")
                        log.warning(
                            "sharded snapshot %s carries an ef_residual "
                            "plane the current grad_compression does "
                            "not use; discarding it (error feedback "
                            "restarts from zero if re-enabled later)", d)
            params_flat = restored["params_flat"]
            mstate = restored["mstate"]
            opt_state = restored["opt_state"]
            if use_ef:
                ef_state = restored["ef_residual"]
            self._apply_driver_state(file_io.load(d + ".driver"))
            # consumed: a later failure-retry must re-resolve the LATEST
            # snapshot, not replay this one
            self._resume_sharded = None

        train_iter, first_batch = self._resume_data_stream(
            train_iter, first_batch)
        # both replicated planes go onto the mesh before the first step:
        # module state left on the first device would give step 1 other
        # input shardings than step 2 sees, and so a second full compile
        params_flat = jax.device_put(params_flat, rep_sharding)
        mstate = jax.device_put(mstate, rep_sharding)

        mon = self.health_monitor
        use_health = mon is not None and mon.enabled
        _, wrap = make_distri_train_step(
            self.model, self.criterion, self.optim_method, flat_space,
            self.mesh, self.axis, self.compute_dtype, self.clip_value,
            self.clip_norm, self.grad_compression, self.sync_bn,
            health_stats=use_health)
        step = wrap(opt_state_eval)

        batch_sharding = NamedSharding(self.mesh, P(self.axis))

        seg_ids = None
        if use_health:
            from bigdl_tpu.observability.health import (layer_labels,
                                                        layer_segment_ids)
            # layer-id map of the flat plane, sharded like the vector:
            # each device holds exactly its chunk's ids
            seg_ids = jax.device_put(
                jnp.asarray(layer_segment_ids(params_tree,
                                              flat_space.padded_size)),
                vec_sharding)
            mon.bind(
                layer_labels(params_tree),
                params_fn=lambda: jax.device_get(
                    {"params_flat": params_flat, "mstate": mstate,
                     "opt_state": opt_state}))

        if self.telemetry is not None:
            self.telemetry.recompile_watchdog.watch(step)
            if getattr(self, "blocking_timing", False):
                # before attach_cost's lazy header write, so the header
                # itself carries the run's timing discipline; the shared
                # driver loop then fences every dispatch (the loss is an
                # output of the one sharded XLA program, so blocking on
                # it fences the whole dp step incl. collectives)
                self.telemetry.set_timing_mode("blocking")
            # real sharded arrays (one extra transfer of the first batch,
            # once at startup): the lowering's avals must carry the
            # GLOBAL shapes/shardings _shard_batch assembles, which
            # host-local specs cannot express under multi-process
            xc, tc = self._shard_batch(first_batch, batch_sharding)
            cost_args = (params_flat, mstate, opt_state, xc, tc,
                         jax.random.key(0))
            labels = ("params_flat", "mstate", "opt_state", "input",
                      "target", "rng")
            if use_ef:
                cost_args += (ef_state,)
                labels += ("ef_residual",)
            if use_health:
                cost_args += (jax.ShapeDtypeStruct((), jnp.bool_), seg_ids)
                labels += ("sample", "seg_ids")
            self.telemetry.attach_cost(
                step, *cost_args, records_per_step=global_batch,
                arg_labels=labels)

        def stage_device(batch):
            # global sharded arrays assembled while the previous step
            # executes (driver-loop double buffering)
            return self._shard_batch(batch, batch_sharding)

        stats_holder = [None]

        def dispatch(staged):
            nonlocal params_flat, mstate, opt_state, ef_state
            x, target = staged
            args = [params_flat, mstate, opt_state, x, target,
                    RNG.next_key()]
            if use_ef:
                args.append(ef_state)
            if use_health:
                args += [mon.due(self.driver_state["neval"]), seg_ids]
            out = step(*args)
            params_flat, mstate, opt_state, loss = out[:4]
            i = 4
            if use_ef:
                ef_state = out[i]
                i += 1
            if use_health:
                stats_holder[0] = out[i]
            return loss

        def validate_cb():
            # reference getModel + Evaluator: reassemble full weights,
            # then eval (optim/DistriOptimizer.scala:645-695)
            params_tree = jax.jit(flat_space.unflatten)(params_flat)
            return validate(self.model, params_tree, mstate,
                            self.validation_dataset,
                            self.validation_methods, self.compute_dtype)

        def feed_plateau(state):
            nonlocal opt_state
            opt_state = self._feed_plateau(state, opt_state)

        #: the manifest ``layout`` block this run stamps on every
        #: snapshot (LayoutSpec superset of PR 8's dp-only keys, so
        #: older readers of padded_size/num_chunks keep working)
        layout_meta = live_layout.to_manifest()

        def checkpoint_cb(state):
            if getattr(self, "sharded_checkpoint_path", None):
                self._sharded_save(state["neval"], params_flat, mstate,
                                   opt_state, state, ef_state=ef_state,
                                   layout=layout_meta)
            else:
                pdict = {"model_params_flat": params_flat}
                if use_ef:
                    pdict["ef_residual"] = ef_state
                file_io.save_checkpoint(
                    self.checkpoint_path, state["neval"], pdict, mstate,
                    opt_state, state,
                    manifest_meta={"layout": layout_meta})

        def health_cb():
            raw = jax.device_get(stats_holder[0])
            if use_ef:
                # residual-norm trajectory: how much quantization error
                # the EF plane is carrying (flat when healthy; growth
                # means the wire is systematically dropping signal)
                raw = dict(raw)
                raw["ef_residual_norm"] = float(jnp.linalg.norm(ef_state))
            return raw

        # the flat plane's per-step wire footprint (both collectives),
        # stamped on every step event: wire_bytes / compression_ratio
        # feed the obs_report "Communication" section
        comm_fields = (uncompressed_wire_summary(flat_space.padded_size)
                       if spec is None
                       else spec.wire_summary(flat_space.padded_size))

        # _shard_batch treats each host's minibatch as process-LOCAL
        # (jax.make_array_from_process_local_data), so the records
        # consumed globally per step = local batch x process count
        # (reference driverState counts global records)
        self._run_driver_loop(
            train_iter, first_batch, dispatch=dispatch,
            stage_device=stage_device,
            records_of=lambda b: b.size() * jax.process_count(),
            validate_cb=validate_cb, feed_plateau=feed_plateau,
            checkpoint_cb=checkpoint_cb,
            health_cb=health_cb if use_health else None,
            event_fields=comm_fields)

        params_tree = jax.jit(flat_space.unflatten)(params_flat)
        self.model.set_parameters(params_tree)
        self.model.set_state(mstate)
        #: the optimizer state as the last step left it: under ZeRO-1
        #: every leaf over the flat plane is sharded over the data axis
        #: (where it lives is what a caller checks on real devices)
        self.opt_state = opt_state
        return self.model


class ParallelOptimizer(DistriOptimizer):
    """Reference: optim/ParallelOptimizer.scala:69 — distributed training
    with per-layer ASYNC gradient sync (BlockManagerParameterSynchronizer,
    priority = layer depth) to overlap backward with communication.

    TPU-native stance: that overlap is the XLA compiler's job.  The whole
    step — backward, psum/reduce-scatter, update — is one XLA program, and
    the latency-hiding scheduler already interleaves per-layer collectives
    with remaining backward compute on the ICI mesh, which is exactly what
    the reference built by hand with priority queues and pinned cores.
    This subclass therefore shares DistriOptimizer's implementation; it
    exists so reference call sites resolve.
    """
