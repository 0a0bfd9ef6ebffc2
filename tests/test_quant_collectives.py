"""Quantized gradient collectives with error feedback (ISSUE 4).

Pins the full vertical slice of the compressed data-parallel plane:

- the blockwise int8 kernels (per-block roundtrip error bound,
  stochastic-rounding determinism + unbiasedness);
- ``CompressionSpec`` parsing (every legacy ``grad_compression=``
  spelling unchanged) and the wire-byte accounting (>= 3.5x for int8);
- the ZeRO-1 chunk layout rounding to the quantization block;
- step parity: the EXISTING bf16/fp16 cast path's loss divergence
  bound (previously untested), and int8 + error feedback converging to
  the fp32-reduction trajectory on a small MLP;
- the driver wiring: ``wire_bytes``/``compression_ratio`` step
  telemetry, ``ef_residual_norm`` in health samples, the EF residual
  plane riding the sharded checkpoint path, and the obs_report
  "Communication" section.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu import optim
from bigdl_tpu.ops.quantization import (CompressionSpec,
                                        dequantize_blockwise,
                                        quantize_blockwise,
                                        uncompressed_wire_summary)
from bigdl_tpu.parallel.zero import FlatParamSpace
from bigdl_tpu.utils.random_generator import RNG

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs the 8-device virtual CPU mesh")


# --------------------------------------------------------------------------- #
# Kernels.
# --------------------------------------------------------------------------- #


class TestBlockwiseKernels:
    def _data(self, n=512, scale=3.0, seed=0):
        rng = np.random.default_rng(seed)
        return (rng.standard_normal(n) * scale).astype(np.float32)

    @pytest.mark.parametrize("scale_dtype", ["bf16", "fp32"])
    def test_roundtrip_error_bounded_per_block(self, scale_dtype):
        """|x - deq(q)| <= stored_scale/2 per element, nearest rounding;
        the stored scale is absmax/127 rounded up one bf16 ulp, so the
        practical bound is absmax/127 * 0.51."""
        x = self._data()
        block = 64
        q, s = quantize_blockwise(jnp.asarray(x), block,
                                  scale_dtype=scale_dtype)
        assert q.dtype == jnp.int8
        back = np.asarray(dequantize_blockwise(q, s, block))
        err = np.abs(x - back).reshape(-1, block)
        absmax = np.abs(x).reshape(-1, block).max(axis=1)
        assert (err <= absmax[:, None] / 127.0 * 0.51 + 1e-9).all()

    def test_int8_range_never_clips(self):
        """The rounded-up scale keeps |q| <= 127 without engaging the
        clip, including at the block absmax itself."""
        x = self._data(scale=100.0)
        q, _ = quantize_blockwise(jnp.asarray(x), 32)
        assert int(jnp.max(jnp.abs(q.astype(jnp.int32)))) <= 127

    def test_zero_block_is_exact(self):
        x = np.zeros(128, np.float32)
        q, s = quantize_blockwise(jnp.asarray(x), 32)
        assert not np.any(np.asarray(q))
        assert not np.any(np.asarray(s, np.float32))
        np.testing.assert_array_equal(
            np.asarray(dequantize_blockwise(q, s, 32)), x)

    def test_stochastic_deterministic_under_fixed_rng(self):
        x = jnp.asarray(self._data())
        key = jax.random.key(7)
        q1, s1 = quantize_blockwise(x, 64, stochastic=True, rng=key)
        q2, s2 = quantize_blockwise(x, 64, stochastic=True, rng=key)
        np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
        np.testing.assert_array_equal(np.asarray(s1, np.float32),
                                      np.asarray(s2, np.float32))
        q3, _ = quantize_blockwise(x, 64, stochastic=True,
                                   rng=jax.random.key(8))
        assert not np.array_equal(np.asarray(q1), np.asarray(q3))

    def test_stochastic_error_bounded_and_unbiased(self):
        x = self._data(n=256)
        block = 64
        backs = []
        for i in range(40):
            q, s = quantize_blockwise(jnp.asarray(x), block,
                                      stochastic=True,
                                      rng=jax.random.key(i))
            backs.append(np.asarray(dequantize_blockwise(q, s, block)))
            err = np.abs(x - backs[-1]).reshape(-1, block)
            absmax = np.abs(x).reshape(-1, block).max(axis=1)
            # one ulp (floor + uniform), with the scale's bf16 headroom
            assert (err <= absmax[:, None] / 127.0 * 1.02 + 1e-9).all()
        # unbiased: the MEAN dequantized value approaches x (this is
        # what lets the quantized REDUCTION cancel error across devices)
        mean_err = np.abs(np.mean(backs, axis=0) - x).mean()
        q, s = quantize_blockwise(jnp.asarray(x), block)
        nearest_err = np.abs(
            np.asarray(dequantize_blockwise(q, s, block)) - x).mean()
        assert mean_err < nearest_err

    def test_stochastic_requires_rng(self):
        with pytest.raises(ValueError, match="rng"):
            quantize_blockwise(jnp.zeros(32), 32, stochastic=True)

    def test_nonfinite_block_drops_instead_of_spreading(self):
        """An Inf/NaN gradient element zeroes its block's scale: the
        block dequantizes to exactly 0 (dropped for the step) and the
        neighboring blocks are untouched -- vs the fp32 psum where one
        NaN poisons every replica's whole sum."""
        x = self._data(n=128)
        bad = x.copy()
        bad[5] = np.inf
        bad[70] = np.nan
        q, s = quantize_blockwise(jnp.asarray(bad), 32)
        back = np.asarray(dequantize_blockwise(q, s, 32))
        assert np.isfinite(back).all()
        np.testing.assert_array_equal(back[:32], 0.0)     # Inf block
        np.testing.assert_array_equal(back[64:96], 0.0)   # NaN block
        # clean blocks quantize exactly as they would alone
        q2, s2 = quantize_blockwise(jnp.asarray(x[32:64]), 32)
        np.testing.assert_array_equal(
            back[32:64], np.asarray(dequantize_blockwise(q2, s2, 32)))

    def test_dequantize_leading_dims(self):
        """The all_to_all layout dequantizes (n_dev, chunk) payloads."""
        x = self._data(n=256).reshape(4, 64)
        qs = [quantize_blockwise(jnp.asarray(r), 32) for r in x]
        q = jnp.stack([a for a, _ in qs])
        s = jnp.stack([b for _, b in qs])
        back = np.asarray(dequantize_blockwise(q, s, 32))
        flat = np.asarray(dequantize_blockwise(
            q.reshape(-1), s.reshape(-1), 32)).reshape(4, 64)
        np.testing.assert_array_equal(back, flat)


# --------------------------------------------------------------------------- #
# Spec parsing + wire accounting.
# --------------------------------------------------------------------------- #


class TestCompressionSpec:
    def test_none_passthrough(self):
        assert CompressionSpec.parse(None) is None

    @pytest.mark.parametrize("legacy,wire", [
        (jnp.bfloat16, "bf16"), (jnp.float16, "fp16"),
        (np.float16, "fp16"), (np.dtype(np.float16), "fp16"),
        ("bf16", "bf16"), ("bfloat16", "bf16"), ("fp16", "fp16"),
        ("float16", "fp16"), ("int8", "int8"), ("INT8", "int8"),
    ])
    def test_legacy_spellings(self, legacy, wire):
        spec = CompressionSpec.parse(legacy)
        assert spec.wire == wire

    def test_fp32_spellings_mean_uncompressed(self):
        assert CompressionSpec.parse("fp32") is None
        assert CompressionSpec.parse(jnp.float32) is None
        assert CompressionSpec.parse(CompressionSpec(wire="fp32")) is None

    def test_dict_and_spec_passthrough(self):
        spec = CompressionSpec.parse(
            {"wire": "int8", "block_size": 128, "error_feedback": True})
        assert (spec.wire, spec.block_size, spec.error_feedback) == \
            ("int8", 128, True)
        assert CompressionSpec.parse(spec) is spec

    def test_invalid_spellings_raise(self):
        with pytest.raises(ValueError, match="grad_compression"):
            CompressionSpec.parse("int4")
        with pytest.raises(ValueError, match="wire"):
            CompressionSpec(wire="int4")
        with pytest.raises(ValueError, match="block_size"):
            CompressionSpec(wire="int8", block_size=0)
        with pytest.raises(ValueError, match="error_feedback"):
            CompressionSpec(wire="fp32", error_feedback=True)
        # the cast path carries no residual plane, so EF must be
        # rejected up front (the step would otherwise crash at trace
        # time with an opaque shard_map out_specs pytree mismatch)
        with pytest.raises(ValueError, match="error_feedback"):
            CompressionSpec(wire="bf16", error_feedback=True)
        with pytest.raises(ValueError, match="error_feedback"):
            CompressionSpec(wire="fp16", error_feedback=True)
        with pytest.raises(ValueError, match="compress_weight_gather"):
            CompressionSpec(wire="bf16", compress_weight_gather=True)

    def test_wire_summary_ratios(self):
        n = 256 * 64
        int8 = CompressionSpec(wire="int8").wire_summary(n)
        # the ISSUE-4 acceptance floor: >= 3.5x on the gradient plane
        assert int8["grad_compression_ratio"] >= 3.5
        bf16 = CompressionSpec(wire="bf16").wire_summary(n)
        assert bf16["grad_compression_ratio"] == 2.0
        assert bf16["weight_wire_bytes"] == 4 * n   # cast path: fp32 gather
        both = CompressionSpec(
            wire="int8", compress_weight_gather=True).wire_summary(n)
        assert both["compression_ratio"] >= 3.5
        flat = uncompressed_wire_summary(n)
        assert flat["compression_ratio"] == 1.0
        assert flat["wire_bytes"] == 8 * n


class TestFlatSpaceBlockLayout:
    def test_chunks_round_to_blocks(self):
        tree = {"w": jnp.zeros((13, 7)), "b": jnp.zeros((5,))}
        fs = FlatParamSpace(tree, 8, block_size=64)
        assert fs.chunk_size % 64 == 0
        assert fs.padded_size == fs.chunk_size * 8
        assert fs.padded_size >= 13 * 7 + 5
        # roundtrip unaffected by the extra padding
        flat = fs.flatten(tree)
        assert flat.shape == (fs.padded_size,)
        back = fs.unflatten(flat)
        assert back["w"].shape == (13, 7)

    def test_default_layout_unchanged(self):
        tree = {"w": jnp.zeros((13, 7)), "b": jnp.zeros((5,))}
        old = FlatParamSpace(tree, 8)
        assert old.padded_size == (13 * 7 + 5 + 7) // 8 * 8


# --------------------------------------------------------------------------- #
# Step parity on the 8-device mesh.
# --------------------------------------------------------------------------- #


def _mlp():
    return (nn.Sequential().add(nn.Linear(12, 32)).add(nn.ReLU())
            .add(nn.Linear(32, 5)))


#: memo for the parity runs -- the trajectories are deterministic, and
#: a shorter run is an exact PREFIX of a longer one (same per-step data
#: stream and params evolution), so tests share one fp32 baseline by
#: slicing instead of recompiling the shard_map step per test
_RUN_CACHE = {}


def _run_steps(compression, n_steps=30, lr=0.1, seed=0, cached=True):
    """n_steps of make_distri_train_step under ``compression``; returns
    (loss stream, final flat params).  ``cached=False`` forces a fresh
    run (the reproducibility test must really execute twice)."""
    key = (repr(compression), n_steps, lr, seed)
    if cached and key in _RUN_CACHE:
        return _RUN_CACHE[key]
    out = _run_steps_impl(compression, n_steps, lr, seed)
    if cached:
        _RUN_CACHE[key] = out
    return out


def _run_steps_impl(compression, n_steps, lr, seed):
    from bigdl_tpu.optim.distri_optimizer import make_distri_train_step

    mesh = jax.sharding.Mesh(
        np.asarray(jax.devices()[:8]).reshape(8), ("data",))
    RNG.set_seed(seed)
    model = _mlp()
    model.build(jax.ShapeDtypeStruct((8, 12), jnp.float32))
    params_tree = model.parameters()[0]
    spec = CompressionSpec.parse(compression)
    fs = FlatParamSpace(
        params_tree, 8,
        block_size=spec.block_size
        if spec is not None and spec.quantized else 1)
    pf = fs.flatten(params_tree)
    method = optim.SGD(learning_rate=lr)
    opt_eval = jax.eval_shape(
        method.init_state,
        jax.ShapeDtypeStruct((fs.padded_size,), jnp.float32))
    _, wrap = make_distri_train_step(
        model, nn.CrossEntropyCriterion(), method, fs, mesh, "data",
        grad_compression=compression)
    step = wrap(opt_eval)
    os_ = method.init_state(jnp.zeros((fs.padded_size,), jnp.float32))
    ef = jnp.zeros((8, fs.padded_size), jnp.float32) \
        if spec is not None and spec.error_feedback else None
    rng = np.random.default_rng(3)
    mstate = model.state()
    losses = []
    for i in range(n_steps):
        x = jnp.asarray(rng.standard_normal((64, 12)), jnp.float32)
        t = jnp.asarray(rng.integers(0, 5, 64), jnp.int32)
        args = [pf, mstate, os_, x, t, jax.random.key(i)]
        if ef is not None:
            args.append(ef)
        out = step(*args)
        pf, mstate, os_, loss = out[:4]
        if ef is not None:
            ef = out[4]
        losses.append(float(loss))
    return losses, np.asarray(pf)


@needs_mesh
class TestCastPathParity:
    """Satellite: the EXISTING bf16/fp16 cast path, previously untested
    beyond one step -- the docstring's divergence guarantee, pinned."""

    @pytest.mark.parametrize("wire", [jnp.bfloat16, jnp.float16])
    def test_cast_wire_tracks_fp32_loss(self, wire):
        base, p_base = _run_steps(None)      # shared via _RUN_CACHE
        cast, p_cast = _run_steps(wire)
        assert np.isfinite(cast).all()
        # per-step divergence stays bounded over the whole run (the
        # guarantee documented on make_distri_train_step)
        diffs = np.abs(np.asarray(base) - np.asarray(cast)) \
            / np.maximum(np.abs(base), 1e-6)
        assert diffs.max() < 1e-2, diffs
        # and it MUST be a different trajectory (the wire did compress)
        assert not np.array_equal(p_base, p_cast)

    def test_legacy_dtype_and_string_spellings_identical(self):
        """grad_compression=jnp.bfloat16 (the historical API) and the
        new "bf16" spelling build bit-identical steps."""
        l_dtype, p_dtype = _run_steps(jnp.bfloat16)
        l_str, p_str = _run_steps("bf16")
        assert l_dtype == l_str
        np.testing.assert_array_equal(p_dtype, p_str)


@needs_mesh
class TestInt8ErrorFeedback:
    def test_int8_ef_converges_to_fp32_trajectory(self):
        """ISSUE-4 acceptance: int8 + error feedback on the test MLP
        stays within tolerance of the fp32-reduction baseline."""
        base, p_base = _run_steps(None)
        q, p_q = _run_steps(
            CompressionSpec(wire="int8", block_size=64,
                            error_feedback=True))
        assert np.isfinite(q).all()
        rel = abs(q[-1] - base[-1]) / max(abs(base[-1]), 1e-6)
        assert rel < 5e-3, (q[-1], base[-1])
        # whole-trajectory bound, not just the endpoint
        diffs = np.abs(np.asarray(base) - np.asarray(q)) \
            / np.maximum(np.abs(base), 1e-6)
        assert diffs.max() < 2e-2, diffs

    @pytest.mark.slow
    def test_stochastic_rounding_reproducible_end_to_end(self):
        """Slow tier: the cheap kernel-level determinism pin
        (TestBlockwiseKernels) carries tier-1."""
        spec = CompressionSpec(wire="int8", block_size=64,
                               stochastic=True, error_feedback=True)
        l1, p1 = _run_steps(spec, n_steps=8, cached=False)
        l2, p2 = _run_steps(spec, n_steps=8, cached=False)
        assert l1 == l2
        np.testing.assert_array_equal(p1, p2)

    def test_quantized_weight_gather_tracks_fp32(self):
        spec = CompressionSpec(wire="int8", block_size=64,
                               error_feedback=True,
                               compress_weight_gather=True)
        base = _run_steps(None)[0][:20]      # prefix of the shared run
        q, p_q = _run_steps(spec, n_steps=20)
        assert np.isfinite(q).all()
        # weight deltas quantize too -> slightly looser than grad-only
        diffs = np.abs(np.asarray(base) - np.asarray(q)) \
            / np.maximum(np.abs(base), 1e-6)
        assert diffs.max() < 5e-2, diffs

    @pytest.mark.slow
    def test_ef_beats_plain_int8_at_coarse_blocks(self):
        """The residual plane is what recovers the fp32 trajectory:
        with aggressive quantization (huge blocks -> coarse scales),
        the EF run must track fp32 more closely than the EF-less run."""
        base, _ = _run_steps(None, n_steps=30)
        no_ef, _ = _run_steps(
            CompressionSpec(wire="int8", block_size=512), n_steps=30)
        ef, _ = _run_steps(
            CompressionSpec(wire="int8", block_size=512,
                            error_feedback=True), n_steps=30)
        err_no_ef = np.abs(np.asarray(base) - np.asarray(no_ef)).sum()
        err_ef = np.abs(np.asarray(base) - np.asarray(ef)).sum()
        assert err_ef < err_no_ef, (err_ef, err_no_ef)


# --------------------------------------------------------------------------- #
# Driver wiring: telemetry, health, checkpoints, report.
# --------------------------------------------------------------------------- #


def _fit_distri(compression, run_dir=None, steps=6, health_every=None,
                ckpt=None, ckpt_every=3, resume=False, seed=0, model=None):
    from bigdl_tpu.observability import StepTelemetry
    from bigdl_tpu.utils.engine import Engine

    Engine.init()
    RNG.set_seed(seed)
    rng = np.random.default_rng(seed)
    n, batch = 512, 64
    x = rng.standard_normal((n, 12)).astype("float32")
    y = rng.integers(0, 5, n).astype("int32")
    from bigdl_tpu.dataset import SampleToMiniBatch, array_dataset
    ds = array_dataset(x, y) >> SampleToMiniBatch(batch)
    model = model or _mlp()
    opt = optim.DistriOptimizer(model, ds, nn.CrossEntropyCriterion(),
                                optim.SGD(learning_rate=0.1),
                                grad_compression=compression)
    opt.set_end_when(optim.Trigger.max_iteration(steps))
    tel = None
    if run_dir:
        tel = StepTelemetry(run_dir, trace=False)
        opt.set_telemetry(tel)
    if health_every:
        opt.set_health_monitor(stats_every=health_every, policy="warn")
    if ckpt:
        opt.set_sharded_checkpoint(
            ckpt, optim.Trigger.several_iteration(ckpt_every))
        if resume:
            opt.resume_from_sharded_checkpoint()
    opt.optimize()
    if tel:
        tel.close()
    return opt


def _events(run_dir):
    with open(os.path.join(run_dir, "telemetry.jsonl")) as f:
        return [json.loads(l) for l in f if l.strip()]


@needs_mesh
class TestDriverWiring:
    def test_step_events_report_wire_reduction(self, tmp_path):
        """ISSUE-4 acceptance: step telemetry reports >= 3.5x gradient
        wire-byte reduction for int8 vs the fp32 baseline events."""
        d32 = str(tmp_path / "fp32")
        d8 = str(tmp_path / "int8")
        _fit_distri(None, run_dir=d32, steps=3)
        _fit_distri(CompressionSpec(wire="int8", error_feedback=True),
                    run_dir=d8, steps=3)
        e32 = [e for e in _events(d32) if e["kind"] == "step"][0]
        e8 = [e for e in _events(d8) if e["kind"] == "step"][0]
        assert e32["compression_ratio"] == 1.0
        assert e8["grad_compression_ratio"] >= 3.5
        # the ratio is also directly recomputable from the raw bytes
        # (padding differs between legs: the int8 layout rounds chunks
        # up to whole blocks, so compare per-element footprints)
        per_el_32 = 4.0                 # fp32 wire
        ev = e8["grad_wire_bytes"]
        n8 = e8["grad_wire_bytes"] / (1 + 2 / 256)   # payload share
        assert per_el_32 * n8 / ev >= 3.5

    def test_health_samples_carry_residual_norm(self, tmp_path):
        d = str(tmp_path / "run")
        _fit_distri(CompressionSpec(wire="int8", block_size=64,
                                    error_feedback=True),
                    run_dir=d, steps=7, health_every=3)
        health = [e for e in _events(d) if e["kind"] == "health"]
        assert health
        norms = [e["ef_residual_norm"] for e in health]
        assert all(np.isfinite(n) and n >= 0 for n in norms)
        assert any(n > 0 for n in norms)   # the wire really dropped bits
        # no EF -> no residual field
        d2 = str(tmp_path / "run2")
        _fit_distri("bf16", run_dir=d2, steps=7, health_every=3)
        health2 = [e for e in _events(d2) if e["kind"] == "health"]
        assert health2
        assert all("ef_residual_norm" not in e for e in health2)

    def test_obs_report_communication_section(self, tmp_path):
        import importlib.util

        spec_ = importlib.util.spec_from_file_location(
            "_qc_obs", os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "tools", "obs_report.py"))
        mod = importlib.util.module_from_spec(spec_)
        spec_.loader.exec_module(mod)
        d = str(tmp_path / "run")
        _fit_distri(CompressionSpec(wire="int8", error_feedback=True),
                    run_dir=d, steps=7, health_every=3)
        rep = mod.build_report(d)
        comm = rep["communication"]
        assert comm["grad_compression_ratio"] >= 3.5
        assert comm["wire_bytes_total"] == \
            comm["wire_bytes_per_step"] * rep["n_steps"]
        assert comm["ef_residual_norm_last"] is not None
        assert comm["ef_residual_trajectory"]
        text = mod.format_report(rep)
        assert "communication:" in text
        assert "error-feedback residual norm" in text
        # strict-JSON contract holds with the new section
        json.dumps(mod._json_safe(rep), allow_nan=False)
        # a residual that blows up by the LAST sample must still print
        # the trajectory line (rendered "non-finite"), not vanish --
        # that is the one run where the signal matters most
        comm["ef_residual_norm_last"] = None
        text2 = mod.format_report(rep)
        assert "error-feedback residual norm" in text2
        assert "non-finite" in text2

    def test_ef_residual_rides_sharded_checkpoint(self, tmp_path):
        """ISSUE-4 acceptance: checkpoints taken with error feedback on
        restore correctly -- the resumed run replays the uninterrupted
        trajectory, which requires the residual plane round-tripping."""
        import orbax.checkpoint as ocp

        spec = CompressionSpec(wire="int8", block_size=64,
                               error_feedback=True)
        # 3 steps + snapshot, then FRESH optimizers resume for 3 more
        ck = str(tmp_path / "snaps")
        _fit_distri(spec, steps=3, ckpt=ck)
        # snapshot DIRS only: the crash-safe write also leaves .driver
        # and .manifest.json sidecars next to each one (docs/robustness.md)
        snaps = [s for s in os.listdir(ck) if s.startswith("snap_")
                 and os.path.isdir(os.path.join(ck, s))]
        assert snaps, os.listdir(ck)
        # the snapshot payload carries the residual plane (orbax ocdbt
        # layout: keys live in the tree metadata, not as dir entries)
        snap_dir = os.path.join(ck, snaps[0])
        meta = open(os.path.join(snap_dir, "_METADATA")).read()
        assert "ef_residual" in meta
        # ... with real accumulated quantization error, not zeros
        with ocp.StandardCheckpointer() as ckptr:
            restored = ckptr.restore(snap_dir)
        ef = np.asarray(restored["ef_residual"])
        assert ef.shape[0] == 8 and np.isfinite(ef).all()
        assert np.abs(ef).max() > 0
        # resumed-and-continued training is deterministic: two fresh
        # optimizers restoring the same snapshot (residual included)
        # replay the identical trajectory
        opt_b = _fit_distri(spec, steps=6, ckpt=ck, ckpt_every=100,
                            resume=True)
        opt_c = _fit_distri(spec, steps=6, ckpt=ck, ckpt_every=100,
                            resume=True)
        assert opt_b.driver_state["neval"] == 7
        assert opt_b.driver_state["loss"] == opt_c.driver_state["loss"]
        assert np.isfinite(opt_b.driver_state["loss"])

    def test_ef_residual_stays_finite_through_transient_nonfinite(self):
        """The EF residual drops non-finite error instead of carrying
        it into the next step's gradient: a transient Inf costs one
        step's block signal, not the whole run."""
        from bigdl_tpu.ops.quantization import quantized_reduce_chunks
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = jax.sharding.Mesh(
            np.asarray(jax.devices()[:8]).reshape(8), ("data",))
        spec = CompressionSpec(wire="int8", block_size=32,
                               error_feedback=True)

        def body(gl, r):
            g = gl[0] + r[0]
            chunk, err = quantized_reduce_chunks(
                g, 8, "data", spec, jax.random.key(0))
            return chunk, err[None, :]

        f = jax.jit(shard_map(body, mesh=mesh,
                              in_specs=(P("data"), P("data")),
                              out_specs=(P("data"), P("data")),
                              check_vma=False))
        rng = np.random.default_rng(0)
        gl = rng.standard_normal((8, 256)).astype(np.float32)
        gl[3, 17] = np.inf                   # one transient bad element
        r = np.zeros((8, 256), np.float32)
        chunk, r = f(gl, r)
        assert np.isfinite(np.asarray(chunk)).all()
        assert np.isfinite(np.asarray(r)).all()
        # next step with a clean gradient fully recovers
        chunk2, r2 = f(gl * 0 + 1.0, r)
        assert np.isfinite(np.asarray(chunk2)).all()
        assert np.isfinite(np.asarray(r2)).all()

    def test_tb_scalars_include_residual_norm(self, tmp_path):
        """TensorBoard health scalars carry Health/EfResidualNorm when
        the event does (same single-source contract as the JSONL)."""
        from bigdl_tpu.visualization import TrainSummary

        s = TrainSummary(str(tmp_path), "qc")
        seen = []
        s.add_scalar = lambda name, val, step: seen.append(name)
        s.add_health_event({"step": 1, "grad_norm": 1.0,
                            "update_ratio_max": 0.1,
                            "nonfinite_grads": 0, "nonfinite_params": 0,
                            "ef_residual_norm": 0.5, "layers": {}})
        assert "Health/EfResidualNorm" in seen

    def test_resume_pre_ef_snapshot_degrades_gracefully(self, tmp_path):
        """A sharded snapshot taken BEFORE error feedback was turned on
        resumes under an EF spec: the residual plane starts from zeros
        (with a warning) instead of hard-failing the restore -- same
        degrade the non-sharded path has."""
        ck = str(tmp_path / "snaps")
        _fit_distri("int8", steps=3, ckpt=ck)          # no EF plane saved
        opt = _fit_distri(
            CompressionSpec(wire="int8", block_size=64,
                            error_feedback=True),
            steps=6, ckpt=ck, ckpt_every=100, resume=True)
        assert opt.driver_state["neval"] == 7
        assert np.isfinite(opt.driver_state["loss"])

    def test_resume_across_block_layouts(self, tmp_path):
        """A snapshot taken under fp32 (no block rounding) resumes
        under an int8+EF spec whose block changes padded_size: the
        layouts differ only in PADDING, which the model math never
        reads (unflatten slices [:true_size]; the tail's gradient is
        0), so turning compression on mid-training Just Works -- the
        EF plane starts from zeros with a warning."""
        ck = str(tmp_path / "snaps")
        _fit_distri(None, steps=3, ckpt=ck)
        opt = _fit_distri(
            CompressionSpec(wire="int8", block_size=64,
                            error_feedback=True),
            steps=6, ckpt=ck, ckpt_every=100, resume=True)
        assert opt.driver_state["neval"] == 7
        assert np.isfinite(opt.driver_state["loss"])

    def test_legacy_constructor_spelling_end_to_end(self):
        """Backward compat: grad_compression=jnp.bfloat16 on the
        optimizer constructor trains exactly as before."""
        opt = _fit_distri(jnp.bfloat16, steps=3)
        assert np.isfinite(opt.driver_state["loss"])
        with pytest.raises(ValueError):
            optim.DistriOptimizer(
                _mlp(), None, nn.CrossEntropyCriterion(),
                grad_compression="int4")

    def test_set_gradient_compression_accepts_spec(self):
        opt = optim.DistriOptimizer(_mlp(), None,
                                    nn.CrossEntropyCriterion())
        opt.set_gradient_compression()                  # legacy default
        assert opt.grad_compression is jnp.bfloat16
        opt.set_gradient_compression(
            CompressionSpec(wire="int8", error_feedback=True))
        assert CompressionSpec.parse(opt.grad_compression).quantized

    def test_raw_wire_bytes_across_legs(self, tmp_path):
        """fp32, bf16 and int8 + error feedback on a model of about 19k
        parameters (large enough that the int8 layout's rounding of
        chunks to whole blocks is amortized): the RAW gradient bytes of
        the fp32 leg's events over the int8 leg's are at least 3.5,
        bf16 is exactly 2x, and each leg's loss is finite."""
        legs = {"fp32": None, "bf16": "bf16",
                "int8_ef": CompressionSpec(wire="int8", block_size=256,
                                           error_feedback=True)}
        event = {}
        for name, compression in legs.items():
            model = (nn.Sequential().add(nn.Linear(12, 128)).add(nn.ReLU())
                     .add(nn.Linear(128, 128)).add(nn.ReLU())
                     .add(nn.Linear(128, 5)))
            run_dir = str(tmp_path / name)
            opt = _fit_distri(compression, run_dir=run_dir, steps=3,
                              model=model)
            assert np.isfinite(opt.driver_state["loss"])
            event[name] = [e for e in _events(run_dir)
                           if e["kind"] == "step"][-1]
        assert (event["fp32"]["grad_wire_bytes"]
                / event["int8_ef"]["grad_wire_bytes"]) >= 3.5
        assert event["bf16"]["grad_compression_ratio"] == 2.0
