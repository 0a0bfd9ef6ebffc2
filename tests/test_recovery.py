"""Preemption-proof elastic training (ISSUE 8, docs/robustness.md):
crash-safe checkpoint atomicity, mid-epoch dataset position resume,
N->M data-parallel restart, and the RunSupervisor auto-restart loop.

Tier-1 keeps to cheap IO crash-injection and a handful of short
tiny-MLP runs; the SIGKILL end-to-end drill rides the slow tier.
"""

import json
import logging
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

import bigdl_tpu.nn as nn
from bigdl_tpu import optim
from bigdl_tpu.dataset import SampleToMiniBatch, array_dataset
from bigdl_tpu.dataset.dataset import AbstractDataSet
from bigdl_tpu.optim import LocalOptimizer, DistriOptimizer, Trigger
from bigdl_tpu.optim.recovery import (RunSupervisor, parse_chaos,
                                      snapshot_step_of)
from bigdl_tpu.parallel.zero import (refit_flat_plane,
                                     repartition_ef_residual)
from bigdl_tpu.utils import file_io
from bigdl_tpu.utils.errors import (CheckpointCorruptionError,
                                    ConfigurationError,
                                    TrainingHaltedError)
from bigdl_tpu.utils.random_generator import RNG

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp():
    return (nn.Sequential().add(nn.Linear(12, 32)).add(nn.ReLU())
            .add(nn.Linear(32, 5)))


def _data(n=96, seed=3):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 12)).astype("float32")
    w = rng.standard_normal((12, 5)).astype("float32")
    return x, np.argmax(x @ w, axis=1).astype("int32")


def _step_losses(run_dir):
    """step -> loss from a telemetry JSONL (later lines win)."""
    out = {}
    with open(os.path.join(run_dir, "telemetry.jsonl"),
              errors="replace") as f:
        for ln in f:
            try:
                ev = json.loads(ln)
            except ValueError:
                continue
            if ev.get("kind") == "step":
                out[ev["step"]] = ev["loss"]
    return out


def _local_run(steps, ckpt=None, ckpt_every=None, resume=False,
               run_dir=None, n=96, batch=16, prefetch=0, end=None):
    from bigdl_tpu.observability import StepTelemetry

    RNG.set_seed(7)
    x, y = _data(n)
    ds = array_dataset(x, y) >> SampleToMiniBatch(batch)
    if prefetch:
        ds = ds.prefetch(num_workers=prefetch, queue_depth=3)
    model = _mlp()
    opt = LocalOptimizer(model, ds, nn.CrossEntropyCriterion(),
                         optim.SGD(learning_rate=0.1, momentum=0.9,
                                   dampening=0.0))
    opt.set_end_when(end or Trigger.max_iteration(steps))
    if ckpt:
        opt.set_checkpoint(str(ckpt), Trigger.several_iteration(ckpt_every))
    if resume:
        opt.resume_from_checkpoint()
    tel = None
    if run_dir:
        tel = StepTelemetry(str(run_dir), trace=False)
        opt.set_telemetry(tel)
    opt.optimize()
    if tel:
        tel.close()
    return opt, model


# --------------------------------------------------------------------------- #
# Crash-safe checkpoint IO.
# --------------------------------------------------------------------------- #


class TestAtomicSnapshots:
    def _snap(self, d, tag=2, payload=None):
        return file_io.save_checkpoint(
            str(d), tag, payload or {"w": np.arange(4.0)}, {}, {},
            {"neval": tag, "epoch": 1})

    def test_save_writes_manifest_that_verifies(self, tmp_path):
        p = self._snap(tmp_path)
        man = file_io.read_manifest(p)
        assert man is not None and man["files"]
        rec = man["files"][os.path.basename(p)]
        assert rec["bytes"] == os.path.getsize(p)
        assert file_io.verify_snapshot(p) is None
        assert file_io.latest_checkpoint(str(tmp_path)) == p

    def test_truncated_snapshot_quarantined_falls_back(self, tmp_path):
        good = self._snap(tmp_path, tag=2)
        bad = self._snap(tmp_path, tag=4)
        with open(bad, "r+b") as f:        # crash mid-write: truncate
            f.truncate(os.path.getsize(bad) // 2)
        intact, quarantined = file_io.scan_checkpoints(str(tmp_path))
        assert intact == [good]
        assert any(p.endswith(".corrupt") for p in quarantined)
        assert not os.path.exists(bad)      # moved aside, not deleted
        assert os.path.exists(bad + ".corrupt")

    def test_digest_flip_quarantined(self, tmp_path):
        good = self._snap(tmp_path, tag=2)
        bad = self._snap(tmp_path, tag=4)
        with open(bad, "r+b") as f:         # bit rot: same size
            f.seek(os.path.getsize(bad) // 2)
            b = f.read(1)
            f.seek(-1, 1)
            f.write(bytes([b[0] ^ 0xFF]))
        assert file_io.latest_checkpoint(str(tmp_path)) == good

    def test_kill_between_temp_write_and_rename(self, tmp_path):
        """A writer killed before the rename leaves only a *.tmp-* file:
        invisible to resume, previous snapshot still the latest."""
        good = self._snap(tmp_path, tag=2)
        orphan = os.path.join(str(tmp_path),
                              "checkpoint.4.pkl" + file_io.TMP_MARKER + "99")
        with open(orphan, "wb") as f:
            f.write(b"half a pickle")
        intact, quarantined = file_io.scan_checkpoints(str(tmp_path))
        assert intact == [good] and quarantined == []

    def test_manifestless_legacy_accepted_but_garbage_quarantined(
            self, tmp_path):
        legacy = os.path.join(str(tmp_path), "checkpoint.2.pkl")
        file_io.save({"model_params": {}, "model_state": {},
                      "opt_state": {}, "driver_state": {"neval": 2}},
                     legacy)                 # old API: no manifest
        garbage = os.path.join(str(tmp_path), "checkpoint.4.pkl")
        with open(garbage, "wb") as f:
            f.write(b"\x80\x04 not a pickle at all")
        intact, quarantined = file_io.scan_checkpoints(str(tmp_path))
        assert intact == [legacy]
        assert quarantined and quarantined[0].endswith(".corrupt")

    def test_write_retries_transient_then_raise(self, tmp_path):
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("transient")
            return "ok"

        slept = []
        assert file_io.with_write_retries(
            flaky, retries=3, backoff_s=0.01,
            sleep=slept.append) == "ok"
        assert len(calls) == 3 and len(slept) == 2
        def dead_disk():
            raise OSError("dead disk")

        with pytest.raises(OSError):
            file_io.with_write_retries(dead_disk, retries=1,
                                       backoff_s=0.0, sleep=lambda s: None)

        def deterministic():
            raise TypeError("unpicklable payload")

        retried = []
        with pytest.raises(TypeError):      # deterministic: no retry
            file_io.with_write_retries(deterministic, retries=5,
                                       sleep=retried.append)
        assert retried == []

    def test_sharded_scan_quarantines_digest_mismatch(self, tmp_path):
        base = str(tmp_path)
        for tag, corrupt in ((2, False), (4, True)):
            d = os.path.join(base, f"snap_{tag}")
            os.makedirs(d)
            payload = os.path.join(d, "data.bin")
            with open(payload, "wb") as f:
                f.write(b"x" * 64)
            file_io.atomic_save({"neval": tag}, d + ".driver")
            file_io.write_snapshot_manifest(
                d, extra_files=(d + ".driver",), meta={"layout": {"n": 1}})
            if corrupt:
                with open(payload, "r+b") as f:
                    f.write(b"Y")
        intact, quarantined = file_io.scan_sharded_snapshots(base)
        assert intact == [os.path.join(base, "snap_2")]
        assert os.path.isdir(os.path.join(base, "snap_4.corrupt"))
        # the manifest rode along with the quarantine
        assert os.path.exists(
            os.path.join(base, "snap_4.manifest.json.corrupt"))

    def test_sharded_scan_skips_dir_without_driver_sidecar(self, tmp_path):
        d = os.path.join(str(tmp_path), "snap_6")
        os.makedirs(d)
        intact, quarantined = file_io.scan_sharded_snapshots(str(tmp_path))
        assert intact == [] and quarantined == []


class TestResumeCorruptVsFresh:
    def test_fresh_start_when_dir_empty(self, tmp_path):
        opt, _ = _local_run(0, end=Trigger.max_iteration(0))
        opt.checkpoint_path = str(tmp_path / "none")
        assert opt.resume_from_checkpoint() is opt
        assert getattr(opt, "_resume", None) is None

    def test_all_corrupt_raises_listing_quarantined(self, tmp_path):
        bad = os.path.join(str(tmp_path), "checkpoint.3.pkl")
        with open(bad, "wb") as f:
            f.write(b"truncated nonsense")
        opt, _ = _local_run(0, end=Trigger.max_iteration(0))
        opt.checkpoint_path = str(tmp_path)
        with pytest.raises(CheckpointCorruptionError) as ei:
            opt.resume_from_checkpoint()
        assert "checkpoint.3.pkl.corrupt" in str(ei.value)

    def test_all_sharded_corrupt_raises(self, tmp_path):
        d = os.path.join(str(tmp_path), "snap_2")
        os.makedirs(d)
        with open(os.path.join(d, "data.bin"), "wb") as f:
            f.write(b"x" * 32)
        file_io.atomic_save({"neval": 2}, d + ".driver")
        file_io.write_snapshot_manifest(d, extra_files=(d + ".driver",))
        with open(os.path.join(d, "data.bin"), "r+b") as f:
            f.write(b"CORRUPT")
        opt, _ = _local_run(0, end=Trigger.max_iteration(0))
        with pytest.raises(CheckpointCorruptionError):
            opt.resume_from_sharded_checkpoint(path=str(tmp_path))


# --------------------------------------------------------------------------- #
# Mid-epoch dataset position.
# --------------------------------------------------------------------------- #


class TestDatasetPosition:
    def test_local_dataset_roundtrip(self):
        x, y = _data(12)
        ds = array_dataset(x, y)
        ds.shuffle()
        state = ds.position_state()
        it = ds.data(train=True)
        first = [next(it) for _ in range(5)]
        ds.shuffle()                       # future epoch mutates order
        ds.restore_position(state)
        it2 = ds.data(train=True)
        again = [next(it2) for _ in range(5)]
        for a, b in zip(first, again):
            np.testing.assert_array_equal(a.feature, b.feature)
        ds.shuffle()                       # restored RNG: same reshuffle
        post = [next(ds.data(train=True)) for _ in range(1)]
        ds.restore_position(state)
        ds.shuffle()
        post2 = [next(ds.data(train=True)) for _ in range(1)]
        np.testing.assert_array_equal(post[0].feature, post2[0].feature)

    def test_position_state_size_mismatch_rejected(self):
        x, y = _data(12)
        state = array_dataset(x, y).position_state()
        with pytest.raises(ValueError):
            array_dataset(x[:6], y[:6]).restore_position(state)

    def test_transformed_and_prefetch_delegate(self):
        x, y = _data(24)
        ds = (array_dataset(x, y) >> SampleToMiniBatch(8)).prefetch(
            num_workers=2)
        state = ds.position_state()
        assert state is not None and state["kind"] == "local"
        ds.restore_position(state)         # no raise; threads retired

    def test_stream_dataset_without_position_resumes_with_warning(
            self, tmp_path, caplog):
        """A source with no position_state: resume falls back to the top
        of the epoch, loudly (documented degradation, not a crash)."""
        x, y = _data(64)
        inner = array_dataset(x, y) >> SampleToMiniBatch(16)

        class NoPos(AbstractDataSet):
            def data(self, train):
                return inner.data(train)

            def size(self):
                return inner.size()

            def shuffle(self):
                inner.shuffle()

        RNG.set_seed(7)
        model = _mlp()
        opt = LocalOptimizer(model, NoPos(), nn.CrossEntropyCriterion(),
                             optim.SGD(learning_rate=0.1))
        opt.set_end_when(Trigger.max_iteration(3))
        opt.set_checkpoint(str(tmp_path), Trigger.several_iteration(2))
        opt.optimize()

        RNG.set_seed(7)
        opt2 = LocalOptimizer(_mlp(), NoPos(), nn.CrossEntropyCriterion(),
                              optim.SGD(learning_rate=0.1))
        opt2.set_checkpoint(str(tmp_path), Trigger.several_iteration(100))
        opt2.resume_from_checkpoint()
        opt2.set_end_when(Trigger.max_iteration(5))
        with caplog.at_level(logging.WARNING, "bigdl_tpu.optim"):
            opt2.optimize()
        assert any("position_state" in r.message for r in caplog.records)
        assert opt2.driver_state["neval"] == 6


class TestMidEpochResume:
    def test_resumed_stream_bit_identical(self, tmp_path):
        """5 steps + mid-epoch checkpoint at neval 4, then a fresh
        optimizer resumes and runs to 10: per-step losses AND final
        params bit-match the uninterrupted run (the ISSUE-8 sample
        stream contract; 6 steps/epoch so the snapshot sits mid-epoch,
        and step 10 is mid-epoch-2 after a reshuffle)."""
        straight_dir = tmp_path / "straight"
        _, m_straight = _local_run(10, run_dir=straight_dir)
        base = _step_losses(str(straight_dir))
        assert sorted(base) == list(range(1, 11))

        ck = tmp_path / "ck"
        a_dir = tmp_path / "a"
        _local_run(5, ckpt=ck, ckpt_every=4, run_dir=a_dir)
        assert os.path.exists(str(ck / "checkpoint.4.pkl"))

        b_dir = tmp_path / "b"
        _, m_res = _local_run(10, ckpt=ck, ckpt_every=100, resume=True,
                              run_dir=b_dir)
        got = dict(_step_losses(str(a_dir)))
        got.update(_step_losses(str(b_dir)))   # resumed steps win
        assert sorted(got) == list(range(1, 11))
        # bit-identical: same program, same device, same sample stream
        for s in base:
            assert got[s] == base[s], (s, got[s], base[s])
        for a, b in zip(jax.tree.leaves(m_straight.get_parameters()[0]),
                        jax.tree.leaves(m_res.get_parameters()[0])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.slow
    def test_resumed_stream_through_prefetch_pipeline(self, tmp_path):
        """Same contract with the async input pipeline in front: the
        serial suffix makes consumed-count well-defined, so resume
        fast-forwards the prefetched iterator deterministically."""
        straight_dir = tmp_path / "straight"
        _local_run(8, run_dir=straight_dir, prefetch=2)
        base = _step_losses(str(straight_dir))

        ck = tmp_path / "ck"
        _local_run(4, ckpt=ck, ckpt_every=3, prefetch=2)
        b_dir = tmp_path / "b"
        _local_run(8, ckpt=ck, ckpt_every=100, resume=True,
                   run_dir=b_dir, prefetch=2)
        got = _step_losses(str(b_dir))
        for s, loss in got.items():
            assert loss == base[s], (s, loss, base[s])


# --------------------------------------------------------------------------- #
# N->M data-parallel resume.
# --------------------------------------------------------------------------- #


def _mesh(ndev):
    return jax.sharding.Mesh(
        np.asarray(jax.devices()[:ndev]).reshape(ndev,), ("data",))


def _distri_run(ndev, steps, ckpt=None, every=None, resume=False,
                sharded=False, compression=None, run_dir=None,
                n=128, batch=32):
    from bigdl_tpu.observability import StepTelemetry

    RNG.set_seed(9)
    x, y = _data(n)
    ds = array_dataset(x, y) >> SampleToMiniBatch(batch)
    model = _mlp()
    opt = DistriOptimizer(model, ds, nn.CrossEntropyCriterion(),
                          optim.SGD(learning_rate=0.1, momentum=0.9,
                                    dampening=0.0),
                          mesh=_mesh(ndev), grad_compression=compression)
    opt.set_end_when(Trigger.max_iteration(steps))
    if ckpt:
        trig = Trigger.several_iteration(every)
        if sharded:
            opt.set_sharded_checkpoint(str(ckpt), trig)
        else:
            opt.set_checkpoint(str(ckpt), trig)
        if resume:
            if sharded:
                opt.resume_from_sharded_checkpoint()
            else:
                opt.resume_from_checkpoint()
    tel = None
    if run_dir:
        tel = StepTelemetry(str(run_dir), trace=False)
        opt.set_telemetry(tel)
    opt.optimize()
    if tel:
        tel.close()
    return opt, model


class TestRechunkUnits:
    def test_refit_flat_plane(self):
        a = np.arange(10.0)
        out = np.asarray(refit_flat_plane(a, 12))
        assert out.shape == (12,) and out[10] == 0 and out[3] == 3
        assert np.asarray(refit_flat_plane(out, 10, true_size=9)).shape \
            == (10,)
        with pytest.raises(ValueError):
            refit_flat_plane(a, 6, true_size=8)   # would drop params
        assert np.asarray(refit_flat_plane(np.float32(3.0), 8)).shape == ()

    def test_repartition_preserves_total_correction(self):
        rng = np.random.default_rng(0)
        true, old_pad = 37, 40
        ef = rng.standard_normal((8, old_pad)).astype(np.float32)
        ef[:, true:] = 0                   # padding carries no residual
        out = repartition_ef_residual(ef, true, 4, 44)
        assert out.shape == (4, 44)
        np.testing.assert_allclose(out.sum(axis=0)[:true],
                                   ef.sum(axis=0)[:true], rtol=1e-6)
        # row j only holds its own chunk's offsets
        chunk = 44 // 4
        for j in range(4):
            mask = np.ones(44, bool)
            mask[j * chunk:(j + 1) * chunk] = False
            assert not out[j][mask].any()
        with pytest.raises(ValueError):
            repartition_ef_residual(ef[0], true, 4, 44)


@pytest.fixture(scope="module")
def dp_baseline(tmp_path_factory):
    """Uninterrupted 8-device 6-step trajectory, shared by both N->M
    tests (one mesh compile instead of two)."""
    d = tmp_path_factory.mktemp("dp_base")
    _distri_run(8, 6, run_dir=d)
    return _step_losses(str(d))


class TestNtoMResume:
    def test_pickle_resume_on_fewer_devices_matches(self, tmp_path,
                                                    dp_baseline):
        base = dp_baseline
        ck = tmp_path / "ck"
        _distri_run(8, 3, ckpt=ck, every=3)   # snapshot at neval 3
        man = file_io.read_manifest(
            file_io.latest_checkpoint(str(ck)))
        assert man["layout"]["num_chunks"] == 8

        res_dir = tmp_path / "resumed"
        opt, _ = _distri_run(4, 6, ckpt=ck, every=100, resume=True,
                             run_dir=res_dir)
        assert opt.driver_state["neval"] == 7
        got = _step_losses(str(res_dir))
        assert sorted(got) == [3, 4, 5, 6]
        for s, loss in got.items():
            assert abs(loss - base[s]) < 1e-5, (s, loss, base[s])

    @pytest.mark.slow
    def test_sharded_resume_on_fewer_devices_matches(self, tmp_path,
                                                     dp_baseline):
        base = dp_baseline
        ck = tmp_path / "ck"
        _distri_run(8, 3, ckpt=ck, every=3, sharded=True)
        snap = os.path.join(str(ck), "snap_3")
        layout = file_io.read_manifest(snap)["layout"]
        assert layout["num_chunks"] == 8 and layout["ef_shape"] is None

        res_dir = tmp_path / "resumed"
        opt, _ = _distri_run(2, 6, ckpt=ck, every=100, resume=True,
                             sharded=True, run_dir=res_dir)
        assert opt.driver_state["neval"] == 7
        got = _step_losses(str(res_dir))
        for s, loss in got.items():
            assert abs(loss - base[s]) < 1e-5, (s, loss, base[s])

    @pytest.mark.slow
    def test_ef_residual_survives_n_to_m(self, tmp_path):
        """int8 + error feedback: the (n_dev, padded) residual plane
        re-partitions 8 -> 4 by global flat offset; training continues
        finite and the accumulated correction's total is preserved."""
        import orbax.checkpoint as ocp

        from bigdl_tpu.ops.quantization import CompressionSpec
        spec = CompressionSpec(wire="int8", block_size=64,
                               error_feedback=True)
        ck = tmp_path / "ck"
        _distri_run(8, 3, ckpt=ck, every=3, sharded=True,
                    compression=spec)
        snap = os.path.join(str(ck), "snap_3")
        assert file_io.read_manifest(snap)["layout"]["ef_shape"] == [
            8, file_io.read_manifest(snap)["layout"]["padded_size"]]
        with ocp.StandardCheckpointer() as ckptr:
            saved_ef = np.asarray(ckptr.restore(snap)["ef_residual"])
        assert np.abs(saved_ef).sum() > 0

        opt, _ = _distri_run(4, 5, ckpt=ck, every=100, resume=True,
                             sharded=True, compression=spec)
        assert opt.driver_state["neval"] == 6
        assert np.isfinite(opt.driver_state["loss"])


# --------------------------------------------------------------------------- #
# RunSupervisor (in-process).
# --------------------------------------------------------------------------- #


class _Boom(Trigger):
    """Raise mid-run exactly once per process (injected transient)."""

    stateful = True
    fired = False

    def __init__(self, at_step, exc=RuntimeError("injected failure")):
        self.at_step = at_step
        self.exc = exc

    def __call__(self, state):
        if not type(self).fired and state.get("neval", 1) > self.at_step:
            type(self).fired = True
            raise self.exc
        return False


class TestRunSupervisor:
    def _factory(self, tmp_path, boom=None, steps=6, every=2):
        def factory(attempt):
            RNG.set_seed(7)
            x, y = _data(96)
            ds = array_dataset(x, y) >> SampleToMiniBatch(16)
            opt = LocalOptimizer(_mlp(), ds, nn.CrossEntropyCriterion(),
                                 optim.SGD(learning_rate=0.1))
            end = Trigger.max_iteration(steps)
            if attempt == 0 and boom is not None:
                end = Trigger.or_(boom, end)
            opt.set_end_when(end)
            opt.set_checkpoint(str(tmp_path),
                               Trigger.several_iteration(every))
            return opt
        return factory

    def test_restarts_from_last_snapshot_and_completes(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("BIGDL_FAILURE_RETRY_TIMES", "0")
        _Boom.fired = False
        slept = []
        sup = RunSupervisor(max_restarts=2, backoff_base_s=0.5,
                            backoff_max_s=4.0, sleep=slept.append)
        opt = sup.run(self._factory(tmp_path, boom=_Boom(4)))
        assert opt.driver_state["neval"] == 7
        assert sup.restarts == 1 and slept == [0.5]
        ev = sup.events[0]
        assert ev["cause"] == "exception" and ev["restart"] == 1
        assert ev["snapshot"].endswith("checkpoint.4.pkl")
        assert ev["at_step"] == 5 and ev["steps_replayed"] == 1

    def test_watchdog_halt_cause_and_budget(self, tmp_path, monkeypatch):
        monkeypatch.setenv("BIGDL_FAILURE_RETRY_TIMES", "0")
        _Boom.fired = False
        sup = RunSupervisor(max_restarts=3, backoff_base_s=0.0,
                            sleep=lambda s: None)
        opt = sup.run(self._factory(
            tmp_path, boom=_Boom(2, TrainingHaltedError("numerics"))))
        assert sup.events[0]["cause"] == "watchdog_halt"
        assert opt.driver_state["neval"] == 7

    def test_repeated_identical_failure_stops_early(self, monkeypatch):
        class Dummy:
            checkpoint_path = None
            sharded_checkpoint_path = None
            driver_state = {"neval": 5}

            def optimize(self):
                raise RuntimeError("always")

        sup = RunSupervisor(max_restarts=10, backoff_base_s=0.0,
                            sleep=lambda s: None)
        with pytest.raises(RuntimeError, match="twice in a row"):
            sup.run(lambda attempt: Dummy())
        assert sup.restarts == 1     # one restart, then the early stop

    def test_budget_exhausted_raises(self):
        class Dummy:
            checkpoint_path = None
            sharded_checkpoint_path = None

            def __init__(self, attempt):
                self.driver_state = {"neval": attempt}

            def optimize(self):
                raise RuntimeError("varying step -> not a repeat")

        sup = RunSupervisor(max_restarts=2, backoff_base_s=0.0,
                            sleep=lambda s: None)
        with pytest.raises(RuntimeError, match="budget"):
            sup.run(lambda attempt: Dummy(attempt))
        assert sup.restarts == 2

    def test_backoff_caps(self):
        sup = RunSupervisor(backoff_base_s=1.0, backoff_max_s=5.0)
        assert [sup.backoff_s(i) for i in range(5)] == [1, 2, 4, 5, 5]

    def test_chaos_parse(self):
        assert parse_chaos("kill:9") == ("kill", 9)
        assert parse_chaos(None) is None
        for bad in ("kill", "kill:0", "kill:x", "explode:3"):
            with pytest.raises(ConfigurationError):
                parse_chaos(bad)

    def test_snapshot_step_of(self):
        assert snapshot_step_of("/a/b/checkpoint.12.pkl") == 12
        assert snapshot_step_of("/a/b/snap_7") == 7
        assert snapshot_step_of(None) is None
        assert snapshot_step_of("weird") is None


# --------------------------------------------------------------------------- #
# Serving: refresh validation (satellite).
# --------------------------------------------------------------------------- #


class TestServingRefreshValidation:
    def test_bad_refresh_rejected_engine_keeps_serving(self):
        from bigdl_tpu.serving import ServingEngine

        x, _ = _data(8)
        model = _mlp()
        model.build(jax.ShapeDtypeStruct((4, 12), np.float32))
        with ServingEngine(model, max_batch_size=4,
                           max_wait_ms=1.0) as eng:
            before = np.asarray(eng.predict(x[0]))
            good = jax.tree.map(lambda l: l, model.parameters()[0])
            bad_shape = jax.tree.map(
                lambda l: np.zeros((3,) + tuple(np.shape(l)), l.dtype),
                good)
            with pytest.raises(ValueError, match="keeps serving"):
                eng.refresh_params(bad_shape)
            bad_struct = {"not": {"the": {"same": np.zeros(3)}}}
            with pytest.raises(ValueError, match="keeps serving"):
                eng.refresh_params(bad_struct)
            # old weights still served after the rejected swaps
            np.testing.assert_array_equal(
                before, np.asarray(eng.predict(x[0])))
            # a VALID refresh goes through and changes the outputs
            new = jax.tree.map(lambda l: np.asarray(l) * 0.5, good)
            eng.refresh_params(new)
            after = np.asarray(eng.predict(x[0]))
            assert not np.array_equal(before, after)


# --------------------------------------------------------------------------- #
# obs_report "Recovery" section.
# --------------------------------------------------------------------------- #


def _load_obs_report():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_rec_obs", os.path.join(REPO, "tools", "obs_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestRecoveryReporting:
    def test_recovery_event_durable_and_rendered(self, tmp_path):
        from bigdl_tpu.observability import StepTelemetry

        run = str(tmp_path / "run")
        tel = StepTelemetry(run, trace=False)
        sup = RunSupervisor(max_restarts=2, backoff_base_s=0.25,
                            telemetry=tel, sleep=lambda s: None)

        class Dummy:
            checkpoint_path = None
            sharded_checkpoint_path = None
            driver_state = {"neval": 9}

            def __init__(self, fail):
                self.fail = fail

            def optimize(self):
                if self.fail:
                    raise RuntimeError("preempted")

        sup.run(lambda attempt: Dummy(fail=(attempt == 0)))
        tel.close()
        mod = _load_obs_report()
        rep = mod.build_report(run)
        rc = rep["recovery"]
        assert rc["restarts"] == 1
        assert rc["causes"] == {"exception": 1}
        assert rc["events"][0]["at_step"] == 9
        text = mod.format_report(rep)
        assert "recovery: 1 restart(s) (exception x1)" in text
        json.dumps(mod._json_safe(rep), allow_nan=False)   # strict JSON


class TestRecoveryEventContract:
    """ISSUE-8 pin: the ``kind: "recovery"`` telemetry event schema the
    RunSupervisor emits (docs/robustness.md) -- obs_report's Recovery
    section and any external consumer parse exactly these keys."""

    def test_recovery_event_schema(self):
        from bigdl_tpu.optim.recovery import (RECOVERY_CAUSES,
                                              RECOVERY_EVENT_KEYS)

        events = []

        class Sink:                    # minimal telemetry duck type
            def record(self, kind, **fields):
                events.append({"kind": kind, **fields})

        class Dummy:
            checkpoint_path = None
            sharded_checkpoint_path = None
            driver_state = {"neval": 7}

            def __init__(self, fail):
                self.fail = fail

            def optimize(self):
                if self.fail:
                    raise RuntimeError("preempted")

        sup = RunSupervisor(max_restarts=1, backoff_base_s=0.5,
                            telemetry=Sink(), sleep=lambda s: None)
        sup.run(lambda attempt: Dummy(fail=(attempt == 0)))
        assert len(events) == 1
        ev = events[0]
        assert ev["kind"] == "recovery"
        # the closed key set, all present even when unknown (None)
        assert set(RECOVERY_EVENT_KEYS) <= set(ev)
        assert ev["cause"] in RECOVERY_CAUSES
        assert ev["restart"] == 1
        assert ev["at_step"] == 7
        assert ev["backoff_s"] == 0.5
        assert ev["snapshot"] is None and ev["steps_replayed"] is None
        json.dumps(ev)                 # JSONL-ready

    def test_recovery_is_durable_kind(self):
        from bigdl_tpu.observability.telemetry import DURABLE_KINDS

        assert "recovery" in DURABLE_KINDS


# --------------------------------------------------------------------------- #
# Slow tier: the SIGKILL acceptance drill (ISSUE 8 acceptance criteria).
# --------------------------------------------------------------------------- #


def _cli(out, *extra):
    cmd = [sys.executable, "-m", "tools.train_supervised", "--out", out,
           "--steps", "12", "--batch", "64", "--datasetSize", "256",
           "--backoff", "0.05"] + list(extra)
    return subprocess.run(cmd, cwd=REPO, capture_output=True, text=True,
                          timeout=420)


def _attempt_losses(out):
    merged, per_attempt = {}, {}
    for att in sorted(os.listdir(out)):
        if not att.startswith("attempt_"):
            continue
        p = os.path.join(out, att)
        if os.path.isfile(os.path.join(p, "telemetry.jsonl")):
            per_attempt[att] = _step_losses(p)
            merged.update(per_attempt[att])
    return merged, per_attempt


@pytest.mark.slow
class TestSIGKILLAcceptance:
    def test_kill_midepoch_restart_fewer_devices_matches_baseline(
            self, tmp_path):
        """ISSUE-8 acceptance: SIGKILL an 8-device ZeRO-1 run at a
        mid-epoch step (checkpoint cadence 3 vs 4 steps/epoch: the
        resumed position sits INSIDE an epoch), auto-restart on 4
        devices via RunSupervisor, and the recovered loss trajectory
        matches the uninterrupted 8-device baseline within 5e-5 with
        zero duplicated or skipped samples (witnessed from the step
        events + the recovery record)."""
        base_out = str(tmp_path / "base")
        r = _cli(base_out, "--devices", "8", "--ckptEvery", "100")
        assert r.returncode == 0, r.stderr[-2000:]
        base, _ = _attempt_losses(base_out)
        assert sorted(base) == list(range(1, 13))

        drill_out = str(tmp_path / "drill")
        r = _cli(drill_out, "--devices", "8", "--restartDevices", "4",
                 "--ckptEvery", "3", "--chaos", "kill:5")
        assert r.returncode == 0, r.stderr[-2000:]
        summary = json.loads(r.stdout.strip().splitlines()[-1])
        assert summary["restarts"] == 1
        ev = summary["recovery_events"][0]
        assert ev["cause"] == "process_death"
        assert ev["snapshot_step"] is not None
        assert ev["steps_replayed"] is not None

        merged, per_attempt = _attempt_losses(drill_out)
        # zero skipped: the union of recorded steps is exactly 1..12
        assert sorted(merged) == list(range(1, 13))
        # zero duplicated/skewed samples: EVERY attempt's loss at every
        # step matches the uninterrupted baseline (replayed steps re-ran
        # the same batches against the same restored params)
        for att, losses in per_attempt.items():
            for s, loss in losses.items():
                assert abs(loss - base[s]) < 5e-5, (att, s, loss, base[s])
        # the supervisor's run report renders the recovery section
        mod = _load_obs_report()
        text = mod.format_report(
            mod.build_report(os.path.join(drill_out, "supervisor")))
        assert "recovery: 1 restart(s) (process_death x1)" in text

    def test_chaos_drill_smoke_second_kill_gives_up_cleanly(
            self, tmp_path):
        """Budget honesty: with max restarts 0 the supervisor emits no
        event, exits nonzero, and leaves the snapshots intact."""
        out = str(tmp_path / "drill")
        r = _cli(out, "--devices", "2", "--ckptEvery", "2",
                 "--chaos", "kill:3", "--maxRestarts", "0")
        assert r.returncode == 2, (r.stdout, r.stderr[-1500:])
        assert file_io.latest_checkpoint(os.path.join(out, "ckpt"))
