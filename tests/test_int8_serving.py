"""Int8 end-to-end serving (ISSUE 11): the general post-training
quantizer (`nn.quantize_model` over Sequential / Graph / TransformerLM in
both param layouts), the `ServingEngine(quantize=...)` path on all three
device layouts, the fp32-vs-int8 accuracy-delta gate riding the
`param_refresh` audit path, the serving-precision telemetry stamp, and
both precisions under the same concurrent clients."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.nn.quantized import (model_bytes, quantize_model,
                                    quantize_params, quantized_leaf_count)
from bigdl_tpu.observability import StepTelemetry
from bigdl_tpu.observability.watchdogs import backend_compile_count
from bigdl_tpu.optim.validation import AccuracyDeltaGate
from bigdl_tpu.serving import ServingEngine
from bigdl_tpu.utils.random_generator import RNG


def _mlp(hidden=32, seed=0):
    RNG.set_seed(seed)
    m = (nn.Sequential().add(nn.Linear(16, hidden)).add(nn.ReLU())
         .add(nn.Linear(hidden, 10)))
    m.build(jax.ShapeDtypeStruct((2, 16), jnp.float32))
    return m


def _xs(n=64, seed=0):
    return np.random.default_rng(seed).standard_normal((n, 16)) \
        .astype("float32")


def _events(d):
    with open(str(d) + "/telemetry.jsonl") as f:
        return [json.loads(l) for l in f]


# --------------------------------------------------------------------------- #
# The general quantizer.
# --------------------------------------------------------------------------- #

class TestQuantizeModelGeneral:
    def test_sequential_new_pair_original_untouched(self):
        m = _mlp()
        x = jnp.asarray(_xs(4))
        ref = np.asarray(m.apply(m._params, m._state, x, training=False)[0])
        qm, qp = quantize_model(m)
        got = np.asarray(qm.apply(qp, qm._state, x, training=False)[0])
        assert np.abs(got - ref).max() / np.abs(ref).max() < 0.05
        # non-mutating: the fp32 original keeps serving during staging
        assert quantized_leaf_count(m._params) == 0
        assert qm is not m and qm._params is qp
        assert quantized_leaf_count(qp) == 2
        assert model_bytes(m._params) / model_bytes(qp) > 2.5

    def test_graph_coverage(self):
        RNG.set_seed(1)
        inp = nn.Input()
        h = nn.Linear(16, 24)(inp)
        a = nn.ReLU()(h)
        out = nn.Linear(24, 5)(a)
        g = nn.Graph([inp], [out])
        g.build(jax.ShapeDtypeStruct((2, 16), jnp.float32))
        x = jnp.asarray(_xs(4))
        ref = np.asarray(g.apply(g._params, g._state, x, training=False)[0])
        qg, qp = quantize_model(g)
        got = np.asarray(qg.apply(qp, qg._state, x, training=False)[0])
        assert quantized_leaf_count(qp) == 2
        assert np.abs(got - ref).max() / np.abs(ref).max() < 0.05

    def test_transformer_both_layouts_agree(self):
        """Unrolled "block{i}" and scan-stacked "blocks" layouts
        quantize to numerically identical int8 models (the stacked
        leaves carry a per-layer leading axis through
        quantize_channelwise)."""
        from bigdl_tpu.nn.attention import TransformerLM

        toks = jnp.asarray(
            np.random.default_rng(1).integers(0, 64, (2, 8)), jnp.int32)
        outs = {}
        for scan in (False, True):
            RNG.set_seed(7)
            lm = TransformerLM(64, 32, 2, 2, max_len=16, scan_layers=scan)
            lm.build(jax.ShapeDtypeStruct((2, 8), jnp.int32))
            qlm, qp = quantize_model(lm)
            # per block: qkv + out + fc1 + fc2; scan stacks them into 4
            assert quantized_leaf_count(qp) == (4 if scan else 8)
            # embeddings / positional / head / layernorms stay fp32
            for k in ("wte", "wpe", "head"):
                assert qp[k].dtype == jnp.float32
            outs[scan] = np.asarray(
                qlm.apply(qp, qlm._state, toks, training=False)[0])
        np.testing.assert_allclose(outs[False], outs[True],
                                   rtol=1e-5, atol=1e-5)

    def test_select_predicate_allow_deny(self):
        m = _mlp()
        qp = quantize_params(m, select=lambda path, mod: path != "0")
        assert quantized_leaf_count(qp) == 1
        assert "weight" in qp["0"] and "weight_q" in qp["2"]
        # predicate sees the module too
        qp2 = quantize_params(
            m, select=lambda path, mod: isinstance(mod, nn.Linear)
            and mod.output_size == 10)
        assert quantized_leaf_count(qp2) == 1 and "weight_q" in qp2["2"]

    def test_subclassed_conv_stems_excluded(self):
        """SpaceToDepthStem restructures its weight inside apply: the
        exact-type check must leave it fp32."""
        RNG.set_seed(2)
        m = nn.Sequential().add(nn.SpaceToDepthStem(3, 8, kernel=7))
        m.build(jax.ShapeDtypeStruct((1, 16, 16, 3), jnp.float32))
        qp = quantize_params(m)
        assert quantized_leaf_count(qp) == 0

    def test_unbuilt_model_rejected(self):
        m = nn.Sequential().add(nn.Linear(4, 2))
        with pytest.raises(ValueError, match="built"):
            quantize_model(m)


# --------------------------------------------------------------------------- #
# The accuracy-delta gate (unit level).
# --------------------------------------------------------------------------- #

class TestAccuracyDeltaGate:
    def _logits(self, n=16, c=5, seed=0):
        return np.random.default_rng(seed).standard_normal((n, c)) \
            .astype("float32")

    def test_agreement_pass_and_fail(self):
        ref = self._logits()
        gate = AccuracyDeltaGate(features=np.zeros((16, 3), "float32"),
                                 min_top1_agreement=0.99)
        ok, detail = gate.check(lambda x: ref, lambda x: ref + 1e-4)
        assert ok and detail["top1_agreement"] == 1.0
        flipped = ref.copy()
        flipped[:8] = -flipped[:8]       # argmax changes on half the rows
        ok, detail = gate.check(lambda x: ref, lambda x: flipped)
        assert not ok
        assert "agreement" in detail["reason"]
        assert detail["top1_agreement"] <= 0.6

    def test_label_accuracy_drop(self):
        ref = self._logits(n=20)
        labels = np.argmax(ref, -1)      # fp32 is 100% accurate
        cand = ref.copy()
        cand[:5] = np.roll(cand[:5], 1, axis=-1)   # 25% of rows wrong
        gate = AccuracyDeltaGate(features=np.zeros((20, 3), "float32"),
                                 labels=labels, min_top1_agreement=None,
                                 max_top1_accuracy_drop=0.1)
        ok, detail = gate.check(lambda x: ref, lambda x: cand)
        assert not ok and "accuracy drop" in detail["reason"]
        assert detail["top1_accuracy_ref"] == 1.0
        gate2 = AccuracyDeltaGate(features=np.zeros((20, 3), "float32"),
                                  labels=labels, min_top1_agreement=None,
                                  max_top1_accuracy_drop=0.3)
        ok2, _ = gate2.check(lambda x: ref, lambda x: cand)
        assert ok2

    def test_logit_rmse_tolerance(self):
        ref = self._logits()
        gate = AccuracyDeltaGate(features=np.zeros((16, 3), "float32"),
                                 min_top1_agreement=None,
                                 max_logit_rmse=0.01)
        ok, detail = gate.check(lambda x: ref, lambda x: ref + 0.5)
        assert not ok and "RMSE" in detail["reason"]

    def test_all_tolerances_disabled_rejected(self):
        with pytest.raises(ValueError, match="gates nothing"):
            AccuracyDeltaGate(features=np.zeros((4, 3)),
                              min_top1_agreement=None,
                              max_top1_accuracy_drop=None)


# --------------------------------------------------------------------------- #
# ServingEngine(quantize=...) on the three device layouts.
# --------------------------------------------------------------------------- #

def _bad_params(m):
    """Spec-valid fp32 weights the per-channel quantizer damages badly:
    the head's every out-channel is dominated by one huge input column,
    so the remaining signal quantizes to zeros and argmax degrades."""
    p = m.parameters()[0]
    w2 = np.asarray(p["2"]["weight"]).copy() * 1e-5
    w2[:, 0] = np.random.default_rng(9).standard_normal(w2.shape[0]) * 1e3
    return {**p, "2": {**p["2"], "weight": jnp.asarray(w2)}}


class TestInt8ServingEngine:
    def test_local_int8_serves_with_zero_recompiles(self, tmp_path):
        m = _mlp(hidden=64)
        xs = _xs()
        tel = StepTelemetry(str(tmp_path), run_name="serve", trace=False)
        with ServingEngine(m, max_batch_size=8, telemetry=tel,
                           quantize=True,
                           accuracy_gate={"features": xs[:32],
                                          "min_top1_agreement": 0.9}) as eng:
            assert eng.quantized
            assert eng.precompile() > 0
            before = backend_compile_count()
            outs = [eng.predict(xs[i]) for i in range(16)]
            assert backend_compile_count() - before == 0
            # int8 outputs track the fp32 model within quant error
            ref = np.asarray(m.forward(xs[:1]))[0]
            rel = np.abs(outs[0] - ref).max() / np.abs(ref).max()
            assert rel < 0.05, rel
            assert eng.serving_model_bytes() * 2.5 \
                < model_bytes(m.parameters()[0])
        tel.close()

    def test_sharded_mesh_int8(self, tmp_path):
        from jax.sharding import Mesh

        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 host devices")
        mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("data",))
        m = _mlp(seed=3)
        xs = _xs()
        tel = StepTelemetry(str(tmp_path), run_name="serve", trace=False)
        with ServingEngine(m, max_batch_size=8, mesh=mesh, telemetry=tel,
                           quantize=True) as eng:
            eng.precompile()
            before = backend_compile_count()
            futs = [eng.submit(xs[i]) for i in range(12)]
            [f.result(30) for f in futs]
            assert backend_compile_count() - before == 0
            # the replica swap stages the int8 payload+scales tree once
            # per mesh device: the audit event records those wire bytes
            eng.refresh_params(params=m.parameters()[0])
            expect_wire = eng.serving_model_bytes() * 2
        tel.close()
        refreshes = [e for e in _events(tmp_path)
                     if e["kind"] == "param_refresh"]
        assert refreshes[-1]["outcome"] == "ok"
        assert refreshes[-1]["quantized"] is True
        assert refreshes[-1]["wire_bytes"] == expect_wire

    def test_round_robin_int8(self):
        if len(jax.local_devices()) < 2:
            pytest.skip("needs >= 2 host devices")
        m = _mlp(seed=4)
        xs = _xs()
        with ServingEngine(m, max_batch_size=4, round_robin=True,
                           quantize=True) as eng:
            eng.precompile()
            before = backend_compile_count()
            outs = [eng.predict(xs[i]) for i in range(8)]
            assert backend_compile_count() - before == 0
            ref = np.asarray(m.forward(xs[:1]))[0]
            assert np.abs(outs[0] - ref).max() / np.abs(ref).max() < 0.05

    def test_refresh_quantizes_incoming_fp32_checkpoint(self, tmp_path):
        m = _mlp(hidden=24, seed=5)
        xs = _xs()
        tel = StepTelemetry(str(tmp_path), run_name="serve", trace=False)
        with ServingEngine(m, max_batch_size=4, telemetry=tel,
                           quantize=True) as eng:
            eng.precompile()
            y_old = eng.predict(xs[0])
            # an UPDATED fp32 checkpoint (as a retrain would hand over)
            newp = jax.tree.map(lambda a: a * 1.5, m.parameters()[0])
            eng.refresh_params(params=newp)
            y_new = eng.predict(xs[0])
            # the engine serves the quantization of the NEW weights
            assert not np.allclose(y_old, y_new)
            qm, qp = eng._qmodel, eng._qmodel.parameters()[0]
            assert quantized_leaf_count(qp) == 2
            expect = np.asarray(
                qm.apply(qp, qm._state, jnp.asarray(xs[:1]),
                         training=False)[0])[0]
            np.testing.assert_allclose(y_new, expect, rtol=1e-5, atol=1e-6)
        tel.close()
        refreshes = [e for e in _events(tmp_path)
                     if e["kind"] == "param_refresh"]
        assert [e["outcome"] for e in refreshes] == ["ok"]
        assert refreshes[0]["model_bytes"] == eng.serving_model_bytes()

    def test_gate_rejects_bad_swap_via_audit_path(self, tmp_path):
        """ISSUE-11 acceptance: the accuracy-delta gate rejects a bad
        swap through the param_refresh rejected-with-reason path and
        the engine keeps serving its previous weights."""
        m = _mlp(hidden=64, seed=6)
        xs = _xs()
        tel = StepTelemetry(str(tmp_path), run_name="serve", trace=False)
        with ServingEngine(m, max_batch_size=4, telemetry=tel,
                           quantize=True,
                           accuracy_gate=AccuracyDeltaGate(
                               features=xs[:32],
                               min_top1_agreement=0.9)) as eng:
            eng.precompile()
            y_before = eng.predict(xs[0])
            with pytest.raises(ValueError, match="accuracy gate"):
                eng.refresh_params(params=_bad_params(m))
            # old weights keep serving, bit for bit
            np.testing.assert_array_equal(y_before, eng.predict(xs[0]))
        tel.close()
        refreshes = [e for e in _events(tmp_path)
                     if e["kind"] == "param_refresh"]
        assert [e["outcome"] for e in refreshes] == ["rejected"]
        assert "agreement" in refreshes[0]["reason"]
        assert refreshes[0]["accuracy_gate"]["ok"] is False

    def test_gate_refuses_initial_quantization(self):
        m = _mlp(hidden=64, seed=8)
        m.set_parameters(_bad_params(m))
        with pytest.raises(ValueError, match="initial int8 quantization"):
            ServingEngine(m, max_batch_size=4, quantize=True,
                          accuracy_gate={"features": _xs()[:32],
                                         "min_top1_agreement": 0.9})

    def test_accuracy_gate_requires_quantize(self):
        m = _mlp()
        with pytest.raises(ValueError, match="quantize"):
            ServingEngine(m, accuracy_gate={"features": _xs()[:8]})

    def test_structural_mismatch_still_rejected_before_gate(self, tmp_path):
        """The PR 8 structure/shape contract runs FIRST: a half-written
        checkpoint never reaches quantization or the gate."""
        m = _mlp(seed=10)
        with ServingEngine(m, max_batch_size=4, quantize=True) as eng:
            p = dict(m.parameters()[0])
            del p["2"]
            with pytest.raises(ValueError, match="tree structure"):
                eng.refresh_params(params=p)

    def test_select_predicate_through_engine(self):
        m = _mlp(seed=11)
        with ServingEngine(m, max_batch_size=4,
                           quantize=lambda path, mod: path == "2") as eng:
            qp = eng._qmodel.parameters()[0]
            assert "weight" in qp["0"] and "weight_q" in qp["2"]


# --------------------------------------------------------------------------- #
# Telemetry stamp + obs_report render (ISSUE-11 satellite).
# --------------------------------------------------------------------------- #

def _obs_report():
    import importlib.util
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "_int8_obs", os.path.join(repo, "tools", "obs_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestServingPrecisionTelemetry:
    def _run(self, d, quantize):
        m = _mlp(hidden=48, seed=12)
        xs = _xs()
        tel = StepTelemetry(str(d), run_name="serve", trace=False)
        kw = {"quantize": True,
              "accuracy_gate": {"features": xs[:16],
                                "min_top1_agreement": 0.8}} if quantize \
            else {}
        with ServingEngine(m, max_batch_size=4, telemetry=tel, **kw) as eng:
            eng.precompile()
            for i in range(6):
                eng.predict(xs[i])
        tel.close()

    def test_header_states_the_precision(self, tmp_path):
        self._run(tmp_path, quantize=True)
        header = [e for e in _events(tmp_path) if e["kind"] == "header"][0]
        sv = header["serving"]
        assert sv["quantized"] is True
        assert sv["weight_dtype"] == "int8"
        assert 0 < sv["model_bytes"] < sv["model_bytes_fp32"]
        assert sv["accuracy_gate"]["ok"] is True

    def test_fp32_run_stamps_float32(self, tmp_path):
        self._run(tmp_path, quantize=False)
        header = [e for e in _events(tmp_path) if e["kind"] == "header"][0]
        sv = header["serving"]
        assert sv["quantized"] is False
        assert sv["weight_dtype"] == "float32"

    def test_obs_report_section_and_text(self, tmp_path):
        self._run(tmp_path, quantize=True)
        mod = _obs_report()
        rep = mod.build_report(str(tmp_path))
        sv = rep["serving"]
        assert sv["quantized"] is True and sv["weight_dtype"] == "int8"
        assert sv["model_bytes_fp32"] > sv["model_bytes"]
        text = mod.format_report(rep)
        assert "serving precision: int8 (quantized)" in text
        assert "accuracy gate: ok" in text
        # strict JSON round-trips
        js = json.dumps(mod._json_safe(rep), allow_nan=False)
        assert json.loads(js)["serving"]["weight_dtype"] == "int8"

    def test_report_lists_rejections(self, tmp_path):
        m = _mlp(hidden=64, seed=13)
        xs = _xs()
        tel = StepTelemetry(str(tmp_path), run_name="serve", trace=False)
        with ServingEngine(m, max_batch_size=4, telemetry=tel,
                           quantize=True,
                           accuracy_gate={"features": xs[:32],
                                          "min_top1_agreement": 0.9}) as eng:
            eng.precompile()
            eng.predict(xs[0])
            with pytest.raises(ValueError):
                eng.refresh_params(params=_bad_params(m))
        tel.close()
        mod = _obs_report()
        rep = mod.build_report(str(tmp_path))
        pr = rep["serving"]["param_refreshes"]
        assert pr["rejected"] == 1 and pr["ok"] == 0
        assert "agreement" in pr["rejection_reasons"][0]
        assert "rejected: accuracy gate" in mod.format_report(rep)


# --------------------------------------------------------------------------- #
# fp32 and int8 engines under the same concurrent clients.
# --------------------------------------------------------------------------- #

class TestInt8UnderConcurrentClients:
    def test_same_requests_through_both_precisions(self, tmp_path):
        """Four clients, twelve requests, one bucket, through an fp32
        and an int8 engine over the same MLP: no compile
        after ``precompile()`` on either, the accuracy gate passes, the
        run report states the precision, the int8 tree is under a third
        of the fp32 bytes and its outputs track the fp32 engine's."""
        from concurrent.futures import ThreadPoolExecutor

        m = _mlp(hidden=64)
        xs = _xs(256)
        outs, model_bytes_of = {}, {}
        for name, kw in (("fp32", {}),
                         ("int8", {"quantize": True, "accuracy_gate": {
                             "features": xs[:64],
                             "min_top1_agreement": 0.98}})):
            run_dir = tmp_path / name
            tel = StepTelemetry(str(run_dir), run_name="serve",
                                trace=False)
            with ServingEngine(m, max_batch_size=4, max_wait_ms=5.0,
                               telemetry=tel, **kw) as eng:
                eng.precompile()
                before = backend_compile_count()
                with ThreadPoolExecutor(4) as clients:
                    outs[name] = list(clients.map(eng.predict, xs[:12]))
                assert backend_compile_count() - before == 0
                model_bytes_of[name] = eng.serving_model_bytes()
                if name == "int8":
                    assert eng._gate_detail["ok"] is True
            tel.close()
            report = _obs_report().build_report(str(run_dir))
            assert report["serving"]["quantized"] is (name == "int8")
        assert model_bytes_of["int8"] * 3 < model_bytes_of["fp32"]
        delta = max(np.abs(q - f).max()
                    for q, f in zip(outs["int8"], outs["fp32"]))
        assert delta / max(np.abs(f).max() for f in outs["fp32"]) < 0.1
