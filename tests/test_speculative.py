"""ISSUE 19 tentpole (b): speculative decoding with the gated int8
twin as drafter.

Pins, per the acceptance criteria:

- the GREEDY speculative stream is BIT-IDENTICAL to verifier-only
  decoding in both param layouts: acceptance only reorders work, every
  emitted token is the fp32 verifier's own;
- seeded sampling replays exactly and matches the plain paged engine
  (the sampler is pure in (seed, position), and the verifier samples
  every position of the round from its own logits);
- speculative decoding composes with ``kv_cache_dtype="int8"``;
- zero steady-state recompiles across mixed prompt lengths AND sampled
  decoding after ``precompile()`` -- the draft loop and the one-shot
  verify ride fixed shapes;
- tick events stamp ``spec_k`` / ``spec_drafted`` / ``spec_accepted``
  and the registry renders ``bigdl_serving_spec_drafted_total`` /
  ``bigdl_serving_spec_accepted_total``;
- refusals are legible (speculative needs the paged layout), the
  accuracy gate composes with ``speculative=k`` to vet the drafter,
  and ``quantize_model`` never leaks the fp32 original's compiled step
  caches into the twin (the drafter must not verify itself);
- the same three legs (fp32 pool, int8 pool, speculation) at prompts of
  64-256 tokens: the int8 byte floor, zero recompiles, greedy identity.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.attention import TransformerLM
from bigdl_tpu.observability.watchdogs import backend_compile_count
from bigdl_tpu.serving import ServingEngine

VOCAB = 50


def _lm(layers=2, max_len=64, scan=False, hidden=32, key=0):
    m = TransformerLM(vocab_size=VOCAB, hidden_size=hidden, num_heads=4,
                      num_layers=layers, max_len=max_len,
                      scan_layers=scan)
    m.build(jax.ShapeDtypeStruct((2, 16), jnp.int32),
            rng=jax.random.PRNGKey(key))
    return m


def _greedy_reference(m, prompt, n_new):
    params = m.parameters()[0]
    toks = [int(t) for t in prompt]
    out = []
    for _ in range(n_new):
        logits, _ = m.apply(params, (),
                            jnp.asarray([toks], jnp.int32))
        nxt = int(np.argmax(np.asarray(logits)[0, -1]))
        toks.append(nxt)
        out.append(nxt)
    return out


class TestSpeculativeIdentity:
    @pytest.mark.parametrize("scan", [False, True])
    def test_greedy_stream_bit_identical(self, scan):
        """The headline contract: speculation changes WHEN tokens are
        computed, never WHICH tokens come out."""
        m = _lm(layers=2, scan=scan)
        prompts = [[1, 2, 3], [7, 8, 9, 10, 11], [4] * 9]
        refs = [_greedy_reference(m, p, 6) for p in prompts]
        streams = {}
        for spec in (0, 3):
            with ServingEngine(m, decode_slots=3, decode_max_len=48,
                               kv_block_size=4,
                               speculative=spec) as eng:
                futs = [eng.generate(p, max_new_tokens=6)
                        for p in prompts]
                streams[spec] = [f.result(60) for f in futs]
        assert streams[3] == streams[0] == refs

    def test_seeded_sampling_replays_and_matches_plain(self):
        m = _lm(layers=2)
        kw = dict(max_new_tokens=6, temperature=0.8, top_k=10, seed=11)
        with ServingEngine(m, decode_slots=2, decode_max_len=48,
                           kv_block_size=4, speculative=2) as eng:
            a = eng.generate([1, 2, 3], **kw).result(60)
            b = eng.generate([1, 2, 3], **kw).result(60)
        with ServingEngine(m, decode_slots=2, decode_max_len=48,
                           kv_block_size=4) as eng:
            c = eng.generate([1, 2, 3], **kw).result(60)
        assert a == b == c

    def test_composes_with_int8_kv_blocks(self):
        """Speculation over the quantized pool: the verifier reads the
        same int8 blocks a plain int8-KV engine would, so the streams
        agree with THAT engine (not necessarily with fp32 KV)."""
        m = _lm(layers=2)
        streams = {}
        for spec in (0, 2):
            with ServingEngine(m, decode_slots=2, decode_max_len=48,
                               kv_block_size=4, kv_cache_dtype="int8",
                               speculative=spec) as eng:
                streams[spec] = eng.generate(
                    [1, 2, 3, 4, 5], max_new_tokens=6).result(60)
        assert streams[2] == streams[0] and len(streams[2]) == 6


class TestSpeculativeSteadyState:
    def test_zero_recompiles_stats_events_and_metrics(self, tmp_path):
        from bigdl_tpu.observability import StepTelemetry
        from bigdl_tpu.observability.metrics import MetricsRegistry

        m = _lm(layers=2)
        tel = StepTelemetry(str(tmp_path), run_name="gen", trace=False)
        reg = MetricsRegistry()
        tel.attach_metrics(reg)
        with ServingEngine(m, decode_slots=2, decode_max_len=48,
                           kv_block_size=4, speculative=2,
                           telemetry=tel) as eng:
            eng.precompile(example_feature=np.zeros((4,), np.int32))
            before = backend_compile_count()
            futs = [eng.generate([1, 2, 3], max_new_tokens=5),
                    eng.generate([5] * 9, max_new_tokens=5),
                    eng.generate([7, 8], max_new_tokens=5,
                                 temperature=0.9, top_p=0.8, seed=5)]
            [f.result(60) for f in futs]
            assert backend_compile_count() - before == 0
            st = eng._generation().stats()["speculative"]
        tel.close()
        assert st["k"] == 2 and st["rounds"] > 0
        assert st["drafted"] >= st["accepted"] >= 0
        assert 0.0 <= st["acceptance_rate"] <= 1.0
        events = [json.loads(ln) for ln in
                  open(os.path.join(str(tmp_path), "telemetry.jsonl"))]
        spec_ticks = [e for e in events if e.get("spec_k")]
        assert spec_ticks, "decode ticks must stamp the round shape"
        for e in spec_ticks:
            assert e["spec_k"] == 2
            assert e["spec_drafted"] >= e["spec_accepted"] >= 0
        text = reg.render()
        assert "bigdl_serving_spec_drafted_total" in text
        assert "bigdl_serving_spec_accepted_total" in text
        # obs_report folds the spec ticks into the generate block and
        # renders the acceptance + tokens-per-verify line
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "_t_obs_spec", os.path.join(
                os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                "tools", "obs_report.py"))
        obs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(obs)
        rep = obs.build_report(str(tmp_path))
        gen = rep["serving"]["generate"]
        assert gen["kv_dtype"] == "fp32"
        sb = gen["speculative"]
        assert sb["k"] == 2
        assert sb["drafted"] >= sb["accepted"] >= 0
        assert sb["tokens_per_verify"] >= 1.0
        rendered = obs.format_report(rep)
        assert "speculative: draft k=2" in rendered
        assert "tokens/verify step" in rendered
        assert "(fp32 blocks)" in rendered


class TestSpeculativeGuards:
    def test_needs_the_paged_layout_and_a_sane_k(self):
        m = _lm(layers=1, max_len=48)
        with pytest.raises(ValueError, match="paged"):
            ServingEngine(m, decode_slots=1, decode_max_len=40,
                          kv_cache="contiguous", speculative=2)
        with pytest.raises(ValueError, match="speculative"):
            ServingEngine(m, decode_slots=1, decode_max_len=40,
                          speculative=-1)

    def test_accuracy_gate_vets_the_drafter(self):
        """``accuracy_gate`` + ``speculative=k`` is legal on an
        UNQUANTIZED engine: the int8 twin it gates is the drafter."""
        m = _lm(layers=1, max_len=48)
        feats = np.random.default_rng(0).integers(
            0, VOCAB, size=(4, 8)).astype(np.int32)
        with ServingEngine(m, decode_slots=1, decode_max_len=40,
                           kv_block_size=4, speculative=2,
                           accuracy_gate={"features": feats,
                                          "min_top1_agreement": 0.0,
                                          "max_top1_accuracy_drop": 1.0},
                           ) as eng:
            assert eng.generate([1, 2, 3],
                                max_new_tokens=3).result(60) == \
                _greedy_reference(m, [1, 2, 3], 3)
        # without a quantized serve path OR a drafter there is nothing
        # for the gate to compare -- still refused
        with pytest.raises(ValueError, match="accuracy_gate"):
            ServingEngine(m, decode_slots=1, decode_max_len=40,
                          accuracy_gate={"features": feats})

    def test_quantize_model_drops_compiled_step_caches(self):
        """copy.copy shares dict-valued attributes; a twin inheriting
        the fp32 original's compiled paged/spec step caches would hand
        the drafter fp32 executables -- it would verify itself."""
        from bigdl_tpu.nn.quantized import quantize_model

        m = _lm(layers=1, max_len=48)
        m._compiled_paged_steps = {"marker": "fp32-executables"}
        m._compiled_spec_steps = {"marker": "fp32-executables"}
        m._compiled_eval_steps = {"marker": "fp32-executables"}
        qmodel, _ = quantize_model(m)
        for slot in ("_compiled_paged_steps", "_compiled_spec_steps",
                     "_compiled_eval_steps"):
            assert slot not in qmodel.__dict__, slot
            assert getattr(m, slot) == {"marker": "fp32-executables"}


class TestLongPromptLegs:
    def test_int8_pool_bytes_and_greedy_identity_at_long_prompts(self):
        """Prompts of 64-256 tokens on block size 16, through the
        fp32 pool, the int8 pool and speculation (k=2): the int8
        scheduler's ``cache_bytes()`` under 1/2.5 of the fp32 one's
        (head_dim 8: 32 B against 12 B a vector), no compile after
        ``precompile()`` on any leg, a sampled stretch on the
        speculative one included, and the speculative greedy streams
        the plain ones."""
        from bigdl_tpu.serving import BucketLadder

        vocab, max_len, block, new_tokens = 64, 512, 16, 8
        plens = (64, 96, 160, 256)
        m = TransformerLM(vocab, 32, 4, 2, max_len=max_len)
        m.build(jax.ShapeDtypeStruct((1, 64), jnp.int32),
                rng=jax.random.PRNGKey(0))
        rng = np.random.default_rng(19)
        prompts = [rng.integers(0, vocab, size=n).astype(np.int32)
                   for n in plens]
        kv_blocks = len(plens) * (-(-(max(plens) + new_tokens) // block))

        def leg(kv_dtype, spec):
            with ServingEngine(
                    m, decode_slots=len(plens), decode_max_len=max_len,
                    prompt_ladder=BucketLadder(max(plens),
                                               min_size=min(plens)),
                    kv_block_size=block, kv_blocks=kv_blocks,
                    kv_cache_dtype=kv_dtype, speculative=spec) as eng:
                sched = eng._generation()
                sched.precompile()
                before = backend_compile_count()
                futs = [eng.generate(p, max_new_tokens=new_tokens)
                        for p in prompts]
                streams = [f.result(600) for f in futs]
                if spec:
                    for i in range(2):
                        eng.generate(prompts[i], max_new_tokens=8,
                                     temperature=0.8, top_k=20,
                                     seed=i).result(600)
                assert backend_compile_count() - before == 0
                return sched.cache_bytes(), streams

        fp32_bytes, plain = leg("fp32", 0)
        int8_bytes, _ = leg("int8", 0)
        _, speculative = leg("fp32", 2)
        assert int8_bytes * 2.5 < fp32_bytes
        assert speculative == plain
        assert all(len(t) == new_tokens for t in plain)
