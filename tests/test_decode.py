"""ISSUE 15: autoregressive generation serving -- KV-cache decode,
prefill/decode split, continuous batching.

Pins, per the acceptance criteria:

- paged chunk-prefill and single-step decode logits match the
  full-context forward within 1e-4 across BOTH block layouts (unrolled
  and scan-stacked), with causal masking honest at every position
  (garbage beyond the frontier is invisible);
- ragged-prompt prefill: one chunk call serves rows of different true
  lengths, each row's logits read at its own ``length - 1``;
- a full generate loop spanning multiple admission counts performs
  ZERO steady-state compiles after ``precompile()`` (the ``compiles``
  tick stamp stays absent and the backend counter is flat);
- int8: ``ServingEngine(quantize=True)`` serves generation through the
  same ``AccuracyDeltaGate``, and fp32-vs-int8 top-1 agreement on
  GENERATED tokens is pinned;
- the ``generate`` verb works over the worker socket protocol and
  through ``ServingFleet`` routing/retries, with hedging disabled for
  multi-token requests.
"""

import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.attention import TransformerLM
from bigdl_tpu.observability.watchdogs import backend_compile_count
from bigdl_tpu.serving import (EngineDraining, InProcessReplica,
                               ServingEngine, ServingFleet)

VOCAB = 50


def _lm(layers=2, max_len=48, scan=False, vocab=VOCAB, hidden=32, key=0):
    m = TransformerLM(vocab_size=vocab, hidden_size=hidden, num_heads=4,
                      num_layers=layers, max_len=max_len,
                      scan_layers=scan)
    # explicit key: the int8 agreement pins depend on THESE weights,
    # not on whatever the global RNG stream happens to hold mid-run
    m.build(jax.ShapeDtypeStruct((2, 16), jnp.int32),
            rng=jax.random.PRNGKey(key))
    return m


def _greedy_reference(m, prompt, n_new):
    """Greedy generation by FULL forward recompute -- the ground truth
    the cached serving path must reproduce token for token."""
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


def _paged_step(m, tables):
    """``m.apply_paged`` jitted over its pool's ``tables``: a chunk with
    ``n`` (the rows' true lengths) or a decode step without."""
    return jax.jit(lambda p, x, pool, pos, n=None: m.apply_paged(
        p, x, pool, tables, pos=pos, lengths=n))


class TestDecodeAgreement:
    """Paged decode is a restructuring of the forward, not an
    approximation: logits agree with the full-context forward."""

    @pytest.mark.parametrize("scan", [False, True])
    def test_paged_steps_match_full_forward(self, scan):
        """Eight positions as one chunk, then one decode step a position
        across block boundaries, each row through its own scattered
        blocks: the chunk's logits and every step's are the forward's."""
        m = _lm(layers=2, scan=scan)
        params = m.parameters()[0]
        toks = np.random.default_rng(0).integers(
            0, VOCAB, size=(2, 16)).astype(np.int32)
        full = np.asarray(m.apply(params, (), jnp.asarray(toks))[0])
        nb = 10
        tables = jnp.asarray([[7, 2, 9, 4, nb], [1, 8, 3, 6, nb]], jnp.int32)
        step = _paged_step(m, tables)
        pool = m.init_paged_cache(nb, 4)
        pre, pool = step(params, toks[:, :8], pool,
                         jnp.zeros((2,), jnp.int32),
                         jnp.full((2,), 8, jnp.int32))
        assert np.max(np.abs(np.asarray(pre) - full[:, :8])) < 1e-4
        for t in range(8, 16):
            lg, pool = step(params, toks[:, t:t + 1], pool,
                            jnp.full((2,), t, jnp.int32))
            assert np.max(np.abs(np.asarray(lg)[:, 0] - full[:, t])) \
                < 1e-4, f"position {t} diverged"

    def test_paged_causal_frontier_at_every_position(self):
        """Garbage beyond the decode frontier -- a previous occupant's
        K/V, the rest of a block, the trash block -- must be invisible:
        poisoning every pool position past ``pos`` changes nothing."""
        m = _lm(layers=2)
        params = m.parameters()[0]
        toks = np.random.default_rng(2).integers(
            0, VOCAB, size=(1, 8)).astype(np.int32)
        nb, bs = 6, 4
        tables = jnp.asarray([[0, 1, 2, nb]], jnp.int32)
        step = _paged_step(m, tables)
        _, pool = step(params, toks, m.init_paged_cache(nb, bs),
                       jnp.zeros((1,), jnp.int32),
                       jnp.full((1,), 8, jnp.int32))
        tok = jnp.asarray([[3]], jnp.int32)
        for t in range(8, 12):
            pos = jnp.full((1,), t, jnp.int32)
            lg, new_pool = step(params, tok, pool, pos)

            def poison(leaf, t=t):
                # the rest of t's block, the blocks the table never maps
                # and the trash block its padding entry points at
                leaf = leaf.at[t // bs, t % bs + 1:].set(1e4)
                return leaf.at[jnp.asarray([3, 4, 5, nb])].set(1e4)

            lg2, _ = step(params, tok, jax.tree.map(poison, pool), pos)
            np.testing.assert_array_equal(np.asarray(lg), np.asarray(lg2))
            pool = new_pool


class TestRaggedPrefill:
    def test_ragged_prompts_one_chunk_call(self):
        """Rows of true lengths 3 and 9 share one padded chunk call, then
        decode greedily side by side: each row's logits, at every
        position it owns, are those of its own full forward over its
        prompt and what it generated, and each generated token is that
        forward's argmax (the full-recompute reference, teacher-forced)."""
        m = _lm(layers=2, max_len=32)
        params = m.parameters()[0]
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, VOCAB, size=n).astype(np.int32)
                   for n in (3, 9)]
        nb = 8
        tables = jnp.asarray([[0, 1, 2, nb], [3, 4, 5, nb]], jnp.int32)
        step = _paged_step(m, tables)
        forward = jax.jit(lambda p, t: m.apply(p, (), t)[0])
        tokens = np.zeros((2, 12), np.int32)
        for row, p in enumerate(prompts):
            tokens[row, :p.size] = p
        lengths = np.asarray([p.size for p in prompts], np.int32)
        lg, pool = step(params, tokens, m.init_paged_cache(nb, 4),
                        jnp.zeros((2,), jnp.int32), jnp.asarray(lengths))
        lg = np.asarray(lg)
        logits = [[lg[row, :n]] for row, n in enumerate(lengths)]
        last = lg[[0, 1], lengths - 1].argmax(-1).astype(np.int32)
        gen = [[int(t)] for t in last]
        pos = lengths.copy()
        for _ in range(3):
            out, pool = step(params, last[:, None], pool, jnp.asarray(pos))
            out = np.asarray(out)[:, 0]
            last = out.argmax(-1).astype(np.int32)
            for row in range(2):
                logits[row].append(out[row][None])
                gen[row].append(int(last[row]))
            pos = pos + 1
        for row, p in enumerate(prompts):
            seq = np.concatenate([p, gen[row][:-1]]).astype(np.int32)
            want = np.asarray(forward(params, seq[None]))
            assert np.max(np.abs(np.concatenate(logits[row]) - want[0])) \
                < 1e-4
            assert list(want[0, p.size - 1:].argmax(-1)) == gen[row]


class TestGenerateServing:
    """The engine's continuous-batching generate() verb."""

    def test_generate_matches_reference_and_streams(self):
        m = _lm(layers=2, max_len=48)
        prompt = np.random.default_rng(6).integers(
            0, VOCAB, size=7).astype(np.int32)
        ref = _greedy_reference(m, prompt, 6)
        with ServingEngine(m, decode_slots=2, decode_max_len=32) as eng:
            fut = eng.generate(prompt, max_new_tokens=6)
            streamed = list(fut.stream(60))
            assert fut.result(5) == ref
            assert streamed == ref
            assert fut.finish_reason == "length"
            assert fut.prompt_len == 7 and fut.latency_s > 0

    @pytest.mark.parametrize("scan", [False, True])
    def test_close_gives_the_cache_back(self, scan):
        """A live engine and its scheduler refer to each other; a KV
        cache that waited for a cycle collection after close() would
        leave no room on the chip for the next engine of the process.
        With the collector off, close() alone leaves no cache leaf
        alive, and no cycle."""
        import gc
        import weakref

        m = _lm(layers=2, max_len=48, scan=scan)
        gc.collect()
        gc.disable()
        try:
            eng = ServingEngine(m, decode_slots=2, decode_max_len=32)
            assert eng.generate(np.arange(5, dtype=np.int32),
                                max_new_tokens=3).result(60)
            sched = eng._generation()
            assert sched.cache_bytes() > 0
            leaves = [weakref.ref(leaf)
                      for leaf in jax.tree.leaves(sched._cache)]
            eng.close()
            assert [r() for r in leaves] == [None] * len(leaves)
            assert sched.cache_bytes() == 0
            assert eng.stats()["generate"]["running"] is False
            # and the closed scheduler let go of the engine's hooks, so
            # no cycle is left: dropping the engine frees it
            engine = weakref.ref(eng)
            del eng, sched
            assert engine() is None
        finally:
            gc.enable()

    def test_eos_stops_early(self):
        m = _lm(layers=2, max_len=48)
        prompt = np.random.default_rng(7).integers(
            0, VOCAB, size=5).astype(np.int32)
        ref = _greedy_reference(m, prompt, 8)
        eos = ref[2]                       # greedy is deterministic
        with ServingEngine(m, decode_slots=2, decode_max_len=32) as eng:
            fut = eng.generate(prompt, max_new_tokens=8, eos_id=eos)
            out = fut.result(60)
            assert out == ref[:3]          # eos included, then stop
            assert fut.finish_reason == "eos"

    def test_zero_recompiles_across_mixed_buckets(self):
        """THE acceptance pin: precompile() closes the generation
        executable set; a closed-loop workload spanning multiple
        admission counts AND prompt lengths -- sequences joining
        and leaving slots mid-flight -- performs zero backend compiles,
        and no tick event carries the ``compiles`` stamp."""
        import tempfile

        from bigdl_tpu.observability import StepTelemetry

        m = _lm(layers=2, max_len=48)
        rng = np.random.default_rng(8)
        with tempfile.TemporaryDirectory() as d:
            tel = StepTelemetry(d, run_name="gen", trace=False)
            eng = ServingEngine(m, decode_slots=2, decode_max_len=32,
                                telemetry=tel)
            try:
                eng.precompile(
                    example_feature=np.zeros((16,), np.int32))
                before = backend_compile_count()
                # wave 1: short and long prompts, staggered max_new so slots
                # free at different ticks; wave 2 joins mid-flight
                futs = [eng.generate(rng.integers(0, VOCAB, size=n),
                                     max_new_tokens=k)
                        for n, k in ((5, 3), (12, 7), (9, 2))]
                time.sleep(0.05)
                futs += [eng.generate(rng.integers(0, VOCAB, size=n),
                                      max_new_tokens=k)
                         for n, k in ((15, 4), (3, 6))]
                outs = [f.result(120) for f in futs]
                assert [len(o) for o in outs] == [3, 7, 2, 4, 6]
                assert backend_compile_count() - before == 0
            finally:
                eng.close()
                tel.close()
            events = [json.loads(ln) for ln in
                      open(os.path.join(d, "telemetry.jsonl"))]
            ticks = [e for e in events if e.get("kind") == "inference"]
            assert ticks, "generation must emit inference tick events"
            assert not any(e.get("compiles") for e in ticks)

    def test_tick_telemetry_and_metrics_bridge(self):
        """Satellite pins: tick events stamp tick_kind / tokens / slot
        occupancy; the registry bridges bigdl_serving_tokens_total and
        the slot-fill gauge; obs_report's Serving section reports
        tokens/s and mean slot fill."""
        import importlib.util
        import tempfile

        from bigdl_tpu.observability import StepTelemetry
        from bigdl_tpu.observability.metrics import MetricsRegistry

        m = _lm(layers=2, max_len=48)
        with tempfile.TemporaryDirectory() as d:
            tel = StepTelemetry(d, run_name="gen", trace=False)
            reg = MetricsRegistry()
            tel.attach_metrics(reg)
            with ServingEngine(m, decode_slots=2, decode_max_len=32,
                               telemetry=tel) as eng:
                futs = [eng.generate(
                    np.random.default_rng(i).integers(0, VOCAB, size=4),
                    max_new_tokens=5) for i in range(2)]
                [f.result(60) for f in futs]
            tel.close()
            events = [json.loads(ln) for ln in
                      open(os.path.join(d, "telemetry.jsonl"))]
            ticks = [e for e in events if e.get("tick_kind")]
            kinds = {e["tick_kind"] for e in ticks}
            assert kinds == {"prefill", "decode"}
            for e in ticks:
                assert e["slots_total"] == 2
                assert 0 <= e["slots_active"] <= 2
                assert e["tokens"] >= 1
            decode_ticks = [e for e in ticks
                            if e["tick_kind"] == "decode"]
            # prefill admits the requests; decode ticks emit the rest
            assert sum(e["tokens"] for e in ticks) == 10
            assert any(e["slots_active"] == 2 for e in decode_ticks)
            # completion latencies ride their OWN field (+ histogram):
            # second-scale generations must never pollute the predict
            # latency series an SLO is tuned against
            assert any(e.get("generate_latency_s") for e in ticks)
            assert not any(e.get("request_latency_s") for e in ticks)
            text = reg.render()
            assert 'bigdl_serving_tokens_total{kind="decode"}' in text
            assert 'bigdl_serving_tokens_total{kind="prefill"}' in text
            assert "bigdl_serving_slot_fill" in text
            assert "bigdl_serving_generate_latency_seconds_bucket" in text
            spec = importlib.util.spec_from_file_location(
                "_t_obs_decode", os.path.join(
                    os.path.dirname(os.path.dirname(
                        os.path.abspath(__file__))),
                    "tools", "obs_report.py"))
            obs = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(obs)
            gen = obs.build_report(d)["serving"]["generate"]
            assert gen["tokens"] == 10
            assert gen["tokens_per_s"] > 0
            assert 0 < gen["slot_fill_mean"] <= 1.0

    def test_tick_failure_resets_the_pool_and_keeps_serving(self):
        """Every compiled step DONATES the pool, so a runtime tick
        failure invalidates the whole pool: the tick's futures fail
        honestly, the cache reallocates, and NEW requests serve
        normally afterwards (no 'Array has been deleted' forever)."""
        m = _lm(layers=2, max_len=48)
        ref = _greedy_reference(m, [1, 2, 3], 4)
        with ServingEngine(m, decode_slots=2, decode_max_len=32) as eng:
            sched = eng._generation()
            good = sched._chunk_fn

            def boom(*a, **k):
                raise RuntimeError("injected tick failure")

            sched._chunk_fn = boom
            fut = eng.generate([1, 2, 3], max_new_tokens=4)
            with pytest.raises(RuntimeError, match="injected"):
                fut.result(30)
            sched._chunk_fn = good
            assert eng.generate([1, 2, 3],
                                max_new_tokens=4).result(60) == ref

    def test_abandon_frees_generation_queue_slot(self):
        """An abandoned (timed-out) pending generation leaves the
        scheduler's queue immediately and its stream ends, instead of
        counting against capacity until an admission drains it."""
        m = _lm(layers=2, max_len=48)
        with ServingEngine(m, decode_slots=1, decode_max_len=32) as eng:
            sched = eng._generation()
            real_decode = sched._decode_fn

            def slow_decode(*a, **k):
                time.sleep(0.05)
                return real_decode(*a, **k)

            sched._decode_fn = slow_decode
            first = eng.generate([1, 2, 3], max_new_tokens=8)
            time.sleep(0.1)            # first occupies the only slot
            second = eng.generate([4, 5], max_new_tokens=2)
            eng._abandon(second)
            assert second.cancelled()
            assert list(second.stream(5)) == []   # sentinel delivered
            with sched._lock:
                assert not any(e[1] is second for e in sched._pending)
            assert len(first.result(60)) == 8     # unaffected

    def test_abandon_evicts_midflight_sequence(self):
        """Abandoning an already-decoding sequence frees its slot at
        the next tick boundary with a PARTIAL result -- the slot must
        not keep decoding max_new_tokens for a caller who left (the
        fleet deadline-retry double-booking case)."""
        m = _lm(layers=2, max_len=48)
        with ServingEngine(m, decode_slots=1, decode_max_len=40) as eng:
            sched = eng._generation()
            real_decode = sched._decode_fn

            def slow_decode(*a, **k):
                time.sleep(0.05)
                return real_decode(*a, **k)

            sched._decode_fn = slow_decode
            fut = eng.generate([1, 2, 3], max_new_tokens=30)
            stream = fut.stream(30)
            next(stream)                   # mid-flight for sure
            eng._abandon(fut)
            partial = fut.result(30)
            assert fut.finish_reason == "abandoned"
            assert 1 <= len(partial) < 30
            assert list(stream) == partial[1:]   # stream ended too
            # the slot is free again: a new request serves promptly
            assert len(eng.generate([4, 5],
                                    max_new_tokens=2).result(30)) == 2
            assert sched.stats()["slots_active"] == 0

    def test_draining_refuses_generation(self):
        m = _lm(layers=2, max_len=48)
        with ServingEngine(m, decode_slots=1, decode_max_len=32) as eng:
            eng.drain(5)
            with pytest.raises(EngineDraining):
                eng.generate([1, 2, 3], max_new_tokens=2)
            eng.undrain()
            assert len(eng.generate([1, 2, 3],
                                    max_new_tokens=2).result(60)) == 2

    def test_request_validation(self):
        m = _lm(layers=2, max_len=48)
        with ServingEngine(m, decode_slots=1, decode_max_len=16) as eng:
            with pytest.raises(ValueError, match="max_len"):
                eng.generate(np.arange(12), max_new_tokens=8)
            with pytest.raises(ValueError, match="at least one token"):
                eng.generate([], max_new_tokens=2)
            with pytest.raises(ValueError, match="max_new_tokens"):
                eng.generate([1], max_new_tokens=0)
        # generation disabled: the knob exists but the verb refuses
        eng = ServingEngine(m, decode_slots=0)
        try:
            with pytest.raises(ValueError, match="decode_slots"):
                eng.generate([1, 2])
        finally:
            eng.close()


class TestInt8Generation:
    """ISSUE-15 int8 satellite: the quantized engine serves generation
    through the decode-mode int8 attention path, gated by the same
    AccuracyDeltaGate, with pinned fp32-vs-int8 token agreement."""

    @staticmethod
    def _confident_lm():
        # damp the residual branches so logits are embedding-dominated:
        # argmax margins then dwarf the int8 noise in the block matmuls
        m = _lm(layers=2, max_len=48, vocab=64)
        p = m.parameters()[0]
        for k in list(p):
            if k.startswith("block"):
                p[k] = jax.tree.map(lambda a: a * 0.2, p[k])
        p["head"] = p["head"] * 4.0
        m.set_parameters(p)
        return m

    def test_int8_generate_through_the_gate(self):
        m = self._confident_lm()
        feats = np.random.default_rng(0).integers(
            0, 64, size=(8, 16)).astype(np.int32)
        e32 = ServingEngine(m, decode_slots=2, decode_max_len=40)
        e8 = ServingEngine(m, decode_slots=2, decode_max_len=40,
                           quantize=True,
                           accuracy_gate={"features": feats,
                                          "min_top1_agreement": 0.9})
        try:
            assert e8.quantized
            assert e8._gate_detail["ok"]
            # the decode path really contracts int8: the served twin's
            # attention params carry the quantized projections
            qp = e8._qmodel.parameters()[0]
            blk = qp["block0"] if "block0" in qp else qp["blocks"]
            assert "qkv_weight_q" in blk["attn"]
            rng = np.random.default_rng(1)
            agree, n = 0, 0
            for _ in range(6):
                prompt = rng.integers(0, 64, size=10).astype(np.int32)
                a = e32.generate(prompt, max_new_tokens=10).result(60)
                b = e8.generate(prompt, max_new_tokens=10).result(60)
                agree += sum(x == y for x, y in zip(a, b))
                n += len(a)
            # the pinned fp32-vs-int8 top-1 agreement on GENERATED
            # tokens (trajectory-level, so any divergence compounds --
            # 1.0 measured on this fixed-key confident config)
            assert agree / n >= 0.9, f"token agreement {agree / n:.3f}"
        finally:
            e32.close()
            e8.close()

    def test_gate_refusal_blocks_int8_generation(self):
        """A gate the quantizer cannot clear refuses the ENGINE, so
        generation never serves damaging weights (same contract as the
        eval path)."""
        m = _lm(layers=2, max_len=48, vocab=64)
        feats = np.random.default_rng(0).integers(
            0, 64, size=(8, 16)).astype(np.int32)
        # a bound no int8 rewrite clears whatever weights the key draws
        # (its logit RMSE here is ~3e-3).  The old pin -- key 0's weights
        # agree on only 0.875 of top-1s -- held for one JAX's PRNG stream
        # and reads 1.0 on the installed one
        with pytest.raises(ValueError, match="accuracy gate"):
            ServingEngine(m, decode_slots=2, decode_max_len=40,
                          quantize=True,
                          accuracy_gate={"features": feats,
                                         "min_top1_agreement": None,
                                         "max_logit_rmse": 1e-6})


class TestWorkerFleetGenerate:
    """The generate verb across the socket protocol and the fleet."""

    def test_worker_generate_op(self):
        from bigdl_tpu.serving.worker import ReplicaServer, call

        m = _lm(layers=2, max_len=48)
        prompt = [1, 2, 3, 4]
        ref = _greedy_reference(m, prompt, 5)
        with ServingEngine(m, decode_slots=2, decode_max_len=32) as eng:
            srv = ReplicaServer(eng, port=0).start()
            try:
                out = call("127.0.0.1", srv.port, "generate",
                           prompt=prompt, max_new_tokens=5)
                assert out == ref
            finally:
                srv.close()

    def test_fleet_generate_routes_retries_and_never_hedges(self):
        m = _lm(layers=2, max_len=48)
        prompt = np.asarray([5, 6, 7], np.int32)
        ref = _greedy_reference(m, prompt, 4)
        e1 = ServingEngine(m, decode_slots=2, decode_max_len=32)
        e2 = ServingEngine(m, decode_slots=2, decode_max_len=32)
        # hedge=True fleet-wide: generation must still never hedge
        fleet = ServingFleet([InProcessReplica(e1, rid=0),
                              InProcessReplica(e2, rid=1)],
                             hedge=True, hedge_min_samples=1,
                             hedge_min_delay_s=0.0)
        try:
            for _ in range(4):
                assert fleet.generate(prompt, max_new_tokens=4,
                                      timeout=60) == ref
            # kill one replica: the request fails there and retries on
            # the sibling (idempotent: greedy re-runs from the prompt)
            e1.close()
            for _ in range(4):
                assert fleet.generate(prompt, max_new_tokens=4,
                                      timeout=60) == ref
            counters = fleet.counters()
            assert counters["ok"] == 8 and counters["failed"] == 0
            assert counters["hedges"] == 0      # disabled by design
        finally:
            fleet.close()
