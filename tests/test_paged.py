"""ISSUE 17: paged KV cache with prefix reuse, chunked prefill and
in-jit sampling.

Pins, per the acceptance criteria:

- BlockAllocator invariants: refcounted alloc/free, leading-run prefix
  matching with LRU ref-0 reuse, copy-on-write detach (and the cheaper
  own-block unregister), typed ``BlockPoolExhausted`` admission sheds
  that leave neighbours untouched, and zero block leaks;
- paged-vs-contiguous GREEDY AGREEMENT on both block layouts (unrolled
  and scan-stacked): the block indirection is a restructuring of the
  cache, not an approximation;
- chunked prefill interleaves with decode ticks (a long prompt never
  starves a live stream);
- abandoned mid-flight sequences release their blocks at the sweep;
- in-jit sampling is deterministic per (seed, position) and rides
  runtime arrays: zero steady-state recompiles across mixed prompt
  lengths AND sampled decoding after one ``precompile()``;
- ``precompile()`` warms generation on an AUTO-mode engine (the old
  gate needed decode_slots spelled out -- the satellite fix);
- tick events stamp block-pool occupancy + prefix-hit deltas, and the
  registry renders ``bigdl_serving_kv_blocks`` /
  ``bigdl_serving_prefix_hits_total``.
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
from bigdl_tpu.serving import (BlockAllocator, BlockPoolExhausted,
                               InProcessReplica, SamplingParams,
                               ServingEngine, ServingFleet)
from bigdl_tpu.serving.sampling import sample_tokens

VOCAB = 50


def _lm(layers=2, max_len=48, scan=False, vocab=VOCAB, hidden=32, key=0):
    m = TransformerLM(vocab_size=vocab, hidden_size=hidden, num_heads=4,
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


class TestBlockAllocator:
    """Pure host-side invariants -- no device work at all."""

    def test_alloc_free_refcount(self):
        a = BlockAllocator(num_blocks=8, block_size=4)
        # 10 positions -> 3 blocks reserved up front
        cached = a.begin_sequence("s1", list(range(10)), 10)
        assert cached == 0
        st = a.stats()
        assert st["blocks_used"] == 3 and st["blocks_free"] == 5
        assert a.trash == 8
        # the fixed-shape row pads with the trash id
        row = a.table_row("s1", 6)
        assert len(row) == 6 and row[3:] == [8, 8, 8]
        a.free_sequence("s1")
        st = a.stats()
        assert st["blocks_used"] == 0 and st["blocks_free"] == 8
        assert st["sequences"] == 0

    def test_prefix_match_shares_and_lru_reuses(self):
        a = BlockAllocator(num_blocks=8, block_size=4)
        prompt = list(range(9))             # 2 full blocks + 1 spill
        a.begin_sequence("s1", prompt, 9)
        a.commit_full_blocks("s1", 9)
        # a twin admitted while s1 is LIVE maps the same physical
        # blocks, refcounted
        cached = a.begin_sequence("s2", prompt, 9)
        assert cached == 8                   # 2 blocks * 4 positions
        assert a.table_row("s1", 3)[:2] == a.table_row("s2", 3)[:2]
        assert a.table_row("s1", 3)[2] != a.table_row("s2", 3)[2]
        a.free_sequence("s1")
        a.free_sequence("s2")
        # ref-0 registered blocks PARK in the LRU, still matchable...
        st = a.stats()
        assert st["blocks_used"] == 0 and st["blocks_cached"] == 2
        cached = a.begin_sequence("s3", prompt, 9)
        assert cached == 8
        a.free_sequence("s3")
        # ...and the pool reclaims them when the free list runs dry
        a.begin_sequence("big", list(range(100, 132)), 32)  # all 8 blocks
        assert a.stats()["blocks_cached"] == 0
        # the evicted hashes are forgotten: no stale match
        a.free_sequence("big")
        assert a.begin_sequence("s4", prompt, 9) == 0

    def test_matching_is_capped_below_the_last_token(self):
        a = BlockAllocator(num_blocks=8, block_size=4)
        prompt = list(range(8))              # exactly 2 full blocks
        a.begin_sequence("s1", prompt, 8)
        a.commit_full_blocks("s1", 8)
        # only block 0 is matchable: the last prompt token must always
        # be computed, so block 1 (holding it) never comes from cache
        assert a.begin_sequence("s2", prompt, 8) == 4

    def test_cow_detach_and_own_unregister(self):
        a = BlockAllocator(num_blocks=8, block_size=4)
        prompt = list(range(9))
        a.begin_sequence("s1", prompt, 12)
        a.commit_full_blocks("s1", 9)
        a.begin_sequence("s2", prompt, 12)   # shares blocks 0-1
        shared = a.table_row("s2", 3)[0]
        # a write into a SHARED block detaches: private copy, remap
        res = a.ensure_writable("s2", 2)
        assert res is not None
        src, dst = res
        assert src == shared and a.table_row("s2", 3)[0] == dst
        assert a.table_row("s1", 3)[0] == shared     # s1 untouched
        assert a.stats()["cow_copies"] == 1
        # a write into an OWN but hash-registered block just
        # unregisters (no copy) -- and the hash no longer matches
        assert a.ensure_writable("s1", 2) is None
        a.free_sequence("s2")
        a.free_sequence("s1")
        assert a.begin_sequence("s3", prompt, 9) == 0

    def test_exhaustion_is_typed_and_leaves_neighbours_alone(self):
        a = BlockAllocator(num_blocks=4, block_size=4)
        a.begin_sequence("live", list(range(8)), 12)     # 3 of 4 blocks
        before = a.table_row("live", 3)
        with pytest.raises(BlockPoolExhausted):
            a.begin_sequence("big", list(range(100, 108)), 16)  # needs 4
        # the shed retained NOTHING and the neighbour's table is intact
        st = a.stats()
        assert st["sequences"] == 1 and st["sheds"] == 1
        assert st["blocks_used"] == 3
        assert a.table_row("live", 3) == before

    def test_flush_cached_forgets_registrations(self):
        a = BlockAllocator(num_blocks=8, block_size=4)
        prompt = list(range(9))
        a.begin_sequence("s1", prompt, 9)
        a.commit_full_blocks("s1", 9)
        a.free_sequence("s1")
        assert a.stats()["blocks_cached"] == 2
        a.flush_cached()                     # the weight-swap hook
        st = a.stats()
        assert st["blocks_cached"] == 0 and st["blocks_free"] == 8
        assert a.begin_sequence("s2", prompt, 9) == 0


def _reference_sample_tokens(logits, temperature, top_k, top_p, seed,
                             position):
    """``sample_tokens`` as it was before the work followed the rows:
    ``argsort``, the ranked logits gathered back, every row drawn and the
    greedy rows' draws thrown away.  Kept as the reference the function is
    held to, token for token."""
    vocab = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    order = jnp.argsort(-logits, axis=-1)
    ranked = jnp.take_along_axis(logits, order, axis=-1)
    temp = jnp.maximum(temperature, 1e-6).astype(jnp.float32)[:, None]
    scaled = ranked / temp
    rank = jnp.arange(vocab, dtype=jnp.int32)[None, :]
    k = jnp.where(top_k > 0, top_k, vocab).astype(jnp.int32)[:, None]
    keep = rank < k
    probs = jax.nn.softmax(scaled, axis=-1)
    mass_before = jnp.cumsum(probs, axis=-1) - probs
    keep = keep & (mass_before < top_p[:, None])
    keep = keep.at[:, 0].set(True)
    masked = jnp.where(keep, scaled, -jnp.inf)
    keys = jax.vmap(
        lambda s, p: jax.random.fold_in(
            jax.random.PRNGKey(s.astype(jnp.uint32)), p))(
        seed, position.astype(jnp.uint32))
    gumbel = jax.vmap(lambda key, row: jax.random.gumbel(
        key, row.shape, dtype=row.dtype))(keys, masked)
    pick = jnp.argmax(masked + gumbel, axis=-1)
    sampled = jnp.take_along_axis(
        order, pick[:, None], axis=-1)[:, 0].astype(jnp.int32)
    return jnp.where(temperature > 0.0, sampled, greedy)


# one jitted handle each for the module: a compile a shape, not a case
_JIT_NEW, _JIT_OLD = jax.jit(sample_tokens), jax.jit(_reference_sample_tokens)


#: rows of a tick, by what they ask for: the temperature of each of 6 rows
_TICKS = {
    "greedy": [0.0] * 6,
    "sampling": [0.7, 1.0, 1.3, 0.2, 2.0, 0.7],
    "mixed": [0.0, 0.0, 0.0, 0.9, 0.0, 0.0],
}


class TestSampleTokens:
    """The in-jit draw: greedy degenerations are exact, randomness is a
    pure function of (seed, position)."""

    def _logits(self, rows=3, vocab=16, seed=0):
        return jnp.asarray(
            np.random.default_rng(seed).normal(size=(rows, vocab)),
            jnp.float32)

    def test_greedy_degenerations_are_argmax(self):
        from bigdl_tpu.serving.sampling import sample_tokens
        logits = self._logits()
        ref = np.argmax(np.asarray(logits), axis=-1)
        seeds = jnp.asarray([1, 2, 3], jnp.int32)
        pos = jnp.asarray([0, 5, 9], jnp.int32)
        z = jnp.zeros((3,), jnp.float32)
        zi = jnp.zeros((3,), jnp.int32)
        # temperature <= 0 is greedy regardless of the other knobs
        got = sample_tokens(logits, z, zi + 7, z + 0.3, seeds, pos)
        assert np.array_equal(np.asarray(got), ref)
        # top_k=1 and top_p=0 both collapse the support to rank 0
        for kwargs in ((z + 1.0, zi + 1, z + 1.0),
                       (z + 1.0, zi, z)):
            got = sample_tokens(logits, *kwargs, seeds, pos)
            assert np.array_equal(np.asarray(got), ref)

    def test_draws_are_pure_in_seed_and_position(self):
        from bigdl_tpu.serving.sampling import sample_tokens
        logits = self._logits(rows=2)
        t = jnp.ones((2,), jnp.float32)
        zi = jnp.zeros((2,), jnp.int32)
        p1 = jnp.ones((2,), jnp.float32)
        seeds = jnp.asarray([9, 9], jnp.int32)
        a = sample_tokens(logits, t, zi, p1, seeds,
                          jnp.asarray([4, 4], jnp.int32))
        b = sample_tokens(logits, t, zi, p1, seeds,
                          jnp.asarray([4, 4], jnp.int32))
        assert np.array_equal(np.asarray(a), np.asarray(b))
        # across positions the stream must actually vary
        draws = {int(sample_tokens(
            logits[:1], t[:1], zi[:1], p1[:1], seeds[:1],
            jnp.asarray([p], jnp.int32))[0]) for p in range(24)}
        assert len(draws) > 1

    def test_top_k_restricts_the_support(self):
        from bigdl_tpu.serving.sampling import sample_tokens
        logits = self._logits(rows=1, vocab=12)
        top2 = set(np.argsort(-np.asarray(logits)[0])[:2].tolist())
        t = jnp.ones((1,), jnp.float32) * 2.0
        for p in range(60):
            tok = int(sample_tokens(
                logits, t, jnp.asarray([2], jnp.int32),
                jnp.ones((1,), jnp.float32), jnp.asarray([3], jnp.int32),
                jnp.asarray([p], jnp.int32))[0])
            assert tok in top2

    @pytest.mark.parametrize("jitted", [True, False],
                             ids=["jit", "bare"])
    @pytest.mark.parametrize("vocab", [16, 1031])
    @pytest.mark.parametrize("top_p", [0.0, 0.3, 1.0])
    @pytest.mark.parametrize("top_k", [0, 1, 5])
    @pytest.mark.parametrize("tied", [False, True], ids=["distinct", "tied"])
    @pytest.mark.parametrize("tick", sorted(_TICKS))
    def test_token_for_token_the_reference(self, tick, tied, top_k, top_p,
                                        vocab, jitted):
        """Greedy, sampling and mixed ticks give, token for token, what
        the function gave when it sorted, gathered and drew for every row
        (``_reference_sample_tokens``): the two-operand sort ranks ties as
        ``argsort`` does, and the branch changes no row's token."""
        rows = len(_TICKS[tick])
        logits = np.random.default_rng(vocab + top_k).normal(
            size=(rows, vocab)).astype(np.float32) * 3.0
        if tied:
            # equal values all through a row, and two at its maximum
            logits = np.round(logits, 1)
            logits[:, vocab // 2] = logits.max(axis=-1)
        args = (jnp.asarray(logits),
                jnp.asarray(_TICKS[tick], jnp.float32),
                jnp.full((rows,), top_k, jnp.int32),
                jnp.full((rows,), top_p, jnp.float32),
                jnp.arange(rows, dtype=jnp.int32) * 7919 + 11,
                jnp.asarray([0, 3, 500, 1, 2, 1023], jnp.int32))
        new, old = (_JIT_NEW, _JIT_OLD) if jitted \
            else (sample_tokens, _reference_sample_tokens)
        got, want = new(*args), old(*args)
        assert got.dtype == want.dtype == jnp.int32
        assert np.array_equal(np.asarray(got), np.asarray(want))
        if tick == "greedy":
            assert np.array_equal(np.asarray(got), np.argmax(logits, axis=-1))

    def test_params_validation(self):
        with pytest.raises(ValueError):
            SamplingParams(temperature=float("nan"))
        with pytest.raises(ValueError):
            SamplingParams(top_k=-1)
        with pytest.raises(ValueError):
            SamplingParams(top_p=1.5)
        with pytest.raises(ValueError):
            SamplingParams(seed=2 ** 31)
        assert SamplingParams().greedy
        assert not SamplingParams(temperature=0.7).greedy


class TestPagedServing:
    """The scheduler + engine: agreement, reuse, interleave, sheds."""

    @pytest.mark.parametrize("scan", [False, True])
    def test_paged_matches_contiguous_and_reference(self, scan):
        m = _lm(layers=2, max_len=64, scan=scan)
        prompts = [[1, 2, 3], [7, 8, 9, 10, 11], [4] * 9]
        refs = [_greedy_reference(m, p, 5) for p in prompts]
        streams = {}
        for kv in ("contiguous", "paged"):
            with ServingEngine(m, decode_slots=3, decode_max_len=48,
                               kv_cache=kv, kv_block_size=4) as eng:
                futs = [eng.generate(p, max_new_tokens=5)
                        for p in prompts]
                streams[kv] = [f.result(60) for f in futs]
        assert streams["paged"] == streams["contiguous"] == refs

    def test_prefix_reuse_end_to_end(self):
        m = _lm(layers=2, max_len=64)
        prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]
        with ServingEngine(m, decode_slots=2, decode_max_len=48,
                           kv_block_size=4) as eng:
            first = eng.generate(prompt, max_new_tokens=4)
            toks = first.result(60)
            assert first.prefix_hit_tokens == 0
            again = eng.generate(prompt, max_new_tokens=4)
            assert again.result(60) == toks
            # 10 tokens at block 4: blocks 0-1 full and matchable
            assert again.prefix_hit_tokens == 8
            kv = eng._generation().stats()["kv"]
            assert kv["prefix_hits"] == 2
            assert kv["sequences"] == 0      # nothing leaked

    def test_exhaustion_sheds_typed_and_neighbour_finishes(self):
        m = _lm(layers=2, max_len=64)
        # 4 blocks of 4: a (prompt 6 + new 6) request reserves 3
        with ServingEngine(m, decode_slots=2, decode_max_len=48,
                           kv_block_size=4, kv_blocks=4) as eng:
            ref = _greedy_reference(m, [1, 2, 3, 4, 5, 6], 6)
            ok = eng.generate([1, 2, 3, 4, 5, 6], max_new_tokens=6)
            bad = eng.generate([9] * 8, max_new_tokens=8)   # needs 4
            with pytest.raises(BlockPoolExhausted):
                bad.result(60)
            assert ok.result(60) == ref      # the neighbour is whole
            kv = eng._generation().stats()["kv"]
            assert kv["sheds"] == 1 and kv["sequences"] == 0

    def test_abandoned_sequence_releases_blocks(self):
        m = _lm(layers=2, max_len=48)
        with ServingEngine(m, decode_slots=1, decode_max_len=40,
                           kv_block_size=4) as eng:
            sched = eng._generation()
            real = sched._decode_fn

            def slow(*a, **k):
                time.sleep(0.05)
                return real(*a, **k)

            sched._decode_fn = slow
            fut = eng.generate([1, 2, 3], max_new_tokens=30)
            stream = fut.stream(30)
            next(stream)                      # mid-flight for sure
            eng._abandon(fut)
            fut.result(30)
            assert fut.finish_reason == "abandoned"
            sched._decode_fn = real
            # the sweep freed the sequence: its blocks are reusable and
            # a new request serves promptly
            assert len(eng.generate([4, 5],
                                    max_new_tokens=2).result(30)) == 2
            kv = sched.stats()["kv"]
            assert kv["sequences"] == 0 and kv["blocks_used"] == 0

    def test_chunked_prefill_interleaves_with_decode(self, tmp_path):
        from bigdl_tpu.observability import StepTelemetry

        m = _lm(layers=2, max_len=64)
        tel = StepTelemetry(str(tmp_path), run_name="gen", trace=False)
        with ServingEngine(m, decode_slots=2, decode_max_len=56,
                           kv_block_size=4, prefill_chunk=4,
                           telemetry=tel) as eng:
            short = eng.generate([1, 2], max_new_tokens=24)
            next(short.stream(30))            # decoding before the long
            #                                   prompt shows up
            long = eng.generate(list(range(1, 17)), max_new_tokens=2)
            assert len(long.result(60)) == 2
            assert len(short.result(60)) == 24
        tel.close()
        events = [json.loads(ln) for ln in
                  open(os.path.join(str(tmp_path), "telemetry.jsonl"))]
        kinds = [e["tick_kind"] for e in events if e.get("tick_kind")]
        # the 16-token prompt at chunk 4 takes >= 4 prefill ticks; the
        # dispatcher must run decode ticks BETWEEN them, not after
        first_p = kinds.index("prefill")
        last_p = len(kinds) - 1 - kinds[::-1].index("prefill")
        assert kinds[first_p:last_p].count("prefill") >= 3
        assert "decode" in kinds[first_p:last_p], \
            "chunked prefill starved the live decode stream"

    def test_sampling_deterministic_and_refused_on_contiguous(self):
        m = _lm(layers=2, max_len=48)
        with ServingEngine(m, decode_slots=2, decode_max_len=40,
                           kv_block_size=4) as eng:
            a = eng.generate([1, 2, 3], max_new_tokens=6,
                             temperature=0.8, top_k=10,
                             seed=11).result(60)
            b = eng.generate([1, 2, 3], max_new_tokens=6,
                             temperature=0.8, top_k=10,
                             seed=11).result(60)
            assert a == b                     # replay is exact
            greedy = eng.generate([1, 2, 3], max_new_tokens=6).result(60)
            assert greedy == _greedy_reference(m, [1, 2, 3], 6)
            # unseeded sampling mints a seed and still serves
            assert len(eng.generate([1, 2, 3], max_new_tokens=3,
                                    temperature=0.8).result(60)) == 3
        with ServingEngine(m, decode_slots=1, decode_max_len=40,
                           kv_cache="contiguous") as eng:
            with pytest.raises(ValueError, match="paged"):
                eng.generate([1, 2, 3], max_new_tokens=2,
                             temperature=0.8)

    def test_zero_steady_state_recompiles_mixed_and_sampled(self):
        m = _lm(layers=2, max_len=64)
        with ServingEngine(m, decode_slots=2, decode_max_len=48,
                           kv_block_size=4) as eng:
            warmed = eng.precompile(
                example_feature=np.zeros((4,), np.int32))
            assert warmed > 0
            before = backend_compile_count()
            futs = [eng.generate([1, 2, 3], max_new_tokens=4),
                    eng.generate([5] * 9, max_new_tokens=4),
                    eng.generate([7, 8], max_new_tokens=4,
                                 temperature=0.9, top_p=0.8, seed=5)]
            [f.result(60) for f in futs]
            assert backend_compile_count() - before == 0

    @pytest.mark.parametrize("sampling", [0, 2])
    def test_prep_spans_count_the_sampling_rows(self, sampling):
        """``rows_sampling`` on the paged scheduler's prep spans: the rows
        of the tick with a temperature, any one of which sends the tick's
        sampler down its sampled branch; 0 on every tick of a greedy
        batch."""
        from bigdl_tpu.observability.spans import recorder

        m = _lm(layers=2, max_len=48)
        before = len(recorder().snapshot())
        with ServingEngine(m, decode_slots=3, decode_max_len=40,
                           kv_block_size=4) as eng:
            futs = [eng.generate([1 + i, 2, 3], max_new_tokens=4,
                                 **({"temperature": 0.9, "seed": i}
                                    if i < sampling else {}))
                    for i in range(3)]
            [f.result(60) for f in futs]
        recs = recorder().snapshot()[before:]
        for name in ("prefill_prep", "decode_prep"):
            attrs = [r.attrs for r in recs if r.name == name]
            assert attrs
            assert all(0 <= a["rows_sampling"] <= min(sampling, a["rows"])
                       for a in attrs)
            assert max(a["rows_sampling"] for a in attrs) == sampling

    def test_auto_engine_precompile_warms_generation(self):
        """The satellite fix: an AUTO-mode engine (decode_slots unset)
        must warm generation in precompile() -- the old gate skipped it
        and the first generate() paid every compile."""
        m = _lm(layers=2, max_len=48)
        with ServingEngine(m, decode_max_len=40) as eng:   # AUTO slots
            assert eng.decode_slots > 0
            eng.precompile(example_feature=np.zeros((4,), np.int32))
            before = backend_compile_count()
            assert len(eng.generate([1, 2, 3],
                                    max_new_tokens=3).result(60)) == 3
            assert backend_compile_count() - before == 0

    def test_tick_events_and_metric_families(self, tmp_path):
        from bigdl_tpu.observability import StepTelemetry
        from bigdl_tpu.observability.metrics import MetricsRegistry

        m = _lm(layers=2, max_len=64)
        tel = StepTelemetry(str(tmp_path), run_name="gen", trace=False)
        reg = MetricsRegistry()
        tel.attach_metrics(reg)
        prompt = [2, 7, 1, 8, 2, 8, 1, 8, 2, 8]
        with ServingEngine(m, decode_slots=2, decode_max_len=48,
                           kv_block_size=4, telemetry=tel) as eng:
            eng.generate(prompt, max_new_tokens=3).result(60)
            eng.generate(prompt, max_new_tokens=3).result(60)
        tel.close()
        events = [json.loads(ln) for ln in
                  open(os.path.join(str(tmp_path), "telemetry.jsonl"))]
        ticks = [e for e in events if e.get("tick_kind")]
        kv_ticks = [e for e in ticks if e.get("kv_blocks_total")]
        assert kv_ticks, "ticks must stamp block-pool occupancy"
        for e in kv_ticks:
            assert (e["kv_blocks_used"] + e["kv_blocks_cached"]
                    + e["kv_blocks_free"]) == e["kv_blocks_total"]
        assert any(e.get("prefix_hit_tokens") for e in ticks)
        assert any(e.get("prompt_tokens") for e in ticks)
        text = reg.render()
        assert 'bigdl_serving_kv_blocks{state="used"}' in text
        assert 'bigdl_serving_kv_blocks{state="cached"}' in text
        assert "bigdl_serving_prefix_hits_total" in text
        assert "bigdl_serving_prefix_hit_tokens_total" in text


def _paged_kernel_case(case):
    """``(geometry, tables, pos)`` of one case of the kernel's test.  The
    last physical block is the trash block, as in the engine."""
    b, h, d, nb, bs, mb = 3, 4, 16, 11, 4, 6
    trash = nb - 1
    # deliberately NON-contiguous, per-row-distinct tables
    tables = [[7, 2, 9, trash, trash, trash],
              [1, 8, 3, 5, trash, trash],
              [4, trash, trash, trash, trash, trash]]
    pos = [9, 14, 2]
    if case == "pos-zero":
        pos = [0, 0, 0]
    elif case == "block-last-row":
        pos = [11, 7, 3]
    elif case == "block-first-row":
        pos = [8, 12, 0]
    elif case == "table-end":
        tables = [[7, 2, 9, 0, 6, 5], [1, 8, 3, 5, 9, 2], [4, 0, 1, 2, 3, 6]]
        pos = [mb * bs - 1, mb * bs - 1, mb * bs - 2]
    elif case == "trash-row":
        # an empty slot (or one mid-prefill): every entry the trash block
        tables[1] = [trash] * mb
        pos = [9, 0, 2]
    elif case in ("cell-geometry", "int8-cell-geometry"):
        # the serving cell's in small: blocks of 16 (32 for int8, its
        # tile), H * D = 128, 64 table entries, several steps a row, one
        # row full, one empty, two ending beside a step's edge
        bs = 32 if case.startswith("int8") else 16
        b, h, d, mb = 5, 4, 32, 64
        lens = [mb * bs, 1, 8 * bs + 1, 8 * bs, 300]
        nb = sum(-(-n // bs) for n in lens) + 3
        trash = nb - 1
        order = iter(np.random.default_rng(1).permutation(nb - 1))
        tables = [[next(order) if j * bs < n and i != 1 else trash
                   for j in range(mb)] for i, n in enumerate(lens)]
        pos = [n - 1 for n in lens]
    return (b, h, d, nb, bs, mb), np.asarray(tables, np.int32), \
        np.asarray(pos, np.int32)


class TestFlashPagedKernel:
    @pytest.mark.parametrize("case", [
        "scattered-tables", "pos-zero", "block-last-row", "block-first-row",
        "table-end", "trash-row", "cell-geometry", "int8-scattered-tables",
        "int8-block-last-row", "int8-cell-geometry"])
    def test_interpret_matches_gather_reference(self, case):
        """The kernel in interpreter mode against gather-and-mask, on the
        pool as the engine stores it (``(NB, bs, H * D)``; int8 payloads
        with ``(NB, bs, H)`` scales)."""
        from bigdl_tpu.ops.flash_attention import \
            flash_paged_decode_attention

        quant = case.startswith("int8")
        (b, h, d, nb, bs, mb), tables, pos = _paged_kernel_case(
            case[5:] if quant and "cell" not in case else case)
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
        pools, scales = [], []
        for _ in "kv":
            if quant:
                pools.append(jnp.asarray(
                    rng.integers(-127, 128, (nb, bs, h * d)), jnp.int8))
                scales.append(jnp.asarray(
                    rng.uniform(0.005, 0.02, (nb, bs, h)), jnp.float32))
            else:
                pools.append(jnp.asarray(
                    rng.normal(size=(nb, bs, h * d)), jnp.float32))
        out = flash_paged_decode_attention(q, *pools, tables, pos, *scales,
                                           interpret=True)

        # reference: gather the mapped context and mask beyond pos
        def ctx(i):
            x = jnp.take(pools[i], tables, axis=0).astype(jnp.float32)
            x = x.reshape(b, mb * bs, h, d)
            if quant:
                x = x * jnp.take(scales[i], tables, axis=0).reshape(
                    b, mb * bs, h, 1)
            return x

        logits = jnp.einsum("bihd,bkhd->bhik", q, ctx(0),
                            precision="highest") / np.sqrt(d)
        mask = (jnp.arange(mb * bs)[None, :]
                <= pos[:, None])[:, None, None, :]
        w = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)
        ref = jnp.einsum("bhik,bkhd->bihd", w, ctx(1), precision="highest")
        assert out.shape == ref.shape
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    @pytest.mark.parametrize("layer", [0, 1, 2])
    @pytest.mark.parametrize("dtype", ["fp32", "bf16", "int8"])
    def test_stacked_pool_with_layer_equals_the_layer_alone(self, dtype,
                                                            layer):
        """The layer-stacked leaf ``(L, NB, bs, H * D)`` with ``layer=l``,
        traced as it is inside the layer loop, gives what the call on
        ``pool[l]`` gives, bit for bit: first, middle and last layer, the
        int8 pool with its stacked scales."""
        from bigdl_tpu.ops.flash_attention import \
            flash_paged_decode_attention

        (b, h, d, nb, bs, mb), tables, pos = _paged_kernel_case(
            "scattered-tables")
        rng = np.random.default_rng(4)
        q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
        shape = (3, nb, bs, h * d)
        if dtype == "int8":
            k, v = (jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
                    for _ in "kv")
            scales = [jnp.asarray(rng.uniform(0.005, 0.02, shape[:3] + (h,)),
                                  jnp.float32) for _ in "kv"]
        else:
            k, v = (jnp.asarray(rng.normal(size=shape), jnp.float32).astype(
                jnp.bfloat16 if dtype == "bf16" else jnp.float32)
                for _ in "kv")
            scales = []
        stacked = jax.jit(lambda l: flash_paged_decode_attention(
            q, k, v, tables, pos, *scales, layer=l, interpret=True))(
                jnp.int32(layer))
        alone = flash_paged_decode_attention(
            q, k[layer], v[layer], tables, pos,
            *(s[layer] for s in scales), interpret=True)
        assert np.isfinite(np.asarray(alone)).all()
        np.testing.assert_array_equal(np.asarray(stacked),
                                      np.asarray(alone))

    def test_rows_never_fetched_cannot_poison_a_slot(self):
        """A short slot after a long one computes on a buffer that still
        holds the long one's rows: whatever they are (here not finite),
        nothing of them reaches the short slot's output."""
        from bigdl_tpu.ops.flash_attention import \
            flash_paged_decode_attention

        (b, h, d, nb, bs, mb), tables, pos = _paged_kernel_case(
            "cell-geometry")
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
        k = np.asarray(rng.normal(size=(nb, bs, h * d)), np.float32)
        v = np.asarray(rng.normal(size=(nb, bs, h * d)), np.float32)
        clean = flash_paged_decode_attention(q, k, v, tables, pos,
                                             interpret=True)
        # slot 0 is full and runs first; poison blocks only it maps, past
        # where slot 1 (one token, in the trash block) will look
        for blk in tables[0, 1:]:
            k[blk], v[blk] = np.nan, np.inf
        out = flash_paged_decode_attention(q, k, v, tables, pos,
                                           interpret=True)
        assert not np.isfinite(np.asarray(out[0])).all()
        np.testing.assert_array_equal(np.asarray(out[1:]),
                                      np.asarray(clean[1:]))

    def test_engine_tokens_equal_with_and_without_the_kernel(self):
        """A finished engine run through the kernel (interpreter mode)
        gives the greedy tokens of the XLA gather path, on prompts and
        outputs that cross block boundaries."""
        streams = {}
        prompts = [[3, 1, 4, 1, 5, 9, 2], [7, 8, 9], [4] * 13]
        for mode in ("never", "interpret"):
            m = _lm(layers=2, max_len=64, scan=True)
            for block in m.blocks:
                block.attn.use_flash = mode
            with ServingEngine(m, decode_slots=3, decode_max_len=48,
                               kv_cache="paged", kv_block_size=4) as eng:
                futs = [eng.generate(p, max_new_tokens=7) for p in prompts]
                streams[mode] = [f.result(120) for f in futs]
        assert streams["interpret"] == streams["never"]
        assert all(len(s) == 7 for s in streams["never"])


class TestStackedPoolStaysInPlace:
    """The ``scan_layers`` layout carries the stacked pool through the layer
    loop and addresses ``(layer, block)`` on it; the unrolled layout hands
    each layer its own leaf.  Same weights, same steps: the same logits
    and, layer for layer, the same pool."""

    @pytest.mark.parametrize("cache", ["fp32", "int8"])
    @pytest.mark.parametrize("flash", ["interpret", "never"])
    def test_scan_layout_equals_unrolled(self, flash, cache):
        from bigdl_tpu.nn.attention import stack_block_params

        layers, nb, bs = 3, 9, 4
        dtype = jnp.int8 if cache == "int8" else jnp.float32
        models = {scan: _lm(layers=layers, max_len=32, scan=scan)
                  for scan in (False, True)}
        for m in models.values():
            for block in m.blocks:
                block.attn.use_flash = flash
        params = {False: models[False].parameters()[0]}
        params[True] = stack_block_params(params[False])
        rng = np.random.default_rng(6)
        toks = rng.integers(0, VOCAB, size=(2, 8)).astype(np.int32)
        tables = jnp.asarray([[5, 0, 7, nb], [2, 6, 3, nb]], jnp.int32)
        start = jnp.asarray([0, 0], jnp.int32)
        lengths = jnp.asarray([6, 3], jnp.int32)
        got = {}
        for scan, m in models.items():
            step = jax.jit(lambda p, x, c, pos, n=None, _m=m: _m.apply_paged(
                p, x, c, tables, pos=pos, lengths=n))
            pool = m.init_paged_cache(nb, bs, dtype=dtype)
            # one chunk of ragged rows, a second that reads the first
            # back, then decode across a block boundary
            lg1, pool = step(params[scan], toks[:, :6], pool, start, lengths)
            lg2, pool = step(params[scan], toks[:, 4:8], pool, lengths,
                             jnp.asarray([2, 4], jnp.int32))
            out = [lg1, lg2]
            pos = np.asarray([8, 7], np.int32)
            for t in range(3):
                lg, pool = step(params[scan], toks[:, t:t + 1], pool,
                                jnp.asarray(pos + t))
                out.append(lg)
            got[scan] = (out, pool)
        for a, b in zip(got[False][0], got[True][0]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        stacked = got[True][1]["blocks"]
        assert stacked["k"].shape == (layers, nb + 1, bs, 32)
        for i in range(layers):
            for name, leaf in got[False][1][f"block{i}"].items():
                np.testing.assert_array_equal(
                    np.asarray(stacked[name][i]), np.asarray(leaf),
                    err_msg=f"layer {i} leaf {name}")
        assert np.asarray(stacked["k"][:, :nb]).any()


class TestSamplingWire:
    def test_fleet_carries_sampling_and_replays(self):
        m = _lm(layers=2, max_len=48)
        e1 = ServingEngine(m, decode_slots=2, decode_max_len=32,
                           kv_block_size=4)
        e2 = ServingEngine(m, decode_slots=2, decode_max_len=32,
                           kv_block_size=4)
        fleet = ServingFleet([InProcessReplica(e1, rid=0),
                              InProcessReplica(e2, rid=1)])
        try:
            a = fleet.generate([5, 6, 7], max_new_tokens=4, timeout=60,
                               temperature=0.9, top_k=8, seed=7)
            b = fleet.generate([5, 6, 7], max_new_tokens=4, timeout=60,
                               temperature=0.9, top_k=8, seed=7)
            # the seed rides the wire: any replica replays the stream
            assert a == b and len(a) == 4
            # unseeded sampling: the FLEET mints the seed (retries stay
            # idempotent) and the request still serves
            assert len(fleet.generate([5, 6, 7], max_new_tokens=3,
                                      timeout=60,
                                      temperature=0.9)) == 3
        finally:
            fleet.close()
