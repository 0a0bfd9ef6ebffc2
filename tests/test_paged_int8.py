"""ISSUE 19 tentpole (a): int8 paged KV blocks.

Pins, per the acceptance criteria:

- the quantized pool layout: int8 K/V payloads + fp32 per-(position,
  head) absmax scales in the ops/quantization.py blockwise format
  (quantization block = head_dim), 4-D leaves so block copies keep the
  one copy_block convention;
- paged chunk-prefill + decode through the int8 pool stay within a
  PINNED logit tolerance of the fp32 pool at EVERY position, in both
  param layouts (unrolled and scan-stacked);
- causal masking survives quantization: poisoning payloads AND scales
  beyond the decode frontier changes nothing (the poisoned-cache pin
  from test_decode, adapted to the block pool);
- the prefix cache refuses a storage-format mismatch legibly, and
  namespaces content hashes by kv dtype;
- ``BlockAllocator.stats()`` reports allocator-measured
  ``bytes_per_block`` / ``pool_bytes`` (ROADMAP item 3's rule: cite
  the pool, never hand-computed dtype math);
- the engine end-to-end: ``kv_cache_dtype="int8"`` serves, the
  MemoryLedger kv_cache source reports real NARROW bytes (>2.5x less
  than fp32 at head_dim 8), recompiles stay 0 after precompile, and
  int8 without the paged layout is refused.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.attention import TransformerLM
from bigdl_tpu.observability.watchdogs import backend_compile_count
from bigdl_tpu.serving import BlockAllocator, ServingEngine

VOCAB = 50


def _lm(layers=2, max_len=48, scan=False, hidden=32, key=0):
    m = TransformerLM(vocab_size=VOCAB, hidden_size=hidden, num_heads=4,
                      num_layers=layers, max_len=max_len,
                      scan_layers=scan)
    m.build(jax.ShapeDtypeStruct((2, 16), jnp.int32),
            rng=jax.random.PRNGKey(key))
    return m


def _pool_bytes(pool):
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(pool))


class TestInt8PoolLayout:
    def test_leaf_dtypes_shapes_and_bytes(self):
        m = _lm(layers=1)
        nb, bs = 6, 4
        fp = m.init_paged_cache(nb, bs)
        q8 = m.init_paged_cache(nb, bs, dtype=jnp.int8)
        layer = q8["block0"]
        h, d = 4, 8                              # hidden 32, 4 heads
        for name in ("k", "v"):
            assert layer[name].dtype == jnp.int8
            assert layer[name].shape == (nb + 1, bs, h * d)
            # one fp32 absmax per (position, head) head_dim vector,
            # of the payload's rank so copy_block treats it like any
            # pool leaf
            assert layer[name + "_scale"].dtype == jnp.float32
            assert layer[name + "_scale"].shape == (nb + 1, bs, h)
        # head_dim 8: fp32 32 B/vector vs int8 8 B + 4 B scale -> 8/3x
        ratio = _pool_bytes(fp) / _pool_bytes(q8)
        assert abs(ratio - 32 / 12) < 1e-6

    @pytest.mark.parametrize("scan", [False, True])
    def test_int8_logits_close_to_fp32_every_position(self, scan):
        """Chunked prefill + decode through the quantized pool, pinned
        against the fp32 pool at every position (the blockwise absmax
        error at head_dim 8 measures ~3e-3; the pin leaves 3x slack)."""
        m = _lm(layers=2, scan=scan)
        params = m.parameters()[0]
        nb, bs, mb = 8, 4, 4
        rng = np.random.default_rng(5)
        toks = rng.integers(0, VOCAB, size=(1, 8)).astype(np.int32)
        tables = jnp.asarray([[0, 1, 2, nb]], jnp.int32)
        logits = {}
        for dt in (jnp.float32, jnp.int8):
            pool = m.init_paged_cache(nb, bs, dtype=dt)
            got = []
            # prefill the first 4 positions as one chunk...
            lg, pool = m.apply_paged(params, jnp.asarray(toks[:, :4]),
                                     pool, tables,
                                     pos=jnp.asarray([0], jnp.int32),
                                     lengths=jnp.asarray([4], jnp.int32))
            got.extend(np.asarray(lg)[0])
            # ...and decode the rest token by token
            for t in range(4, 8):
                lg, pool = m.apply_paged(
                    params, jnp.asarray(toks[:, t:t + 1]), pool, tables,
                    pos=jnp.asarray([t], jnp.int32))
                got.append(np.asarray(lg)[0, 0])
            logits[dt] = np.stack(got)
        err = np.max(np.abs(logits[jnp.int8] - logits[jnp.float32]))
        assert err < 0.01, f"int8 KV perturbed logits by {err}"
        assert np.array_equal(np.argmax(logits[jnp.int8], -1),
                              np.argmax(logits[jnp.float32], -1))

    def test_poisoned_int8_cache_is_causally_masked(self):
        """Garbage beyond the frontier -- payloads at the int8 rails,
        scales at 1e4 -- must be invisible to the decode step."""
        m = _lm(layers=2)
        params = m.parameters()[0]
        nb, bs = 8, 4
        toks = np.random.default_rng(2).integers(
            0, VOCAB, size=(1, 6)).astype(np.int32)
        tables = jnp.asarray([[0, 1, 2, nb]], jnp.int32)
        pool = m.init_paged_cache(nb, bs, dtype=jnp.int8)
        _, pool = m.apply_paged(params, jnp.asarray(toks), pool, tables,
                                pos=jnp.asarray([0], jnp.int32),
                                lengths=jnp.asarray([6], jnp.int32))
        tok = jnp.asarray([[3]], jnp.int32)
        pos = jnp.asarray([6], jnp.int32)
        lg, _ = m.apply_paged(params, tok, pool, tables, pos=pos)

        def poison(leaf):
            # position 6 lives in block 1 at offset 2: poison offset 3
            # of block 1, all of block 2, and the trash block -- every
            # pool position a causal read at pos=6 must ignore
            bad = 127 if leaf.dtype == jnp.int8 else 1e4
            leaf = leaf.at[1, 3:].set(bad)
            return leaf.at[jnp.asarray([2, nb])].set(bad)

        lg2, _ = m.apply_paged(params, tok, jax.tree.map(poison, pool),
                               tables, pos=pos)
        np.testing.assert_array_equal(np.asarray(lg), np.asarray(lg2))


class TestAllocatorDtypeContract:
    def test_mixed_dtype_admission_is_refused_legibly(self):
        a = BlockAllocator(num_blocks=8, block_size=4, kv_dtype="int8")
        with pytest.raises(ValueError, match="KV-dtype mismatch"):
            a.begin_sequence("s1", list(range(9)), 9, kv_dtype="fp32")
        # the matching declaration (and the back-compat default on an
        # fp32 pool) both admit
        assert a.begin_sequence("s1", list(range(9)), 9,
                                kv_dtype="int8") == 0
        b = BlockAllocator(num_blocks=8, block_size=4)
        assert b.begin_sequence("s1", list(range(9)), 9,
                                kv_dtype="fp32") == 0

    def test_hash_roots_namespace_by_dtype(self):
        """Same prompt, different storage formats -> different content
        hashes, so a serialized/shared cache can never alias an int8
        block into an fp32 read (fp32 keeps the pre-ISSUE-19 root "")."""
        from bigdl_tpu.serving.paging import chain_hash

        fp = BlockAllocator(num_blocks=8, block_size=4)
        q8 = BlockAllocator(num_blocks=8, block_size=4, kv_dtype="int8")
        assert fp._hash_root == ""
        assert q8._hash_root == "kv:int8"
        block = list(range(4))
        assert chain_hash(fp._hash_root, block) \
            != chain_hash(q8._hash_root, block)

    def test_stats_report_allocator_measured_bytes(self):
        a = BlockAllocator(num_blocks=8, block_size=4, kv_dtype="int8",
                           bytes_per_block=1536)
        st = a.stats()
        assert st["kv_dtype"] == "int8"
        assert st["bytes_per_block"] == 1536
        assert st["pool_bytes"] == 1536 * 8
        # unmeasured pools say so instead of guessing
        st = BlockAllocator(num_blocks=4, block_size=4).stats()
        assert st["bytes_per_block"] is None and st["pool_bytes"] is None


class TestEngineInt8KV:
    def test_serves_and_ledger_reports_narrow_bytes(self):
        m = _lm(layers=2, max_len=64)
        prompts = [[1, 2, 3], [7, 8, 9, 10, 11]]
        bytes_of = {}
        streams = {}
        for dt in ("fp32", "int8"):
            with ServingEngine(m, decode_slots=2, decode_max_len=48,
                               kv_block_size=4,
                               kv_cache_dtype=dt) as eng:
                eng.precompile(example_feature=np.zeros((4,), np.int32))
                before = backend_compile_count()
                futs = [eng.generate(p, max_new_tokens=5)
                        for p in prompts]
                streams[dt] = [f.result(60) for f in futs]
                assert backend_compile_count() - before == 0
                kv = eng._kv_cache_bytes()     # the ledger's source
                assert kv["kv_dtype"] == dt
                assert kv["bytes"] == (kv["active_bytes"]
                                       + kv["cached_bytes"]
                                       + kv["free_bytes"]
                                       # the trash block is pool-only
                                       + kv["bytes"]
                                       // (kv["blocks_total"] + 1))
                bytes_of[dt] = kv["bytes"]
        assert all(len(s) == 5 for s in streams["int8"])
        # head_dim 8: layout math says 32/12 = 2.67x narrower
        assert bytes_of["fp32"] / bytes_of["int8"] > 2.5

    def test_int8_needs_the_paged_layout(self):
        m = _lm(layers=1, max_len=48)
        with pytest.raises(ValueError, match="paged"):
            ServingEngine(m, decode_slots=1, decode_max_len=40,
                          kv_cache="contiguous", kv_cache_dtype="int8")
        with pytest.raises(ValueError, match="kv_cache_dtype"):
            ServingEngine(m, decode_slots=1, decode_max_len=40,
                          kv_cache_dtype="int4")
