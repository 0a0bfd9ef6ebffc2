"""The Granite-4.0-H hybrid model (Mamba-2 state-space mixers with a
per-slot state beside grouped-query attention in the paged cache, a tied
head, the family's four multipliers) on the full forward and on the
serving path, each against the plain reference the benchmark keeps
(``benchmark/models/granite-4.0-h-micro.py``: ``jax.numpy``, float32,
token-serial recurrence, no chunks, no cache), on seeded random weights at
small sizes.

Tolerances: everything here runs in float32 at matmul precision
``highest`` (tests/conftest.py), so program and reference differ by the
order of float32 sums only: 1e-4 of the logits' scale after five layers
and tens of recurrent steps (the chunked scan sums a chunk's tokens in
another order than the recurrence does)."""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import resolve  # noqa: E402

from bigdl_tpu.nn import Mamba2Mixer, MultiHeadAttention  # noqa: E402
from bigdl_tpu.nn.attention import dot_product_attention  # noqa: E402
from bigdl_tpu.nn.generation_state import has_slot_state  # noqa: E402
from bigdl_tpu.ops import ssd  # noqa: E402
from bigdl_tpu.ops.flash_attention import (  # noqa: E402
    flash_paged_decode_attention)
from bigdl_tpu.serving import ServingEngine  # noqa: E402
from bigdl_tpu.serving.generation import PagedGenerateScheduler  # noqa: E402

CELL = "granite-4.0-h-micro.serve.short-chat"
FLOAT32 = {"program": {"class": "bigdl_tpu.models.granite.GraniteHybrid",
                       "dtype": "float32"}}


@pytest.fixture(scope="module")
def toy():
    """The benchmark's model file, its toy configuration in float32, the
    weights of seed 7 and the program's model holding them."""
    cell = resolve.Cell(CELL)
    cfg, _ = cell.sized(True, (FLOAT32, {}))
    ref = cell.model
    params = ref.make_params(cfg, 7)
    return ref, cfg, params, _model(ref, cfg, params)


def _model(ref, cfg, params, use_kernel="never"):
    spec = jax.ShapeDtypeStruct((1, cfg["n_positions"]), jnp.int32)
    model = ref.program_model(cfg, params, spec)
    model.chunk_rows = 2
    model.mamba_layer["op"].use_kernel = use_kernel
    model.attention_layer["op"].use_flash = use_kernel
    return model


def close(a, b, rel):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-30)
    assert np.abs(a - b).max() <= rel * scale, np.abs(a - b).max() / scale


def tokens_of(seed, shape, vocab):
    return np.random.default_rng(seed).integers(0, vocab, shape) \
        .astype(np.int32)


# ------------------------------------------------- the full forward -- #

@pytest.mark.parametrize("length", [5, 37])
def test_full_forward_against_reference(toy, length):
    ref, cfg, params, model = toy
    toks = tokens_of(length, (2, length), cfg["vocab_size"])
    got, _ = model.apply(params, (), jnp.asarray(toks))
    close(got, ref.reference_logits(params, jnp.asarray(toks), cfg), 1e-4)


@pytest.mark.parametrize("length", [3, 16, 41])
def test_mixer_full_forward_against_the_recurrence(toy, length):
    """``Mamba2Mixer.apply`` (the chunked scan, chunks of 16) against the
    reference's mixer, which takes a row token by token."""
    ref, cfg, params, model = toy
    s = ref.sizes(cfg)
    p = jax.tree.map(lambda a: a[1], params["mamba0"]["op"])
    u = jnp.asarray(np.random.default_rng(length).normal(
        size=(2, length, s["D"])), jnp.float32)
    got, _ = model.mamba_layer["op"].apply(p, (), u)
    close(got, ref._mamba(u, p, s, "f32"), 1e-5)


def test_the_tree_is_tied_and_the_multipliers_are_the_configs(toy):
    ref, cfg, params, model = toy
    assert "head" not in params and model.tied_head
    assert (model.embedding_multiplier, model.residual_multiplier,
            model.logits_scaling) == (12.0, 0.22, 8.0)
    attn = model.attention_layer["op"]
    assert (attn.num_heads, attn.num_kv_heads, attn.groups) == (4, 2, 2)
    assert attn.scale == 0.015625 and not attn.bias
    assert model.runs == [("mamba", 2), ("attention", 1), ("mamba", 2)]
    op = params["mamba1"]["op"]
    assert all(op[k].dtype == jnp.float32
               for k in ("A_log", "dt_bias", "D", "o_norm"))
    assert ref.param_count(cfg) == sum(
        a.size for a in jax.tree.leaves(params))


# -------------------------------------------------- the three forms -- #

def _ssd_inputs(seed, rows, tokens, heads=8, p=8, k=16):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (rows, tokens, heads)),
                     jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 16, (heads,)), jnp.float32)
    return (f(rows, heads, p, k), f(rows, tokens, heads, p), dt, A,
            f(rows, tokens, k), f(rows, tokens, k), f(heads))


def _token_by_token(state, x, dt, A, B, C, D):
    ys = []
    for t in range(x.shape[1]):
        state, y = ssd.ssd_step(state, x[:, t], dt[:, t], A, B[:, t],
                                C[:, t], D)
        ys.append(y)
    return state, jnp.stack(ys, 1)


@pytest.mark.parametrize("tokens,chunk", [(37, 16), (37, 8), (16, 16),
                                          (5, 256), (50, 7)])
def test_chunk_scan_against_steps(tokens, chunk):
    """Lengths that are no multiple of the chunk, from a state that is
    not nought, with padding: row 1's last tokens have ``dt`` 0 and must
    leave its state as it is."""
    state, x, dt, A, B, C, D = _ssd_inputs(tokens, 2, tokens)
    real = tokens - tokens // 4
    valid = np.ones((2, tokens), bool)
    valid[1, real:] = False
    dt = dt * valid[..., None]
    want_s, want_y = _token_by_token(state, x, dt, A, B, C, D)
    got_s, got_y = ssd.ssd_chunk_scan(ssd.to_stored(state), x, dt, A, B, C,
                                      D, chunk)
    got_s = ssd.from_stored(got_s, x.shape[2])
    close(got_s, want_s, 1e-5)
    close(got_y * valid[..., None, None], want_y * valid[..., None, None],
          1e-5)
    # row 1's state is what its real tokens alone leave
    alone, _ = _token_by_token(state[1:], x[1:, :real], dt[1:, :real], A,
                               B[1:, :real], C[1:, :real], D)
    close(got_s[1:], alone, 1e-5)


@pytest.mark.parametrize("heads,p,k", [(64, 64, 128), (8, 8, 16),
                                       (4, 128, 16)])
def test_stored_layout_round_trips(heads, p, k):
    x = jnp.arange(3 * heads * p * k, dtype=jnp.float32).reshape(
        3, heads, p, k)
    stored = ssd.to_stored(x)
    assert stored.shape == (3,) + ssd.stored_shape(heads, p, k)
    assert stored.shape[-1] == max(p, min(128, heads * p))
    assert bool((ssd.from_stored(stored, heads) == x).all())


@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize("heads,p,k", [(64, 64, 128), (8, 8, 16)])
def test_decode_step_kernel_against_one_step(heads, p, k, stacked):
    """The Pallas kernel in interpreter mode against ``ssd_step``: live
    rows' states are the step's, in place; the trash slot and every slot
    no row names are untouched, and a row on the trash slot reads
    nought."""
    slots_n = 5
    state, x, dt, A, B, C, D = _ssd_inputs(heads, slots_n + 1, 1, heads, p,
                                           k)
    rows = jnp.asarray([3, slots_n, 0], jnp.int32)       # row 1 is not live
    one = lambda a: a[:3, 0]
    stored = ssd.to_stored(state)
    if stacked:
        other = jnp.full_like(stored, 7.0)
        new, y = ssd.ssd_decode_step(
            jnp.stack([other, stored]), rows, one(x), one(dt), A, one(B),
            one(C), D, layer=jnp.int32(1), interpret=True)
        assert bool((new[0] == 7.0).all())
        new = new[1]
    else:
        new, y = ssd.ssd_decode_step(stored, rows, one(x), one(dt), A,
                                     one(B), one(C), D, interpret=True)
    want_s, want_y = ssd.ssd_step(state[rows], one(x), one(dt), A, one(B),
                                  one(C), D)
    got = ssd.from_stored(new, heads)
    for row, slot in ((0, 3), (2, 0)):
        close(got[slot], want_s[row], 1e-6)
        close(y[row], want_y[row], 1e-5)
    assert bool((y[1] == 0).all())
    for slot in (1, 2, 4, slots_n):
        assert bool((got[slot] == state[slot]).all())


# ------------------------------------- grouped queries in the pool -- #

def _paged_case(seed, b, kv_heads, groups, d, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    bs, nb, mb = 8, 16, 4
    q = jnp.asarray(rng.normal(size=(b, 1, kv_heads * groups, d)),
                    jnp.float32)
    k = jnp.asarray(rng.normal(size=(nb, bs, kv_heads * d)), dtype)
    v = jnp.asarray(rng.normal(size=(nb, bs, kv_heads * d)), dtype)
    tables = jnp.asarray(rng.permutation(nb)[:b * mb].reshape(b, mb),
                         jnp.int32)
    pos = jnp.asarray([0, 13, 31][:b], jnp.int32)
    return q, k, v, tables, pos


@pytest.mark.parametrize("groups", [1, 2, 4])
def test_grouped_paged_decode_against_plain_attention(groups):
    """Query head ``g`` reads KV head ``g // groups`` of blocks the kernel
    fetched once (interpreter mode), against plain attention over the
    gathered context with K and V repeated to the query heads."""
    kv_heads, d = 2, 32
    q, k, v, tables, pos = _paged_case(groups, 3, kv_heads, groups, d)
    got = flash_paged_decode_attention(q, k, v, tables, pos, interpret=True)
    ctx = tables.shape[1] * k.shape[1]
    rows = lambda pool: jnp.repeat(
        pool[tables].reshape(3, ctx, kv_heads, d), groups, axis=2)
    mask = (jnp.arange(ctx)[None, :] <= pos[:, None])[:, None, None, :]
    close(got, dot_product_attention(q, rows(k), rows(v), mask=mask), 1e-5)


@pytest.mark.parametrize("case,digest", [
    ("float32", "c97c56b2c4498831"), ("bfloat16-stacked", "1aa83a6d47e4f881"),
    ("int8-stacked", "32b09e37c4785257")])
def test_one_group_is_bit_for_bit_what_it_was(case, digest):
    """Multi-head attention through the kernel that now serves groups:
    the digests are of the parent commit's outputs (PR 38) on these
    inputs, in interpreter mode on the CPU."""
    rng = np.random.default_rng(7)
    b, h, d, bs, nb, mb = 3, 4, 32, 8, 12, 4
    stacked = case.endswith("stacked")
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    shape = ((2,) if stacked else ()) + (nb, bs, h * d)
    ks = vs = None
    if case.startswith("int8"):
        k = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.001, 0.02, shape[:-1] + (h,)),
                         jnp.float32)
        vs = jnp.asarray(rng.uniform(0.001, 0.02, shape[:-1] + (h,)),
                         jnp.float32)
    else:
        dtype = jnp.float32 if case == "float32" else jnp.bfloat16
        k = jnp.asarray(rng.normal(size=shape), dtype)
        v = jnp.asarray(rng.normal(size=shape), dtype)
    tables = jnp.asarray(rng.permutation(nb)[:b * mb].reshape(b, mb),
                         jnp.int32)
    out = flash_paged_decode_attention(
        q, k, v, tables, jnp.asarray([0, 13, 31], jnp.int32), ks, vs,
        layer=jnp.int32(1) if stacked else None, interpret=True)
    assert hashlib.sha256(np.asarray(out, np.float32).tobytes()) \
        .hexdigest()[:16] == digest


def test_the_pool_is_sized_by_kv_heads_and_says_which_width_failed():
    attn = MultiHeadAttention(256, 8, causal=True, num_kv_heads=2,
                              bias=False)
    pool = attn.init_paged_cache(5, 16, jnp.bfloat16)
    assert pool["k"].shape == pool["v"].shape == (5, 16, 64)
    assert attn.state_spec(jnp.int8)["k_scale"].shape == (2,)
    params, _ = attn.setup(jax.random.key(0), None)
    assert set(params) == {"qkv_weight", "out_weight"}
    assert params["qkv_weight"].shape == (256 + 2 * 64, 256)
    # 64 values a row are not whole lanes of 128: the gate says no
    attn.use_flash = "auto"
    assert not attn._flash_paged_ok(16, jnp.bfloat16)
    q, k, v, tables, pos = _paged_case(0, 3, 2, 2, 32)
    with pytest.raises(AssertionError, match="a pool row of 48 values"):
        flash_paged_decode_attention(q, k[..., :48], v[..., :48], tables,
                                     pos, interpret=True)


def test_grouped_queries_through_the_paged_cache():
    """A chunk and then decode steps through ``init_paged_cache`` (sized
    by KV heads) and ``apply_paged`` against the full forward, with a
    softmax scale of its own."""
    attn = MultiHeadAttention(64, 4, causal=True, use_flash="never",
                              num_kv_heads=2, bias=False, scale=0.05)
    params, _ = attn.setup(jax.random.key(1), None)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 12, 64)),
                    jnp.float32)
    want, _ = attn.apply(params, (), x)
    pool = attn.init_paged_cache(9, 4)      # 8 blocks and the trash block
    assert pool["k"].shape == (9, 4, 2 * 16)
    tables = jnp.asarray([[5, 0, 3, 8], [1, 6, 2, 8]], jnp.int32)
    step = jax.jit(lambda p, h, pool, pos, n=None: attn.apply_paged(
        p, h, pool, tables, pos, n))
    got, pool = step(params, x[:, :9], pool, jnp.zeros((2,), jnp.int32),
                     jnp.full((2,), 9, jnp.int32))
    steps = [got]
    for t in range(9, 12):
        y, pool = step(params, x[:, t:t + 1], pool,
                       jnp.full((2,), t, jnp.int32))
        steps.append(y)
    close(jnp.concatenate(steps, 1), want, 1e-5)
    # the scale is the one asked for, not head_dim ** -0.5
    plain = MultiHeadAttention(64, 4, causal=True, use_flash="never",
                               num_kv_heads=2, bias=False)
    assert float(jnp.abs(plain.apply(params, (), x)[0] - want).max()) > 1e-4


# ------------------------------------------------ the serving path -- #

def _paged_logits(model, params, prompts, new, chunk, slots=3, bs=8,
                  left_behind=0.0):
    """Prefill ``prompts`` in chunks of ``chunk`` through ``apply_paged``
    (all rows in one step, a row that is done waiting on the trash slot),
    with room for ``new`` decode steps after them: ``(the logits of each
    prompt's last position, the pool, the tables)``.  ``left_behind`` is
    what an earlier sequence left in every slot."""
    n = len(prompts)
    mb = -(-(max(len(p) for p in prompts) + new) // bs)
    pool = model.init_paged_cache(n * mb, bs, jnp.float32, slots=slots)
    pool["mamba"] = jax.tree.map(lambda a: a + left_behind, pool["mamba"])
    tables = np.arange(n * mb, dtype=np.int32).reshape(n, mb)
    trash_table = np.full((mb,), n * mb, np.int32)
    done = np.zeros(n, np.int32)
    first = [None] * n
    step = jax.jit(model.apply_paged)
    while any(done[i] < len(p) for i, p in enumerate(prompts)):
        toks = np.zeros((n, chunk), np.int32)
        lens = np.zeros(n, np.int32)
        ids = np.full(n, slots, np.int32)
        tabs = np.tile(trash_table, (n, 1))
        for i, p in enumerate(prompts):
            part = p[done[i]:done[i] + chunk]
            if len(part):
                toks[i, :len(part)] = part
                lens[i], ids[i], tabs[i] = len(part), i, tables[i]
        logits, pool = step(
            params, jnp.asarray(toks), pool, tabs, pos=done.copy(),
            lengths=lens, slots=ids,
            logits_at=np.clip(lens - 1, 0, chunk - 1))
        for i, p in enumerate(prompts):
            if lens[i] and done[i] + lens[i] == len(p):
                first[i] = logits[i, 0]
        done += lens
    return first, pool, tables


@pytest.mark.parametrize("use_kernel", ["never", "interpret"])
@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_chunked_prefill_then_decode_logits_against_reference(toy, chunk,
                                                              use_kernel):
    """Two sequences of 37 and 21 tokens prefilled in chunks that share
    steps (the shorter one is done first and waits), then 16 decode steps
    through both caches: every logit against the reference's full forward
    over prompt + continuation."""
    ref, cfg, params, _ = toy
    model = _model(ref, cfg, params, use_kernel)
    new = 16
    rows = [tokens_of(i, (n + new,), cfg["vocab_size"])
            for i, n in enumerate((37, 21))]
    prompts = [r[:-new] for r in rows]
    first, pool, tables = _paged_logits(model, params, prompts, new, chunk)
    got = [[f] for f in first]
    pos = np.asarray([len(p) for p in prompts], np.int32)
    step = jax.jit(model.apply_paged)
    for t in range(new - 1):
        toks = np.asarray([r[len(p) + t] for r, p in zip(rows, prompts)])
        logits, pool = step(
            params, jnp.asarray(toks, jnp.int32)[:, None], pool, tables,
            pos=pos + t, slots=np.arange(2, dtype=np.int32))
        for i in range(2):
            got[i].append(logits[i, 0])
    for i, (r, p) in enumerate(zip(rows, prompts)):
        want = ref.reference_logits(params, jnp.asarray(r[:-1])[None],
                                    cfg)[0, len(p) - 1:]
        close(jnp.stack(got[i]), want, 1e-4)


def test_more_rows_than_a_group_run_group_by_group(toy):
    """Five rows with ``chunk_rows`` 2 is no whole number of groups and
    runs as one step; four rows run two at a time: both against three."""
    ref, cfg, params, model = toy
    prompts = [tokens_of(i, (n,), cfg["vocab_size"])
               for i, n in enumerate((9, 30, 17, 24))]
    together, _, _ = _paged_logits(model, params, prompts, 0, 32, slots=4)
    for i, p in enumerate(prompts):
        want = ref.reference_logits(params, jnp.asarray(p)[None], cfg)[0, -1]
        close(together[i], want, 1e-4)


def _reference_gaps(ref, cfg, params, prompt, tokens):
    """How far each served token's logit lies below the reference's best
    at its position, over the logits' spread."""
    seq = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    logits = ref.reference_logits(params, jnp.asarray(seq)[None], cfg)[0]
    at = logits[len(prompt) - 1:]
    gaps = at.max(-1) - at[np.arange(len(tokens)), np.asarray(tokens)]
    return np.asarray(gaps / jnp.abs(at).max())


def _serve(model, prompts, news, slots):
    engine = ServingEngine(model, decode_slots=slots, decode_max_len=128,
                           kv_cache="paged", kv_block_size=8, kv_blocks=40,
                           prefill_chunk=16)
    try:
        sched = engine._generation()
        assert type(sched) is PagedGenerateScheduler
        futs = [engine.generate(p, max_new_tokens=m)
                for p, m in zip(prompts, news)]
        outs = [f.result(timeout=600) for f in futs]
        assert all(f.prefix_hit_tokens == 0 for f in futs)
        return outs, sched.stats()
    finally:
        engine.close()


@pytest.mark.parametrize("use_kernel", ["never", "interpret"])
def test_served_tokens_against_reference(toy, use_kernel):
    """Through ``ServingEngine.generate()``: prompts that a chunk of 16
    does not divide, more requests than slots, so rows start and finish at
    different ticks, share ticks and freed slots are used again; greedy
    tokens held to the reference's full forward by their logit gap."""
    ref, cfg, params, _ = toy
    model = _model(ref, cfg, params, use_kernel)
    assert has_slot_state(model.paged_state_spec())
    lengths = [(37, 16), (5, 9), (50, 3), (21, 16), (16, 4)]
    prompts = [tokens_of(i, (n,), cfg["vocab_size"])
               for i, (n, _) in enumerate(lengths)]
    outs, stats = _serve(model, prompts, [m for _, m in lengths], 2)
    assert stats["kv"]["prefix_hits"] == 0
    for p, (_, m), out in zip(prompts, lengths, outs):
        assert len(out) == m
        assert _reference_gaps(ref, cfg, params, p, out).max() < 1e-4


def test_a_slot_used_again_is_served_as_a_fresh_one(toy):
    """One slot, two sequences one after the other, through the engine:
    the second agrees with the reference, which starts from nought."""
    ref, cfg, params, model = toy
    prompts = [tokens_of(i, (20,), cfg["vocab_size"]) for i in (1, 2)]
    outs, _ = _serve(model, prompts, [4, 4], 1)
    for p, out in zip(prompts, outs):
        assert _reference_gaps(ref, cfg, params, p, out).max() < 1e-4


@pytest.mark.parametrize("reset", [True, False])
def test_a_first_chunk_starts_from_a_zero_state(toy, monkeypatch, reset):
    """Slots that hold what an earlier sequence left (state and tail all
    3): a sequence's first chunk zeroes them, and the logits are the
    reference's, which starts from nought; with the reset taken out they
    are not."""
    ref, cfg, params, model = toy
    if not reset:
        real = Mamba2Mixer.apply_paged

        def no_reset(self, params, input, pool, slots, pos, lengths=None,
                     layer=None):
            return real(self, params, input, pool, slots,
                        jnp.maximum(pos, 1) if lengths is not None else pos,
                        lengths, layer)

        monkeypatch.setattr(Mamba2Mixer, "apply_paged", no_reset)
    prompts = [tokens_of(i, (n,), cfg["vocab_size"])
               for i, n in enumerate((20, 9))]
    got, _, _ = _paged_logits(model, params, prompts, 0, 16,
                              left_behind=3.0)
    worst = 0.0
    for i, p in enumerate(prompts):
        want = ref.reference_logits(params, jnp.asarray(p)[None], cfg)[0, -1]
        worst = max(worst, float(jnp.abs(got[i] - want).max()
                                 / jnp.abs(want).max()))
    assert (worst < 1e-4) == reset


def test_the_pool_holds_both_kinds_stacked(toy):
    ref, cfg, params, model = toy
    pool = model.init_paged_cache(10, 8, jnp.float32, slots=3)
    s = ref.sizes(cfg)
    assert pool["mamba"]["state"].shape == (4, 4) + ssd.stored_shape(
        s["H"], s["P"], s["N"])
    assert pool["mamba"]["state"].dtype == jnp.float32
    assert pool["mamba"]["conv"].shape == (4, 4, s["taps"] - 1, s["conv"])
    assert pool["attention"]["k"].shape == (1, 11, 8, s["Hkv"] * s["dh"])
    with pytest.raises(NotImplementedError):
        model.paged_state_spec(jnp.int8)
    with pytest.raises(ValueError, match="slots="):
        model.apply_paged(params, jnp.zeros((1, 1), jnp.int32), pool,
                          np.zeros((1, 2), np.int32), pos=np.zeros(1))
