"""The kanana-2-30b-a3b model (DeepSeek-V3's layer: latent attention on
every layer over a latent paged cache, sigmoid-scored experts with shared
ones, the identical layers as one scan) on the full forward and on the
serving path, each against the plain reference the benchmark keeps
(``benchmark/models/kanana-2-30b-a3b.py``: ``jax.numpy``, float32, one
full forward, no cache), on seeded random weights at small sizes.

Tolerances: everything here runs in float32 at matmul precision
``highest`` (tests/conftest.py), so program and reference differ by the
order of float32 sums only."""

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

from bigdl_tpu.nn import DroplessMoE, LatentAttention  # noqa: E402
from bigdl_tpu.nn.generation_state import allocate, has_slot_state  # noqa: E402
from bigdl_tpu.nn.latent_attention import rotary_at  # noqa: E402
from bigdl_tpu.observability.spans import recorder  # noqa: E402
from bigdl_tpu.ops.flash_attention import (  # noqa: E402
    latent_paged_decode_attention)
from bigdl_tpu.serving import ServingEngine  # noqa: E402
from bigdl_tpu.serving.generation import (PagedGenerateScheduler,  # noqa: E402
                                          paged_generate_steps)

CELL = "kanana-2-30b-a3b.serve.long-prompt"
FLOAT32 = {"program": {"class": "bigdl_tpu.models.kanana.Kanana",
                       "dtype": "float32"}}


@pytest.fixture(scope="module")
def toy():
    """The benchmark's model file, its toy configuration in float32, the
    weights of seed 7 and the program's model holding them."""
    cell = resolve.Cell(CELL)
    cfg, _ = cell.sized(True, (FLOAT32, {}))
    ref = cell.model
    params = ref.make_params(cfg, 7)
    spec = jax.ShapeDtypeStruct((1, cfg["n_positions"]), jnp.int32)
    return ref, cfg, params, ref.program_model(cfg, params, spec)


def close(a, b, rel):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-30)
    assert np.abs(a - b).max() <= rel * scale, np.abs(a - b).max() / scale


def tokens_of(seed, shape, vocab):
    return np.random.default_rng(seed).integers(0, vocab, shape) \
        .astype(np.int32)


def mla(**kw):
    """DeepSeek-V3's latent layer at toy widths."""
    kw = dict(dict(kv_rank=32, nope_dim=16, rope_dim=8, v_dim=16,
                   rope_theta=1e4, gate=False, qk_norm=False,
                   rope_interleave=True, use_kernel="never"), **kw)
    layer = LatentAttention(64, 4, **kw)
    return layer, layer.setup(jax.random.key(0), None)[0]


# ------------------------------------------------- the full forward -- #

@pytest.mark.parametrize("length", [5, 37])
def test_full_forward_against_reference(toy, length):
    ref, cfg, params, model = toy
    tokens = tokens_of(length, (2, length), cfg["vocab_size"])
    got, _ = model.apply(params, (), jnp.asarray(tokens))
    close(got, ref.reference_logits(params, jnp.asarray(tokens), cfg), 1e-4)


@pytest.mark.parametrize("path", ["forward", "chunk", "decode"])
def test_scanned_layers_against_the_same_layers_unrolled(toy, path):
    """One ``lax.scan`` over the stacked parameters against the same
    parameters run layer by layer: logits, every leaf of the pool and the
    counts summed through the loop."""
    ref, cfg, params, model = toy
    spec = jax.ShapeDtypeStruct((1, cfg["n_positions"]), jnp.int32)
    unrolled = ref.program_model(cfg, params, spec)
    unrolled.scan_layers = False
    assert model.scan_layers
    tokens = jnp.asarray(tokens_of(1, (2, 16), cfg["vocab_size"]))
    if path == "forward":
        close(model.apply(params, (), tokens)[0],
              unrolled.apply(params, (), tokens)[0], 1e-5)
        return
    tables = jnp.array([[0, 2, 6], [1, 3, 6]], jnp.int32)
    zero = jnp.zeros((2,), jnp.int32)
    kw = dict(pos=zero, lengths=jnp.array([16, 11], jnp.int32),
              logits_at=jnp.array([15, 10], jnp.int32))
    both = []
    for m in (model, unrolled):
        out = m.apply_paged(params, tokens, m.init_paged_cache(6, 8), tables,
                            **kw)
        if path == "decode":
            out = m.apply_paged(params, tokens[:, :1], out[1], tables,
                                pos=jnp.array([16, 11], jnp.int32))
        both.append(out)
    (a, pool_a), (b, pool_b) = both
    close(a, b, 1e-5)
    assert int(pool_a["moe_load"][0]) > 0
    assert (pool_a["moe_load"] == pool_b["moe_load"]).all()
    for x, y in zip(jax.tree.leaves(pool_a), jax.tree.leaves(pool_b)):
        close(x, y, 1e-5)


# ------------------------------------------------- latent attention -- #

def _turned_in_place(x, positions, theta):
    """The pairs ``(x_2i, x_2i+1)`` turned by angle ``i`` where they lie."""
    dh = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                      x2 * jnp.cos(ang) + x1 * jnp.sin(ang)],
                     -1).reshape(x.shape)


def test_interleaved_pairs_against_permuted_rotate_half():
    """What the program does for ``rope_interleave`` (pairs pulled apart,
    then rotate-half) is the in-place rotation with its columns permuted
    (evens, then odds), the same permutation for a query and a key: so
    every score is the reference's."""
    q = jax.random.normal(jax.random.key(0), (2, 9, 3, 8))
    k = jax.random.normal(jax.random.key(1), (2, 9, 1, 8))
    positions = jnp.broadcast_to(jnp.arange(9)[None] + 100, (2, 9))
    perm = jnp.concatenate([jnp.arange(0, 8, 2), jnp.arange(1, 8, 2)])
    for x in (q, k):
        got = rotary_at(x, positions, 1e6, interleave=True)
        close(got, _turned_in_place(x, positions, 1e6)[..., perm], 1e-6)
        close(got, rotary_at(x[..., perm], positions, 1e6), 1e-6)
    scores = lambda a, b: jnp.einsum("nqhd,nkgd->nhqk", a, b)
    close(scores(rotary_at(q, positions, 1e6, True),
                 rotary_at(k, positions, 1e6, True)),
          scores(_turned_in_place(q, positions, 1e6),
                 _turned_in_place(k, positions, 1e6)), 1e-5)


def test_the_layer_is_told_what_it_is():
    """No gate, no q/k norms: their leaves are not there; the cache row is
    stored in whole tiles of 128 columns."""
    layer, params = mla(row_align=128)
    assert sorted(params) == ["kv_norm", "kva_weight", "kvb_weight",
                              "out_weight", "q_weight"]
    assert layer.state_spec(jnp.float32)["latent"].shape == (128,)
    ling = LatentAttention(64, 4, 32, 16, 8, 16)
    lparams = ling.setup(jax.random.key(0), None)[0]
    assert {"q_norm", "kr_norm", "gate_weight"} <= set(lparams)
    assert ling.state_spec(jnp.float32)["latent"].shape == (40,)


@pytest.mark.parametrize("stacked", [False, True])
def test_latent_decode_kernel_against_the_gather(stacked, monkeypatch):
    """``latent_paged_decode_attention`` in interpret mode against
    ``_absorbed_attention``'s gather of the whole table, at uneven
    lengths (one slot of one token, one inside a block, one over several
    steps), with a slot that is not live between them: a layer's own leaf
    and one layer of a stacked leaf."""
    fa = sys.modules["bigdl_tpu.ops.flash_attention"]
    monkeypatch.setattr(fa, "_LATENT_STEP_ROWS", 16)     # two blocks a step
    layer, params = mla(row_align=128)
    nb, bs, mb = 40, 8, 9
    lead = (3,) if stacked else ()
    leaf = jax.random.normal(jax.random.key(2), lead + (nb + 1, bs, 128))
    which = jnp.int32(1) if stacked else None
    pos = np.array([0, 70, 33, 0, 7], np.int32)
    tables = np.full((5, mb), nb, np.int32)
    order, k = np.random.default_rng(0).permutation(nb), 0
    for r in (0, 1, 2, 4):                               # slot 3 is not live
        n = pos[r] // bs + 1
        tables[r, :n] = order[k:k + n]
        k += n
    q = jax.random.normal(jax.random.key(3), (5, 4, 24))
    want = layer._absorbed_attention(params, q, leaf, jnp.asarray(tables),
                                     jnp.asarray(pos), which)
    layer.use_kernel = "interpret"
    got = layer._absorbed_attention(params, q, leaf, jnp.asarray(tables),
                                    jnp.asarray(pos), which)
    live = np.array([0, 1, 2, 4])
    close(got[live], want[live], 1e-5)
    assert not np.asarray(got[3]).any()                  # skipped: nought
    lat = latent_paged_decode_attention(
        jnp.zeros((5, 4, 128)), leaf, jnp.asarray(tables), jnp.asarray(pos),
        which, rank=32, scale=1.0, interpret=True)
    # a zero query weighs every seen row alike: the mean of the rows
    block = np.asarray(leaf[1] if stacked else leaf)[tables[2, :5]]
    close(lat[2, 0], block.reshape(-1, 128)[:34, :32].mean(0), 1e-5)


@pytest.mark.parametrize("context", [21, 37, 64])
def test_bounded_chunk_attention_against_the_full_forward(monkeypatch,
                                                          context):
    """Chunks of 16 over a context that ends inside a block (21, 37) or on
    one (64), expanded 16 context tokens at a time: the loop ends at each
    chunk's own last position (blocks past it hold NaN here and must not
    be read), a row of padding runs no block."""
    import bigdl_tpu.nn.latent_attention as la

    monkeypatch.setattr(la, "CONTEXT_BLOCK", 16)
    layer, params = mla()
    x = jax.random.normal(jax.random.key(1), (1, context, 64))
    full, _ = layer.apply(params, (), x)
    pool = allocate(layer.state_spec(jnp.float32), 12, 8)
    row = [5, 0, 3, 1, 7, 2, 9, 4, 12, 12]
    past = row[2 * ((context - 1) // 16 + 1):]
    pool = {"latent": pool["latent"].at[jnp.array(past)].set(jnp.nan)}
    tables = jnp.array([row, [12] * 10], jnp.int32)
    outs = []
    for start in range(0, context, 16):
        n = min(16, context - start)
        chunk = jnp.zeros((2, 16, 64)).at[0, :n].set(x[0, start:start + n])
        out, pool = layer.apply_paged(
            params, chunk, pool, tables, jnp.array([start, 0], jnp.int32),
            jnp.array([n, 0], jnp.int32))
        outs.append(out[:1, :n])
        assert not np.isnan(np.asarray(out[1])).any()
    close(jnp.concatenate(outs, 1), full, 1e-5)


# ------------------------------------------------------- the mixture -- #

def test_the_eight_shares_add_up(toy):
    """The eight shares' routed parts plus the shared experts once are the
    uncut layer: the program's ``DroplessMoE`` holding 2 of 16 experts,
    eight times, against the reference's mixture over all 16."""
    ref = toy[0]
    d, f, e, k = 32, 16, 16, 3
    whole = DroplessMoE(d, f, e, k, (0, e), True, 2.448, "never",
                        shared_width=2 * f)
    params, _ = whole.setup(jax.random.key(0), None)
    params["router_bias"] = 0.1 * jax.random.normal(jax.random.key(5), (e,))
    x = jax.random.normal(jax.random.key(1), (2, 11, d))
    s = {"k": k, "scaling": 2.448, "first": 0}
    want = ref._moe(x, params, s, "f32")
    shared = ref._moe(x, dict(params, w1=params["w1"][:0],
                              w3=params["w3"][:0], w2=params["w2"][:0]),
                      s, "f32")
    close(whole.generate(params, x)[0], want, 1e-5)
    total = jnp.zeros_like(x)
    for first in range(0, e, 2):
        share = DroplessMoE(d, f, e, k, (first, 2), True, 2.448, "never",
                            shared_width=2 * f)
        held = dict(params, **{w: params[w][first:first + 2]
                               for w in ("w1", "w3", "w2")})
        out, counts = share.generate(held, x)
        total = total + out - shared
        close(out, ref._moe(x, held, dict(s, first=first), "f32"), 1e-5)
    close(total + shared, want, 1e-5)


# -------------------------------------------- the serving path ------- #

def _reference_gaps(ref, cfg, params, prompt, tokens):
    """How far each served token's logit lies below the reference's best
    at its position."""
    seq = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    logits = ref.reference_logits(params, jnp.asarray(seq)[None], cfg)[0]
    at = logits[len(prompt) - 1:]
    return np.asarray(at.max(-1) - at[np.arange(len(tokens)),
                                      np.asarray(tokens)])


def _engine(ref, cfg, params, use_kernel="never", **kw):
    spec = jax.ShapeDtypeStruct((1, cfg["n_positions"]), jnp.int32)
    model = ref.program_model(cfg, params, spec)
    for block in model.dense_layers + [model.expert_layer]:
        block["op"].use_kernel = use_kernel
    kw = dict(dict(decode_slots=2, decode_max_len=128, kv_cache="paged",
                   kv_block_size=8, kv_blocks=40, prefill_chunk=16), **kw)
    return ServingEngine(model, **kw)


@pytest.mark.parametrize("use_kernel", ["never", "interpret"])
def test_chunked_prefill_then_paged_decode_against_reference(toy,
                                                             use_kernel):
    """Through ``ServingEngine.generate()`` and the scanned layers:
    prompts that a chunk of 16 does not divide, more requests than slots,
    so rows start and finish at different ticks and freed slots are used
    again; greedy tokens held to the reference's full forward by their
    logit gap; the prep spans say how much context a tick read."""
    ref, cfg, params, _ = toy
    engine = _engine(ref, cfg, params, use_kernel)
    before = len(recorder().snapshot())
    try:
        sched = engine._generation()
        assert type(sched) is PagedGenerateScheduler
        assert not has_slot_state(sched.model.paged_state_spec())
        lengths = [(37, 6), (5, 9), (50, 3), (21, 7), (16, 4)]
        prompts = [tokens_of(i, (n,), cfg["vocab_size"])
                   for i, (n, _) in enumerate(lengths)]
        futs = [engine.generate(p, max_new_tokens=m)
                for p, (_, m) in zip(prompts, lengths)]
        outs = [f.result(timeout=600) for f in futs]
    finally:
        engine.close()
    for p, (_, m), out in zip(prompts, lengths, outs):
        assert len(out) == m
        assert _reference_gaps(ref, cfg, params, p, out).max() < 1e-4
    recs = recorder().snapshot()[before:]
    decode = [r.attrs for r in recs if r.name == "decode_prep"]
    assert all(a["context_tokens"] >= a["rows"] > 0 for a in decode)
    # a slot that wrote its 6th new token read its 37 + 5 rows and that one
    assert max(a["context_tokens"] for a in decode) >= 37 + 6
    chunks = [r.attrs for r in recs if r.name == "prefill_prep"]
    assert min(a["context_tokens"] for a in chunks) == 0
    assert max(a["context_tokens"] for a in chunks) >= 48   # 50 = 3 x 16 + 2
    loads = [r.attrs for r in recs if r.name == "moe_load"]
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    assert loads and all(
        a["rows_routed"] % (layers * cfg["num_experts_per_tok"]) == 0
        for a in loads)


def test_a_prefix_hit_on_a_latent_leaf(toy):
    """A model whose state is block leaves only shares a prompt's full
    blocks: the second request of the same prompt computes its last
    block alone and is served the tokens of the first, which the
    reference confirms."""
    ref, cfg, params, _ = toy
    engine = _engine(ref, cfg, params)
    try:
        prompt = tokens_of(11, (37,), cfg["vocab_size"])
        first = engine.generate(prompt, max_new_tokens=5)
        alone = first.result(timeout=600)
        again = engine.generate(prompt, max_new_tokens=5)
        shared = again.result(timeout=600)
        stats = engine._generation().stats()["kv"]
    finally:
        engine.close()
    assert first.prefix_hit_tokens == 0 and again.prefix_hit_tokens == 32
    assert stats["prefix_hits"] == 4
    assert list(shared) == list(alone)
    assert _reference_gaps(ref, cfg, params, prompt, shared).max() < 1e-4


def test_copy_on_write_leaves_the_donors_rows_alone(toy):
    """``copy_block`` on the latent leaves, a layer's own and the stacked
    one: the copy holds the donor's rows in every layer, no other block
    changes, and a row then written into the copy (a decode step through
    a table that maps it) leaves the donor as it was."""
    _, cfg, params, model = toy
    _, decode, copy = paged_generate_steps(model, jnp.float32)
    pool = model.init_paged_cache(6, 8)
    noise = lambda i, leaf: jax.random.normal(jax.random.key(i), leaf.shape)
    pool = {"layer0": {"latent": noise(0, pool["layer0"]["latent"])},
            "layers": {"latent": noise(1, pool["layers"]["latent"])},
            "moe_load": pool["moe_load"]}
    before = jax.tree.map(np.asarray, pool)
    pool = copy(pool, np.int32(2), np.int32(4))
    def blocks_first(key, tree):
        """The leaf with its block axis in front."""
        leaf = np.asarray(tree[key]["latent"])
        return leaf if leaf.ndim == 3 else np.moveaxis(leaf, 1, 0)

    for key in ("layer0", "layers"):
        leaf, was = blocks_first(key, pool), blocks_first(key, before)
        assert (leaf[4] == was[2]).all()
        others = [b for b in range(7) if b != 4]
        assert (leaf[others] == was[others]).all()
    knobs = (np.zeros((1,), np.float32), np.zeros((1,), np.int32),
             np.ones((1,), np.float32), np.zeros((1,), np.int32))
    _, pool = decode(params, pool, np.zeros((1,), np.int32),
                     np.array([3], np.int32), np.array([[4, 6]], np.int32),
                     *knobs)
    for key in ("layer0", "layers"):
        leaf, was = blocks_first(key, pool), blocks_first(key, before)
        assert (leaf[2] == was[2]).all()
        assert (leaf[4][..., 3, :] != was[2][..., 3, :]).any()
        assert (leaf[4][..., 2, :] == was[2][..., 2, :]).all()
