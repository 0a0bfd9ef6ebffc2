"""The Ling-3.0-flash hybrid model (delta-rule linear attention with a
per-slot state, latent attention with a latent paged cache, a dropless
mixture with group-limited selection and a shared expert) on the full
forward and on the serving path, each against the plain reference the
benchmark keeps (``benchmark/models/ling-3.0-flash-vl.py``: ``jax.numpy``,
float32, token-serial recurrence, no cache), on seeded random weights at
small sizes.

Tolerances: everything here runs in float32 at matmul precision
``highest`` (tests/conftest.py), so program and reference differ by the
order of float32 sums only: 1e-4 of the logits' scale after seven layers
and tens of recurrent steps."""

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

from bigdl_tpu.nn import DroplessMoE, LatentAttention  # noqa: E402
from bigdl_tpu.nn.attention import TransformerLM  # noqa: E402
from bigdl_tpu.nn.generation_state import allocate, has_slot_state  # noqa: E402
from bigdl_tpu.ops.kda import kda_decode_step, kda_scan, kda_step  # noqa: E402
from bigdl_tpu.serving import ServingEngine  # noqa: E402
from bigdl_tpu.serving.generation import (PagedGenerateScheduler,  # noqa: E402
                                          SpeculativeScheduler,
                                          paged_generate_steps)

CELL = "ling-3.0-flash-vl.serve.long-decode"
FLOAT32 = {"program": {"class": "bigdl_tpu.models.ling.Ling",
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


# ------------------------------------------------- the full forward -- #

@pytest.mark.parametrize("length", [5, 37])
def test_full_forward_against_reference(toy, length):
    ref, cfg, params, model = toy
    toks = tokens_of(length, (2, length), cfg["vocab_size"])
    got, _ = model.apply(params, (), jnp.asarray(toks))
    close(got, ref.reference_logits(params, jnp.asarray(toks), cfg), 1e-4)


@pytest.mark.parametrize("layer", ["kda", "latent_attention", "moe"])
def test_a_layer_against_reference(toy, layer):
    ref, cfg, params, model = toy
    s = ref.sizes(cfg)
    index = {"kda": 1, "latent_attention": s["kinds"].index(
        "latent_attention"), "moe": 1}[layer]
    p = params[f"layer{index}"]
    x = jax.random.normal(jax.random.key(3), (2, 19, s["D"]), jnp.float32)
    if layer == "moe":
        got, _ = model.layers[index]["ffn"].apply(p["ffn"], (), x)
        want = ref._moe(x, p["ffn"], s, "f32")
    else:
        got, _ = model.layers[index]["op"].apply(p["op"], (), x)
        want = (ref._kda if layer == "kda" else ref._mla)(x, p["op"], s,
                                                          "f32")
    close(got, want, 2e-5)


def test_bf16_state_control_differs_from_reference(toy):
    """The control that keeps the delta rule's state in bfloat16 is not
    the reference: the benchmark's readings rest on that."""
    ref, cfg, params, _ = toy
    toks = jnp.asarray(tokens_of(1, (1, 48), cfg["vocab_size"]))
    exact = ref.reference_logits(params, toks, cfg)
    low = ref.reference_logits(params, toks, cfg, "bf16_state")
    assert 1e-5 < float(jnp.abs(exact - low).max()) < 0.1


# ------------------------------------------- the delta rule's forms -- #

def _kda_inputs(seed, rows, heads, d, tokens=None):
    ks = jax.random.split(jax.random.key(seed), 6)
    lead = (rows,) if tokens is None else (rows, tokens)
    q, k, v = (jax.random.normal(ks[i], lead + (heads, d)) for i in range(3))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -5 * jax.nn.sigmoid(jax.random.normal(ks[3], lead + (heads, d)) - 3)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], lead + (heads,)))
    return q, k, v, g, beta


def _direct_step(S, q, k, v, g, beta):
    """One head, as the equation is written."""
    S = np.diag(np.exp(g)) @ S
    S = (np.eye(len(k)) - beta * np.outer(k, k)) @ S + beta * np.outer(k, v)
    return S, S.T @ q


@pytest.mark.parametrize("heads,d", [(8, 128), (2, 128)])
def test_kda_decode_step_kernel_against_one_step(heads, d):
    """The Pallas kernel (interpreter mode) against one step of the
    recurrence written out with numpy: live rows updated in place, every
    other slot untouched, the trash slot's row skipped."""
    slots = jnp.array([2, 4, 0], jnp.int32)          # 4 is the trash slot
    state = jax.random.normal(jax.random.key(9), (5, heads, d, d))
    q, k, v, g, beta = _kda_inputs(1, 3, heads, d)
    new, o = kda_decode_step(state, slots, q, k, v, g, beta, interpret=True)
    as64 = lambda a: np.asarray(a, np.float64)
    for row in (0, 2):
        for h in (0, heads - 1):
            S, out = _direct_step(
                as64(state[slots[row], h]), as64(q[row, h]), as64(k[row, h]),
                as64(v[row, h]), as64(g[row, h]), float(beta[row, h]))
            close(new[slots[row], h], S, 1e-5)
            close(o[row, h], out, 1e-5)
    np.testing.assert_array_equal(np.asarray(new[jnp.array([1, 3])]),
                                  np.asarray(state[jnp.array([1, 3])]))
    s2, o2 = kda_step(state[slots], q, k, v, g, beta)
    close(o[jnp.array([0, 2])], o2[jnp.array([0, 2])], 1e-5)


def test_kda_scan_skips_padding_tokens():
    q, k, v, g, beta = _kda_inputs(2, 2, 3, 16, tokens=6)
    valid = jnp.arange(6)[None, :] < jnp.array([6, 4])[:, None]
    s0 = jnp.zeros((2, 3, 16, 16))
    full, _ = kda_scan(s0, q, k, v, g, beta, valid)
    short, _ = kda_scan(s0[1:], q[1:, :4], k[1:, :4], v[1:, :4], g[1:, :4],
                        beta[1:, :4], jnp.ones((1, 4), bool))
    close(full[1], short[0], 1e-6)


# ------------------------------------------------- latent attention -- #

def test_latent_decode_absorbed_against_prefill_expanded():
    """One token a row through the absorbed path (attention over the
    cached latent rows) against the same token as the last of an expanded
    chunk, and both against the full forward."""
    layer = LatentAttention(64, 4, kv_rank=32, nope_dim=16, rope_dim=8,
                            v_dim=16, rope_theta=1e4)
    params, _ = layer.setup(jax.random.key(0), None)
    x = jax.random.normal(jax.random.key(1), (2, 21, 64))
    full, _ = layer.apply(params, (), x)
    spec = layer.state_spec(jnp.float32)
    tables = jnp.array([[0, 2, 4], [1, 3, 5]], jnp.int32)

    def prefill(upto):
        pool = allocate(spec, 6, 8)
        lengths = jnp.full((2,), upto, jnp.int32)
        return layer.apply_paged(params, x[:, :upto], pool, tables,
                                 jnp.zeros((2,), jnp.int32), lengths)

    expanded, _ = prefill(21)
    close(expanded, full, 1e-5)
    _, pool = prefill(20)
    absorbed, _ = layer.apply_paged(params, x[:, 20:21], pool, tables,
                                    jnp.full((2,), 20, jnp.int32))
    close(absorbed[:, 0], expanded[:, 20], 1e-5)


def test_latent_chunk_skips_and_blocks_agree(monkeypatch):
    """A chunk over a context of several expansion blocks, starting in
    the middle, against the full forward."""
    import bigdl_tpu.nn.latent_attention as la

    monkeypatch.setattr(la, "CONTEXT_BLOCK", 16)
    layer = LatentAttention(32, 2, kv_rank=16, nope_dim=8, rope_dim=4,
                            v_dim=8, rope_theta=1e4)
    params, _ = layer.setup(jax.random.key(0), None)
    x = jax.random.normal(jax.random.key(1), (1, 40, 32))
    full, _ = layer.apply(params, (), x)
    pool = allocate(layer.state_spec(jnp.float32), 8, 8)
    tables = jnp.array([[5, 0, 3, 1, 7, 8, 8, 8]], jnp.int32)
    outs = []
    for start in (0, 16, 32):
        n = min(16, 40 - start)
        chunk = jnp.zeros((1, 16, 32)).at[:, :n].set(x[:, start:start + n])
        out, pool = layer.apply_paged(
            params, chunk, pool, tables, jnp.array([start], jnp.int32),
            jnp.array([n], jnp.int32))
        outs.append(out[:, :n])
    close(jnp.concatenate(outs, 1), full, 1e-5)


# ------------------------------------------------------- the mixture -- #

def _moe(held=(0, 16), **kw):
    return DroplessMoE(32, 16, 16, 4, held, True, 2.5, n_group=4,
                       topk_group=2, shared_width=8, **kw)


def _direct_moe(params, x, held):
    """The layer by a direct loop over tokens and experts (numpy)."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    x = np.asarray(x, np.float64)
    out = np.zeros_like(x)
    silu = lambda a: a / (1 + np.exp(-a))
    for t, u in enumerate(x):
        s = 1 / (1 + np.exp(-(p["router_weight"] @ u)))
        pick = (s + p["router_bias"]).reshape(4, 4)
        groups = np.argsort(-np.sort(pick, -1)[:, -2:].sum(-1),
                            kind="stable")[:2]
        allowed = np.full(16, -np.inf)
        for gi in groups:
            allowed[gi * 4:(gi + 1) * 4] = pick[gi]
        chosen = np.argsort(-allowed, kind="stable")[:4]
        w = s[chosen] / (s[chosen].sum() + 1e-6) * 2.5
        for e, we in zip(chosen, w):
            if held[0] <= e < held[0] + held[1]:
                i = e - held[0]
                out[t] += we * (silu(u @ p["w1"][i]) * (u @ p["w3"][i])) \
                    @ p["w2"][i]
        sh = p["shared"]
        out[t] += sh["w2"] @ (silu(sh["w1"] @ u) * (sh["w3"] @ u))
    return out


@pytest.mark.parametrize("path", ["apply", "generate", "generate_live"])
def test_group_limited_top_k_with_shared_expert_against_direct_loop(path):
    layer = _moe()
    params, _ = layer.setup(jax.random.key(0), None)
    params["router_bias"] = 0.1 * jax.random.normal(jax.random.key(5), (16,))
    x = jax.random.normal(jax.random.key(1), (1, 12, 32))
    live = 12
    if path == "apply":
        got, _ = layer.apply(params, (), x)
    else:
        # the last three tokens are padding: routed nowhere, not counted
        live = 12 if path == "generate" else 9
        got, counts = layer.generate(
            params, x, None if live == 12 else jnp.arange(12)[None] < live)
        assert int(counts[0]) == live * 4 and int(counts[1]) == live * 4
        assert 1 <= int(counts[4]) <= 16
    close(got[0, :live], _direct_moe(params, x[0], (0, 16))[:live], 1e-5)


def test_the_shares_add_up():
    """The four shares' routed parts plus the shared expert once equal
    the uncut layer."""
    whole = _moe()
    params, _ = whole.setup(jax.random.key(0), None)
    x = jax.random.normal(jax.random.key(1), (2, 9, 32))
    want, _ = whole.apply(params, (), x)
    sh = params["shared"]
    shared = (jax.nn.silu(x @ sh["w1"].T) * (x @ sh["w3"].T)) @ sh["w2"].T
    total = shared
    for first in range(0, 16, 4):
        share = _moe((first, 4))
        p = dict(params, **{k: params[k][first:first + 4]
                            for k in ("w1", "w3", "w2")})
        part, _ = share.apply(p, (), x)
        total = total + (part - shared)
    close(total, want, 1e-5)


# -------------------------------------------- the serving path ------- #

def _reference_gaps(ref, cfg, params, prompt, tokens):
    """How far each served token's logit lies below the reference's best
    at its position."""
    seq = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    logits = ref.reference_logits(params, jnp.asarray(seq)[None], cfg)[0]
    at = logits[len(prompt) - 1:]
    return np.asarray(at.max(-1) - at[np.arange(len(tokens)),
                                      np.asarray(tokens)])


@pytest.mark.parametrize("use_kernel", ["never", "interpret"])
def test_chunked_prefill_then_paged_decode_against_reference(toy,
                                                             use_kernel):
    """Through ``ServingEngine.generate()``: prompts that a chunk of 16
    does not divide, more requests than slots, so rows start and finish at
    different ticks and freed slots are used again; greedy tokens held to
    the reference's full forward by their logit gap."""
    ref, cfg, params, _ = toy
    spec = jax.ShapeDtypeStruct((1, cfg["n_positions"]), jnp.int32)
    model = ref.program_model(cfg, params, spec)
    for layer in model.layers:
        for m in layer.values():
            if hasattr(m, "use_kernel"):
                m.use_kernel = use_kernel
    engine = ServingEngine(model, decode_slots=2, decode_max_len=128,
                           kv_cache="paged", kv_block_size=8, kv_blocks=40,
                           prefill_chunk=16)
    try:
        sched = engine._generation()
        assert type(sched) is PagedGenerateScheduler
        lengths = [(37, 6), (5, 9), (50, 3), (21, 7), (16, 4)]
        prompts = [tokens_of(i, (n,), cfg["vocab_size"])
                   for i, (n, _) in enumerate(lengths)]
        futs = [engine.generate(p, max_new_tokens=m)
                for p, (_, m) in zip(prompts, lengths)]
        outs = [f.result(timeout=600) for f in futs]
        assert all(f.prefix_hit_tokens == 0 for f in futs)
        assert sched.stats()["kv"]["prefix_hits"] == 0
    finally:
        engine.close()
    for p, (_, m), out in zip(prompts, lengths, outs):
        assert len(out) == m
        assert _reference_gaps(ref, cfg, params, p, out).max() < 1e-4


def test_a_stale_state_fails_the_comparison(toy, monkeypatch):
    """The same traffic with the reset at a sequence's first chunk taken
    out: a slot used again starts from what the last sequence left, and
    the comparison above does not pass."""
    ref, cfg, params, _ = toy
    import bigdl_tpu.nn.linear_attention as la

    real = la.KimiDeltaAttention.apply_paged

    def no_reset(self, params, input, pool, slots, pos, lengths=None):
        return real(self, params, input, pool, slots,
                    jnp.maximum(pos, 1) if lengths is not None else pos,
                    lengths)

    monkeypatch.setattr(la.KimiDeltaAttention, "apply_paged", no_reset)
    spec = jax.ShapeDtypeStruct((1, cfg["n_positions"]), jnp.int32)
    model = ref.program_model(cfg, params, spec)
    model.__dict__.pop("_compiled_paged_steps", None)
    engine = ServingEngine(model, decode_slots=1, decode_max_len=128,
                           kv_cache="paged", kv_block_size=8, kv_blocks=40,
                           prefill_chunk=16)
    try:
        prompts = [tokens_of(i, (20,), cfg["vocab_size"]) for i in (1, 2)]
        outs = [engine.generate(p, max_new_tokens=4).result(timeout=600)
                for p in prompts]
    finally:
        engine.close()
    worst = max(_reference_gaps(ref, cfg, params, p, o).max()
                for p, o in zip(prompts[1:], outs[1:]))
    assert worst > 1e-4


def test_no_prefix_hit_for_a_model_with_slot_state(toy):
    """The same prompt twice: the second is computed from its first token
    (no block of it was hashed), and its tokens are the first's."""
    ref, cfg, params, model = toy
    assert has_slot_state(model.paged_state_spec())
    model.__dict__.pop("_compiled_paged_steps", None)
    engine = ServingEngine(model, decode_slots=2, decode_max_len=128,
                           kv_cache="paged", kv_block_size=8, kv_blocks=40,
                           prefill_chunk=16)
    try:
        prompt = tokens_of(11, (40,), cfg["vocab_size"])
        first = engine.generate(prompt, max_new_tokens=3)
        a = first.result(timeout=600)
        second = engine.generate(prompt, max_new_tokens=3)
        b = second.result(timeout=600)
        kv = engine._generation().stats()["kv"]
    finally:
        engine.close()
    assert a == b
    assert second.prefix_hit_tokens == 0 and kv["prefix_hits"] == 0
    assert kv["blocks_cached"] == 0


def test_speculative_scheduler_refuses_slot_state(toy):
    _, _, _, model = toy
    with pytest.raises(TypeError, match="per-slot generation state"):
        SpeculativeScheduler(model, model, spec_k=2, slots=2, max_len=64)


def test_spans_of_a_served_request(toy):
    """``moe_load`` under every tick, ``state_reset`` at admission, and the
    request's span with what it held of each kind of state."""
    from bigdl_tpu.observability.spans import recorder

    _, cfg, _, model = toy
    model.__dict__.pop("_compiled_paged_steps", None)
    engine = ServingEngine(model, decode_slots=2, decode_max_len=128,
                           kv_cache="paged", kv_block_size=8, kv_blocks=40,
                           prefill_chunk=16)
    rec = recorder()
    before = len(rec.snapshot())
    try:
        engine.generate(tokens_of(3, (20,), cfg["vocab_size"]),
                        max_new_tokens=3).result(timeout=600)
        slot_bytes = engine._generation().slot_state_bytes()
    finally:
        engine.close()
    new = rec.snapshot()[before:]
    by_name = {}
    for r in new:
        by_name.setdefault(r.name, []).append(r)
    loads = [r.attrs for r in by_name["moe_load"]]
    # two chunks (16 + 4 tokens) and two decode ticks with one slot of
    # the two live; 4 expert layers, 4 experts a token: padding tokens
    # and rows that are not live are routed nowhere and not counted
    assert [a["rows_routed"] for a in loads] == [256, 64, 16, 16]
    assert all(0 < a["experts_touched"] <= 4 * 8 for a in loads)
    assert all(0 < a["rows_here"] <= a["rows_routed"] for a in loads)
    assert [r.attrs["slots"] for r in by_name["state_reset"]] == [1]
    request = by_name["request"][-1].attrs
    assert request["state_bytes"] == slot_bytes > 0
    assert request["latent_tokens"] == 20 + 3
    ticks = {r.span_id for r in by_name["tick"]}
    assert all(r.parent_id in ticks for r in by_name["moe_load"])


# ------------------------------- what the seam leaves as it was ------ #

#: sha256 (first 16 hex digits) of the lowered text of TransformerLM's
#: paged decode, chunk-prefill and block-copy programs at the sizes
#: below, taken at the parent of the PR that brought the state-spec seam
#: (d154f15) with the installed JAX (0.9.0) under this suite's settings
#: (tests/conftest.py: matmul precision ``highest``): unrolled, then
#: scan-stacked.  The scan layout's decode and chunk programs are those of
#: the PR that put the stacked pool in the layer loop's carry (PR 34, taken
#: on its tree, parent c6c266d; they were 7960c747552fad7c and
#: 79dd0cc04c68a5c2); its block copy and the unrolled three are as before.
#: Both layouts' decode and chunk programs end in the sampler, and are
#: those of the PR that made it a conditional with one two-operand sort
#: (PR 36, taken on its tree, parent 360d345; with SSA names struck out
#: the text differs from the parent's in the sampler's lines alone; they
#: were c287b3308b6e8195, c409795aa41b88c0 and 8888f3e56b3e264c,
#: ceb64c0210814d8c)
LOWERED_BEFORE = {
    False: ["a3141351fc1c862e", "936449bb236d178d", "79004a5f4eb5c2d9"],
    True: ["045214b27ac0ad1e", "fec5f04665d3c1a5", "0751efbb7a7d66ff"],
}


@pytest.mark.parametrize("scan", [False, True])
def test_transformer_lm_paged_programs_lower_as_before(scan):
    model = TransformerLM(64, 32, 4, 2, max_len=64, scan_layers=scan)
    model.build(jax.ShapeDtypeStruct((1, 64), jnp.int32),
                rng=jax.random.key(0))
    params = model.weights()
    pool = model.init_paged_cache(8, 16, jnp.float32)
    chunk, decode, copy = paged_generate_steps(model, jnp.float32)
    knobs = lambda n: (np.zeros((n,), np.float32), np.zeros((n,), np.int32),
                       np.ones((n,), np.float32), np.zeros((n,), np.int32))
    z = lambda *shape: np.zeros(shape, np.int32)
    texts = [
        decode.lower(params, pool, z(4), z(4), np.full((4, 4), 8, np.int32),
                     *knobs(4)).as_text(),
        chunk.lower(params, pool, z(2, 16), z(2), np.ones((2,), np.int32),
                    np.full((2, 4), 8, np.int32), *knobs(2)).as_text(),
        copy.lower(pool, np.int32(0), np.int32(1)).as_text()]
    got = [hashlib.sha256(t.encode()).hexdigest()[:16] for t in texts]
    assert got == LOWERED_BEFORE[scan]
    assert not has_slot_state(model.paged_state_spec())


#: the same for Ling's own three programs at the toy sizes below, taken at
#: the parent of the PR that told ``nn.LatentAttention`` what it is (gate,
#: q/k norms, rotary convention and row width as arguments whose defaults
#: are Ling's; a4f0e79): decode and block copy are the parent's; the chunk
#: program is that PR's, whose loop over context blocks ends at the row's
#: own last position (it was a70d51d148ac7d7b with every block gone
#: through); decode and chunk end in the sampler and are PR 36's, as above
#: (they were 130a0ad7bce524fd and 02502564ac740ab7)
LING_LOWERED_BEFORE = ["6132cd16f3dcb8cd", "4c2f2c1945bcbe41",
                       "a57c305d0332a8f5"]


def test_ling_paged_programs_lower_as_before(toy):
    _, cfg, _, model = toy
    params = model.weights()
    pool = model.init_paged_cache(8, 16, jnp.float32, slots=4)
    model.__dict__.pop("_compiled_paged_steps", None)
    chunk, decode, copy = paged_generate_steps(model, jnp.float32)
    knobs = lambda n: (np.zeros((n,), np.float32), np.zeros((n,), np.int32),
                       np.ones((n,), np.float32), np.zeros((n,), np.int32))
    z = lambda *shape: np.zeros(shape, np.int32)
    texts = [
        decode.lower(params, pool, z(4), z(4), np.full((4, 4), 8, np.int32),
                     *knobs(4), np.full((4,), 4, np.int32)).as_text(),
        chunk.lower(params, pool, z(2, 16), z(2), np.ones((2,), np.int32),
                    np.full((2, 4), 8, np.int32), *knobs(2),
                    np.full((2,), 4, np.int32)).as_text(),
        copy.lower(pool, np.int32(0), np.int32(1)).as_text()]
    got = [hashlib.sha256(t.encode()).hexdigest()[:16] for t in texts]
    assert got == LING_LOWERED_BEFORE
    assert has_slot_state(model.paged_state_spec())


# ------------------------------------------- a pool that is lost ----- #

def test_a_failed_rebuild_fails_the_waiting_requests(toy, monkeypatch):
    """A tick fails and the pool cannot be rebuilt: the tick's requests
    and everything waiting fail at once (no client hangs in stream()),
    and the scheduler serves again once the pool can be built."""
    _, cfg, _, model = toy
    model.__dict__.pop("_compiled_paged_steps", None)
    engine = ServingEngine(model, decode_slots=1, decode_max_len=128,
                           kv_cache="paged", kv_block_size=8, kv_blocks=40,
                           prefill_chunk=16)
    try:
        sched = engine._generation()
        prompt = tokens_of(5, (12,), cfg["vocab_size"])
        assert len(engine.generate(prompt, max_new_tokens=2)
                   .result(timeout=600)) == 2

        def boom(*a, **k):
            raise RuntimeError("device lost")

        build = sched._build_pool
        monkeypatch.setattr(sched, "_chunk_fn", boom)
        monkeypatch.setattr(sched, "_build_pool", boom)
        futs = [engine.generate(prompt, max_new_tokens=2) for _ in range(3)]
        for f in futs:
            with pytest.raises(RuntimeError, match="device lost"):
                list(f.stream(timeout=60))
        monkeypatch.undo()
        sched._build_pool = build
        assert len(engine.generate(prompt, max_new_tokens=2)
                   .result(timeout=600)) == 2
    finally:
        engine.close()
