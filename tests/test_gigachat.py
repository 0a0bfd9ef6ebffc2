"""The GigaChat3.5 hybrid model (gated delta-rule layers with 64 value heads
over 32 key heads and a decay a head, a latent-attention layer with a
query latent, YaRN and an element-wise gate, a dropless mixture with the
SwiGLU clamp and a shared expert, norms before and after every sublayer)
on the full forward and on the serving path, each against the plain
reference the benchmark keeps (``benchmark/models/gigachat3.5-432b-a28b.py``:
``jax.numpy``, float32, token-serial recurrence, no cache), on seeded
random weights at small sizes.

Tolerances: everything here runs in float32 at matmul precision
``highest`` (tests/conftest.py), so program and reference differ by the
order of float32 sums only: 1e-4 of the logits' scale after five layers
and tens of recurrent steps (a gap between a served token's logit and the
reference's best, where the logits spread over about 1), 1e-5 for a
single layer, and 1e-5 of the state's scale between the chunked scan and
the token-serial one (the chunk's products against the token's multiply
and sum: float32 rounding, a few ulps a step).  A control that computes
the reference in bfloat16 moves the judged gaps above the first of these
by orders: the comparison can tell the precision the test states from
the next lower one."""

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

from bigdl_tpu.nn import (DroplessMoE, GatedDeltaNet,  # noqa: E402
                          LatentAttention)
from bigdl_tpu.nn.generation_state import allocate, has_slot_state  # noqa: E402
from bigdl_tpu.observability.spans import recorder  # noqa: E402
from bigdl_tpu.ops.kda import (gated_delta_chunk_scan,  # noqa: E402
                               kda_decode_step, kda_scan, kda_step)
from bigdl_tpu.serving import ServingEngine  # noqa: E402
from bigdl_tpu.serving.generation import (PagedGenerateScheduler,  # noqa: E402
                                          paged_generate_steps)

CELL = "gigachat3.5-432b-a28b.serve.long-reasoning"
FLOAT32 = {"program": {"class": "bigdl_tpu.models.gigachat.GigaChat35",
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


def _model(ref, cfg, params, use_kernel="never", chunk_rows=2):
    spec = jax.ShapeDtypeStruct((1, cfg["n_positions"]), jnp.int32)
    model = ref.program_model(cfg, params, spec)
    model.chunk_rows = chunk_rows
    for layer in model.layers:
        for m in layer.values():
            if hasattr(m, "use_kernel"):
                m.use_kernel = use_kernel
    return model


def close(a, b, rel):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-30)
    assert np.abs(a - b).max() <= rel * scale, np.abs(a - b).max() / scale


def tokens_of(seed, shape, vocab):
    return np.random.default_rng(seed).integers(0, vocab, shape) \
        .astype(np.int32)


# ------------------------------------------------- the full forward -- #

@pytest.mark.parametrize("length", [5, 37, 70])
def test_full_forward_against_reference(toy, length):
    """70 tokens are two chunks of the chunked scan and a part of one.
    The reference computed in bfloat16 (matmul inputs and the stream)
    lies more than ten times the tolerance away: the comparison refuses
    the next lower precision."""
    ref, cfg, params, model = toy
    toks = jnp.asarray(tokens_of(length, (2, length), cfg["vocab_size"]))
    got, _ = model.apply(params, (), toks)
    want = ref.reference_logits(params, toks, cfg)
    close(got, want, 1e-4)
    low = ref.reference_logits(params, toks, cfg, "bf16")
    assert float(jnp.abs(low - want).max()) > 1e-3 * float(
        jnp.abs(want).max())


@pytest.mark.parametrize("layer", ["gated_delta", "latent_attention", "moe"])
def test_a_layer_against_reference(toy, layer):
    ref, cfg, params, model = toy
    s = ref.sizes(cfg)
    index = {"gated_delta": 1, "moe": 1,
             "latent_attention": s["kinds"].index("latent_attention")}[layer]
    p = params[f"layer{index}"]
    x = jax.random.normal(jax.random.key(3), (2, 19, s["D"]), jnp.float32)
    if layer == "moe":
        got, _ = model.layers[index]["ffn"].apply(p["ffn"], (), x)
        want = ref._moe(x, p["ffn"], s, "f32")
    else:
        got, _ = model.layers[index]["op"].apply(p["op"], (), x)
        want = (ref._gated_delta if layer == "gated_delta" else ref._mla)(
            x, p["op"], s, "f32")
    close(got, want, 1e-5)


# ------------------------------------------- the delta rule's forms -- #

def _inputs(seed, rows, tokens, heads, d):
    ks = jax.random.split(jax.random.key(seed), 6)
    q, k, v = (jax.random.normal(ks[i], (rows, tokens, heads, d))
               for i in range(3))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jax.nn.softplus(jax.random.normal(ks[3], (rows, tokens, heads)) - 3)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (rows, tokens, heads)))
    s0 = jax.random.normal(ks[5], (rows, heads, d, d))
    return s0, q, k, v, g, beta


@pytest.mark.parametrize("lengths", [(150, 150), (150, 97), (64, 0)])
def test_chunked_scan_against_the_token_serial_scan(lengths):
    """Across chunk boundaries (150 tokens: two chunks of 64 and a part of
    one), with padding at a row's end, and a row of padding alone: the
    state after the last valid token and every valid token's output are
    ``kda_scan``'s with the decay broadcast over the key channels; a row
    of padding leaves its state as it was."""
    s0, q, k, v, g, beta = _inputs(0, 2, 150, 3, 16)
    valid = jnp.arange(150)[None] < jnp.array(lengths)[:, None]
    s1, o1 = kda_scan(s0, q, k, v, jnp.broadcast_to(g[..., None], q.shape),
                      beta, valid)
    s2, o2 = gated_delta_chunk_scan(s0, q, k, v, g, beta, valid)
    close(s2, s1, 1e-5)
    keep = valid[..., None, None]
    close(jnp.where(keep, o2, 0), jnp.where(keep, o1, 0), 1e-5)
    if 0 in lengths:
        np.testing.assert_array_equal(np.asarray(s2[1]), np.asarray(s0[1]))


def test_value_heads_read_their_key_heads_through_the_decode_kernel():
    """Four value heads over two key heads (value head ``h`` reads key
    head ``h // 2``), one decay a value head: the Pallas decode step
    (interpreter mode) on what the mixer hands it, against ``kda_step``
    head by head with the key head and the decay written out; the trash
    slot's row is skipped and every other slot untouched."""
    layer = GatedDeltaNet(32, key_heads=2, value_heads=4, head_dim=16)
    ks = jax.random.split(jax.random.key(1), 5)
    q, k = (jax.random.normal(ks[i], (3, 2, 16)) for i in (0, 1))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (3, 4, 16))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (3, 4)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (3, 4)))
    state = jax.random.normal(jax.random.key(9), (5, 4, 16, 16))
    slots = jnp.array([2, 4, 0], jnp.int32)          # 4 is the trash slot
    new, o = kda_decode_step(state, slots,
                             *layer._per_value_head(q, k, v, g, beta),
                             interpret=True)
    for row in (0, 2):
        for h in range(4):
            s, out = kda_step(state[slots[row], h], q[row, h // 2],
                              k[row, h // 2], v[row, h],
                              jnp.full((16,), g[row, h]), beta[row, h])
            close(new[slots[row], h], s, 1e-5)
            close(o[row, h], out, 1e-5)
    np.testing.assert_array_equal(np.asarray(new[jnp.array([1, 3])]),
                                  np.asarray(state[jnp.array([1, 3])]))
    assert float(jnp.abs(o[1]).max()) == 0.0


@pytest.mark.parametrize("use_kernel", ["never", "interpret"])
def test_gated_delta_chunks_then_tokens_against_the_full_forward(
        use_kernel):
    """A layer's paged path: a chunk of 21 tokens (padded to 32), then
    three tokens one at a time through the decode step, against the
    layer's full forward over the same 24 tokens."""
    layer = GatedDeltaNet(32, key_heads=2, value_heads=4, head_dim=16,
                          use_kernel=use_kernel)
    params, _ = layer.setup(jax.random.key(0), None)
    params["A_log"] = jnp.log(jnp.array([1.0, 2.0, 3.0, 4.0]))
    params["dt_bias"] = jnp.full((4,), -3.0)
    x = jax.random.normal(jax.random.key(1), (2, 24, 32))
    full, _ = layer.apply(params, (), x)
    pool = allocate(layer.state_spec(jnp.float32), 0, 1, slots=3)
    slots = jnp.array([2, 0], jnp.int32)
    chunk = jnp.zeros((2, 32, 32)).at[:, :21].set(x[:, :21])
    out, pool = layer.apply_paged(params, chunk, pool, slots,
                                  jnp.zeros((2,), jnp.int32),
                                  jnp.full((2,), 21, jnp.int32))
    outs = [out[:, :21]]
    for t in range(21, 24):
        out, pool = layer.apply_paged(params, x[:, t:t + 1], pool, slots,
                                      jnp.full((2,), t, jnp.int32))
        outs.append(out)
    close(jnp.concatenate(outs, 1), full, 1e-5)


# ------------------------------------------------- latent attention -- #

YARN = {"type": "yarn", "factor": 8, "original_max_position_embeddings": 64,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}


def _latent(use_kernel="never"):
    """The cell's latent layer at a small size: a query latent, YaRN over
    an original context of 64 positions (of the 4 rotary pairs one keeps
    its frequency and three are interpolated), interleaved pairs, an
    element-wise gate, rows in 128 columns."""
    return LatentAttention(64, 4, kv_rank=32, nope_dim=16, rope_dim=8,
                           v_dim=16, rope_theta=1e5, gate="element",
                           qk_norm=False, rope_interleave=True, row_align=128,
                           q_rank=24, rope_scaling=YARN,
                           use_kernel=use_kernel)


def test_yarn_frequencies_and_scale_are_the_references():
    """The program's YaRN frequencies and softmax scale against the
    reference's own, at the cell's rotary width and settings, and the
    ramp in between kept and interpolated pairs."""
    ref = resolve.Cell(CELL).model
    s = ref.sizes(resolve.Cell(CELL).config)
    layer = LatentAttention(64, 4, rope_dim=64, rope_theta=s["theta"],
                            rope_scaling=s["yarn"])
    want = ref.yarn_frequencies(64, s["theta"], s["yarn"])
    close(layer.inv_freq, want, 1e-6)
    plain = s["theta"] ** (-np.arange(0, 64, 2) / 64)
    close(want[:14], plain[:14], 1e-6)                 # kept
    close(want[24:], plain[24:] / 8, 1e-6)             # interpolated
    assert layer.scale == pytest.approx(ref.softmax_scale(s))
    assert layer.scale == pytest.approx(192 ** -0.5 * 1.2079441541679836 ** 2)


def test_query_latent_and_yarn_against_reference():
    """The expanded forward of the layer against the reference's latent
    attention, positions far past the original context included."""
    ref = resolve.Cell(CELL).model
    layer = _latent()
    params, _ = layer.setup(jax.random.key(0), None)
    assert sorted(params) == ["gate_weight", "kv_norm", "kva_weight",
                              "kvb_weight", "out_weight", "qa_norm",
                              "qa_weight", "qb_weight"]
    assert params["gate_weight"].shape == (64, 64)
    s = {"H": 4, "nope": 16, "rope": 8, "vd": 16, "rank": 32, "eps": 1e-6,
         "theta": 1e5, "yarn": YARN}
    x = jax.random.normal(jax.random.key(1), (2, 90, 64))
    got, _ = layer.apply(params, (), x)
    close(got, ref._mla(x, params, s, "f32"), 1e-5)


@pytest.mark.parametrize("use_kernel", ["never", "interpret"])
def test_latent_absorbed_decode_against_the_expanded_forward(use_kernel):
    """Prefill expanded in a chunk, then tokens absorbed one at a time,
    through the XLA gather or the Pallas kernel (interpreter mode), the
    query latent, YaRN and the element-wise gate on both paths."""
    layer = _latent(use_kernel)
    params, _ = layer.setup(jax.random.key(0), None)
    x = jax.random.normal(jax.random.key(1), (2, 40, 64))
    full, _ = layer.apply(params, (), x)
    pool = allocate(layer.state_spec(jnp.float32), 12, 8)
    tables = jnp.array([[0, 2, 4, 6, 8], [1, 3, 5, 7, 9]], jnp.int32)
    out, pool = layer.apply_paged(params, x[:, :37], pool, tables,
                                  jnp.zeros((2,), jnp.int32),
                                  jnp.full((2,), 37, jnp.int32))
    outs = [out]
    for t in range(37, 40):
        out, pool = layer.apply_paged(params, x[:, t:t + 1], pool, tables,
                                      jnp.full((2,), t, jnp.int32))
        outs.append(out)
    close(jnp.concatenate(outs, 1), full, 1e-5)


# ------------------------------------------------------- the mixture -- #

def _moe(held=(0, 16), limit=10.0):
    return DroplessMoE(32, 16, 16, 4, held, True, 2.5, shared_width=8,
                       swiglu_limit=limit)


def _direct_moe(params, x, limit):
    """The layer by a direct loop over tokens and experts (numpy)."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    x = np.asarray(x, np.float64)
    silu = lambda a: a / (1 + np.exp(-a))
    glu = lambda a, b: silu(np.minimum(a, limit)) * np.clip(b, -limit, limit)
    out = np.zeros_like(x)
    for t, u in enumerate(x):
        s = 1 / (1 + np.exp(-(p["router_weight"] @ u)))
        chosen = np.argsort(-(s + p["router_bias"]), kind="stable")[:4]
        w = s[chosen] / (s[chosen].sum() + 1e-6) * 2.5
        for e, we in zip(chosen, w):
            out[t] += we * glu(u @ p["w1"][e], u @ p["w3"][e]) @ p["w2"][e]
        sh = p["shared"]
        out[t] += sh["w2"] @ glu(sh["w1"] @ u, sh["w3"] @ u)
    return out


@pytest.mark.parametrize("path", ["apply", "generate"])
def test_the_clamp_binds_where_values_pass_ten(path):
    """Inputs large enough that the experts' products pass 10 on both
    sides: the layer is the clamped loop's, and not the unclamped one's."""
    layer = _moe()
    params, _ = layer.setup(jax.random.key(0), None)
    params["router_bias"] = 0.1 * jax.random.normal(jax.random.key(5), (16,))
    x = 30.0 * jax.random.normal(jax.random.key(1), (1, 12, 32))
    hidden = np.asarray(x[0]) @ np.asarray(params["w1"][0])
    assert hidden.max() > 10 and hidden.min() < -10
    run = layer.apply if path == "apply" else \
        (lambda p, _, x: layer.generate(p, x))
    got = run(params, (), x)[0][0]
    close(got, _direct_moe(params, x[0], 10.0), 1e-5)
    free, _ = _moe(limit=None).apply(params, (), x)
    assert float(jnp.abs(free[0] - got).max()) > 0.1 * float(
        jnp.abs(got).max())


def test_the_sixteen_shares_add_up():
    """Sixteen chips' shares of a small uncut layer (one expert each, the
    clamp on): their routed parts plus the shared expert once equal the
    uncut layer."""
    whole = _moe()
    params, _ = whole.setup(jax.random.key(0), None)
    x = 3.0 * jax.random.normal(jax.random.key(1), (2, 9, 32))
    want, _ = whole.apply(params, (), x)
    sh = params["shared"]
    shared = (jax.nn.silu(jnp.minimum(x @ sh["w1"].T, 10.0))
              * jnp.clip(x @ sh["w3"].T, -10.0, 10.0)) @ sh["w2"].T
    total = shared
    for first in range(16):
        p = dict(params, **{k: params[k][first:first + 1]
                            for k in ("w1", "w3", "w2")})
        part, _ = _moe((first, 1)).apply(p, (), x)
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


LENGTHS = [(37, 6), (5, 9), (70, 3), (21, 7), (16, 4), (40, 5)]


@pytest.mark.parametrize("use_kernel", ["never", "interpret"])
def test_chunked_prefill_then_paged_decode_against_reference(toy,
                                                             use_kernel):
    """Through ``ServingEngine.generate()``: prompts that a chunk of 16
    does not divide, more requests than slots, four rows admitted at once
    (run two at a time, ``chunk_rows``), rows that start and finish at
    different ticks and freed slots used again; greedy tokens held to the
    reference's full forward by their logit gap.  The same tokens judged
    against a bfloat16 reference read gaps far above the tolerance."""
    ref, cfg, params, _ = toy
    model = _model(ref, cfg, params, use_kernel)
    engine = ServingEngine(model, decode_slots=4, decode_max_len=128,
                           kv_cache="paged", kv_block_size=8, kv_blocks=72,
                           prefill_chunk=16)
    try:
        sched = engine._generation()
        assert type(sched) is PagedGenerateScheduler
        prompts = [tokens_of(i, (n,), cfg["vocab_size"])
                   for i, (n, _) in enumerate(LENGTHS)]
        futs = [engine.generate(p, max_new_tokens=m)
                for p, (_, m) in zip(prompts, LENGTHS)]
        outs = [f.result(timeout=600) for f in futs]
        assert sched.stats()["kv"]["prefix_hits"] == 0
    finally:
        engine.close()
    for p, (_, m), out in zip(prompts, LENGTHS, outs):
        assert len(out) == m
        assert _reference_gaps(ref, cfg, params, p, out).max() < 1e-4


def test_a_grouped_chunk_step_is_the_ungrouped_one(toy):
    """Four rows of a chunk, one of them padding on the trash slot, run
    two at a time (``chunk_rows`` 2) inside the one program: the logits
    and the new state are those of the four run together, and the step's
    ``moe_load`` counts the live tokens of both groups in every expert
    layer, not of the last group alone."""
    ref, cfg, params, _ = toy
    pool = lambda m: m.init_paged_cache(16, 8, jnp.float32, slots=4)
    args = (jnp.asarray(tokens_of(4, (4, 16), cfg["vocab_size"])),)
    kw = dict(pos=jnp.zeros((4,), jnp.int32),
              lengths=jnp.array([16, 9, 0, 12], jnp.int32),
              slots=jnp.array([0, 1, 4, 2], jnp.int32),
              logits_at=jnp.array([15, 8, 0, 11], jnp.int32))
    tables = jnp.array([[0, 1], [2, 3], [16, 16], [4, 5]], jnp.int32)
    out = {}
    for rows in (2, None):
        m = _model(ref, cfg, params, chunk_rows=rows)
        out[rows] = m.apply_paged(params, *args, pool(m), tables, **kw)
    # two rows and four block the products differently: float32 rounding
    close(out[2][0][jnp.array([0, 1, 3])], out[None][0][jnp.array([0, 1, 3])],
          1e-5)
    grouped, whole = (dict(out[rows][1]) for rows in (2, None))
    for a, b in zip(jax.tree.leaves(grouped), jax.tree.leaves(whole)):
        if a.dtype == jnp.float32:
            close(a, b, 1e-5)
    # rows routed and rows here add up over the groups as over the layers;
    # the busiest expert is the busier group's, at most the whole step's;
    # an expert two groups touch counts twice (its weights are read twice)
    k, expert_layers = cfg["num_experts_per_tok"], 4
    g, w = ([int(c) for c in out["moe_load"]] for out in (grouped, whole))
    assert g[0] == w[0] == 37 * k * expert_layers
    assert g[1] == w[1]
    assert 0 < g[2] <= w[2] <= 2 * g[2]
    assert w[4] <= g[4] <= 2 * w[4]


def test_spans_of_a_served_request(toy):
    """``moe_load`` under every tick, and the request's span with what it
    held of each kind of state."""
    ref, cfg, params, _ = toy
    model = _model(ref, cfg, params)
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
    # two chunks (16 + 4 tokens) and two decode ticks with one slot of
    # the two live; 4 expert layers, 4 experts a token
    assert [r.attrs["rows_routed"] for r in new if r.name == "moe_load"] \
        == [256, 64, 16, 16]
    request = [r.attrs for r in new if r.name == "request"][-1]
    assert request["state_bytes"] == slot_bytes > 0
    assert request["latent_tokens"] == 20 + 3


def test_no_prefix_hit_for_a_model_with_slot_state(toy):
    _, _, _, model = toy
    assert has_slot_state(model.paged_state_spec())
    assert set(model.paged_state_spec()["layer0"]) == {"state", "conv"}
    assert set(model.paged_state_spec()["layer4"]) == {"latent"}


# ------------------------------- what the shared code leaves as it was #

#: sha256 (first 16 hex digits) of the lowered text of the kanana and
#: granite cells' paged decode, chunk-prefill (2 rows, and 8 rows: granite
#: runs them four at a time) and block-copy programs at their toy sizes,
#: taken at the parent of the PR that brought the gated delta rule, the
#: query latent and the clamp into the shared modules (600bb61), with the
#: installed JAX (0.9.0) under this suite's settings.  Ling's three are
#: pinned in tests/test_ling.py.
LOWERED_BEFORE = {
    "kanana-2-30b-a3b.serve.long-prompt": [
        "7600df783d4c6abb", "5168529f7e53eb9b", "08c3498d1fa1d860",
        "9476738c4e6c2af2"],
    "granite-4.0-h-micro.serve.short-chat": [
        "14676a6fb9cfe2ba", "f530ab1c131b75b9", "9bbe14d2ce90ff2c",
        "7dd7f0be243ea1b9"],
}


@pytest.mark.parametrize("cell_name", list(LOWERED_BEFORE))
def test_the_cells_sharing_the_code_lower_as_before(cell_name):
    cell = resolve.Cell(cell_name)
    cfg, _ = cell.sized(True)
    spec = jax.ShapeDtypeStruct((2, cfg["n_positions"]), jnp.int32)
    model = cell.model.program_model(cfg, cell.model.make_params(cfg, 7),
                                     spec)
    params = model.weights()
    slotted = has_slot_state(model.paged_state_spec())
    pool = model.init_paged_cache(8, 16, jnp.float32,
                                  **({"slots": 4} if slotted else {}))
    chunk, decode, copy = paged_generate_steps(model, jnp.float32)
    knobs = lambda n: (np.zeros((n,), np.float32), np.zeros((n,), np.int32),
                       np.ones((n,), np.float32), np.zeros((n,), np.int32))
    z = lambda *shape: np.zeros(shape, np.int32)
    slots = lambda n: (np.full((n,), 4, np.int32),) if slotted else ()
    tables = lambda n: np.full((n, 4), 8, np.int32)
    chunk_of = lambda n: chunk.lower(
        params, pool, z(n, 16), z(n), np.ones((n,), np.int32), tables(n),
        *knobs(n), *slots(n)).as_text()
    texts = [decode.lower(params, pool, z(4), z(4), tables(4), *knobs(4),
                          *slots(4)).as_text(),
             chunk_of(2), chunk_of(8),
             copy.lower(pool, np.int32(0), np.int32(1)).as_text()]
    got = [hashlib.sha256(t.encode()).hexdigest()[:16] for t in texts]
    assert got == LOWERED_BEFORE[cell_name]
