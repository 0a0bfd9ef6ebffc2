"""The LFM2 hybrid model's layers (gated short convolution, grouped-query
attention with q/k norm and rotary positions, gated MLP, dropless mixture
of experts), its grouped matrix product and the whole model, each against
the plain reference the benchmark keeps (``benchmark/models/lfm2-8b-a1b.py``:
``jax.numpy``, float32), on seeded random weights at small sizes.

Tolerances: everything here runs in float32 at matmul precision
``highest`` (tests/conftest.py), so program and reference differ by the
order of float32 sums only: 1e-5 relative on outputs, 1e-4 on gradients
(sums over hundreds of rows).  The two bfloat16 comparisons say so."""

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

from bigdl_tpu.nn import (DroplessMoE, GatedMLP, GatedShortConv,  # noqa: E402
                          GroupedQueryAttention)
from bigdl_tpu.ops.grouped_matmul import (buffer_rows, group_layout,  # noqa: E402
                                          grouped_matmul,
                                          grouped_matmul_reference)

CELL = "lfm2-8b-a1b.train.seq4096"
TOY = {"hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2,
       "intermediate_size": 96, "moe_intermediate_size": 32,
       "router_width": 8, "num_experts": 8, "experts_held": [0, 8],
       "num_experts_per_tok": 2, "vocab_size": 128}


@pytest.fixture(scope="module")
def ref():
    """The benchmark's model file and its sizes at a toy width (8 query
    heads over 2 KV heads: 4 query heads a KV head, as published)."""
    cell = resolve.Cell(CELL)
    cfg, _ = cell.sized(True, (TOY, {}))
    return cell.model, cell.model.sizes(cfg)


def close(a, b, rel):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-30)
    assert np.abs(a - b).max() <= rel * scale, np.abs(a - b).max() / scale


def tree_close(a, b, rel):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        close(x, y, rel)


def seeded(module, shape, seed=0):
    params, _ = module.setup(jax.random.key(seed), None)
    x = jax.random.normal(jax.random.key(seed + 1), shape, jnp.float32)
    return params, x


def against(module, plain, params, x, rel=1e-5, grad_rel=1e-4):
    """Output and gradients (wrt parameters and input) of ``module``
    against the plain function ``plain(x, params)``."""
    probe = jnp.cos(jnp.arange(x.shape[-1], dtype=jnp.float32))
    run = lambda p, x: module.apply(p, (), x, training=True)[0]
    close(run(params, x), plain(x, params), rel)
    got = jax.grad(lambda p, x: (run(p, x) * probe).sum(), (0, 1))(params, x)
    want = jax.grad(lambda p, x: (plain(x, p) * probe).sum(), (0, 1))(
        params, x)
    tree_close(got, want, grad_rel)


# ------------------------------------------------------------------ (a) #

def test_gated_short_conv_against_reference(ref):
    mod, s = ref
    layer = GatedShortConv(64, 3)
    params, x = seeded(layer, (2, 24, 64))
    against(layer, lambda x, p: mod._conv(x, p, s, "f32"), params, x)


def test_gated_short_conv_is_causal():
    """The output at ``t`` is unchanged by inputs after ``t``."""
    layer = GatedShortConv(64, 3)
    params, x = seeded(layer, (1, 16, 64))
    later = x.at[:, 9:].set(jax.random.normal(jax.random.key(7),
                                              (1, 7, 64)))
    a = layer.apply(params, (), x)[0]
    b = layer.apply(params, (), later)[0]
    np.testing.assert_array_equal(np.asarray(a[:, :9]), np.asarray(b[:, :9]))
    assert np.abs(np.asarray(a[:, 9:] - b[:, 9:])).max() > 1e-3


@pytest.mark.parametrize("how", ["whole", "by-kv-head", "flash-interpret"])
def test_grouped_query_attention_against_reference(ref, how):
    """8 query heads over 2 KV heads (4 a KV head), q/k norm, rotary
    positions; in one call, one (row, KV head) pair a call, and through
    the flash kernel in interpreter mode (whose online softmax sums in
    another order: 1e-4 on the output)."""
    mod, s = ref
    layer = GroupedQueryAttention(
        64, 8, 2, rope_theta=1e6,
        kv_heads_per_call=1 if how == "by-kv-head" else None,
        use_flash="interpret" if how == "flash-interpret" else "never")
    params, x = seeded(layer, (2, 32, 64))
    params["q_norm"] = 1 + 0.1 * jax.random.normal(jax.random.key(3), (8,))
    params["k_norm"] = 1 + 0.1 * jax.random.normal(jax.random.key(4), (8,))
    plain = lambda x, p: mod._attention(x, p, s, "f32")
    flash = how == "flash-interpret"
    against(layer, plain, params, x, rel=1e-4 if flash else 1e-5)


def test_gated_mlp_against_reference(ref):
    mod, _ = ref
    layer = GatedMLP(64, 96)
    params, x = seeded(layer, (2, 24, 64))
    against(layer, lambda x, p: mod._gated_mlp(x, p["w1"], p["w3"], p["w2"],
                                               "f32"), params, x)


def moe_params(layer, seed=0, bias=None):
    params, _ = layer.setup(jax.random.key(seed), None)
    params["router_weight"] = jax.random.normal(
        jax.random.key(seed + 5), params["router_weight"].shape) / 8.0
    params["router_bias"] = 0.2 * jax.random.normal(
        jax.random.key(seed + 6), params["router_bias"].shape) \
        if bias is None else bias
    return params


def share_of(params, first, count):
    return {**params, **{k: params[k][first:first + count]
                         for k in ("w1", "w3", "w2")}}


@pytest.mark.parametrize("kernel", ["never", "interpret"])
def test_dropless_moe_against_reference(ref, kernel):
    """Experts 2-5 of 8 held: plain XLA products and the Pallas kernel in
    interpreter mode."""
    mod, s = ref
    layer = DroplessMoE(64, 32, 8, 2, experts_held=(2, 4),
                        use_kernel=kernel)
    params = share_of(moe_params(DroplessMoE(64, 32, 8, 2)), 2, 4)
    x = jax.random.normal(jax.random.key(1), (2, 24, 64))
    held = {**s, "first": 2, "held": 4}
    against(layer, lambda x, p: mod._mixture(x, p, held, "f32"), params, x)


# ------------------------------------------------------------------ (b) #

def test_the_four_shares_add_up_to_the_whole_layer(ref):
    """Experts 0-1, 2-3, 4-5, 6-7 of one expert layer, each as a chip
    computes its share, add up to the uncut reference's whole layer."""
    mod, s = ref
    whole = moe_params(DroplessMoE(64, 32, 8, 2))
    x = jax.random.normal(jax.random.key(1), (2, 24, 64))
    total = 0
    for first in (0, 2, 4, 6):
        layer = DroplessMoE(64, 32, 8, 2, experts_held=(first, 2))
        total = total + layer.apply(share_of(whole, first, 2), (), x)[0]
    uncut = {**s, "first": 0, "held": 8}
    close(total, mod._mixture(x, whole, uncut, "f32"), 1e-5)


# ------------------------------------------------------------------ (c) #

@pytest.mark.parametrize("kernel", ["never", "interpret"])
def test_no_token_is_dropped_under_the_worst_load(ref, kernel):
    """A bias makes EVERY token choose held expert 1 (half of all
    assignments at top-2: the most one expert can get) and keeps held
    expert 3 from being chosen at all: output and gradients equal the
    reference's, so nothing was dropped and an empty group is handled."""
    mod, s = ref
    bias = jnp.zeros((8,)).at[1].set(10.0).at[3].set(-10.0)
    layer = DroplessMoE(64, 32, 8, 2, experts_held=(0, 4),
                        use_kernel=kernel)
    params = share_of(moe_params(DroplessMoE(64, 32, 8, 2), bias=bias), 0, 4)
    x = jax.random.normal(jax.random.key(1), (2, 24, 64))
    _, state = layer.apply(params, (), x)
    routed, here, busiest, mean = np.asarray(state["moe_load"])
    assert (routed, busiest, mean) == (96, 48, here // 4) \
        and 48 <= here <= 96
    idx, _ = layer.route(params, x.reshape(48, 64))
    assert (np.asarray(idx) == 1).sum() == 48
    assert (np.asarray(idx) == 3).sum() == 0
    held = {**s, "first": 0, "held": 4}
    against(layer, lambda x, p: mod._mixture(x, p, held, "f32"), params, x)


# ------------------------------------------------------------------ (d) #

@pytest.mark.parametrize("transpose_rhs", [False, True])
def test_grouped_matmul_against_einsum(transpose_rhs):
    """Interpreter mode, uneven groups with an empty one among them and an
    empty one last: forward and both gradients, on the rows in use."""
    block = 8
    sizes = jnp.array([5, 0, 16, 3, 0], jnp.int32)
    rows = buffer_rows(40, 5, block)
    offsets, tile_group, tiles = group_layout(sizes, rows, block)
    assert list(np.asarray(offsets)) == [0, 8, 8, 24, 32] and tiles == 4
    row = jnp.arange(rows)[:, None]
    used = ((row >= offsets[None]) & (row < (offsets + sizes)[None])).any(1)
    lhs = jax.random.normal(jax.random.key(0), (rows, 128)) * used[:, None]
    shape = (5, 256, 128) if transpose_rhs else (5, 128, 256)
    rhs = jax.random.normal(jax.random.key(1), shape)
    probe = used[:, None] * jnp.cos(jnp.arange(256.0))

    def kernel(l, r):
        return grouped_matmul(l, r, sizes, block_rows=block,
                              transpose_rhs=transpose_rhs, interpret=True)

    def plain(l, r):
        return grouped_matmul_reference(l, r, sizes, block, transpose_rhs)

    keep = lambda a: np.where(np.asarray(used)[:, None], np.asarray(a), 0)
    close(keep(kernel(lhs, rhs)), plain(lhs, rhs), 1e-6)
    got = jax.grad(lambda l, r: (kernel(l, r) * probe).sum(), (0, 1))(
        lhs, rhs)
    want = jax.grad(lambda l, r: (plain(l, r) * probe).sum(), (0, 1))(
        lhs, rhs)
    close(keep(got[0]), want[0], 1e-5)
    close(got[1], want[1], 1e-5)
    assert not np.asarray(got[1][1]).any() and not np.asarray(got[1][4]).any()


# ------------------------------------------------------------------ (e) #

def session(seed=3, mix=None):
    cell = resolve.Cell(CELL)
    s = cell.driver.Session(cell, seed, True, ({}, mix or {}))
    s.make_data()
    return s


@pytest.mark.parametrize("tiles,asked", [
    # training (512 rows of LFM2's 2048 x 896 and 1792 x 1024 weight tiles)
    (((512, 2048), (2048, 896), (512, 896)), True),
    (((512, 1792), (1792, 1024), (512, 1024)), True),
    # generation (16 to 256 rows of Ling's 2560 x 256 and 768 x 512 tiles)
    (((16, 2560), (2560, 256), (16, 256)), False),
    (((256, 2560), (2560, 256), (256, 256)), False),
    (((256, 768), (768, 512), (256, 512)), False)])
def test_grouped_matmul_claims_vmem_only_for_tiles_that_need_it(tiles,
                                                                asked):
    """A limit over the compiler's default re-lays the VMEM of the whole
    program around the call (the chunk step of ``models/ling.py`` never
    returned on the chip under it, PERF.md section 6, PR 33): only tiles
    that do not fit under the default ask for it."""
    from bigdl_tpu.ops import grouped_matmul as gm

    limit = gm._vmem_limit(*tiles, itemsize=2)
    assert limit == (gm._VMEM_LIMIT if asked else None)


def test_whole_model_loss_and_gradients_against_reference():
    """The rehearsal sizes (a conv layer with the dense FFN, an attention
    layer and a conv layer with experts), float32: loss to 1e-5, every
    parameter's gradient to 1e-3 of the largest (three layers deep)."""
    s = session()
    cfg, mod = s.cfg, s.model_mod
    params = mod.make_params(cfg, 3)
    x, y = (jnp.asarray(a) for a in s.first[0])
    model = mod.program_model(cfg, params, jax.ShapeDtypeStruct(x.shape,
                                                                x.dtype))
    criterion, _ = mod.program_training(cfg, s.mix)

    def loss(p):
        out, state = model.apply(p, model.state(), x, training=True)
        return criterion.apply(out.astype(jnp.float32), y), state

    (got, state), grads = jax.value_and_grad(loss, has_aux=True)(params)
    want, ref_grads = jax.value_and_grad(mod.reference_loss)(params, (x, y),
                                                             cfg)
    assert abs(float(got) - float(want)) <= 1e-5 * float(want)
    tree_close(grads, ref_grads, 1e-3)
    assert set(state) == {"moe_load"}       # the expert layers' counts
    bias = params["layer1"]["ffn"]["router_bias"]
    assert float(jnp.abs(bias).max()) > 0           # a seeded constant
    assert not np.asarray(grads["layer1"]["ffn"]["router_bias"]).any()


@pytest.fixture(scope="module")
def optimized():
    """Three steps of ``Optimizer.optimize()`` in bfloat16 compute at the
    rehearsal sizes, and the plain reference's three Adam steps."""
    from bigdl_tpu.observability.spans import recorder

    s = session()
    s.build()
    before = len(recorder().snapshot())
    s.run(0.2)
    loads = [r for r in recorder().snapshot()[before:]
             if r.name == "moe_load"]
    got = s.program_reading()
    s.free()
    return s, got, s.reference(), loads


def test_three_optimizer_steps_against_the_reference(optimized):
    """What ``run.py --workload lfm2-8b-a1b.train.seq4096 --rehearse``
    checks.  bfloat16 compute against the float32 reference at toy widths
    (64 wide: rounding is not averaged down as at 2048): losses to 1e-3,
    the worst unit's gradient norm to 0.1, its change to 0.05 (CPU
    readings, seeds 1-4: at most 0.03 and 0.012)."""
    s, got, want, _ = optimized
    by = {c["name"]: c["value"] for c in s.compare(got, want)}
    assert by["loss1"] < 1e-3 and by["loss2"] < 1e-3 and by["loss3"] < 1e-3
    assert by["grad_norm"] < 0.1 and by["change_norm"] < 0.05, by


def test_the_fp8_control_reads_worse_than_the_program(optimized):
    """At 64 wide the norms by unit cannot tell bfloat16 from e4m3 (the
    cell's limits are set from chip readings at 2048 wide); the first
    loss can: the control's is an order of magnitude further off."""
    s, got, want, _ = optimized
    control = s.compare(s.reference(mode="fp8"), want)
    by = {c["name"]: c["value"] for c in control}
    mine = {c["name"]: c["value"] for c in s.compare(got, want)}
    assert by["loss1"] > 5 * mine["loss1"], (by, mine)


# ------------------------------------------------------------------ (f) #

def test_moe_load_span_adds_up(optimized):
    """One ``moe_load`` span a step under ``step``: ``rows_here <=
    rows_routed = tokens x k x expert layers``, the busiest held expert
    between the mean and all of them."""
    s, _, _, loads = optimized
    assert len(loads) >= s.first_steps
    tokens = s.batch * s.row_shape[0]
    layers = s.model_mod.expert_layers(s.cfg)
    for r in loads:
        a = r.attrs
        assert a["rows_routed"] == tokens * s.cfg["num_experts_per_tok"] \
            * layers
        assert 0 < a["rows_here"] <= a["rows_routed"]
        assert a["rows_mean_expert"] <= a["rows_busiest_expert"] \
            <= a["rows_here"]
        # whole rows: each layer's share is rounded down
        assert 0 <= a["rows_here"] / s.cfg["num_experts"] \
            - a["rows_mean_expert"] < layers


def test_router_matrix_stays_float32_under_a_compute_dtype():
    """``_cast_params`` rounds every matrix to the compute dtype but the
    leaves a module names; a model without such a module names none
    (GPT-2's compiled step is as it was)."""
    from bigdl_tpu.nn.attention import TransformerLM
    from bigdl_tpu.optim.train_step import (_cast_params,
                                            full_precision_param_names)

    layer = DroplessMoE(64, 32, 8, 2)
    assert full_precision_param_names(layer) == {"router_weight"}
    assert full_precision_param_names(TransformerLM(32, 16, 2, 1)) == set()
    params = moe_params(layer)
    cast = _cast_params({"ffn": params}, jnp.bfloat16,
                        {"router_weight"})["ffn"]
    assert cast["router_weight"].dtype == jnp.float32
    assert cast["w1"].dtype == jnp.bfloat16
    assert cast["router_bias"].dtype == jnp.float32
    assert _cast_params(params, jnp.bfloat16)["router_weight"].dtype \
        == jnp.bfloat16
