"""The LFM2-8B-A1B files (configuration, model, mix): the catalog's keys as
run, the parameter count at the published widths from shapes alone, the
work functions against a hand count, and the program's trees."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import resolve

CELL = "lfm2-8b-a1b.train.seq4096"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def cell():
    return resolve.Cell(CELL)


def test_configuration_holds_the_source_as_run(cell):
    cfg = cell.config
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "num_experts", "vocab_size"]
    # no width is cut; the router keeps its published width and top-4
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["num_experts_per_tok"],
            cfg["router_width"]) == (2048, 7168, 1792, 32, 8, 4, 32)
    assert cfg["experts_held"] == [0, cfg["num_experts"]] == [0, 8]
    kinds = [cfg["layer_types"][i] for i in cfg["layers_run"]]
    assert kinds == ["conv", "full_attention", "conv", "conv", "conv"]
    assert len(kinds) == cfg["num_hidden_layers"]
    # the floors: a whole period of four after the leading dense layer,
    # 8 experts, an eighth of the vocabulary
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    row = [json.loads(l) for l in open(CATALOG)
           if '"LFM2-8B-A1B"' in l][0]
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key


def test_parameters_held_from_shapes_alone(cell):
    """507.8 M: layer 0 60.8 M, layer 2 98.6 M, layers 3-5 104.9 M each,
    the embedding slice 33.6 M (tied)."""
    m, cfg = cell.model, cell.config
    shapes = m.param_shapes(cfg)
    count = lambda t: sum(int(np.prod(s)) for s in jax.tree.leaves(
        t, is_leaf=lambda x: isinstance(x, tuple)))
    D, F, Fe = 2048, 7168, 1792
    conv = D * 3 * D + 3 * D + D * D
    attn = D * (D + 2 * 512) + 2 * 64 + D * D
    moe = 32 * D + 32 + 8 * 3 * D * Fe
    assert count(shapes["layer0"]) == conv + 3 * D * F + 2 * D
    assert count(shapes["layer1"]) == attn + moe + 2 * D
    assert count(shapes["layer2"]) == conv + moe + 2 * D
    assert count(shapes["embed"]) == 16384 * D
    assert m.param_count(cfg) == 507_820_288
    assert 16 * m.param_count(cfg) > 8.1e9       # over half the chip


def test_work_by_hand(cell):
    m, cfg, mix = cell.model, cell.config, cell.traffic
    D, F, Fe, V, T = 2048, 7168, 1792, 16384, 4096
    conv = 2 * D * 3 * D + 2 * D * D + 6 * D
    attn = 2 * D * 3072 + 2 * D * D
    moe = 2 * D * 32 + 1.0 * 6 * D * Fe          # one expert-row a token
    per_token = conv + 6 * D * F + attn + moe + 3 * (conv + moe) + 2 * D * V
    one = m.forward_flops(cfg, [T])              # a token with T keys
    assert one == pytest.approx(per_token + 4 * D * T)
    step = m.train_step_flops(cfg, 4, T)
    assert step == pytest.approx(
        3 * (4 * T * per_token + 4 * D * 4 * T * (T + 1) / 2))
    assert step / (4 * T) == pytest.approx(1.25e9, rel=0.01)
    assert m.held_share(cfg) == 1.0
    a = m.kernel_work(cfg, mix, "attention_fwd")
    assert a["flops"] == 4.0 * 64 * 32 * T * (T + 1) / 2
    assert a["bytes"] == 4.0 * 32 * T * 64 * 2
    g = m.kernel_work(cfg, mix, "grouped_matmul")
    assert g["flops_per_row"] == 2.0 * D * Fe
    assert (g["calls_per_layer"], g["layers"]) == (12, 4)
    ce = m.kernel_work(cfg, mix, "cross_entropy_bwd")
    assert ce["bytes"] == 2 * 4 * 4 * T * V


def test_trees_match_the_program(cell):
    from bigdl_tpu.models.lfm2 import LFM2

    m = cell.model
    cfg, _ = cell.sized(True)
    s = m.sizes(cfg)
    model = LFM2(s["V"], s["D"], s["kinds"], s["dense"], s["F"], s["Fe"],
                 s["H"], s["Hkv"], s["E"], s["k"],
                 experts_held=(s["first"], s["held"]))
    spec = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    p, state = jax.eval_shape(lambda k: model.setup(k, spec),
                              jax.random.key(0))
    assert jax.tree.map(lambda a: a.shape, p) == m.param_shapes(cfg)
    assert set(state) == {"moe_load"}


def test_units_keep_an_experts_matrices_together(cell):
    m = cell.model
    cfg, _ = cell.sized(True)
    params = m.make_params(cfg, 5)
    from harness import compare

    units = compare.flatten_units(jax.jit(m.unit_sq_norms)(params))
    assert "layer1.ffn.expert[0]" in units and "layer1.op.k" in units
    total = sum(v * v for v in units.values())
    want = sum(float(jnp.sum(jnp.square(a)))
               for a in jax.tree.leaves(params))
    assert total == pytest.approx(want, rel=1e-5)
