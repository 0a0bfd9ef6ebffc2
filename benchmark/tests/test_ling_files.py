"""The Ling-3.0-flash-VL files (configuration, model, mix, metrics): the
catalog's keys as run, the parameter count at the published widths from
shapes alone, the work functions against a hand count, the program's
trees, the rehearsal of the cell on the CPU, and the readers of the new
metrics."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import resolve

CELL = "ling-3.0-flash-vl.serve.long-decode"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("kda_decode_device_share.serve", "kda_decode_roofline.serve",
               "grouped_matmul_device_share.serve",
               "grouped_matmul_roofline.serve",
               "expert_rows_here_share.serve", "expert_load_peak.serve")


@pytest.fixture(scope="module")
def cell():
    return resolve.Cell(CELL)


def test_configuration_holds_the_source_as_run(cell):
    cfg = cell.config
    assert cfg["reduced"] == cell.config_entry["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "num_experts",
        "vocab_size"]
    assert len(cfg["source"]) <= 200
    assert cfg["source"] == cell.config_entry["source"]
    # no width is cut; the router keeps its published width, groups and top-8
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_attention_heads"],
            cfg["head_dim"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["n_group"], cfg["topk_group"],
            cfg["router_width"]) == (2560, 6144, 768, 32, 128, 512, 128, 64,
                                     128, 768, 8, 8, 4, 512)
    assert cfg["experts_held"] == [0, cfg["num_experts"]] == [0, 128]
    s = cell.model.sizes(cfg)
    assert s["kinds"] == ["kda", "kda", "kda", "kda", "latent_attention",
                          "kda", "kda"]
    assert cfg["layers_run"] == [0, 2, 3, 4, 5, 6, 7]
    # the floors: a whole period of six after the leading dense layer, at
    # least 8 experts, at least an eighth of the vocabulary
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert cfg["vocab_size"] * 4 == cfg["published"]["vocab_size"]
    for key in ("layer_pattern", "layers", "vision_tower", "kda", "mla",
                "use_qk_norm", "gate", "router", "expert_bias",
                "swiglu_limit", "untied_head", "context", "init",
                "multi_token_prediction"):
        assert cfg["assumed"][key]
    assert "four chips share each layer" in cfg["deployment"]
    assert all(cfg["expert_swiglu_limit_list"][i] == 0
               and cfg["share_expert_swiglu_limit_list"][i] == 0
               for i in cfg["layers_run"])
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    row = [json.loads(l) for l in open(CATALOG)
           if '"Ling-3.0-flash-VL"' in l][0]
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key


def test_parameters_held_from_shapes_alone(cell):
    """5.23 B, 10.5 GB in bfloat16: a delta-rule mixer 63.0 M, the latent
    mixer 32.0 M, the dense MLP 47.2 M, an expert layer's 128 held experts
    755.0 M with its router 1.3 M and shared expert 5.9 M, embedding and
    head slices 100.6 M each."""
    m, cfg = cell.model, cell.config
    shapes = m.param_shapes(cfg)
    count = lambda t: sum(int(np.prod(s)) for s in jax.tree.leaves(
        t, is_leaf=lambda x: isinstance(x, tuple)))
    D, C, F, Fe = 2560, 4096, 6144, 768
    kda = D * (3 * C + 2 * C + 32) + 4 * 3 * C + C + 32 + 128 + C * D
    mla = D * (32 * 192 + 576 + 32) + 192 + 512 + 64 + 512 * 32 * 256 \
        + 32 * 128 * D
    moe = 512 * D + 512 + 128 * 3 * D * Fe + 3 * D * 768
    assert count(shapes["layer0"]) == kda + 3 * D * F + 2 * D
    assert count(shapes["layer1"]) == kda + moe + 2 * D
    assert count(shapes["layer4"]) == mla + moe + 2 * D
    assert count(shapes["embed"]) == count(shapes["head"]) == 39296 * D
    assert m.param_count(cfg) == cfg["parameters_held"] == 5_231_790_272
    held = sum(int(np.prod(s)) * d.itemsize for s, d in zip(
        jax.tree.leaves(shapes, is_leaf=lambda x: isinstance(x, tuple)),
        jax.tree.leaves(m.param_dtypes(cfg))))
    assert 10.4e9 < held < 10.5e9             # two thirds of the chip


def test_work_by_hand(cell):
    m, cfg, mix = cell.model, cell.config, cell.traffic
    D, C, F, Fe, V, H = 2560, 4096, 6144, 768, 39296, 32
    kda = 2 * D * (5 * C + H) + 2 * C * D + 2 * 4 * 3 * C + 8 * H * 128 * 128
    mla = 2 * D * (H * 192 + 576 + H) + 2 * 512 * H * 256 + 2 * H * 128 * D
    moe = 2 * D * 512 + 6 * D * 768 + 2.0 * 6 * D * Fe    # two expert-rows
    per_token = 6 * kda + mla + 6 * D * F + 6 * moe + 2 * D * V
    T = 3000
    assert m.forward_flops(cfg, [T]) == pytest.approx(
        per_token + 2 * H * (192 + 128) * T)
    assert m.held_share(cfg) == 2.0
    k = m.kernel_work(cfg, mix, "kda_decode")
    assert k["bytes_per_row"] == 2.0 * H * 128 * 128 * 4
    assert (k["layers"], k["bytes_per_call"]) == (1, 0.0)
    g = m.kernel_work(cfg, mix, "grouped_matmul")
    assert g["flops_per_row"] == 2.0 * D * Fe
    assert g["bytes_per_expert"] == D * Fe * 2
    assert (g["calls_per_layer"], g["layers"]) == (3, 6)


def test_trees_match_the_program(cell):
    m = cell.model
    cfg, _ = cell.sized(True)
    params = m.make_params(cfg, 3)
    spec = jax.ShapeDtypeStruct((1, cfg["n_positions"]), jnp.int32)
    model = m.program_model(cfg, params, spec)       # raises on a mismatch
    assert model.weights() is params
    dtypes = {str(a.dtype) for a in jax.tree.leaves(params)}
    assert dtypes == {"bfloat16", "float32"}
    assert params["layer1"]["ffn"]["router_weight"].dtype == jnp.float32
    assert params["layer1"]["ffn"]["w1"].dtype == jnp.bfloat16
    again = m.make_params(cfg, 3)
    assert all(bool((a == b).all()) for a, b in zip(
        jax.tree.leaves(params), jax.tree.leaves(again)))


def test_the_mix_is_the_issues(cell):
    mix = cell.traffic
    assert mix["driver"] == "closed_loop_generate"
    assert mix["engine"] == {"decode_slots": 32, "decode_max_len": 8192,
                             "kv_cache": "paged", "kv_block_size": 16,
                             "kv_blocks": 16384, "prefill_chunk": 512}
    assert mix["clients"] == 32 and mix["check_requests"] == 6
    assert mix["control"] == "fp8" and mix["faults"] == ["token_altered"]
    # the warm-up's prompts span at least three chunks
    assert mix["warmup"]["prompt_tokens"] > 2 * mix["engine"]["prefill_chunk"]
    # every slot can reach its full length: the pool is never exhausted
    e = mix["engine"]
    assert e["kv_blocks"] * e["kv_block_size"] \
        >= e["decode_slots"] * e["decode_max_len"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_every_new_metric_names_a_reader_that_loads(cell, name):
    entry = [m for m in cell.bench["per_layer"] if m["name"] == name][0]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "serve_tokens_per_s" and entry["unit"] == "%"
    spec = cell.metric_file(name)
    assert spec["layer"] == entry["layer"]
    assert callable(cell.reader(spec["reader"]).read)
    work = spec["args"].get("work")
    if work:
        assert cell.model.kernel_work(cell.config, cell.traffic, work)


def test_every_metric_of_the_cell_moves_one_it_reports(cell):
    """The cell reports the tokens a second and the set-up; its tails
    spread too widely between seeds to be held to their bounds (PERF.md
    section 6, PR 33), so the metrics that move them do not list it."""
    reported = {m["name"] for m in cell.metrics("end_to_end")}
    assert reported == {"serve_tokens_per_s", "setup_s"}
    layered = cell.metrics("per_layer")
    assert len(layered) == 12
    assert all(m["moves"] in reported for m in layered)


def test_touched_roofline_reader_on_known_ticks(cell, monkeypatch):
    """Two ticks, a decode tick bound by the weights of the experts
    touched and a chunk tick bound by FLOPs, against a hand count."""
    from types import SimpleNamespace as NS

    reader = cell.reader("grouped_touched_roofline")
    work = cell.model.kernel_work(cell.config, cell.traffic,
                                  "grouped_matmul")
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ticks = [{"rows_here": 6 * 64, "experts_touched": 6 * 50},
             {"rows_here": 6 * 65536, "experts_touched": 6 * 128}]
    recs = [NS(name="moe_load", start_ns=10 + i, attrs=t)
            for i, t in enumerate(ticks)]
    monkeypatch.setattr(reader.spans, "window",
                        lambda env, fence: (recs, (0, 100), 0))
    monkeypatch.setattr(reader.spans, "named",
                        lambda recs, name: [r for r in recs
                                            if r.name == name])
    plane = NS(matching=lambda ev: list(range(36)),
               op_dur=np.full(36, 2e6))              # 36 events of 2 ms
    env = {"planes": [plane], "model": cell.model, "config": cell.config,
           "mix": cell.traffic, "peaks": peaks}
    decode = 18 * (work["bytes_per_row"] * 64
                   + work["bytes_per_expert"] * 50) / 819e9
    chunk = 18 * work["flops_per_row"] * 65536 / 197e12
    assert work["flops_per_row"] * 64 / 197e12 < decode / 18
    want = 100 * ((decode + chunk) / 2) * (36 / 18) / (36 * 2e-3)
    got = reader.read(env, cell.metric_file(
        "grouped_matmul_roofline.serve")["args"])
    assert got == pytest.approx(want) and 0 < got < 100


def test_peak_over_mean_reader_on_known_ticks(cell, monkeypatch):
    """A decode tick of 64 rows over the 128 held (mean half a row, the
    busiest expert 3 in each of 6 layers) reads 600; the span outside the
    window is left out."""
    from types import SimpleNamespace as NS

    reader = cell.reader("span_peak_over_mean")
    ticks = [{"rows_here": 6 * 64, "rows_busiest_expert": 6 * 3},
             {"rows_here": 6 * 64, "rows_busiest_expert": 6 * 3},
             {"rows_here": 1, "rows_busiest_expert": 1000}]
    recs = [NS(name="moe_load", start_ns=10 + 100 * i, attrs=t)
            for i, t in enumerate(ticks)]
    monkeypatch.setattr(reader.spans, "window",
                        lambda env, fence: (recs, (0, 150), 0))
    monkeypatch.setattr(reader.spans, "named",
                        lambda recs, name: [r for r in recs
                                            if r.name == name])
    args = cell.metric_file("expert_load_peak.serve")["args"]
    assert reader.read({"config": cell.config}, args) \
        == pytest.approx(100 * 3 / 0.5)
    monkeypatch.setattr(reader.spans, "window", lambda env, fence: None)
    assert reader.read({"config": cell.config}, args) is None


def test_the_rehearsal_passes_on_the_cpu():
    """``run.py --workload <the cell> --rehearse``: the whole path at the
    files' toy sizes (bfloat16 weights, chunked prefill over three
    chunks, both kinds of state, the reference's replay)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(resolve.BENCH_DIR, "run.py"),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "2",
         "--rehearse"], env=env, capture_output=True, text=True,
        timeout=1500)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] == "passed", line
    assert {c["name"] for c in line["checks"]} == {
        "logit_gap_max", "logit_gap_mean", "requests_failed"}
