"""The GigaChat3.5-432B-A28B files (configuration, model, mix, metrics): the
source's keys as run, the parameter count at the published widths from
shapes alone, the cut held to the floors and the pool to "never
exhausted", the work functions against a hand count, the program's trees,
the rehearsal of the cell on the CPU, the tool the cell's limits are read
with, and the reader of the new roofline.  The source's numbers are
pinned here as its config.json gives them, so the check runs wherever the
repo does."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import resolve

CELL = "gigachat3.5-432b-a28b.serve.long-reasoning"
NEW_METRICS = ("gated_delta_decode_roofline.serve",
               "gated_delta_decode_device_share.serve",
               "gated_delta_prefill_device_share.serve")
#: the accepted serving metrics whose lists the cell joins
JOINED = ("decode_tick_ms.serve", "step_mfu.serve",
          "device_idle_share.serve", "decode_slot_fill.serve",
          "tick_host_ms.serve", "idle_attributed_share.serve",
          "launch_ahead_share.serve")
#: the source's values of the keys ``reduced`` names
PUBLISHED = {"num_hidden_layers": 40, "first_k_dense_replace": 3,
             "n_routed_experts": 256, "vocab_size": 128256,
             "num_nextn_predict_layers": 2}
D, F, Fe, V, H = 7168, 18432, 2048, 16032, 64
#: the delta rule's q, k, v widths and a value head's state
CQ, CV, HV, HD = 4096 + 4096 + 8192, 8192, 64, 128


@pytest.fixture(scope="module")
def cell():
    return resolve.Cell(CELL)


def count(tree):
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple)))


def test_configuration_holds_the_source_as_run(cell):
    cfg = cell.config
    assert cfg["reduced"] == cell.config_entry["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert len(cfg["source"]) <= 200
    assert cfg["source"] == cell.config_entry["source"]
    # no width is cut; the router keeps its published width and top-8
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["qk_head_dim"], cfg["v_head_dim"],
            cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
            cfg["linear_conv_kernel_dim"], cfg["n_shared_experts"],
            cfg["num_experts_per_tok"], cfg["router_width"]) == (
        D, F, Fe, H, 1536, 512, 128, 64, 192, 128, 32, 64, 128, 128, 4, 1, 8,
        256)
    assert cfg["experts_held"] == [0, cfg["n_routed_experts"]] == [0, 16]
    # the source's other numbers, as its config.json gives them
    assert {k: v for k, v in cfg["published"].items() if k != "parameters"} \
        == PUBLISHED
    assert (cfg["max_position_embeddings"], cfg["routed_scaling_factor"],
            cfg["n_group"], cfg["topk_group"], cfg["rms_norm_eps"],
            cfg["rope_theta"], cfg["swiglu_limit"],
            cfg["layernorm_gating_weight"], cfg["linear_sigmoid_gate_scale"],
            cfg["linear_attn_o_norm_eps"], cfg["num_key_value_heads"],
            cfg["tie_word_embeddings"], cfg["norm_topk_prob"]) == (
        262144, 2.5, 1, 1, 1e-6, 100000, 10, 2, 2, 1e-6, 64, False, True)
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 32768,
        "type": "yarn"}
    assert cfg["full_attention_layers"] == list(range(3, 40, 4))
    # the floors: a whole period after the leading dense layer and four
    # layers or more after it, 8 experts or more, an eighth of the ids
    s = cell.model.sizes(cfg)
    assert cfg["layers_run"] == [0, 4, 5, 6, 7]
    assert s["kinds"] == ["gated_delta"] * 4 + ["latent_attention"]
    period = [i for i in cfg["full_attention_layers"] if i > 0][:2]
    assert cfg["layers_run"][1:] == list(range(period[0] + 1, period[1] + 1))
    assert len(cfg["layers_run"]) - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["n_routed_experts"] * 16 == cfg["published"][
        "n_routed_experts"]
    assert cfg["num_nextn_predict_layers"] == 0
    for key in ("layer_pattern", "layers", "norm", "gated_delta",
                "gated_delta_output", "attention_gate", "mla", "rope",
                "softmax_scale", "router", "expert_bias", "shared_expert",
                "swiglu_limit", "untied_head", "multi_token_prediction",
                "context", "init"):
        assert cfg["assumed"][key]
    assert "sixteen chips share each layer" in cfg["deployment"]
    assert "accepted" in cfg["compile"]


def test_parameters_held_from_shapes_alone(cell):
    """4.73 B, 9.48 GB in bfloat16: a delta-rule mixer 235.9 M, the latent
    mixer 159.8 M, the dense MLP 396.4 M, an expert layer's 16 held
    experts 704.6 M with its router 1.8 M and shared expert 44.0 M,
    embedding and head slices 114.9 M each; four norms a layer."""
    m, cfg = cell.model, cell.config
    shapes = m.param_shapes(cfg)
    delta = D * (CQ + 2 * HV + CV) + 4 * CQ + HV + HV + HD + D * CV
    mla = D * 1536 + 1536 + H * 192 * 1536 + D * 576 + 512 \
        + H * 256 * 512 + H * 128 * D + D * H * 128
    moe = 256 * D + 256 + 16 * 3 * D * Fe + 3 * D * Fe
    assert (delta, mla) == (235_864_320, 159_844_352)
    assert count(shapes["layer0"]) == delta + 3 * D * F + 4 * D
    for i in (1, 2, 3):
        assert count(shapes[f"layer{i}"]) == delta + moe + 4 * D
    assert count(shapes["layer4"]) == mla + moe + 4 * D
    assert count(shapes["embed"]) == count(shapes["head"]) == V * D
    assert m.param_count(cfg) == cfg["parameters_held"] == 4_731_722_752
    weights, state, pool = m.memory_bytes(cfg, cell.traffic)
    assert 9.47e9 < weights < 9.49e9
    # a slot's four (64, 128, 128) float32 states and convolution tails;
    # the latent row in 640 columns of bfloat16, 1,280 B a token
    assert state == 129 * 4 * (HV * HD * HD * 4 + 3 * CQ * 2)
    assert pool == (65536 + 1) * 16 * 1280
    # what a deployment holds fills the chip: 13.0 of 16 GB before the
    # programs' temporaries
    assert 0.25 * 16e9 < weights + state + pool < 13.1e9


def test_work_by_hand(cell):
    m, cfg, mix = cell.model, cell.config, cell.traffic
    delta = 2 * D * (CQ + 2 * HV + CV) + 2 * CV * D + 2 * 4 * CQ \
        + 8 * HV * HD * HD
    mla = 2 * D * 1536 + 2 * 1536 * H * 192 + 2 * D * 576 \
        + 2 * 512 * H * 256 + 2 * D * H * 128 + 2 * H * 128 * D
    moe = 2 * D * 256 + 6 * D * Fe + 0.5 * 6 * D * Fe    # half an expert-row
    per_token = 4 * delta + mla + 6 * D * F + 4 * moe + 2 * D * V
    T = 3000
    assert m.forward_flops(cfg, [T]) == pytest.approx(
        per_token + 2 * H * (192 + 128) * T)
    assert m.held_share(cfg) == 0.5
    k = m.kernel_work(cfg, mix, "gated_delta_decode")
    assert k["bytes_per_row"] == 2.0 * HV * HD * HD * 4 == 8_388_608
    assert k["flops_per_row"] == 8.0 * HV * HD * HD
    assert (k["layers"], k["bytes_per_call"]) == (1, 0.0)
    g = m.kernel_work(cfg, mix, "grouped_matmul")
    assert g["bytes_per_expert"] == D * Fe * 2
    assert (g["calls_per_layer"], g["layers"]) == (3, 4)


def test_trees_match_the_program(cell):
    m = cell.model
    cfg, _ = cell.sized(True)
    params = m.make_params(cfg, 3)
    spec = jax.ShapeDtypeStruct((1, cfg["n_positions"]), jnp.int32)
    model = m.program_model(cfg, params, spec)       # raises on a mismatch
    assert model.weights() is params
    assert {str(a.dtype) for a in jax.tree.leaves(params)} \
        == {"bfloat16", "float32"}
    for name, dtype in (("router_weight", jnp.float32),
                        ("w1", jnp.bfloat16)):
        assert params["layer1"]["ffn"][name].dtype == dtype
    for name in ("A_log", "dt_bias", "o_norm"):
        assert params["layer1"]["op"][name].dtype == jnp.float32
    assert params["layer4"]["op"]["qa_norm"].dtype == jnp.float32
    again = m.make_params(cfg, 3)
    assert all(bool((a == b).all()) for a, b in zip(
        jax.tree.leaves(params), jax.tree.leaves(again)))


def test_the_mix_fills_the_cell(cell):
    mix = cell.traffic
    assert mix["driver"] == "closed_loop_generate"
    assert mix["engine"] == {"decode_slots": 128, "decode_max_len": 8192,
                             "kv_cache": "paged", "kv_block_size": 16,
                             "kv_blocks": 65536, "prefill_chunk": 512}
    req = mix["requests"]
    assert (req["prompt"], req["output"], req["max_total"]) == (
        {"median": 1024, "sigma": 0.7, "min": 256, "max": 2048},
        {"median": 1536, "sigma": 0.7, "min": 384, "max": 6144}, 8192)
    assert mix["clients"] == 128 and mix["check_requests"] == 6
    # the ramp outlasts most of the first cohort's answers (93% of them
    # end within 90 s at 20 ms a token), so that a window's mix of
    # prefill and decode depends little on the seed's order of sizes
    assert (mix["ramp_seconds"], mix["drain_seconds"]) == (90, 180)
    assert mix["control"] == "fp8" and mix["faults"] == ["token_altered"]
    # the warm-up's prompts span more than two chunks
    assert mix["warmup"]["prompt_tokens"] > 2 * mix["engine"]["prefill_chunk"]
    # every slot can reach its full length: the pool is never exhausted
    e = mix["engine"]
    assert e["kv_blocks"] * e["kv_block_size"] \
        >= e["decode_slots"] * e["decode_max_len"]
    assert cell.chips == 1


@pytest.mark.parametrize("mode", ["fp8", "bf16", "bf16_state", "bf16_scan"])
def test_a_control_differs_from_the_reference(cell, mode):
    """The mix's control, the whole model in bfloat16, and the delta
    rule's recurrence alone lowered (its state kept in bfloat16, or its
    products at bfloat16 inputs): each moves the logits."""
    m = cell.model
    cfg, _ = cell.sized(True, ({"program": {"dtype": "float32"}}, {}))
    params = m.make_params(cfg, 5)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (1, 40)), jnp.int32)
    ref = m.reference_logits(params, tokens, cfg)
    low = m.reference_logits(params, tokens, cfg, mode)
    assert float(jnp.abs(low - ref).max()) > 1e-3 * float(jnp.abs(ref).max())


@pytest.mark.parametrize("name", NEW_METRICS)
def test_every_new_metric_names_a_reader_that_loads(cell, name):
    entry = [m for m in cell.bench["per_layer"] if m["name"] == name][0]
    assert CELL in entry["workloads"]
    assert entry["moves"] == "serve_tokens_per_s" and entry["unit"] == "%"
    spec = cell.metric_file(name)
    assert spec["layer"] == entry["layer"]
    assert callable(cell.reader(spec["reader"]).read)
    work = spec["args"].get("work")
    if work:
        assert cell.model.kernel_work(cell.config, cell.traffic, work)


def test_every_metric_of_the_cell_moves_one_it_reports(cell):
    """The cell reports the tokens a second and the set-up: a closed loop
    of 128 on 128 slots is saturated by construction, and its tails are
    read in PERF.md only, as the other model cells' are."""
    reported = {m["name"] for m in cell.metrics("end_to_end")}
    assert reported == {"serve_tokens_per_s", "setup_s"}
    layered = {m["name"]: m for m in cell.metrics("per_layer")}
    assert set(NEW_METRICS) | set(JOINED) <= set(layered)
    assert all(m["moves"] in reported for m in layered.values())


def test_gated_delta_roofline_reader_on_known_ticks(cell, monkeypatch):
    """Two decode ticks of 120 and 128 live slots, four calls a tick of
    1.6 ms each: memory-bound (a slot's state read and written, 8.39 MB),
    against a hand count."""
    from types import SimpleNamespace as NS

    reader = cell.reader("grouped_roofline")
    recs = [NS(name="decode_prep", start_ns=10 + i, attrs={"rows": r})
            for i, r in enumerate((120, 128))]
    monkeypatch.setattr(reader.spans, "window",
                        lambda env, fence: (recs, (0, 100), 0))
    monkeypatch.setattr(reader.spans, "named",
                        lambda recs, name: [r for r in recs
                                            if r.name == name])
    plane = NS(matching=lambda ev: list(range(8)), op_dur=np.full(8, 1.6e6))
    env = {"planes": [plane], "model": cell.model, "config": cell.config,
           "mix": cell.traffic,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    args = cell.metric_file("gated_delta_decode_roofline.serve")["args"]
    assert args["events"] == ["^kda_decode_step"]
    flops, moved = 8 * HV * HD * HD * 124, 8_388_608 * 124
    assert flops / 197e12 < moved / 819e9
    want = 100 * (moved / 819e9) / 1.6e-3
    assert reader.read(env, args) == pytest.approx(want)
    assert 75 < want < 85


def test_the_rehearsal_passes_on_the_cpu():
    """``run.py --workload <the cell> --rehearse``: the whole path at the
    files' toy sizes (bfloat16 weights, chunked prefill over several
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


def test_the_readings_tool_tells_program_control_and_fault_apart():
    """``tools/serve_readings.py`` at the files' toy sizes: one line for
    the program, one for each control asked and one for the planted fault,
    all through the driver's comparison; the fault reads above the
    program."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(resolve.BENCH_DIR, "tools",
                                      "serve_readings.py"),
         "--workload", CELL, "--seeds", "2147483999", "--controls",
         "fp8,bf16_state", "--faults", "token_altered", "--seconds", "2",
         "--rehearse"], env=env, capture_output=True, text=True,
        timeout=1500)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(x) for x in out.stdout.strip().splitlines()]
    got = {x["what"]: x for x in lines}
    assert list(got) == ["program", "control:fp8", "control:bf16_state",
                         "fault:token_altered"]
    assert got["program"]["tokens_checked"] > 0
    assert got["fault:token_altered"]["logit_gap_max"] \
        > got["program"]["logit_gap_max"]
