"""The kanana-2-30b-a3b files (configuration, model, mix, metrics): the
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

CELL = "kanana-2-30b-a3b.serve.long-prompt"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("latent_decode_roofline.serve",
               "latent_decode_device_share.serve")
#: the accepted metrics whose lists the cell joins.  The four of the expert
#: layer are read in the builder's traced runs (PERF.md section 6, PR 35)
#: and not listed: ``test_ling_files.py`` pins their lists to the Ling cell
JOINED = ("decode_tick_ms.serve", "step_mfu.serve",
          "device_idle_share.serve", "decode_slot_fill.serve",
          "tick_host_ms.serve", "idle_attributed_share.serve")


@pytest.fixture(scope="module")
def cell():
    return resolve.Cell(CELL)


def count(tree):
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple)))


def test_configuration_holds_the_source_as_run(cell):
    cfg = cell.config
    assert cfg["reduced"] == cell.config_entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert len(cfg["source"]) <= 200
    assert cfg["source"] == cell.config_entry["source"]
    # no width is cut; the router keeps its published width and top-6
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["qk_head_dim"], cfg["v_head_dim"],
            cfg["n_shared_experts"], cfg["num_experts_per_tok"],
            cfg["n_group"], cfg["topk_group"], cfg["router_width"]) == (
        2048, 6144, 768, 32, 512, 128, 64, 192, 128, 2, 6, 1, 1, 128)
    assert cfg["experts_held"] == [0, cfg["n_routed_experts"]] == [0, 16]
    assert cfg["rope_interleave"] is True and cfg["q_lora_rank"] is None
    # an eighth of the experts and of the vocabulary, a third of the depth
    assert cfg["n_routed_experts"] * 8 == cfg["published"]["n_routed_experts"]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["num_hidden_layers"] * 3 == cfg["published"][
        "num_hidden_layers"]
    for key in ("mla", "rope", "router", "router_epsilon", "expert_bias",
                "shared_experts", "untied_head", "context", "init"):
        assert cfg["assumed"][key]
    assert "eight chips share each layer, attention data-parallel, " \
        "16 of 48 layers" in cfg["deployment"]
    assert "accepted" in cfg["compile"]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    row = [json.loads(l) for l in open(CATALOG)
           if '"kanana-2-30b-a3b-instruct-2601"' in l][0]
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key


def test_parameters_held_from_shapes_alone(cell):
    """1.80 B, 3.6 GB in bfloat16: a latent mixer 26.35 M, the dense MLP
    37.75 M, an expert layer's 16 held experts 75.5 M with its router
    0.26 M and shared experts 9.44 M, embedding and head slices 32.8 M
    each."""
    m, cfg = cell.model, cell.config
    shapes = m.param_shapes(cfg)
    D, F, Fe = 2048, 6144, 768
    mla = D * (32 * 192 + 576) + 512 + 512 * 32 * 256 + 32 * 128 * D
    moe = 128 * D + 128 + 16 * 3 * D * Fe + 3 * D * 2 * Fe
    assert mla == 26_345_984
    assert count(shapes["layer0"]) == mla + 3 * D * F + 2 * D
    assert count(shapes["layers"]) == 15 * (mla + moe + 2 * D)
    assert count(shapes["embed"]) == count(shapes["head"]) == 16032 * D
    assert m.param_count(cfg) == cfg["parameters_held"] == 1_802_973_056
    held = sum(int(np.prod(s)) * d.itemsize for s, d in zip(
        jax.tree.leaves(shapes, is_leaf=lambda x: isinstance(x, tuple)),
        jax.tree.leaves(m.param_dtypes(cfg))))
    assert 3.6e9 < held < 3.62e9
    # the cache is twice the weights: 24576 blocks (and the trash block)
    # of 16 rows of 640 stored columns in 16 layers
    e = cell.traffic["engine"]
    pool = (e["kv_blocks"] + 1) * e["kv_block_size"] * 640 * 2 * 16
    assert 2.2 * held < pool < 2.3 * held


def test_work_by_hand(cell):
    m, cfg, mix = cell.model, cell.config, cell.traffic
    D, F, Fe, V, H = 2048, 6144, 768, 16032, 32
    mla = 2 * D * (H * 192 + 576) + 2 * 512 * H * 256 + 2 * H * 128 * D
    moe = 2 * D * 128 + 6 * D * 2 * Fe + 0.75 * 6 * D * Fe
    per_token = 16 * mla + 6 * D * F + 15 * moe + 2 * D * V
    T = 5000
    assert m.forward_flops(cfg, [T]) == pytest.approx(
        per_token + 16 * 2 * H * (192 + 128) * T)
    assert m.held_share(cfg) == 0.75
    k = m.kernel_work(cfg, mix, "latent_decode")
    assert k["bytes_per_row"] == 1152.0
    assert k["flops_per_row"] == 2.0 * 32 * (576 + 512)
    assert (k["layers"], k["bytes_per_call"]) == (1, 0.0)
    g = m.kernel_work(cfg, mix, "grouped_matmul")
    assert g["flops_per_row"] == 2.0 * D * Fe
    assert g["bytes_per_expert"] == D * Fe * 2
    assert (g["calls_per_layer"], g["layers"]) == (3, 15)


def test_trees_match_the_program(cell):
    m = cell.model
    cfg, _ = cell.sized(True)
    params = m.make_params(cfg, 3)
    spec = jax.ShapeDtypeStruct((1, cfg["n_positions"]), jnp.int32)
    model = m.program_model(cfg, params, spec)       # raises on a mismatch
    assert model.weights() is params
    assert {str(a.dtype) for a in jax.tree.leaves(params)} \
        == {"bfloat16", "float32"}
    stacked = params["layers"]
    assert stacked["ffn"]["router_weight"].dtype == jnp.float32
    assert stacked["op"]["kv_norm"].dtype == jnp.float32
    assert stacked["ffn"]["w1"].dtype == jnp.bfloat16
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    assert all(a.shape[0] == layers for a in jax.tree.leaves(stacked))
    again = m.make_params(cfg, 3)
    assert all(bool((a == b).all()) for a, b in zip(
        jax.tree.leaves(params), jax.tree.leaves(again)))


def test_the_mix_is_the_issues(cell):
    mix = cell.traffic
    assert mix["driver"] == "closed_loop_generate"
    assert mix["engine"] == {"decode_slots": 32, "decode_max_len": 16384,
                             "kv_cache": "paged", "kv_block_size": 16,
                             "kv_blocks": 24576, "prefill_chunk": 512}
    assert mix["requests"] == {
        "pool": 64,
        "prompt": {"median": 4096, "sigma": 0.8, "min": 512, "max": 14336},
        "output": {"median": 192, "sigma": 0.7, "min": 32, "max": 1024},
        "max_total": 15360, "epochs": 6}
    assert mix["clients"] == 32 and mix["check_requests"] == 6
    assert mix["control"] == "fp8" and mix["faults"] == ["token_altered"]
    # the warm-up's prompts span at least three chunks
    assert mix["warmup"]["prompt_tokens"] > 2 * mix["engine"]["prefill_chunk"]
    assert cell.chips == 1


@pytest.mark.parametrize("mode", ["fp8", "fp8_latent", "bf16"])
def test_a_control_differs_from_the_reference(cell, mode):
    """Each control moves the logits, ``fp8_latent`` (the cached rows
    alone) less than ``fp8`` (every matmul's inputs)."""
    m = cell.model
    cfg, _ = cell.sized(True, ({"program": {"dtype": "float32"}}, {}))
    params = m.make_params(cfg, 5)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (1, 40)), jnp.int32)
    ref = m.reference_logits(params, tokens, cfg)
    err = lambda mode: float(jnp.abs(
        m.reference_logits(params, tokens, cfg, mode) - ref).max())
    assert err(mode) > 1e-5
    if mode == "fp8_latent":
        assert err(mode) < err("fp8")


@pytest.mark.parametrize("name", NEW_METRICS)
def test_every_new_metric_names_a_reader_that_loads(cell, name):
    entry = [m for m in cell.bench["per_layer"] if m["name"] == name][0]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "serve_tokens_per_s" and entry["unit"] == "%"
    spec = cell.metric_file(name)
    assert spec["layer"] == entry["layer"] == "kernels"
    assert callable(cell.reader(spec["reader"]).read)
    work = spec["args"].get("work")
    if work:
        assert cell.model.kernel_work(cell.config, cell.traffic, work)


def test_every_metric_of_the_cell_moves_one_it_reports(cell):
    """The cell reports the tokens a second and the set-up: a closed loop
    of 32 on 32 is saturated by construction and its tails are read in
    PERF.md only (section 6, PRs 33 and 35)."""
    reported = {m["name"] for m in cell.metrics("end_to_end")}
    assert reported == {"serve_tokens_per_s", "setup_s"}
    layered = {m["name"]: m for m in cell.metrics("per_layer")}
    assert set(NEW_METRICS) | set(JOINED) == set(layered)
    assert all(m["moves"] in reported for m in layered.values())


def test_latent_roofline_reader_on_known_ticks(cell, monkeypatch):
    """Two decode ticks of 150,000 and 190,000 context tokens, 16 calls a
    tick of 0.5 ms each: memory-bound, against a hand count."""
    from types import SimpleNamespace as NS

    reader = cell.reader("grouped_roofline")
    recs = [NS(name="decode_prep", start_ns=10 + i,
               attrs={"rows": 30, "context_tokens": t})
            for i, t in enumerate((150_000, 190_000))]
    monkeypatch.setattr(reader.spans, "window",
                        lambda env, fence: (recs, (0, 100), 0))
    monkeypatch.setattr(reader.spans, "named",
                        lambda recs, name: [r for r in recs
                                            if r.name == name])
    plane = NS(matching=lambda ev: list(range(32)),
               op_dur=np.full(32, 5e5))
    env = {"planes": [plane], "model": cell.model, "config": cell.config,
           "mix": cell.traffic,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    args = cell.metric_file("latent_decode_roofline.serve")["args"]
    assert 2 * 32 * 1088 * 170_000 / 197e12 < 1152 * 170_000 / 819e9
    want = 100 * (1152 * 170_000 / 819e9) / 5e-4
    assert reader.read(env, args) == pytest.approx(want)
    assert 40 < want < 50


def test_the_rehearsal_passes_on_the_cpu():
    """``run.py --workload <the cell> --rehearse``: the whole path at the
    files' toy sizes (bfloat16 weights, chunked prefill over three
    chunks, the scanned layers, the reference's replay)."""
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
