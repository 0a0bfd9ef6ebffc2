"""The granite-4.0-h-micro files (configuration, model, mix, metrics): the
catalog's keys as run with nothing reduced, the parameter count at the
published widths from shapes alone and what the cell's device holds, the
work functions against a hand count, the program's trees, the controls,
the rehearsal of the cell on the CPU, and the readers of the new
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

CELL = "granite-4.0-h-micro.serve.short-chat"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("ssd_decode_roofline.serve", "ssd_decode_device_share.serve",
               "state_mixer_device_share.serve",
               "state_mixer_prefill_device_share.serve")
#: the accepted metrics whose lists the cell joins.  Three more that ISSUE
#: 39 names (``scope_named_share.serve``, ``attention_device_share.serve``,
#: ``head_sampler_device_share.serve``) read its runs unedited and are not
#: listed: ``test_scope_readers.py`` pins their lists to the GPT-2 cell
JOINED = ("decode_tick_ms.serve", "step_mfu.serve",
          "device_idle_share.serve", "decode_slot_fill.serve",
          "tick_host_ms.serve", "idle_attributed_share.serve",
          "launch_ahead_share.serve")


@pytest.fixture(scope="module")
def cell():
    return resolve.Cell(CELL)


def count(tree):
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple)))


def test_configuration_holds_the_source_unchanged(cell):
    cfg = cell.config
    assert cfg["reduced"] == cell.config_entry["reduced"] == []
    assert len(cfg["source"]) <= 200
    assert cfg["source"] == cell.config_entry["source"]
    assert (cfg["hidden_size"], cfg["shared_intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
            cfg["mamba_d_conv"], cfg["mamba_chunk_size"],
            cfg["num_hidden_layers"], cfg["vocab_size"]) == (
        2048, 8192, 32, 8, 64, 64, 128, 4, 256, 40, 100352)
    assert cfg["layer_types"].count("mamba") == 36
    assert [i for i, k in enumerate(cfg["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    assert cfg["published"]["num_hidden_layers"] == 40
    assert cfg["n_positions"] == 2304
    for key in ("mixer", "in_proj_leaves", "gated_norm", "time_step_limit",
                "state_dtype", "state_layout", "attention", "mlp",
                "multipliers", "context", "init"):
        assert cfg["assumed"][key]
    assert "one chip serves the whole model in bfloat16: all 40 layers, " \
        "the whole vocabulary, nothing shared with another chip" \
        in cfg["deployment"]
    assert "accepted" in cfg["compile"]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    row = [json.loads(l) for l in open(CATALOG)
           if '"granite-4.0-h-micro"' in l][0]
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert cfg[key] == value, key


def test_parameters_from_shapes_alone_and_what_the_device_holds(cell):
    """3,191,396,096 parameters, 6.38 GB in bfloat16: a Mamba layer
    76,182,976, an attention layer 60,821,504, the tied embedding
    205,520,896 and the final norm; beside them 65 slots of 76.4 MB and
    9,217 blocks of 16 tokens of 8 KB: 12.6 GB of the chip's 16."""
    m, cfg, mix = cell.model, cell.config, cell.traffic
    shapes = m.param_shapes(cfg)
    D, F = 2048, 8192
    mlp_and_norms = 2 * D * F + F * D + 2 * D
    mamba = D * 8512 + (4352 * 4 + 4352) + 192 + 4096 + 4096 * D \
        + mlp_and_norms
    attention = 2 * D * D + 2 * D * 512 + mlp_and_norms
    assert (mamba, attention) == (76_182_976, 60_821_504)
    assert count(shapes["attention2"]) == attention
    assert [count(shapes[f"mamba{r}"]) for r in range(5)] \
        == [n * mamba for n in (5, 9, 9, 9, 4)]
    assert count(shapes["embed"]) == 100352 * D and "head" not in shapes
    assert m.param_count(cfg) == cfg["parameters_held"] == 3_191_396_096
    weights, slots, pool = m.memory_bytes(cfg, mix)
    assert 6.38e9 < weights < 6.39e9
    a_slot = 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    assert slots == 65 * a_slot and 76.4e6 < a_slot < 76.5e6
    assert pool == 9217 * 16 * 4 * 2 * 512 * 2 and 1.2e9 < pool < 1.22e9
    assert 12.5e9 < weights + slots + pool < 12.7e9
    # once 84 sequences are live their state outweighs the weights
    assert 83 * a_slot < weights < 84 * a_slot


def test_work_by_hand(cell):
    m, cfg, mix = cell.model, cell.config, cell.traffic
    D, F, V = 2048, 8192, 100352
    mamba = 2 * D * 8512 + 2 * 4096 * D + 2 * 4 * 4352 + 4 * 64 * 64 * 128
    attention = 2 * D * (2048 + 1024) + 2 * D * D
    per_token = 36 * mamba + 4 * attention + 40 * 6 * D * F + 2 * D * V
    T = 700
    assert m.forward_flops(cfg, [T]) == pytest.approx(
        per_token + 4 * 2 * 2 * 32 * 64 * T)
    assert 6.3e9 < per_token < 6.5e9          # about two a parameter
    k = m.kernel_work(cfg, mix, "ssd_decode")
    assert k["bytes_per_row"] == 2 * 64 * 64 * 128 * 4 == 4_194_304
    assert k["flops_per_row"] == 4 * 64 * 64 * 128
    assert (k["layers"], k["bytes_per_call"]) == (1, 0.0)
    with pytest.raises(KeyError):
        m.kernel_work(cfg, mix, "paged_decode")


def test_trees_match_the_program(cell):
    m = cell.model
    cfg, _ = cell.sized(True)
    params = m.make_params(cfg, 3)
    spec = jax.ShapeDtypeStruct((1, cfg["n_positions"]), jnp.int32)
    model = m.program_model(cfg, params, spec)       # raises on a mismatch
    assert model.weights() is params
    assert {str(a.dtype) for a in jax.tree.leaves(params)} \
        == {"bfloat16", "float32"}
    op = params["mamba0"]["op"]
    assert all(op[k].dtype == jnp.float32
               for k in ("A_log", "dt_bias", "D", "o_norm"))
    assert op["in_weight"].dtype == op["conv_kernel"].dtype == jnp.bfloat16
    assert all(a.shape[0] == 2 for a in jax.tree.leaves(params["mamba1"]))
    # the rates and steps are where the configuration's ``assumed`` puts them
    rate = np.exp(np.asarray(op["A_log"]))
    step = np.log1p(np.exp(np.asarray(op["dt_bias"], np.float64)))
    assert 1 <= rate.min() and rate.max() <= 16
    assert 0.99e-3 <= step.min() and step.max() <= 1.01e-1
    assert bool((op["D"] == 1).all())
    assert float(jnp.abs(op["conv_kernel"].astype(jnp.float32)).max()) <= 0.5
    again = m.make_params(cfg, 3)
    assert all(bool((a == b).all()) for a, b in zip(
        jax.tree.leaves(params), jax.tree.leaves(again)))


def test_the_mix_is_the_issues(cell):
    mix = cell.traffic
    assert mix["driver"] == "closed_loop_generate"
    assert mix["engine"] == {"decode_slots": 64, "decode_max_len": 2304,
                             "kv_cache": "paged", "kv_block_size": 16,
                             "kv_blocks": 9216, "prefill_chunk": 512}
    assert mix["requests"] == {
        "pool": 512,
        "prompt": {"median": 256, "sigma": 0.8, "min": 32, "max": 1536},
        "output": {"median": 192, "sigma": 0.6, "min": 32, "max": 768},
        "max_total": 2304, "epochs": 4}
    assert mix["clients"] == 64 and mix["check_requests"] == 6
    assert mix["ramp_seconds"] == 15 and mix["trace_seconds"] in (4, 8)
    assert mix["control"] == "fp8" and mix["faults"] == ["token_altered"]
    # the warm-up's prompt spans more than two chunks
    assert mix["warmup"]["prompt_tokens"] == 1100 \
        > 2 * mix["engine"]["prefill_chunk"]
    # every slot can reach its full length: the pool is never exhausted
    e = mix["engine"]
    assert e["kv_blocks"] * e["kv_block_size"] \
        == e["decode_slots"] * e["decode_max_len"]
    assert mix["limits"]["logit_gap_max"] and mix["limits"]["logit_gap_mean"]
    assert cell.chips == 1


@pytest.mark.parametrize("mode", ["fp8", "bf16", "bf16_state"])
def test_a_control_differs_from_the_reference(cell, mode):
    """Each control moves the logits; ``bf16_state`` (the recurrent state
    alone) far less than ``fp8`` (every matmul's inputs): at the toy
    widths the state's share of a mixer's output is small."""
    m = cell.model
    cfg, _ = cell.sized(True, ({"program": {"dtype": "float32"}}, {}))
    params = m.make_params(cfg, 5)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (1, 40)), jnp.int32)
    ref = m.reference_logits(params, tokens, cfg)
    err = lambda mode: float(jnp.abs(
        m.reference_logits(params, tokens, cfg, mode) - ref).max())
    if mode == "bf16_state":
        assert 0 < err(mode) < err("fp8")
    else:
        assert err(mode) > 1e-5


@pytest.mark.parametrize("name", NEW_METRICS)
def test_every_new_metric_names_a_reader_that_loads(cell, name):
    entry = [m for m in cell.bench["per_layer"] if m["name"] == name][0]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "serve_tokens_per_s" and entry["unit"] == "%"
    spec = cell.metric_file(name)
    assert spec["layer"] == entry["layer"]
    assert entry["layer"] == ("kernels" if name.startswith("ssd_decode")
                              else "serving engine")
    assert callable(cell.reader(spec["reader"]).read)
    work = spec["args"].get("work")
    if work:
        assert cell.model.kernel_work(cell.config, cell.traffic, work)
    assert (work is not None) == name.endswith("roofline.serve")


def test_every_metric_of_the_cell_moves_one_it_reports(cell):
    """The cell reports the tokens a second and the set-up: a closed loop
    of 64 on 64 is saturated by construction and its tails are read in
    PERF.md only."""
    reported = {m["name"] for m in cell.metrics("end_to_end")}
    assert reported == {"serve_tokens_per_s", "setup_s"}
    layered = {m["name"]: m for m in cell.metrics("per_layer")}
    assert set(NEW_METRICS) | set(JOINED) == set(layered)
    assert all(m["moves"] in reported for m in layered.values())


def test_ssd_roofline_reader_on_known_ticks(cell, monkeypatch):
    """Two decode ticks of 60 and 64 live slots, 36 calls a tick of 0.4 ms
    each: memory-bound, against a hand count."""
    from types import SimpleNamespace as NS

    reader = cell.reader("grouped_roofline")
    recs = [NS(name="decode_prep", start_ns=10 + i, attrs={"rows": r})
            for i, r in enumerate((60, 64))]
    monkeypatch.setattr(reader.spans, "window",
                        lambda env, fence: (recs, (0, 100), 0))
    monkeypatch.setattr(reader.spans, "named",
                        lambda recs, name: [r for r in recs
                                            if r.name == name])
    plane = NS(matching=lambda ev: list(range(72)),
               op_dur=np.full(72, 4e5))
    env = {"planes": [plane], "model": cell.model, "config": cell.config,
           "mix": cell.traffic,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    args = cell.metric_file("ssd_decode_roofline.serve")["args"]
    assert args["events"] == ["^ssd_decode_step"]
    want = 100 * (4_194_304 * 62 / 819e9) / 4e-4
    assert reader.read(env, args) == pytest.approx(want)
    assert 75 < want < 85


def test_the_rehearsal_passes_on_the_cpu():
    """``run.py --workload <the cell> --rehearse``: the whole path at the
    files' toy sizes (bfloat16 weights, chunked prefill over three
    chunks, both kinds of generation state, the scanned runs, the
    reference's replay)."""
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
