"""The ResNet-50 files (configuration, model, mix) that wait for their
cell: PERF.md section 7, row 0.  No BENCHMARK.json entry names them yet;
these tests keep them true until one does."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import resolve


def grown():
    bench = resolve.benchmark_json()
    bench = json.loads(json.dumps(bench))
    bench["configs"].append(
        {"name": "resnet50", "source": "https://arxiv.org/abs/1512.03385",
         "file": "benchmark/configs/resnet50.json", "reduced": [],
         "why": "ResNet-50"})
    bench["workloads"].append(
        {"name": "resnet50.train.b256", "config": "resnet50",
         "traffic": "train.b256", "chips": 1, "why": "x"})
    return bench


def test_work_by_hand():
    cell = resolve.Cell("resnet50.train.b256", grown())
    m, cfg = cell.model, cell.config
    assert m.param_count(cfg) == 25_557_032          # the program's count
    # 4.09 G multiply-adds an image with the stride on the 3x3 (the
    # paper's 3.8 G has it on the first 1x1): stem 118 M, classifier 2 M
    stem = 112 * 112 * 49 * 3 * 64
    assert stem == 118_013_952
    flops = m.forward_flops_per_image(cfg)
    assert flops / 2 == pytest.approx(4.09e9, rel=0.005)
    assert m.train_step_flops(cfg, 256) == 3 * 256 * flops
    # first bottleneck by hand: 56x56 x (64*64 + 9*64*64 + 64*256 + 64*256)
    first = 56 * 56 * (64 * 64 + 9 * 64 * 64 + 2 * 64 * 256)
    blocks, n_last, _ = m.plan(cfg)
    assert blocks[0][1] == (64, 64, 256, 1, True) and n_last == 2048
    assert first == 231_211_008


def test_trees_match_the_program():
    from bigdl_tpu.models.resnet import ResNet

    cell = resolve.Cell("resnet50.train.b256", grown())
    m, cfg = cell.model, cell.config
    model = ResNet(depth=50, class_num=1000)
    spec = jax.ShapeDtypeStruct((2, 224, 224, 3), jnp.float32)
    p, s = jax.eval_shape(lambda k: model.setup(k, spec), jax.random.key(0))
    assert jax.tree.map(lambda a: a.shape, p) == m.param_shapes(cfg)
    mine = jax.eval_shape(lambda: m.make_state(cfg))
    assert jax.tree.map(lambda a: a.shape, s) \
        == jax.tree.map(lambda a: a.shape, mine)


def test_reference_agrees_with_the_program_in_float32():
    """At toy size the bf16 step is chaotic (BatchNorm over 16 images), so
    the agreement is shown with the program computing in float32: same
    losses, same first gradient, same three SGD steps."""
    cell = resolve.Cell("resnet50.train.b256", grown())
    s = cell.driver.Session(cell, 1, True, (
        {"image_size": 64}, {"compute_dtype": "float32", "batch": 16}))
    s.make_data()
    s.build()
    s.run(0.2)
    got = s.program_reading()
    s.free()
    by = {c["name"]: c["value"] for c in s.compare(got, s.reference())}
    assert by["loss1"] < 1e-5 and by["loss2"] < 1e-4 and by["loss3"] < 1e-3
    assert by["grad_norm"] < 1e-3
    assert by["change_norm"] < 0.1
