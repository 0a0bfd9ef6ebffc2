"""ISSUE 40: the generation programs are handed the served weight tree
(``serving/generation.py`` ``ServedWeights``), built once per weight
version.  Where the backend rounds a default-precision float32 matmul's
inputs to bfloat16 anyway (the TPU), the leaves a model declares as matmul
weights (``TransformerLM.served_matmul_leaves``) are held as bfloat16
copies and ``nn.linear.matmul_t`` feeds them to the product as they are:
the same arithmetic, without a cast of every weight inside every call.

Here, on the CPU, the rule is forced by patching the backend probe, and
the served programs are held to the same programs fed float32 weights
whose matmul inputs are rounded through bfloat16 by hand (the arithmetic
of ``benchmark/harness/precision.py``, written out, not imported)."""

import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import resolve  # noqa: E402

import bigdl_tpu.nn.attention as attention  # noqa: E402
import bigdl_tpu.nn.linear as linear  # noqa: E402
import bigdl_tpu.serving.generation as generation  # noqa: E402
from bigdl_tpu.nn.attention import TransformerLM  # noqa: E402
from bigdl_tpu.nn.quantized import quantize_model  # noqa: E402
from bigdl_tpu.observability.spans import now_ns, recorder  # noqa: E402
from bigdl_tpu.serving import ServingEngine  # noqa: E402
from bigdl_tpu.serving.generation import (ServedWeights,  # noqa: E402
                                          rounds_matmul_inputs)

VOCAB = 50
PROMPTS = [[1, 2, 3], [7, 8, 9, 10, 11], [4] * 9, list(range(20, 33))]


def _lm(scan, key=0, layers=2):
    m = TransformerLM(vocab_size=VOCAB, hidden_size=32, num_heads=4,
                      num_layers=layers, max_len=64, scan_layers=scan)
    m.build(jax.ShapeDtypeStruct((2, 16), jnp.int32),
            rng=jax.random.PRNGKey(key))
    return m


def _set_precision(value):
    jax.config.update("jax_default_matmul_precision", value)


@pytest.fixture
def tpu_rule(monkeypatch):
    """The rule as a TPU at default precision sees it (tests/conftest.py
    sets ``highest`` for the whole run; it is put back after)."""
    was = jax.config.jax_default_matmul_precision
    monkeypatch.setattr(generation, "_backend", lambda: "tpu")
    _set_precision(None)
    yield
    _set_precision(was)


def _through_bf16(x, w):
    """``x @ w.T`` in float32 with both inputs rounded to bfloat16 first."""
    r = lambda v: v.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.einsum("...i,oi->...o", r(x), r(w), precision="highest")


def _flat(tree):
    return {tuple(k.key for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _serve_spans(since):
    return [r for r in recorder().snapshot(since)
            if r.name == "serve_weights_cast"]


@pytest.mark.parametrize("backend,precision,rounds", [
    ("tpu", None, True), ("tpu", "default", True),
    ("tpu", "bfloat16", True), ("tpu", "highest", False),
    ("tpu", "float32", False), ("cpu", None, False),
    ("cpu", "default", False), ("gpu", "default", False)])
def test_the_rule_reads_the_backend_and_the_precision(monkeypatch, backend,
                                                      precision, rounds):
    was = jax.config.jax_default_matmul_precision
    monkeypatch.setattr(generation, "_backend", lambda: backend)
    _set_precision(precision)
    try:
        assert rounds_matmul_inputs() is rounds
        m = _lm(True)
        served = ServedWeights(m)()
        assert (served is m.weights()) is not rounds
    finally:
        _set_precision(was)


@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scan"])
def test_only_the_declared_leaves_change(tpu_rule, scan):
    m = _lm(scan)
    declared = set(m.served_matmul_leaves())
    assert len(declared) == (5 if scan else 9)
    served, weights = _flat(ServedWeights(m)()), _flat(m.weights())
    assert served.keys() == weights.keys() and declared <= weights.keys()
    for path, leaf in weights.items():
        if path in declared:
            assert served[path].dtype == jnp.bfloat16, path
            np.testing.assert_array_equal(
                np.asarray(served[path]),
                np.asarray(leaf.astype(jnp.bfloat16)))
        else:
            assert served[path] is leaf, path
    for kept in (("wte",), ("wpe",)):
        assert served[kept] is weights[kept]


def _paged_logits(model, params):
    """Logits of one chunk step over two rows (8 and 5 real tokens), then
    of one decode step, through the paged pool."""
    pool = model.init_paged_cache(8, 4)
    tables = jnp.asarray([[0, 1, 2, 8], [3, 4, 5, 8]], jnp.int32)
    chunk = jnp.asarray(np.random.default_rng(3).integers(0, VOCAB, (2, 8)),
                        jnp.int32)
    step = jax.jit(lambda p, pool, x, pos, lengths: model.apply_paged(
        p, x, pool, tables, pos=pos, lengths=lengths))
    first, pool = step(params, pool, chunk, jnp.zeros((2,), jnp.int32),
                       jnp.asarray([8, 5], jnp.int32))
    nxt = jnp.argmax(first[jnp.arange(2), jnp.asarray([7, 4])], -1)
    decode = jax.jit(lambda p, pool, x, pos: model.apply_paged(
        p, x, pool, tables, pos=pos))
    second, _ = decode(params, pool, nxt[:, None].astype(jnp.int32),
                       jnp.asarray([8, 5], jnp.int32))
    return np.asarray(first), np.asarray(second)


@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scan"])
def test_served_paged_logits_are_the_rounded_fp32_logits(tpu_rule,
                                                         monkeypatch, scan):
    m = _lm(scan)
    served = ServedWeights(m)()
    assert served is not m.weights()
    got = _paged_logits(m, served)
    monkeypatch.setattr(linear, "matmul_t", _through_bf16)
    monkeypatch.setattr(attention, "matmul_t", _through_bf16)
    want = _paged_logits(m, m.weights())
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


def _streams(model):
    with ServingEngine(model, decode_slots=3, decode_max_len=48,
                       kv_block_size=4) as eng:
        futs = [eng.generate(p, max_new_tokens=8) for p in PROMPTS]
        return [f.result(60) for f in futs]


@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scan"])
def test_served_greedy_tokens_are_the_rounded_fp32_tokens(tpu_rule,
                                                          monkeypatch,
                                                          scan):
    since = now_ns()
    served = _streams(_lm(scan))
    assert len(_serve_spans(since)) == 1
    monkeypatch.setattr(generation, "_backend", lambda: "cpu")
    monkeypatch.setattr(linear, "matmul_t", _through_bf16)
    monkeypatch.setattr(attention, "matmul_t", _through_bf16)
    assert served == _streams(_lm(scan))


def test_a_served_model_and_an_int8_twin_are_served_as_they_are(tpu_rule):
    """``ServedLM`` (granite, Ling, kanana) declares no matmul leaves: its
    weights are bfloat16 already.  A quantized twin's gate passed its tree
    as it is."""
    cell = resolve.Cell("granite-4.0-h-micro.serve.short-chat")
    cfg, _ = cell.sized(True)
    spec = jax.ShapeDtypeStruct((1, cfg["n_positions"]), jnp.int32)
    granite = cell.model.program_model(cfg, cell.model.make_params(cfg, 7),
                                       spec)
    since = now_ns()
    assert ServedWeights(granite)() is granite.weights()
    twin, qparams = quantize_model(_lm(True))
    twin.set_parameters(qparams)
    assert ServedWeights(twin)() is twin.weights()
    assert not _serve_spans(since)


def test_a_weight_version_is_cast_once(tpu_rule):
    """One ``serve_weights_cast`` span a version: at the first build and
    at each accepted ``refresh_params``, never a tick; the next tick after
    a swap serves the new weights."""
    since = now_ns()
    m = _lm(True)
    new = _lm(True, key=1)
    with ServingEngine(m, decode_slots=3, decode_max_len=48,
                       kv_block_size=4) as eng:
        before = [eng.generate(p, max_new_tokens=8).result(60)
                  for p in PROMPTS]
        spans = _serve_spans(since)
        assert len(spans) == 1
        bytes_in = sum(leaf.size * 4 for path, leaf in _flat(m.weights())
                       .items() if path in set(m.served_matmul_leaves()))
        assert spans[0].attrs == {"leaves": 5, "bytes_in": bytes_in,
                                  "bytes_out": bytes_in // 2}
        eng.refresh_params(new.weights())
        assert len(_serve_spans(since)) == 2
        served = eng._generation().served()
        assert served["head"].dtype == jnp.bfloat16
        assert served["wte"] is new.weights()["wte"]
        after = [eng.generate(p, max_new_tokens=8).result(60)
                 for p in PROMPTS]
        assert len(_serve_spans(since)) == 2
    assert after != before
    assert after == _streams(_lm(True, key=1))


def test_readers_never_see_a_torn_tree_while_versions_swap(tpu_rule):
    """The dispatcher reads the served tree while a swap rebuilds it on
    another thread: every tree a reader gets is one version's, whole (its
    copies and its untouched leaves from the same ``model.weights()``),
    and after the last swap the reader gets the last version's."""
    m = _lm(True)
    first = m.weights()
    versions = [_lm(True, key=k).weights() for k in range(1, 7)]
    served = ServedWeights(m)
    torn, stop = [], threading.Event()

    def read():
        while not stop.is_set():
            tree = served()
            owner = [v for v in [first] + versions
                     if v["wte"] is tree["wte"]]
            if len(owner) != 1 or not np.array_equal(
                    np.asarray(tree["head"]),
                    np.asarray(owner[0]["head"].astype(jnp.bfloat16))):
                torn.append(tree)

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    readers = [threading.Thread(target=read) for _ in range(8)]
    try:
        for t in readers:
            t.start()
        for v in versions * 3:
            m.set_parameters(v)
            served.rebuild()
    finally:
        stop.set()
        for t in readers:
            t.join(30)
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in readers)
    assert not torn
    assert served()["wte"] is versions[-1]["wte"]
