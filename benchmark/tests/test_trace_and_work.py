"""The trace reduction on the committed traces, the required-work
functions against hand-worked numbers, the peaks table, the statistics
and the seeded generators."""

import os

import numpy as np
import pytest

from harness import device, resolve, stats, trace, traffic

R4 = os.path.join(resolve.ROOT, "docs", "traces", "r4_tpu_b128")
SYN = os.path.join(resolve.ROOT, "tests", "fixtures", "synthetic.xplane.pb")
SYN_MULTI = os.path.join(resolve.ROOT, "tests", "fixtures",
                         "synthetic_multi.xplane.pb")


def _reader(name):
    return resolve.load_module(os.path.join(
        resolve.BENCH_DIR, "metrics", "readers", name + ".py"), "r_" + name)


@pytest.mark.skipif(not os.path.exists(SYN), reason="fixture not here")
def test_synthetic_trace_by_hand():
    # ops at [1000,5000] [5500,8500] [9000,10500] [10600,11000] ns: busy
    # 8900 of the 10000 ns from the first start to the last end
    (dev,) = trace.load(SYN)
    assert dev.window() == (1000.0, 11000.0)
    assert dev.busy_ns() == pytest.approx(8900.0)
    assert dev.busy_ns(2000.0, 6000.0) == pytest.approx(3500.0)
    by = dev.seconds_by_op()
    assert by["fusion.1"] == pytest.approx(5.5e-6)
    assert by["convolution.7"] == pytest.approx(3e-6)
    idx = dev.matching(["convolution"])
    assert [trace.short_name(dev.op_names[i]) for i in idx] \
        == ["convolution.7"]
    s = trace.summary([dev])
    assert s["busy_s"] == pytest.approx(8.9e-6)
    assert s["window_s"] == pytest.approx(1e-5)
    assert _reader("idle_share").read({"summary": s}, {}) \
        == pytest.approx(11.0)
    assert len(s["breakdown"]["device_ops"]) <= 10


@pytest.mark.skipif(not os.path.exists(SYN_MULTI), reason="fixture not here")
def test_two_device_planes_are_averaged():
    planes = trace.load(SYN_MULTI)
    assert [p.name.split()[0] for p in planes] \
        == ["/device:TPU:0", "/device:TPU:1"]
    s = trace.summary(planes)
    assert s["busy_s"] == pytest.approx(
        np.mean([p.busy_ns() for p in planes]) * 1e-9)


@pytest.mark.skipif(not os.path.isdir(R4), reason="trace not here")
def test_round4_resnet_trace():
    # docs/performance.md: 16 steps, 46.3 ms of device time a step
    (dev,) = trace.load(R4)
    runs = dev.module_runs("^jit_train_step")
    assert len(runs) == 16
    assert dev.busy_ns() * 1e-9 / 16 == pytest.approx(0.0463, rel=0.01)
    env = {"planes": [dev], "summary": trace.summary([dev])}
    gap = _reader("module_gap").read(env, {"module": "^jit_train_step"})
    assert gap == pytest.approx(0.0065, rel=0.05)        # ms
    period = _reader("module_gap").read(
        env, {"module": "^jit_train_step", "what": "period"})
    assert period == pytest.approx(46.35, rel=0.01)      # ms
    assert _reader("idle_share").read(env, {}) < 0.1
    # step_mfu: 16 steps of a known amount of work over 15 periods
    env["peaks"] = {"bf16_flops_per_s": 197e12}
    env["counters"] = {"required_flops_per_step": 197e12 * 0.04635}
    assert _reader("step_mfu").read(
        env, {"per_run_of": "^jit_train_step"}) == pytest.approx(100, rel=0.01)
    assert dev.matching(["flash_attention"]) == []
    assert _reader("kernel_roofline").read(
        {**env, "model": None, "config": {}, "mix": {}},
        {"kernels": [{"events": ["flash_attention"], "work": "x"}]}) is None


def test_union_handles_nesting_and_overlap():
    starts = np.array([0.0, 2.0, 3.0, 10.0])
    ends = np.array([8.0, 4.0, 9.0, 11.0])
    assert trace._union_ns(starts, ends) == pytest.approx(10.0)
    assert trace._union_ns(starts[:0], ends[:0]) == 0.0


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert stats.percentile(v, 90) == 90
    assert stats.percentile(v, 95) == 95
    assert stats.percentile([5.0], 90) == 5.0
    assert stats.percentile([], 90) is None
    assert stats.median([3, 1, 2]) == 2 and stats.median([1, 2, 3, 4]) == 2.5


def test_peaks_table():
    p = device.peaks("TPU v5 lite")
    assert p == {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                 "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")


def test_gpt2_medium_work_by_hand():
    cell = resolve.Cell("gpt2-medium.train.seq1024")
    m, cfg = cell.model, cell.config
    # 24 layers x (qkv 3.1M + out 1.0M + mlp 8.4M + biases and norms)
    # + two embeddings of 50257 x 1024, positions, final norm
    per_layer = 3 * 1024 * 1024 + 3 * 1024 + 1024 * 1024 + 1024 \
        + 2 * 4096 * 1024 + 4096 + 1024 + 4 * 1024
    want = 24 * per_layer + 2 * 50257 * 1024 + 1024 * 1024 + 2 * 1024
    assert m.param_count(cfg) == want == 406_286_336
    # one token with one key: 24 x 2 x (3 + 1 + 8) x 1024^2 for the blocks,
    # 2 x 1024 x 50257 for the head, 24 x 4 x 1024 for its one key
    one = 24 * 2 * 12 * 1024 ** 2 + 2 * 1024 * 50257 + 24 * 4 * 1024
    assert m.forward_flops(cfg, [1]) == one
    step = m.train_step_flops(cfg, 16, 1024)
    keys = 16 * 1024 * 1025 / 2
    assert step == pytest.approx(
        3 * (16 * 1024 * (one - 24 * 4 * 1024) + 24 * 4 * 1024 * keys))
    assert 2.2e9 < step / (16 * 1024) < 2.35e9          # per token
    att = m.attention_fwd_work(cfg, 16, 1024, 2)
    assert att["flops"] == 4 * 1024 * keys
    assert att["bytes"] == 4 * 16 * 1024 * 1024 * 2
    ce = m.cross_entropy_work(cfg, 16384, 4)
    assert ce["fwd"]["bytes"] == 16384 * 50257 * 4
    assert ce["bwd"]["bytes"] == 2 * 16384 * 50257 * 4
    work = m.kernel_work(cfg, cell.traffic, "attention_fwd")
    assert work == att


def test_generators_are_seeded():
    a = traffic.markov_tokens(7, 8, 32, 500)
    b = traffic.markov_tokens(7, 8, 32, 500)
    c = traffic.markov_tokens(8, 8, 32, 500)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    assert np.array_equal(a[0][:, 1:], a[1][:, :-1])      # next-token pairs
    assert len({r.tobytes() for r in a[0]}) == 8           # rows all differ
    spec = {"pool": 64, "epochs": 2, "max_total": 1024,
            "prompt": {"median": 256, "sigma": 0.7, "min": 32, "max": 832},
            "output": {"median": 64, "sigma": 0.6, "min": 16, "max": 192}}
    big = 2 ** 31 + 12345
    r1 = traffic.generate_requests(big, spec, 50257)
    r2 = traffic.generate_requests(big, spec, 50257)
    r3 = traffic.generate_requests(big + 1, spec, 50257)
    assert all(np.array_equal(p, q) and n == m
               for (p, n), (q, m) in zip(r1, r2))
    assert any(not np.array_equal(p, q) for (p, _), (q, _) in zip(r1, r3))
    # every seed the same SET of sizes, in another order
    assert sorted(len(p) for p, _ in r1) == sorted(len(p) for p, _ in r3)
    assert sorted((len(p), n) for p, n in r1[:64]) \
        == sorted((len(p), n) for p, n in r1[64:])       # each pass: the set
    assert not any(np.array_equal(p, q)                   # with fresh ids
                   for p, _ in r1[:64] for q, _ in r1[64:])
    assert [len(p) for p, _ in r1] != [len(p) for p, _ in r3]
    assert all(32 <= len(p) <= 832 and 16 <= n <= 192
               and len(p) + n <= 1024 for p, n in r1)


def test_memory_peak_counts_buffers_and_reserved_temporaries():
    class Dev:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    a = Dev({"peak_bytes_in_use": 7, "peak_bytes_reserved": 6})
    b = Dev({"peak_bytes_in_use": 9})
    assert device.memory_peak_bytes([a, b]) == 13
    assert device.memory_peak_bytes([Dev(None)]) == 0


def test_lower_precision_rounds_values_not_cotangents():
    import jax
    import jax.numpy as jnp

    from harness import precision

    x = jnp.asarray([1.0 + 2.0 ** -10, 3.14159, -1e-3], jnp.float32)
    assert precision.through(x, "f32") is x
    assert float(precision.through(x, "bf16")[0]) == 1.0
    fp8 = precision.through(x, "fp8")
    assert float(jnp.max(jnp.abs(fp8 - x) / jnp.abs(x))) < 0.07
    assert float(fp8[1]) != float(x[1])
    for mode in ("bf16", "fp8"):
        g = jax.grad(lambda v: jnp.sum(precision.through(v, mode) * 1e-6))(x)
        assert jnp.allclose(g, 1e-6)
    with pytest.raises(ValueError):
        precision.through(x, "int3")
