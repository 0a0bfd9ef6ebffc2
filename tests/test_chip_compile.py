"""The Pallas kernels of the main path, compiled for a v5e that is
described and not attached (the TPU compiler is installed with jaxlib), at
the widths of ``transformer_lm("medium")`` (head 64) and ``"large"`` (head
96) and at the sequence, cache and pool sizes ``chip_smoke.py`` drives.
What interpret mode cannot see -- a block shape the lowering refuses, more
VMEM than a kernel may take -- fails here, at no chip time.

All of these stay in ONE file: only one process may load the TPU library,
and the xdist worker that is handed this file is the one that does.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from bigdl_tpu.ops.cross_entropy import fused_softmax_cross_entropy
from bigdl_tpu.ops.grouped_matmul import buffer_rows, grouped_matmul
from bigdl_tpu.ops.kda import kda_decode_step
from bigdl_tpu.ops.ssd import ssd_decode_step
from bigdl_tpu.ops.flash_attention import (flash_attention,
                                           flash_paged_decode_attention,
                                           flash_paged_prefill_attention,
                                           latent_paged_decode_attention)

f32, bf16, i8, i32 = jnp.float32, jnp.bfloat16, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off around
    the compiles: an entry written for a described device cannot be read
    back without the chip, and the next run would only warn about it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(one_chip, fn, *shapes):
    """``fn`` compiled for the described chip, as text."""
    args = [None if s is None
            else jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _compile(one_chip, fn, *shapes):
    """Compile ``fn`` for the described chip; returns the number of Pallas
    kernels (``tpu_custom_call``) in the compiled program."""
    return _compiled_text(one_chip, fn, *shapes).count("tpu_custom_call")


def _flash_grad(q, k, v):
    # the forward kernel (writing its log-sum-exp) and attention_bwd
    return jax.value_and_grad(
        lambda *a: flash_attention(*a).astype(f32).sum(),
        argnums=(0, 1, 2))(q, k, v)


def _ce_grad(x, y):
    return jax.grad(lambda a: fused_softmax_cross_entropy(a, y).sum())(x)


def _grouped_grad(lhs, rhs, sizes):
    # the forward product and both backward products, all grouped kernels
    return jax.value_and_grad(
        lambda a, w: grouped_matmul(a, w, sizes).astype(f32).sum(),
        argnums=(0, 1))(lhs, rhs)


def _grouped(k, n, rows=65536, groups=8):
    """The expert layer of the LFM2 cell: a buffer for the worst case of
    ``rows`` assignments over ``groups`` experts held."""
    m = buffer_rows(rows, groups, 512)
    return [((m, k), bf16), ((groups, k, n), bf16), ((groups,), i32)]


def _grouped_small(lhs, rhs, sizes):
    # a decode tick's tiles: 16 rows, one bf16 tile
    return grouped_matmul(lhs, rhs, sizes, block_rows=16)


def _kda(slots, h=32, d=128):
    """The delta-rule decode step of the Ling cell: every slot's state and
    the trash slot's, one token a slot."""
    vec = ((slots, h, d), f32)
    return [((slots + 1, h, d, d), f32), ((slots,), i32), vec, vec, vec, vec,
            ((slots, h), f32)]


def _ssd(slots, layers=None, h=64, p=64, k=128):
    """The state-space decode step of the granite cell: every slot's state
    and the trash slot's as ``ops/ssd.py`` stores it, one token a slot; a
    layer's own leaf, or the Mamba layers stacked and the layer."""
    stack = () if layers is None else (layers,)
    return [(stack + (slots + 1, h // 2, k, 2 * p), f32), ((slots,), i32),
            ((slots, h, p), f32), ((slots, h), f32), ((h,), f32),
            ((slots, k), f32), ((slots, k), f32), ((h,), f32)] \
        + ([] if layers is None else [((), i32)])


def _qkv(b, t, d, dt, h=16):
    return [((b, t, h, d), dt)] * 3


def _paged(b, nb, bs, d, dtype, h=16, mb=64, layers=None, groups=1):
    """The pool as the engine stores it: ``(NB, bs, H * D)``, an int8
    one with ``(NB, bs, H)`` fp32 scales; with ``layers`` the stacked leaf
    of a ``scan_layers`` model, ``(L, NB, bs, H * D)``, and the layer;
    with ``groups`` that many query heads on each of the ``h`` KV heads."""
    stack = () if layers is None else (layers,)
    pool = (stack + (nb, bs, h * d), dtype)
    scale = (stack + (nb, bs, h), f32) if dtype == i8 else None
    return [((b, 1, h * groups, d), f32), pool, pool, ((b, mb), i32),
            ((b,), i32),
            scale, scale, None if layers is None else ((), i32)]


def _prefill(q, k, v, tables, start, lengths, layer):
    return flash_paged_prefill_attention(q, k, v, tables, start, lengths,
                                         layer)


def _paged_chunk(rows, tc, nb, d, dtype, h=16, mb=64, layers=24, groups=1):
    """A chunk step's rows of ``tc`` tokens against the stacked pool of
    ``_paged`` (the layer an argument), with their starts and lengths."""
    pool = ((layers, nb, 16, h * d), dtype)
    return [((rows, tc, h * groups, d), f32), pool, pool, ((rows, mb), i32),
            ((rows,), i32), ((rows,), i32), ((), i32)]


def _latent(q, pool, tables, pos, layer=None):
    return latent_paged_decode_attention(q, pool, tables, pos, layer,
                                         rank=512, scale=192 ** -0.5)


def _latent_pool(layers=None, width=640):
    """The kanana cell's decode step: 32 slots of 32 absorbed heads over
    rows of 576 values stored in 640 columns, blocks of 16, 1024 table
    entries; a layer's own leaf, or 15 layers stacked and the layer."""
    stack = () if layers is None else (layers,)
    return [((32, 32, width), bf16), (stack + (2049, 16, width), bf16),
            ((32, 1024), i32), ((32,), i32)] \
        + ([] if layers is None else [((), i32)])


# (kernel, shapes)
CASES = {
    # train_lm's sequence, bf16 compute.  The forward kernel streams K/V
    # a block at a time: no gate bounds it by bytes
    "flash-medium": (flash_attention, _qkv(2, 2048, 64, bf16)),
    "flash-large": (flash_attention, _qkv(2, 2048, 96, bf16)),
    "flash-grad-medium": (_flash_grad, _qkv(2, 2048, 64, bf16)),
    # the benchmark's training cell: 16 sequences of 1024, 16 heads of 64
    "flash-cell": (flash_attention, _qkv(16, 1024, 64, bf16)),
    "flash-grad-cell": (_flash_grad, _qkv(16, 1024, 64, bf16)),
    # the paged decode kernel streams a slot's blocks through a double
    # buffer: no gate bounds it by bytes.  The benchmark's serving cell
    # (32 slots, 1280 blocks of 16 and the trash block, 16 heads of 64,
    # 64 table entries, fp32), the same tokens in a bf16 pool, and the
    # large model's heads of 96 in an int8 pool of 32-row blocks
    "paged-cell-fp32": (flash_paged_decode_attention,
                        _paged(32, 1281, 16, 64, f32)),
    "paged-cell-bf16": (flash_paged_decode_attention,
                        _paged(32, 1281, 16, 64, bf16)),
    "paged-large-int8": (flash_paged_decode_attention,
                         _paged(8, 641, 32, 96, i8, mb=32)),
    # the cell's pool as its scan_layers model holds it, all 24 layers in
    # one leaf (3.75 GiB each of K and V) and the layer an argument; and
    # an int8 one with its stacked scales
    "paged-cell-stacked-fp32": (flash_paged_decode_attention,
                                _paged(32, 1281, 16, 64, f32, layers=24)),
    "paged-stacked-int8": (flash_paged_decode_attention,
                           _paged(8, 641, 32, 96, i8, mb=32, layers=4)),
    # the LFM2 cell: one call of the attention layer takes a row's 8 KV
    # heads as 8 pairs of 4 query heads at 4096 positions
    "flash-grad-lfm2": (_flash_grad, _qkv(8, 4096, 64, bf16, h=4)),
    # the backward at the other shapes the forward accepts: fp32 operands,
    # one block under 128, and a head's whole dq resident at 8192 x 128
    "flash-grad-fp32": (_flash_grad, _qkv(2, 1024, 64, f32)),
    "flash-grad-short": (_flash_grad, _qkv(2, 64, 64, bf16, h=4)),
    "flash-grad-long": (_flash_grad, _qkv(1, 8192, 128, bf16, h=2)),
    # its experts: 2048 -> 1792 (w1, w3) and 1792 -> 2048 (w2), 8 groups,
    # 65,536 rows at worst; forward and both backward products
    "grouped-up": (grouped_matmul, _grouped(2048, 1792)),
    "grouped-grad-up": (_grouped_grad, _grouped(2048, 1792)),
    "grouped-grad-down": (_grouped_grad, _grouped(1792, 2048)),
    # the Ling serving cell: the delta-rule decode step over 32 slots of 32
    # heads of 128 x 128, and its experts at decode rows (2560 -> 768 and
    # back, 128 held, 256 assignments at worst, tiles of 16 rows)
    "kda-decode-cell": (kda_decode_step, _kda(32)),
    "grouped-decode-up": (_grouped_small, [
        ((buffer_rows(256, 128, 16), 2560), bf16), ((128, 2560, 768), bf16),
        ((128,), i32)]),
    "grouped-decode-down": (_grouped_small, [
        ((buffer_rows(256, 128, 16), 768), bf16), ((128, 768, 2560), bf16),
        ((128,), i32)]),
    # the granite serving cell: the state-space decode step over 64 slots
    # of 64 heads of 64 x 128 (a layer's own leaf, and the 36 Mamba layers
    # in one leaf of 4.9 GB), and the grouped paged decode, 32 query heads
    # on 8 KV heads of 64 in the 4 attention layers' stacked bf16 pool
    "ssd-decode-cell": (ssd_decode_step, _ssd(64)),
    "ssd-decode-cell-stacked": (
        lambda *a: ssd_decode_step(*a[:-1], layer=a[-1]), _ssd(64, 36)),
    "paged-grouped-cell-bf16": (
        flash_paged_decode_attention,
        _paged(64, 9217, 16, 64, bf16, h=8, mb=144, layers=4, groups=4)),
    "paged-grouped-int8": (
        flash_paged_decode_attention,
        _paged(8, 641, 32, 64, i8, h=8, mb=32, layers=2, groups=4)),
    # the paged prefill kernel: the serving cell's largest chunk step of
    # 64 tokens (8 rows, fp32, all 24 layers in one leaf), the speculative
    # verify step's k + 1 = 5 tokens, and granite's chunk step (4 rows of
    # 512 tokens, 32 query heads on 8 KV heads, its 4 attention layers'
    # bf16 pool of 9216 blocks and 144 table entries)
    "paged-prefill-cell-fp32": (_prefill,
                                _paged_chunk(8, 64, 1281, 64, f32)),
    "paged-prefill-verify": (_prefill, _paged_chunk(32, 5, 1281, 64, f32)),
    "paged-prefill-grouped-bf16": (
        _prefill, _paged_chunk(4, 512, 9217, 64, bf16, h=8, mb=144,
                               layers=4, groups=4)),
    # the kanana serving cell: the latent decode kernel over a layer's own
    # leaf (the leading dense layer) and over the scanned layers' stacked one
    "latent-decode-cell": (_latent, _latent_pool()),
    "latent-decode-cell-stacked": (_latent, _latent_pool(15)),
    # train_lm's head: 2 sequences of 2048 tokens, vocab 32000
    "ce-forward": (fused_softmax_cross_entropy,
                   [((4096, 32000), f32), ((4096,), i32)]),
    "ce-gradient": (_ce_grad, [((4096, 32000), f32), ((4096,), i32)]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = CASES[case]
    assert _compile(one_chip, fn, *shapes) >= 1


@pytest.mark.parametrize("case", ["flash-grad-cell", "flash-grad-lfm2"])
def test_gradient_is_two_kernels(one_chip, case):
    """The differentiated call is the forward kernel and ``attention_bwd``
    and nothing of XLA's over ``(T, T)`` scores."""
    fn, shapes = CASES[case]
    text = _compiled_text(one_chip, fn, *shapes)
    assert text.count("tpu_custom_call") == 2
    t = shapes[0][0][1]
    assert f"{t},{t}]" not in text


def test_forward_outgrows_the_gate(one_chip):
    """T=8192 fp32 was refused while the forward kept one head's whole K
    and V in VMEM (16.25 MiB against a 16 MiB limit).  With the key blocks
    on the grid it compiles."""
    assert _compile(one_chip, flash_attention, *_qkv(1, 8192, 64, f32)) == 1


@pytest.mark.parametrize("block_size,dtype,hidden,admitted", [
    (16, f32, 1024, True),        # the serving cell
    (8, f32, 1024, True), (4, f32, 1024, False),
    (16, bf16, 1024, True), (8, bf16, 1024, False),
    (32, i8, 1536, True), (16, i8, 1024, False),
    (16, f32, 1088, False),       # 17 heads of 64: not whole lanes
])
def test_paged_gate_reads_tile_and_width(monkeypatch, block_size, dtype,
                                         hidden, admitted):
    """``auto`` on a TPU takes the paged decode kernel when a block is
    whole tiles of the pool's dtype and ``H * D`` whole lanes; the pool's
    size is nothing to it."""
    import bigdl_tpu.nn.attention as attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    attn = attention.MultiHeadAttention(hidden, hidden // 64, causal=True)
    assert attn._flash_paged_ok(block_size, dtype) is admitted
    monkeypatch.setattr(attention, "_on_tpu", lambda: False)
    assert not attn._flash_paged_ok(block_size, dtype)


def _cell_paged_programs(one_chip, monkeypatch, scan_layers, served=False,
                         rows=2, tpu=True):
    """The engine's paged programs at the serving cell's widths and pool
    (32 slots, 1280 blocks of 16 and the trash block, 64 table entries;
    two layers, a small vocabulary), lowered for the described chip with
    the gate seeing a TPU (or, ``tpu=False``, the CPU's gather path):
    ``{"decode": ..., "chunk": ...}`` (the chunk program at ``rows`` rows
    of 64 tokens) and the pool's bytes.  ``served`` hands them the tree
    ``ServedWeights`` builds where the backend is a TPU, in place of the
    float32 weights."""
    import bigdl_tpu.nn.attention as attention
    from bigdl_tpu.serving import generation
    from bigdl_tpu.serving.generation import paged_generate_steps

    monkeypatch.setattr(attention, "_on_tpu", lambda: tpu)
    slots, blocks, bs, entries = 32, 1280, 16, 64
    model = attention.TransformerLM(512, 1024, 16, 2, max_len=1024,
                                    scan_layers=scan_layers)
    model.build(jax.ShapeDtypeStruct((2, 16), i32))
    assert model.blocks[0].attn._flash_paged_ok(bs, f32) is tpu

    def described(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def knobs(n):
        return arg(f32, n), arg(i32, n), arg(f32, n), arg(i32, n)

    pool = jax.eval_shape(lambda: model.init_paged_cache(blocks, bs, f32))
    pool_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pool))
    pool = jax.tree.map(described, pool)
    weights = model.weights()
    if served:
        monkeypatch.setattr(generation, "_backend", lambda: "tpu")
        weights = generation.ServedWeights(model)()
        assert weights["head"].dtype == bf16
    weights = jax.tree.map(described, weights)
    chunk, decode, _ = paged_generate_steps(model, f32)
    return {
        "decode": decode.lower(
            weights, pool, arg(i32, slots), arg(i32, slots),
            arg(i32, slots, entries), *knobs(slots)),
        "chunk": chunk.lower(
            weights, pool, arg(i32, rows, 64), arg(i32, rows),
            arg(i32, rows), arg(i32, rows, entries), *knobs(rows)),
    }, pool_bytes


def test_decode_step_is_one_kernel_a_layer(one_chip, monkeypatch):
    """The engine's ``jit_decode`` at the serving cell's widths and pool
    (two layers, a small vocabulary), compiled for the described chip
    with the gate seeing a TPU: one Pallas kernel a layer, no gathered
    ``(slots, max_blocks * bs, H, D)`` context in any shape, and the pool
    leaves neither copied nor transposed on their way to the kernel."""
    import re

    programs, _ = _cell_paged_programs(one_chip, monkeypatch, False)
    text = programs["decode"].compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert text.count("flash_paged_decode_attention") >= 2
    assert not re.search(r"\[32,(1024|64,16),", text)
    assert not re.search(r"f32\[1281,16,1024\]\S* (copy|transpose)\(", text)


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_scan_layout_leaves_the_pool_where_it_lies(one_chip, monkeypatch,
                                                   program):
    """The same two programs of a ``scan_layers`` model: the stacked pool
    rides the layer loop's carry and is updated in place.  No copy, slice
    or update-slice, fused or not, has the stacked leaf or one layer's
    leaf as its result, the loop body holds the one Pallas kernel (the
    chunk program's since its attention reads the pool through
    ``flash_paged_prefill_attention``; it held none while it gathered),
    and the program's temporaries are a small part of the pool (they held
    a second pool while it went through the loop as ``xs`` and ``ys``:
    0.49 and 0.51 GiB beside a pool of 0.31)."""
    import re

    programs, pool_bytes = _cell_paged_programs(one_chip, monkeypatch, True)
    compiled = programs[program].compile()
    text = compiled.as_text()
    leaf = r"f32\[(2,)?1281,16,1024\]"
    moved = r"copy|dynamic-slice|dynamic-update-slice"
    assert re.search(leaf + r"\S* fusion\(", text), "the scatter is there"
    assert not re.search(leaf + rf"\S* ({moved})\(", text)
    assert not re.search(rf"%\S*({moved})\S* = " + leaf, text)
    assert " while(" in text
    assert text.count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes / 8


def test_chunk_step_reads_the_pool_through_the_prefill_kernel(one_chip,
                                                              monkeypatch):
    """The cell's chunk program (scanned layers, the served tree at the
    cell's default precision) at the buckets its traffic runs, 1, 2, 4
    and 8 rows: accepted for the chip, ONE ``flash_paged_prefill_attention``
    call in the layer loop, no gathered ``(rows, 1024, 16, 64)`` or
    ``(rows, 64, 16, 1024)`` context in any shape (the gather program, the
    control, holds them from 2 rows up), and temporaries no larger than
    the gather program's.  The largest over the buckets is compared: a
    process reserves for its programs together, and at one bucket the two
    programs' prefetch buffers for the head's weight differ by 64 KiB
    either way (the gather's ``(8, 64, 16, 1024)`` context is 32 MiB at 8
    rows)."""
    import re

    temps = {True: [], False: []}
    for rows in (1, 2, 4, 8):
        gathered = rf"\[{rows},(1024,16|64,16,1024)\]"
        for kernel in (True, False):
            with jax.default_matmul_precision("default"):
                programs, _ = _cell_paged_programs(
                    one_chip, monkeypatch, True, served=True, rows=rows,
                    tpu=kernel)
                compiled = programs["chunk"].compile()
            text = compiled.as_text()
            temps[kernel].append(
                compiled.memory_analysis().temp_size_in_bytes)
            found = re.search(gathered, text)
            if kernel:
                comps, entry = _computations(text)
                (loop,) = [line for line in comps[entry]
                           if " while(" in line]
                body = re.search(r"body=%([\w.\-]+)", loop).group(1)
                calls = [line for line in _reached(comps, body)
                         if "custom_call_target=\"tpu_custom_call\"" in line]
                assert len(calls) == 1
                assert "flash_paged_prefill_attention" in calls[0]
                assert not found
            elif rows > 1:
                assert found
    assert max(temps[True]) <= max(temps[False])


def test_granite_chunk_attention_reads_the_pool_through_the_kernel(
        one_chip, monkeypatch):
    """Granite's attention layer on a chunk step of its ``chunk_rows``
    of 4 rows of 512 tokens (32 query heads on 8 KV heads of 64, the
    multiplier as the softmax scale, the 4 attention layers' stacked bf16
    pool of 9216 blocks and 144 table entries, the layer traced): one
    prefill kernel and no gathered ``(4, 2304, ...)`` context, and
    temporaries no larger than the gather's."""
    import re

    import bigdl_tpu.nn.attention as attention

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(kernel):
        monkeypatch.setattr(attention, "_on_tpu", lambda: kernel)
        attn = attention.MultiHeadAttention(2048, 32, causal=True,
                                            num_kv_heads=8, bias=False,
                                            scale=0.015625)
        params = jax.eval_shape(lambda k: attn.setup(k, None)[0],
                                jax.random.key(0))
        params = jax.tree.map(lambda x: arg(bf16, *x.shape), params)
        pool = {name: arg(bf16, 4, 9217, 16, 512) for name in "kv"}
        with jax.default_matmul_precision("default"):
            return jax.jit(
                lambda p, pool, x, tables, start, lengths, layer:
                attn.apply_paged(p, x, pool, tables, start, lengths, layer),
                donate_argnums=(1,)).lower(
                    params, pool, arg(bf16, 4, 512, 2048), arg(i32, 4, 144),
                    arg(i32, 4), arg(i32, 4), arg(i32)).compile()

    kernel, gather = step(True), step(False)
    text = kernel.as_text()
    assert text.count("flash_paged_prefill_attention") >= 1
    assert text.count("tpu_custom_call") == 1
    assert not re.search(r"\[4,(2304,8|144,16,512)\]", text)
    assert re.search(r"\[4,(2304,8|144,16,512)\]", gather.as_text())
    assert kernel.memory_analysis().temp_size_in_bytes \
        <= gather.memory_analysis().temp_size_in_bytes


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_served_weights_reach_the_products_unconverted(one_chip, monkeypatch,
                                                       program):
    """ISSUE 40: at the cell's default matmul precision the two programs,
    fed float32 weights, turn each stacked weight leaf into bfloat16 inside
    every call (the control: the cell's trace shows these as
    ``convert.12/.14/.10/.8``, 2.59 ms a call); fed the served tree, no
    ``convert`` has a float32 weight as its operand, and the programs'
    temporaries lose the bfloat16 copies.  (Compiled text names an
    operand without its type, so the copies are found by their result:
    a bfloat16 array of a weight's shape made by a ``convert``.)"""
    import re

    weight = (r"= bf16\[(2,)?(3072,1024|1024,1024|4096,1024|1024,4096"
              r"|512,1024)\]\S* convert\(")
    with jax.default_matmul_precision("default"):
        fp32, _ = _cell_paged_programs(one_chip, monkeypatch, True)
        fp32 = fp32[program].compile()
        served, _ = _cell_paged_programs(one_chip, monkeypatch, True, True)
        served = served[program].compile()
    assert len(re.findall(weight, fp32.as_text())) >= 4
    assert not re.search(weight, served.as_text())
    assert served.memory_analysis().temp_size_in_bytes \
        < fp32.memory_analysis().temp_size_in_bytes


def _computations(text):
    """Compiled HLO text as ``{computation name: its lines}`` and the
    entry computation's name."""
    import re

    comps, entry, lines = {}, None, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%(\S+) \(.*\) -> .* \{$", line)
        if head:
            lines = comps[head.group(2)] = []
            entry = head.group(2) if head.group(1) else entry
        elif lines is not None:
            lines.append(line)
    return comps, entry


def _reached(comps, root, but=()):
    """The lines of ``root`` and of every computation it calls (fusions,
    loop bodies, comparators, branches), not going into ``but``."""
    import re

    seen, todo, lines = set(but), [root], []
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        lines += comps[name]
        todo += [c for line in comps[name]
                 for c in re.findall(r"%([\w.\-]+)", line) if c in comps]
    return lines


@pytest.mark.parametrize("scan_layers", [False, True],
                         ids=["unrolled", "scan"])
@pytest.mark.parametrize("program,rows", [("decode", 32), ("chunk", 2)])
def test_sampler_sorts_only_in_its_sampled_branch(one_chip, monkeypatch,
                                                  program, rows,
                                                  scan_layers):
    """The sampler of the cell's two programs is a conditional on the
    chip (not a select that runs both sides): the vocabulary's sort lies
    in the sampled branch and nowhere else, the greedy branch and the
    program around the conditional hold neither a sort nor an
    element-wise gather with a ``(rows, vocab)`` result, and the sampled
    branch gathers nothing of that size either: its ranked logits are the
    sort's own keys."""
    import re

    programs, _ = _cell_paged_programs(one_chip, monkeypatch, scan_layers)
    comps, entry = _computations(programs[program].compile().as_text())
    everything = _reached(comps, entry)
    (cond,) = [line for line in everything if " conditional(" in line]
    assert re.search(rf"\(s32\[{rows}\]\S*\) conditional\(", cond)
    greedy, sampled = re.search(
        r"branch_computations=\{%([\w.\-]+), %([\w.\-]+)\}", cond).groups()
    # an element at a time: the chunk program's pick of each row's last
    # position out of ``(rows, tokens, vocab)`` is a gather of whole rows
    wide_gather = (rf"= \S*\[{rows},512\]\S* gather\(.*"
                   r"slice_sizes=\{1(,1)*\}")
    outside = _reached(comps, entry, but=(greedy, sampled)) \
        + _reached(comps, greedy)
    inside = _reached(comps, sampled)
    assert len(outside) + len(inside) == len(everything)
    assert not [line for line in outside
                if " sort(" in line or re.search(wide_gather, line)]
    assert sum(" sort(" in line for line in inside) == 1
    assert not [line for line in inside if re.search(wide_gather, line)]


def test_latent_rows_are_fetched_in_whole_tiles(one_chip):
    """Why the latent leaf says 640 columns for 576 values: the chip
    stores a row in tiles of 128 columns whatever the leaf says, and the
    kernel's DMA of a block 576 wide is refused ("must be aligned to
    tiling (128)"); ``LatentAttention._kernel_ok`` keeps such a leaf
    (Ling's) on the gather."""
    with pytest.raises(Exception, match="aligned to tiling"):
        _compile(one_chip, _latent, *_latent_pool(width=576))


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_latent_scan_leaves_the_pool_where_it_lies(one_chip, monkeypatch,
                                                   program):
    """The engine's paged programs of ``models/kanana.py`` at the cell's
    widths (three layers, two of them scanned; 8192 blocks of 16; a small
    vocabulary): the stacked latent leaf rides the layer loop's carry
    beside the counts and is updated in place.  No copy, slice or
    update-slice has the stacked leaf or a layer's leaf as its result,
    the decode program holds no gather of a whole table (no ``(32,
    1024, 16, 640)`` or ``(32, 16384, 640)`` in any shape) and one latent
    kernel a layer, the chunk program's loop over context blocks is a
    ``while`` inside the layer loop, and the temporaries are a small part
    of the pool."""
    import re

    import bigdl_tpu.nn.attention as attention
    from bigdl_tpu.models.kanana import Kanana
    from bigdl_tpu.serving.generation import paged_generate_steps

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    model = Kanana(512, 2048, 3, 1, 6144, 768, 32, 128, 6, (0, 16), 1536,
                   2.448, max_len=16384, dtype=bf16)
    weights, _ = jax.eval_shape(
        lambda k: model.setup(k, jax.ShapeDtypeStruct((1, 16), i32)),
        jax.random.key(0))

    def described(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def knobs(n):
        return arg(f32, n), arg(i32, n), arg(f32, n), arg(i32, n)

    pool = jax.eval_shape(lambda: model.init_paged_cache(8192, 16))
    pool_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pool))
    pool, weights = jax.tree.map(described, (pool, weights))
    chunk, decode, _ = paged_generate_steps(model, f32)
    if program == "decode":
        lowered = decode.lower(weights, pool, arg(i32, 32), arg(i32, 32),
                               arg(i32, 32, 1024), *knobs(32))
    else:
        lowered = chunk.lower(weights, pool, arg(i32, 2, 512), arg(i32, 2),
                              arg(i32, 2), arg(i32, 2, 1024), *knobs(2))
    compiled = lowered.compile()
    text = compiled.as_text()
    leaf = r"bf16\[(2,)?2049,16,640\]"
    moved = r"copy|dynamic-slice|dynamic-update-slice"
    assert not re.search(leaf + rf"\S* ({moved})\(", text)
    assert not re.search(rf"%\S*({moved})\S* = " + leaf, text)
    assert not re.search(r"\[(32|2),(1024,16|16384),640\]", text)
    # the layer loop, and in the chunk program a row's loop over context
    # blocks inside it and inside the leading layer (and the rows' own)
    assert text.count(" while(") >= (1 if program == "decode" else 3)
    kernels = text.count("latent_paged_decode_attention")
    assert (kernels >= 2) == (program == "decode")
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes / 8


def test_lm_gradient_through_flash_matches_plain():
    """Finding 1 of ISSUE 22: ``jax.grad`` through a ``TransformerLM``
    whose attention takes the flash kernel (interpret mode here, ``auto``
    on a TPU) used to raise; with the kernel's ``custom_vjp`` it agrees
    with the plain path."""
    from bigdl_tpu.nn.attention import TransformerLM
    from bigdl_tpu.utils.random_generator import RNG

    def loss_and_grads(mode):
        RNG.set_seed(0)
        model = TransformerLM(64, 32, 4, 2, max_len=16, scan_layers=True)
        for block in model.blocks:
            block.attn.use_flash = mode
        model.build(jax.ShapeDtypeStruct((2, 16), i32))
        x = jnp.asarray(
            np.random.default_rng(0).integers(0, 64, (2, 16)), i32)

        def loss(p):
            logits, _ = model.apply(p, (), x, training=True,
                                    rng=jax.random.key(0))
            return jnp.mean(jnp.square(logits))

        return jax.value_and_grad(loss)(model.parameters()[0])

    loss_plain, grads_plain = loss_and_grads("never")
    loss_flash, grads_flash = loss_and_grads("interpret")
    np.testing.assert_allclose(loss_flash, loss_plain, rtol=1e-5)
    for got, want in zip(jax.tree.leaves(grads_flash),
                         jax.tree.leaves(grads_plain)):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-6)
