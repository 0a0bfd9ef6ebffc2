"""Attention and transformer blocks.

No reference analogue -- the reference is a pre-transformer codebase
(SURVEY.md section 5 'Long-context: Absent') -- but the north star requires
sequence-scale capability, so the transformer stack is first-class here.
Distribution: see parallel/ring_attention.py (sequence parallelism) and
parallel/tp.py (tensor parallelism).

Layout: (N, T, D); heads split last.  bf16-friendly: softmax in fp32.
"""

import math
import re
from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.containers import (ScanLayers, resolve_checkpoint_policy,
                                     stack_layer_trees, unstack_layer_trees)
from bigdl_tpu.nn.initialization import Xavier, Zeros
from bigdl_tpu.nn.linear import Linear, matmul_t
from bigdl_tpu.nn.module import Container, Module, child_rng
from bigdl_tpu.nn.normalization import Dropout, LayerNorm


def dot_product_attention(q, k, v, causal=False, mask=None, scale=None):
    """Plain attention; q,k,v (..., T, H, Dh) with heads on axis -2.

    Softmax runs in fp32 regardless of input dtype (bf16-safe).
    """
    *_, tq, h, d = q.shape
    scale = scale or (1.0 / math.sqrt(d))
    scores = jnp.einsum("...qhd,...khd->...hqk", q, k).astype(jnp.float32)
    scores = scores * scale
    if causal:
        tk = k.shape[-3]
        qpos = jnp.arange(tq)[:, None]
        kpos = jnp.arange(tk)[None, :]
        scores = jnp.where(kpos <= qpos, scores, -jnp.inf)
    if mask is not None:
        scores = jnp.where(mask, scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("...hqk,...khd->...qhd", weights, v)


def _on_tpu():
    return jax.devices()[0].platform == "tpu"


class MultiHeadAttention(Module):
    """Self-attention with fused qkv projection (one big MXU matmul).

    ``num_kv_heads`` (default: ``num_heads``) makes it GROUPED-QUERY
    attention: query head ``g`` reads key/value head ``g // groups``,
    ``groups = num_heads // num_kv_heads``; K and V are projected and
    paged at ``num_kv_heads * head_dim`` (``kv_width``) and the paged
    decode kernel fetches a block once for a group's query heads.
    ``bias=False`` leaves the two biases out of the parameter tree;
    ``scale`` replaces the softmax scale ``head_dim ** -0.5`` (the
    queries are multiplied by the ratio once, after their projection, so
    every path and kernel below sees the usual scale)."""

    def __init__(self, hidden_size: int, num_heads: int, causal: bool = False,
                 dropout: float = 0.0, seq_axis_name: Optional[str] = None,
                 seq_mode: str = "ring", use_flash: str = "auto", name=None,
                 num_kv_heads: Optional[int] = None, bias: bool = True,
                 scale: Optional[float] = None):
        super().__init__(name)
        assert hidden_size % num_heads == 0
        assert seq_mode in ("ring", "ulysses")
        assert use_flash in ("auto", "never", "always", "interpret")
        self.hidden_size = hidden_size
        self.num_heads = num_heads
        self.head_dim = hidden_size // num_heads
        self.num_kv_heads = num_heads if num_kv_heads is None \
            else int(num_kv_heads)
        assert num_heads % self.num_kv_heads == 0, \
            (num_heads, self.num_kv_heads)
        self.groups = num_heads // self.num_kv_heads
        #: what a token's K (and V) row holds: the width the paged pool
        #: is stored at
        self.kv_width = self.num_kv_heads * self.head_dim
        assert self.groups == 1 or seq_axis_name is None, \
            "grouped-query attention has no sequence-parallel path"
        self.bias = bool(bias)
        self.scale = None if scale is None else float(scale)
        self.causal = causal
        self.dropout = dropout
        #: when set, apply() is assumed to run inside shard_map with the
        #: sequence sharded over this mesh axis; ``seq_mode`` picks the
        #: strategy: "ring" (ppermute K/V rotation) or "ulysses"
        #: (all-to-all head re-sharding, parallel/ulysses.py).
        self.seq_axis_name = seq_axis_name
        self.seq_mode = seq_mode
        #: "auto": the Pallas flash kernel (ops/flash_attention.py) on TPU
        #: when T is block-aligned (the forward streams K/V a block at a
        #: time, so no length is too long for it; the paged kernels
        #: stream a slot's blocks and ask only that a block be whole
        #: tiles); plain attention
        #: otherwise.  "interpret" forces the kernel in
        #: interpreter mode (CPU tests).
        self.use_flash = use_flash

    @staticmethod
    def _flash_block_ok(t):
        """Whether T tiles into flash blocks: the kernel takes a short
        sequence as one block, so any sublane-aligned ``t < 128`` is
        block-alignable (a single (t, d) VMEM tile); longer sequences
        must tile exactly into 128-blocks.  (The old
        ``t % 128`` test rejected EVERY short sequence even though the
        kernel handles them -- tests/test_flash_attention.py pins the
        short-T flash-vs-plain agreement.)"""
        if t < 128:
            return t % 8 == 0
        return t % 128 == 0

    def _flash_ok(self, t):
        if self.use_flash == "never" or self.seq_axis_name is not None:
            return False
        if self.use_flash in ("always", "interpret"):
            return True
        return self._flash_block_ok(t) and _on_tpu()

    def setup(self, rng, input_spec):
        d = self.hidden_size
        rows = d + 2 * self.kv_width
        init = Xavier()
        params = {
            "qkv_weight": init.init(child_rng(rng, 0), (rows, d), d, d),
            "qkv_bias": jnp.zeros((rows,), jnp.float32),
            "out_weight": init.init(child_rng(rng, 1), (d, d), d, d),
            "out_bias": jnp.zeros((d,), jnp.float32),
        }
        if not self.bias:
            del params["qkv_bias"], params["out_bias"]
        return params, ()

    def _with_bias(self, y, params, name):
        return y + params[name].astype(y.dtype) if name in params else y

    def _split_qkv(self, qkv):
        """The fused projection's ``(q (.., H Dh), k, v (.., Hkv Dh))``,
        the queries brought to the softmax scale asked for."""
        q, k, v = jnp.split(
            qkv, [self.hidden_size, self.hidden_size + self.kv_width],
            axis=-1)
        if self.scale is not None:
            q = q * jnp.asarray(self.scale * math.sqrt(self.head_dim),
                                q.dtype)
        return q, k, v

    def _grouped(self, x):
        """K or V ``(..., Hkv, Dh)`` as the query heads read it ``(..., H,
        Dh)``: head ``g`` is KV head ``g // groups``."""
        return x if self.groups == 1 else jnp.repeat(x, self.groups, axis=-2)

    def _project_qkv(self, params, input):
        """Fused qkv projection; ONE implementation for the full-sequence
        and paged (chunk prefill/decode) paths, so the int8 branch covers
        generation with no second code path."""
        dt = input.dtype
        if "qkv_weight_q" in params:
            # post-training-quantized projections (nn/quantized): the
            # fused qkv and output matmuls -- the layer's MXU work --
            # contract in int8; attention itself stays in the activation
            # dtype (softmax in fp32 as always)
            from bigdl_tpu.nn.quantized import int8_matmul

            return self._with_bias(
                int8_matmul(input, params["qkv_weight_q"],
                            params["qkv_scale"]), params,
                "qkv_bias").astype(dt)
        return self._with_bias(matmul_t(input, params["qkv_weight"]),
                               params, "qkv_bias")

    def _project_out(self, params, y, dt):
        if "out_weight_q" in params:
            from bigdl_tpu.nn.quantized import int8_matmul

            return self._with_bias(
                int8_matmul(y, params["out_weight_q"], params["out_scale"]),
                params, "out_bias").astype(dt)
        return self._with_bias(matmul_t(y, params["out_weight"]),
                               params, "out_bias")

    # ----- paged KV-cache decode mode --------------------------------------- #
    def init_paged_cache(self, num_blocks: int, block_size: int,
                         dtype=jnp.float32):
        """Per-layer K/V BLOCK POOL for paged decode: fixed-shape
        ``(num_blocks, block_size, kv_heads * head_dim)`` zero tensors that
        ``_apply_paged`` reads and writes THROUGH per-sequence block
        tables (serving/paging.py).  The leading axis is physical
        blocks, not slots: memory scales with tokens actually resident,
        not ``slots x max_len`` worst case.  The caller includes the
        trash block in ``num_blocks`` (by convention the last id).

        Block ``b`` of this layer is ``leaf[b]``.  A ``scan_layers``
        model stacks its layers' leaves (``TransformerLM.
        init_paged_cache``) and block ``b`` of layer ``l`` is then
        ``leaf[l, b]``; ``_apply_paged`` takes either.

        KV heads and head_dim share the last axis (``kv_width = Hkv * D``:
        a grouped-query layer's pool is sized by its KV heads, not its
        query heads) so that a block is one contiguous piece of device
        memory; the decode kernel wants ``Hkv * D`` a multiple of 128
        (``_flash_paged_ok``).  A TPU array's last two axes
        are tiled (8 x 128 fp32), and the compiler stores a
        ``(..., heads, 64)`` array with the BLOCK axis on the lanes
        rather than pad 64 to 128: a block's values then lie 512 bytes
        apart, nothing can fetch one, and every step re-lays the whole
        pool out and back (PERF.md section 6, PR 32).

        ``dtype=jnp.int8`` selects the QUANTIZED block layout: int8
        K/V payloads plus fp32 absmax scales ``(num_blocks, block_size,
        heads)`` -- one scale per (position, head) ``head_dim`` vector,
        i.e. the ops/quantization.py blockwise format with the
        quantization block = ``head_dim``.  The scale leaves keep the
        payload's rank so every pool consumer that tree-maps by rank
        (block copies, donation, byte accounting) handles both layouts
        with one code path."""
        from bigdl_tpu.nn.generation_state import allocate

        return allocate(self.state_spec(dtype), int(num_blocks) - 1,
                        block_size)

    def state_spec(self, dtype=jnp.float32):
        """What this layer keeps between the steps of paged generation
        (nn/generation_state.py): K and V a token, in blocks; an int8 pool
        adds their scales."""
        from bigdl_tpu.nn.generation_state import BLOCK, StateSpec

        if jnp.dtype(dtype) == jnp.dtype(jnp.int8):
            payload = StateSpec(BLOCK, (self.kv_width,), jnp.int8)
            scale = StateSpec(BLOCK, (self.num_kv_heads,), jnp.float32)
            return {"k": payload, "v": payload,
                    "k_scale": scale, "v_scale": scale}
        leaf = StateSpec(BLOCK, (self.kv_width,), dtype)
        return {"k": leaf, "v": leaf}

    def _paged_quant(self, x):
        """fp K/V rows ``(rows, heads * head_dim)`` -> (int8 payload,
        fp32 scales ``(rows, heads)``) through the blockwise wire
        kernel (one absmax scale per head_dim vector; non-finite
        vectors drop to exact zero, same contract as the wire path)."""
        from bigdl_tpu.ops.quantization import quantize_blockwise

        q8, sc = quantize_blockwise(x.reshape(-1), self.head_dim,
                                    scale_dtype=jnp.float32)
        return q8.reshape(x.shape), sc.reshape(x.shape[:-1]
                                               + (self.num_kv_heads,))

    def _paged_dequant(self, q8, sc, dt):
        """Inverse of ``_paged_quant`` over gathered context blocks:
        ``(..., heads * head_dim)`` int8 + ``(..., heads)`` scales ->
        ``dt`` values."""
        from bigdl_tpu.ops.quantization import dequantize_blockwise

        return dequantize_blockwise(q8, sc, self.head_dim).astype(dt)

    def _flash_paged_ok(self, block_size, dtype):
        """Whether paged attention goes through the Pallas kernels --
        decode through ``flash_paged_decode_attention``, a chunk of a
        pool that is not int8 through ``flash_paged_prefill_attention``:
        in ``auto`` on a TPU, when a block is whole tiles of the pool's
        dtype -- 8 rows of fp32, 16 of bf16, 32 of int8 -- and a row of
        it, ``Hkv * D`` values, whole lanes of 128."""
        if self.use_flash == "never" or self.seq_axis_name is not None:
            return False
        if self.use_flash in ("always", "interpret"):
            return True
        tile_rows = 32 // jnp.dtype(dtype).itemsize
        return _on_tpu() and block_size % tile_rows == 0 \
            and self.kv_width % 128 == 0

    def apply_paged(self, params, input, pool, tables, pos, lengths=None,
                    layer=None):
        """``_apply_paged`` under the name a served model's layers call
        their mixers by (``models/ling.py`` ``ServedLM._paged_layer``)."""
        return self._apply_paged(params, input, pool, tables, pos, lengths,
                                 layer)

    def _apply_paged(self, params, input, pool, tables, pos, lengths,
                     layer=None):
        """Incremental attention against a paged K/V pool.  Returns
        ``(y, new_pool)``.  ``tables`` maps each row's LOGICAL block
        index to a physical pool block, padded with the trash block id
        (the pool's last block), so the compiled step never sees how
        long any sequence really is.

        Where a block lies is told by what is handed in.  With
        ``layer=None`` ``pool`` is this layer's own leaves ``(NB, bs,
        Hkv * D)`` and block ``b`` is ``leaf[b]`` (the unrolled layout).
        With ``layer`` an int32 scalar, traced inside the layer loop,
        ``pool`` is the layer-STACKED leaves ``(L, NB, bs, Hkv * D)`` of a
        layer-scanned model and block ``b`` is ``leaf[layer, b]``: rows
        are written ``.at[layer, block, offset]``, a row's context is one
        gather over ``(layer, tables)`` and the two kernels index
        ``(layer, block)`` themselves.  ``leaf[layer]`` is never formed (it
        would be a copy of a layer's whole pool, 84 MB in the serving
        cell), so the loop that carries the leaf updates it in place and
        no program copies the pool.  ``new_pool`` has the shape of
        ``pool`` either way.

        Two shapes:

        - CHUNK PREFILL (``lengths`` an ``(N,)`` int vector): ``input``
          is one fixed-size chunk per row ``(N, Tc, D)`` whose first
          ``lengths[i]`` tokens are real and start at absolute position
          ``pos[i]``; K/V scatter token-by-token through the table
          (padding tokens redirect to the trash block), then each token
          attends to the row's context up to its own absolute position
          -- all previously-filled blocks (including shared prefix
          blocks it never computed) plus its own earlier tokens, read
          back from the pool.  Where ``_flash_paged_ok`` admits the pool
          and it is not int8, ``flash_paged_prefill_attention`` fetches a
          row's blocks through its table only up to its frontier;
          otherwise attention gathers the row's FULL mapped context and
          masks it.
        - DECODE (``lengths is None``): ``input`` is one token per row
          ``(N, 1, D)`` written at ``pos[i]``; rows whose table is all
          trash (empty slots, rows mid-prefill) write garbage into the
          trash block and read garbage out -- harmless, because a live
          row masks every position past its frontier and never maps the
          trash block below it.  Where
          ``_flash_paged_ok`` admits the pool, the decode kernel reads a
          row's blocks up to ``pos``; otherwise the gather and mask as
          above.
        """
        n, t, d = input.shape
        dt = input.dtype
        cdt = pool["k"].dtype
        quant = "k_scale" in pool      # int8 payload + fp32 scale leaves
        at = () if layer is None else (layer,)
        bs = pool["k"].shape[-2]
        max_blocks = tables.shape[1]
        ctx = max_blocks * bs
        trash = pool["k"].shape[-3] - 1
        tables = jnp.asarray(tables, jnp.int32)
        pos = jnp.asarray(pos, jnp.int32)
        # q (n, t, d), k and v (n, t, kv_width)
        q, k, v = self._split_qkv(self._project_qkv(params, input))
        heads = (n, t, self.num_heads, self.head_dim)
        kvw, kv_heads = self.kv_width, self.num_kv_heads

        def scatter(phys, off, kf, vf):
            """Write one batch of K/V rows ``(rows, kv_width)`` through the
            table: quantize first on an int8 pool (payload + scales land
            at the same (block, offset) address, so the table
            indirection, COW block copies and prefix sharing are
            format-blind)."""
            if quant:
                kq, ksc = self._paged_quant(kf)
                vq, vsc = self._paged_quant(vf)
                rows = {"k": kq, "v": vq, "k_scale": ksc, "v_scale": vsc}
            else:
                rows = {"k": kf.astype(cdt), "v": vf.astype(cdt)}
            return {name: pool[name].at[at + (phys, off)].set(x)
                    for name, x in rows.items()}

        def blocks_of(leaf):
            """``leaf``'s blocks through the tables ``(n, max_blocks,
            bs, width)``: one gather, over ``(layer, tables)`` on a
            stacked leaf (``jnp.take`` with its fill mode is what a
            single layer's leaf has always lowered to)."""
            if layer is None:
                return jnp.take(leaf, tables, axis=0)
            return leaf.at[layer, tables].get(mode="fill")

        def gather_ctx(new_pool, name):
            """The row's full mapped context from the pool ``(n, ctx,
            kv heads, head_dim)``, dequantized to the compute dtype on an
            int8 pool."""
            raw = blocks_of(new_pool[name]).reshape(n, ctx, kvw)
            if quant:
                sc = blocks_of(new_pool[name + "_scale"]).reshape(
                    n, ctx, kv_heads)
                raw = self._paged_dequant(raw, sc, dt)
            return raw.astype(dt).reshape(n, ctx, kv_heads, self.head_dim)

        def gathered_attention(new_pool, mask):
            return dot_product_attention(
                q.reshape(heads), self._grouped(gather_ctx(new_pool, "k")),
                self._grouped(gather_ctx(new_pool, "v")), mask=mask)

        if lengths is not None:                           # chunk prefill
            lengths = jnp.asarray(lengths, jnp.int32)
            gpos = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
            valid = jnp.arange(t, dtype=jnp.int32)[None, :] \
                < lengths[:, None]
            logical = jnp.clip(gpos // bs, 0, max_blocks - 1)
            phys = jnp.take_along_axis(tables, logical, axis=1)
            phys = jnp.where(valid, phys, trash)
            off = gpos % bs
            new_pool = scatter(phys.reshape(n * t), off.reshape(n * t),
                               k.reshape(n * t, kvw), v.reshape(n * t, kvw))
            if self._flash_paged_ok(bs, cdt) and not quant:
                from bigdl_tpu.ops.flash_attention import \
                    flash_paged_prefill_attention

                y = flash_paged_prefill_attention(
                    q.reshape(heads), new_pool["k"], new_pool["v"], tables,
                    pos, lengths, layer=layer,
                    interpret=self.use_flash == "interpret").astype(dt)
            else:
                # (N, 1, Tc, ctx): key at logical position kp is visible to
                # the chunk token at absolute position gpos iff kp <= gpos
                mask = (jnp.arange(ctx, dtype=jnp.int32)[None, None, :]
                        <= gpos[:, :, None])[:, None]
                y = gathered_attention(new_pool, mask)
        else:                                             # one-token step
            if t != 1:
                raise ValueError(
                    f"paged decode steps take one token per row, got T={t}")
            phys = jnp.take_along_axis(
                tables, (pos // bs)[:, None], axis=1)[:, 0]
            new_pool = scatter(phys, pos % bs, k[:, 0], v[:, 0])
            if self._flash_paged_ok(bs, cdt):
                from bigdl_tpu.ops.flash_attention import \
                    flash_paged_decode_attention

                y = flash_paged_decode_attention(
                    q.reshape(heads), new_pool["k"], new_pool["v"], tables,
                    pos, new_pool.get("k_scale"), new_pool.get("v_scale"),
                    layer=layer,
                    interpret=self.use_flash == "interpret").astype(dt)
            else:
                mask = (jnp.arange(ctx, dtype=jnp.int32)[None, :]
                        <= pos[:, None])[:, None, None, :]
                y = gathered_attention(new_pool, mask)
        y = y.reshape(n, t, d)
        return self._project_out(params, y, dt), new_pool

    def apply(self, params, state, input, *, training=False, rng=None):
        n, t, d = input.shape
        dt = input.dtype
        q, k, v = self._split_qkv(self._project_qkv(params, input))
        shape = (n, t, self.num_heads, self.head_dim)
        if self.groups > 1:
            kv_shape = (n, t, self.num_kv_heads, self.head_dim)
            k = self._grouped(k.reshape(kv_shape))
            v = self._grouped(v.reshape(kv_shape))
        if self.seq_axis_name is not None and self.seq_mode == "ulysses":
            from bigdl_tpu.parallel.ulysses import ulysses_self_attention

            y = ulysses_self_attention(q.reshape(shape), k.reshape(shape),
                                       v.reshape(shape), self.seq_axis_name,
                                       causal=self.causal)
        elif self.seq_axis_name is not None:
            from bigdl_tpu.parallel.ring_attention import ring_self_attention

            y = ring_self_attention(q.reshape(shape), k.reshape(shape),
                                    v.reshape(shape), self.seq_axis_name,
                                    causal=self.causal)
        elif self._flash_ok(t):
            from bigdl_tpu.ops.flash_attention import flash_attention

            y = flash_attention(q.reshape(shape), k.reshape(shape),
                                v.reshape(shape), causal=self.causal,
                                interpret=self.use_flash == "interpret")
        else:
            y = dot_product_attention(q.reshape(shape), k.reshape(shape),
                                      v.reshape(shape), causal=self.causal)
        y = self._project_out(params, y.reshape(n, t, d), dt)
        if training and self.dropout > 0 and rng is not None:
            keep = 1.0 - self.dropout
            y = jnp.where(jax.random.bernoulli(rng, keep, y.shape),
                          y / keep, 0.0).astype(dt)
        return y, state


def rotary_embedding(x, theta: float):
    """Rotary positions on ``x (N, T, H, Dh)``, rotate-half convention:
    the head's first and second halves are the two components that
    position ``t`` turns by ``t * theta ** (-2i / Dh)``.  Computed in
    float32, returned in ``x``'s dtype."""
    t, dh = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


class GroupedQueryAttention(Module):
    """Causal self-attention of today's open models: ``num_heads`` query
    heads over ``num_kv_heads`` key/value heads (each serving
    ``num_heads // num_kv_heads`` query heads), RMSNorm with a learned
    weight over each head's width on q and on k, rotary positions, no
    bias.  Training path only (no cache).  Shares ``flash_attention``
    with ``MultiHeadAttention``: on the TPU forward and backward are its
    Pallas kernels, over K and V broadcast to a group's query heads (XLA
    sums dk and dv over the group).

    ``kv_heads_per_call``: the (row, KV head) pairs one attention call
    takes; the calls run one after the other (``lax.map``).  It bounds
    the plain path's float32 scores to ``(pairs, group, T, T)`` at a
    time; the kernels write no scores.  None: one call for everything.
    """

    def __init__(self, hidden_size: int, num_heads: int, num_kv_heads: int,
                 rope_theta: float = 10000.0, norm_eps: float = 1e-5,
                 kv_heads_per_call: Optional[int] = None,
                 use_flash: str = "auto", name=None):
        super().__init__(name)
        assert hidden_size % num_heads == 0
        assert num_heads % num_kv_heads == 0
        assert use_flash in ("auto", "never", "interpret")
        self.hidden_size = hidden_size
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = hidden_size // num_heads
        self.rope_theta = float(rope_theta)
        self.norm_eps = norm_eps
        self.kv_heads_per_call = kv_heads_per_call
        self.use_flash = use_flash

    def setup(self, rng, input_spec):
        d, dh = self.hidden_size, self.head_dim
        rows = (self.num_heads + 2 * self.num_kv_heads) * dh
        init = Xavier()
        return {
            "qkv_weight": init.init(child_rng(rng, 0), (rows, d), d, d),
            "q_norm": jnp.ones((dh,), jnp.float32),
            "k_norm": jnp.ones((dh,), jnp.float32),
            "out_weight": init.init(child_rng(rng, 1), (d, d), d, d),
        }, ()

    def _head_norm(self, x, weight):
        sq = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
        return (x * jax.lax.rsqrt(sq + self.norm_eps).astype(x.dtype)
                * weight.astype(x.dtype))

    def _attend(self, q, k, v):
        """q ``(P, T, G, Dh)``, k and v ``(P, T, 1, Dh)`` -> ``(P, T, G,
        Dh)``: every pair's ``G`` query heads against its one KV head."""
        k = jnp.broadcast_to(k, q.shape)
        v = jnp.broadcast_to(v, q.shape)
        t = q.shape[1]
        flash = self.use_flash == "interpret" or (
            self.use_flash == "auto" and _on_tpu()
            and MultiHeadAttention._flash_block_ok(t))
        if flash:
            from bigdl_tpu.ops.flash_attention import flash_attention

            return flash_attention(q, k, v, causal=True,
                                   interpret=self.use_flash == "interpret")
        return dot_product_attention(q, k, v, causal=True)

    def apply(self, params, state, input, *, training=False, rng=None):
        n, t, d = input.shape
        dt = input.dtype
        h, hkv, dh = self.num_heads, self.num_kv_heads, self.head_dim
        qkv = input @ params["qkv_weight"].astype(dt).T
        q, k, v = jnp.split(qkv, [h * dh, (h + hkv) * dh], axis=-1)
        q = self._head_norm(q.reshape(n, t, h, dh), params["q_norm"])
        k = self._head_norm(k.reshape(n, t, hkv, dh), params["k_norm"])
        q = rotary_embedding(q, self.rope_theta)
        k = rotary_embedding(k, self.rope_theta)
        v = v.reshape(n, t, hkv, dh)

        def pairs(x):                    # (N, T, Hkv, G, Dh) -> (N Hkv, T, G, Dh)
            g = x.shape[2] // hkv
            return x.reshape(n, t, hkv, g, dh).transpose(0, 2, 1, 3, 4) \
                .reshape(n * hkv, t, g, dh)

        q, k, v = pairs(q), pairs(k), pairs(v)
        per = self.kv_heads_per_call or n * hkv
        if per >= n * hkv:
            y = self._attend(q, k, v)
        else:
            assert (n * hkv) % per == 0, (n, hkv, per)
            split = lambda x: x.reshape((n * hkv // per, per) + x.shape[1:])
            y = jax.lax.map(lambda a: self._attend(*a),
                            (split(q), split(k), split(v)))
        y = y.reshape(n, hkv, t, h // hkv, dh).transpose(0, 2, 1, 3, 4) \
            .reshape(n, t, d)
        return y @ params["out_weight"].astype(dt).T, state


class TransformerBlock(Container):
    """Pre-LN block: x + MHA(LN(x)); x + MLP(LN(x))."""

    def __init__(self, hidden_size, num_heads, mlp_ratio=4, causal=True,
                 dropout=0.0, seq_axis_name=None, seq_mode="ring", name=None):
        super().__init__(name)
        self.ln1 = LayerNorm(hidden_size)
        self.attn = MultiHeadAttention(hidden_size, num_heads, causal, dropout,
                                       seq_axis_name, seq_mode)
        self.ln2 = LayerNorm(hidden_size)
        self.fc1 = Linear(hidden_size, mlp_ratio * hidden_size)
        self.fc2 = Linear(mlp_ratio * hidden_size, hidden_size)
        for m in (self.ln1, self.attn, self.ln2, self.fc1, self.fc2):
            self.add(m)

    def setup(self, rng, input_spec):
        params = {}
        for i, (key, m) in enumerate(
                [("ln1", self.ln1), ("attn", self.attn), ("ln2", self.ln2),
                 ("fc1", self.fc1), ("fc2", self.fc2)]):
            p, _ = m.setup(child_rng(rng, i), input_spec)
            params[key] = p
        return params, ()

    def _param_child_items(self, params):
        # params are keyed by ROLE ("ln1".."fc2"), not by child index --
        # align accordingly so the frozen-mask and quantizer walks reach
        # the right sublayers
        return [("ln1", self.ln1), ("attn", self.attn), ("ln2", self.ln2),
                ("fc1", self.fc1), ("fc2", self.fc2)]

    def init_paged_cache(self, num_blocks: int, block_size: int,
                         dtype=jnp.float32):
        """This block's paged K/V pool (the attention sublayer's)."""
        return self.attn.init_paged_cache(num_blocks, block_size, dtype)

    def apply_paged(self, params, input, pool, tables, pos, lengths=None,
                    layer=None):
        """Paged prefill-chunk/decode through this block; returns
        ``(out, new_pool)``.  ``pool`` is this block's own leaves, or
        with ``layer`` the layer-stacked leaves this block is layer
        ``layer`` of (see MultiHeadAttention._apply_paged)."""
        with jax.named_scope("attention"):
            h, _ = self.ln1.apply(params["ln1"], (), input)
            a, new_pool = self.attn._apply_paged(params["attn"], h, pool,
                                                 tables, pos, lengths, layer)
            x = input + a
        return self._mlp(params, x), new_pool

    def _mlp(self, params, x):
        """The MLP half: ``x + MLP(LN(x))``."""
        with jax.named_scope("mlp"):
            h, _ = self.ln2.apply(params["ln2"], (), x)
            h, _ = self.fc1.apply(params["fc1"], (), h)
            h = jax.nn.gelu(h)
            h, _ = self.fc2.apply(params["fc2"], (), h)
            return x + h

    def apply(self, params, state, input, *, training=False, rng=None):
        with jax.named_scope("attention"):
            h, _ = self.ln1.apply(params["ln1"], (), input)
            a, _ = self.attn.apply(params["attn"], (), h, training=training,
                                   rng=child_rng(rng, 0))
            x = input + a
        return self._mlp(params, x), state


class TransformerLM(Container):
    """Decoder-only LM: embed + blocks + LN + tied-free head.

    The long-context flagship; pairs with sequence parallelism
    (parallel/ring_attention.py) for T beyond one chip's HBM.

    ``scan_layers=True`` runs the N structurally-identical blocks as ONE
    ``lax.scan`` over LAYER-STACKED params (``nn.ScanLayers``): XLA
    compiles the block body once instead of N times, so jit-compile wall
    time drops roughly N-fold at the deep configs (docs/performance.md,
    "Step-time campaign").  Params then carry one ``"blocks"`` entry
    (every leaf gains a leading num_layers axis) instead of
    ``"block0"``..``"block{N-1}"``; ``stack_block_params`` /
    ``unstack_block_params`` interconvert the two layouts, so stacked
    and unrolled checkpoints are mutually loadable.  Initialization is
    BIT-IDENTICAL across the two modes (per-block setup keys are derived
    the same way, then stacked), as is the per-block dropout rng
    derivation -- scan and unrolled runs from one seed produce the same
    losses.

    ``remat_policy`` names a ``jax.checkpoint_policies`` entry
    (``"nothing_saveable"`` / ``"dots_saveable"`` / None = save block
    inputs only) applied per block during training: per-scan-iteration
    under ``scan_layers``, as a ``jax.checkpoint`` wrapper around each
    unrolled block otherwise (no param-keying change either way).
    """

    def __init__(self, vocab_size, hidden_size, num_heads, num_layers,
                 max_len=2048, mlp_ratio=4, seq_axis_name=None,
                 seq_mode="ring", scan_layers=False, remat_policy=None,
                 name=None):
        super().__init__(name)
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.max_len = max_len
        self.seq_axis_name = seq_axis_name
        self.scan_layers = scan_layers
        resolve_checkpoint_policy(remat_policy)  # unknown names fail HERE
        self.remat_policy = remat_policy
        self.blocks = [TransformerBlock(hidden_size, num_heads, mlp_ratio,
                                        seq_axis_name=seq_axis_name,
                                        seq_mode=seq_mode)
                       for _ in range(num_layers)]
        self.ln_f = LayerNorm(hidden_size)
        if scan_layers:
            self.scan = ScanLayers(self.blocks, policy=remat_policy)
            self.add(self.scan)
        else:
            self.scan = None
            for b in self.blocks:
                self.add(b)
        self.add(self.ln_f)

    def setup(self, rng, input_spec):
        d = self.hidden_size
        params = {
            "wte": 0.02 * jax.random.normal(child_rng(rng, 0),
                                            (self.vocab_size, d)),
            "wpe": 0.01 * jax.random.normal(child_rng(rng, 1),
                                            (self.max_len, d)),
            "head": 0.02 * jax.random.normal(child_rng(rng, 2),
                                             (self.vocab_size, d)),
        }
        hid_spec = jax.ShapeDtypeStruct(
            (input_spec.shape[0], input_spec.shape[1], d), jnp.float32)
        # per-block init keys are derived identically in both layouts, so
        # scan and unrolled models from one seed start bit-identical
        block_params = [b.setup(child_rng(rng, 3 + i), hid_spec)[0]
                        for i, b in enumerate(self.blocks)]
        if self.scan_layers:
            params["blocks"] = stack_layer_trees(block_params)
        else:
            for i, p in enumerate(block_params):
                params[f"block{i}"] = p
        params["ln_f"], _ = self.ln_f.setup(child_rng(rng, 99), hid_spec)
        return params, ()

    def _param_child_items(self, params):
        # params are keyed "block{i}" (unrolled) or "blocks" (the
        # scan-stacked layout, routed to the ScanLayers child) plus
        # "ln_f"; wte/wpe/head are this module's OWN leaves and align to
        # no child (they stay fp32 under the quantizer walk)
        items = [("ln_f", self.ln_f)]
        if self.scan is not None:
            items.append(("blocks", self.scan))
        else:
            items.extend((f"block{i}", b)
                         for i, b in enumerate(self.blocks))
        return items

    def served_matmul_leaves(self):
        """The paths of the leaves this model feeds ONLY to a
        default-precision matmul (``matmul_t``): the projections, the MLP
        and the head, in this model's layout.  Serving may hold them as
        bfloat16 (``serving/generation.py`` ``ServedWeights``); ``wte`` and
        ``wpe`` are gathered, and norms and biases are added, so they are
        not among them."""
        blocks = ["blocks"] if self.scan_layers \
            else [f"block{i}" for i in range(len(self.blocks))]
        return [(b,) + leaf for b in blocks
                for leaf in (("attn", "qkv_weight"), ("attn", "out_weight"),
                             ("fc1", "weight"), ("fc2", "weight"))] \
            + [("head",)]

    def _layer_caches(self, init):
        """``init(block)`` for every block, in this model's layout.  The
        stacked layout is allocated directly (the blocks are alike and a
        cache starts as zeros): stacking per-layer arrays would hold the
        whole cache twice while it is built."""
        if self.scan_layers:
            n = len(self.blocks)
            return {"blocks": jax.tree.map(
                lambda s: jnp.zeros((n,) + s.shape, s.dtype),
                jax.eval_shape(lambda: init(self.blocks[0])))}
        return {f"block{i}": init(b) for i, b in enumerate(self.blocks)}

    def init_paged_cache(self, num_blocks: int, block_size: int,
                         dtype=jnp.float32):
        """Per-layer paged K/V pools in THIS model's param layout
        (``"block{i}"`` unrolled / stacked ``"blocks"`` under
        ``scan_layers``, as the params are laid out).  ``num_blocks`` is
        the allocator's pool size; every layer gets ONE EXTRA block on
        top -- the TRASH block, id ``num_blocks`` -- that padded table
        entries and inactive rows write into (serving/paging.py).

        Where a block lies: unrolled, block ``b`` of layer ``i`` is
        ``pool["block{i}"][leaf][b]``, a leaf ``(NB + 1, bs, H * D)`` a
        layer; under ``scan_layers`` it is ``pool["blocks"][leaf][i, b]``,
        ONE leaf ``(L, NB + 1, bs, H * D)`` for all layers.  Either way
        ``apply_paged`` writes and reads the leaves where they lie and,
        the pool donated, no program copies it."""
        return self._layer_caches(
            lambda b: b.init_paged_cache(int(num_blocks) + 1, block_size,
                                         dtype))

    def paged_state_spec(self, dtype=jnp.float32):
        """The kinds of the pool's leaves, in the pool's layout: every
        layer keeps K and V a token, in blocks, and nothing a sequence
        (under ``scan_layers`` a leaf of the one ``"blocks"`` entry stands
        for all layers: the stacked leaf has the layer axis in front)."""
        spec = self.blocks[0].attn.state_spec(dtype)
        if self.scan_layers:
            return {"blocks": spec}
        return {f"block{i}": spec for i in range(len(self.blocks))}

    def apply_paged(self, params, input, pool, tables, *, pos,
                    lengths=None):
        """Paged generation step: chunk prefill (``lengths`` given,
        ``input`` ``(N, Tc)`` token chunks starting at absolute
        positions ``pos``) or single-token decode (``lengths=None``,
        ``input`` ``(N, 1)`` at per-row ``pos``).  K/V live in the
        block pools from ``init_paged_cache`` and every row addresses
        them through its padded block-table row -- the shapes the
        executable sees never depend on sequence length, block
        residency, or how a prompt was chunked.  Returns ``(logits,
        new_pool)``.

        Unrolled, each layer is handed its own leaves and returns them
        updated.  Under ``scan_layers`` the stacked leaves ride the layer
        loop's CARRY, whole, beside the activations, and the loop scans
        over the stacked params and the layer index: every access
        addresses ``(layer, block)`` on the stacked leaf
        (``MultiHeadAttention._apply_paged``), so with the pool donated
        the compiled program updates it in place -- no layer's leaf is
        sliced out, restacked or copied, and the program's temporaries
        hold nothing the size of the pool
        (tests/test_chip_compile.py)."""
        if self.seq_axis_name is not None:
            raise ValueError("cached decode runs on a replicated model; "
                             "sequence-parallel serving is not a thing "
                             "(shard the BATCH axis instead)")
        t = input.shape[1]
        pos = jnp.asarray(pos, jnp.int32)
        with jax.named_scope("embed"):
            x = jnp.take(params["wte"], input.astype(jnp.int32), axis=0)
            if lengths is not None:
                # absolute position of each chunk token; jnp.take clips,
                # so padding tokens past max_len just reuse the last wpe
                # row (they write to trash and are never read)
                gpos = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
                x = x + jnp.take(params["wpe"], gpos, axis=0)
            else:
                x = x + jnp.take(params["wpe"], pos, axis=0)[:, None, :]
        if self.scan_layers:
            inner = self.blocks[0]

            def body(carry, sliced):
                # the stacked pool rides in the carry, whole: as xs/ys
                # the loop would slice a layer's leaf out, update the
                # slice, restack it and copy the result (PERF.md
                # section 6, PR 34)
                h, stacked = carry
                p, layer = sliced
                return inner.apply_paged(p, h, stacked, tables, pos,
                                         lengths, layer), None

            layers = jnp.arange(len(self.blocks), dtype=jnp.int32)
            (x, stacked), _ = jax.lax.scan(
                body, (x, pool["blocks"]), (params["blocks"], layers))
            new_pool = {"blocks": stacked}
        else:
            new_pool = {}
            for i, b in enumerate(self.blocks):
                x, nc = b.apply_paged(params[f"block{i}"], x,
                                      pool[f"block{i}"], tables, pos,
                                      lengths)
                new_pool[f"block{i}"] = nc
        return self._head(params, x), new_pool

    def _head(self, params, x):
        """Final norm and the logits of every position."""
        with jax.named_scope("head"):
            x, _ = self.ln_f.apply(params["ln_f"], (), x)
            return matmul_t(x, params["head"])

    def apply(self, params, state, input, *, training=False, rng=None):
        t = input.shape[1]
        with jax.named_scope("embed"):
            x = jnp.take(params["wte"], input.astype(jnp.int32), axis=0)
            if self.seq_axis_name is not None:
                # inside shard_map the block holds T_local tokens; use
                # global positions derived from the device's ring index
                offset = jax.lax.axis_index(self.seq_axis_name) * t
                pos = offset + jnp.arange(t)
                x = x + jnp.take(params["wpe"], pos, axis=0)[None]
            else:
                x = x + params["wpe"][:t][None]
        if self.scan_layers:
            # one scanned block body; layer i draws fold_in(rng, i), the
            # same per-block key derivation as the unrolled loop below
            x, _ = self.scan.apply(params["blocks"], (), x,
                                   training=training, rng=rng)
        else:
            policy = self.remat_policy
            for i, b in enumerate(self.blocks):
                key = child_rng(rng, i)
                if training and policy is not None:
                    # functional remat wrapper: same params keying, the
                    # block's forward re-runs in backward under the policy
                    def f(p, h, _b=b, _key=key):
                        return _b.apply(p, (), h, training=True,
                                        rng=_key)[0]
                    x = jax.checkpoint(
                        f, policy=resolve_checkpoint_policy(policy))(
                        params[f"block{i}"], x)
                else:
                    x, _ = b.apply(params[f"block{i}"], (), x,
                                   training=training, rng=key)
        return self._head(params, x), state


#: matches the unrolled per-block param keys ("block0".."block{N-1}")
_BLOCK_KEY = re.compile(r"^block(\d+)$")


def stack_block_params(params):
    """Unrolled ``TransformerLM`` params (``"block{i}"`` keys) -> the
    ``scan_layers`` layout (one ``"blocks"`` entry, every leaf stacked
    along a new leading layer axis).  Non-block entries (wte/wpe/head/
    ln_f) pass through unchanged; this is the checkpoint import path
    into a scan model (docs/performance.md, "Step-time campaign")."""
    idx = sorted(int(m.group(1)) for k in params
                 if (m := _BLOCK_KEY.match(k)))
    if not idx:
        raise ValueError("no 'block{i}' entries to stack (already the "
                         "scan layout?)")
    if idx != list(range(len(idx))):
        raise ValueError(f"non-contiguous block indices {idx}")
    out = {k: v for k, v in params.items() if not _BLOCK_KEY.match(k)}
    out["blocks"] = stack_layer_trees(
        [params[f"block{i}"] for i in idx])
    return out


def unstack_block_params(params):
    """Scan-layout ``TransformerLM`` params (stacked ``"blocks"``) ->
    the unrolled ``"block{i}"`` keying -- the checkpoint export path
    back to per-layer keys (what quantize/regularizer traversals and
    per-layer resharding address)."""
    if "blocks" not in params:
        raise ValueError("no 'blocks' entry to unstack (already the "
                         "unrolled layout?)")
    out = {k: v for k, v in params.items() if k != "blocks"}
    for i, p in enumerate(unstack_layer_trees(params["blocks"])):
        out[f"block{i}"] = p
    return out
