"""Granite-4.0-H-shaped hybrid language model as granite-4.0-h-micro
(ibm-granite; ``granitemoehybrid`` with no routed experts) publishes it:
Mamba-2 mixers (``nn.Mamba2Mixer``) with a grouped-query attention layer
(``nn.MultiHeadAttention`` given ``num_kv_heads``; no positional encoding
at all, softmax scale ``attention_multiplier``) every few layers, each
followed by a gated MLP; RMSNorm before each, a TIED head, and the
family's four multipliers::

    x0     = embedding_multiplier * embed[ids]
    h      = x + residual_multiplier * Mixer_l(RMSNorm(x))
    y      = h + residual_multiplier * MLP(RMSNorm(h))
    logits = RMSNorm(x_L) @ embed^T / logits_scaling

``layer_types[l]`` is ``"mamba"`` or ``"attention"``.  Built for the
serving path (``ServingEngine(model, kv_cache="paged").generate()``), with
a full forward (``apply``) beside it that the tests hold it to; ``dtype``
as in ``models/ling.py``, whose residual stream, MLP step, logits and
state plumbing this model shares (``ServedLM``).

The Mamba layers between two attention layers are identical, so each such
RUN is one ``lax.scan`` over stacked parameters: a program holds one Mamba
layer's code a run and one attention layer's a layer, whatever the depth.
Generation state (``paged_state_spec``), two kinds side by side:
``mamba``'s ``state`` and ``conv`` are per SLOT, stacked over ALL Mamba
layers ``(Lm, S + 1, ...)``; ``attention``'s ``k`` and ``v`` are per
TOKEN, in blocks behind the block tables, stacked over the attention
layers ``(La, NB + 1, bs, Hkv * D)``.  The stacked leaves ride the scans'
carries whole and are read and written at ``(layer, slot)`` or ``(layer,
block)`` where they lie: never sliced out and written back (PERF.md
section 6, PR 34).

A chunk step of more than ``chunk_rows`` rows runs ``chunk_rows`` rows at
a time, one after the other inside the one program (the rows of a step
share no slot and no block): what a step holds beside the weights is then
bounded by ``chunk_rows`` chunks however many sequences are admitted at
once.

Parameter tree::

    embed (V, D)   norm_f {weight}           (no ``head``: it is tied)
    mamba{r}: run r's layers, every leaf stacked over them
    attention{j}: the j-th attention layer
        op_norm {weight}  ffn_norm {weight}
        op:  nn.Mamba2Mixer's leaves, or {qkv_weight ((H + 2 Hkv) Dh, D),
             out_weight (D, D)}
        ffn: {w1 (F, D), w3 (F, D), w2 (D, F)}
"""

from itertools import groupby
from typing import Sequence

import jax
import jax.numpy as jnp

from bigdl_tpu.models.ling import ServedLM
from bigdl_tpu.nn.attention import MultiHeadAttention
from bigdl_tpu.nn.gated import GatedMLP
from bigdl_tpu.nn.generation_state import allocate
from bigdl_tpu.nn.module import child_rng
from bigdl_tpu.nn.normalization import RMSNorm
from bigdl_tpu.nn.state_space import Mamba2Mixer


class GraniteHybrid(ServedLM):
    """Decoder-only hybrid LM: ``(N, T)`` token ids -> ``(N, T, V)``
    float32 logits."""

    tied_head = True
    #: no expert layer, so nothing is counted
    tick_counters = {}
    #: rows of a chunk step that run together: at 512 tokens a row the
    #: attention layers' float32 scores over a 2304-token table are 0.15
    #: GB a row, and a group's weights are read once for 2048 tokens
    chunk_rows = 4

    def __init__(self, vocab_size: int, hidden_size: int,
                 layer_types: Sequence[str], intermediate_size: int,
                 num_heads: int, num_kv_heads: int, mamba_heads: int,
                 mamba_head_dim: int, state_dim: int = 128,
                 conv_kernel: int = 4, chunk_size: int = 256,
                 attention_multiplier: float = None,
                 embedding_multiplier: float = 1.0,
                 residual_multiplier: float = 1.0,
                 logits_scaling: float = 1.0, norm_eps: float = 1e-5,
                 max_len: int = 4096, dtype=jnp.float32,
                 use_kernel: str = "auto", name=None):
        super().__init__(name)
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.layer_types = tuple(layer_types)
        unknown = set(self.layer_types) - {"mamba", "attention"}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        #: (kind, layers) of each stretch of like layers, in order
        self.runs = [(kind, len(list(same)))
                     for kind, same in groupby(self.layer_types)]
        self.mamba_layers = self.layer_types.count("mamba")
        self.attention_layers = self.layer_types.count("attention")
        self.embedding_multiplier = float(embedding_multiplier)
        self.residual_multiplier = float(residual_multiplier)
        self.logits_scaling = float(logits_scaling)
        self.max_len = max_len
        self.dtype = jnp.dtype(dtype)
        #: the one layer of each kind every step of its kind runs
        self.mamba_layer = self._new_layer(
            Mamba2Mixer(hidden_size, mamba_heads, mamba_head_dim, state_dim,
                        conv_kernel, chunk_size, norm_eps, use_kernel),
            GatedMLP(hidden_size, intermediate_size), norm_eps)
        self.attention_layer = self._new_layer(
            MultiHeadAttention(hidden_size, num_heads, causal=True,
                               use_flash=use_kernel,
                               num_kv_heads=num_kv_heads,
                               bias=False, scale=attention_multiplier),
            GatedMLP(hidden_size, intermediate_size), norm_eps)
        self.norm_f = RMSNorm(hidden_size, norm_eps)
        self.add(self.norm_f)

    def setup(self, rng, input_spec):
        params, spec = self._setup_tables(rng, input_spec)
        at = mamba = attention = 0
        for kind, count in self.runs:
            if kind == "mamba":
                each = [self._setup_layer(self.mamba_layer,
                                          child_rng(rng, 1 + at + i), spec)
                        for i in range(count)]
                params[f"mamba{mamba}"] = jax.tree.map(
                    lambda *a: jnp.stack(a), *each)
                mamba += 1
            else:
                for i in range(count):
                    params[f"attention{attention}"] = self._setup_layer(
                        self.attention_layer, child_rng(rng, 1 + at + i),
                        spec)
                    attention += 1
            at += count
        return self._stored(params), ()

    def _over_layers(self, params, x, mamba, attention):
        """``x`` through the layers in order.  ``x = mamba(x, a Mamba
        layer's parameters, its index among the Mamba layers)`` runs as
        one ``lax.scan`` a run; ``x = attention(x, parameters, index among
        the attention layers)`` once a layer.  ``x`` may be any carry."""
        run = m = a = 0
        for kind, count in self.runs:
            if kind == "mamba":
                layers = jnp.arange(m, m + count, dtype=jnp.int32)
                x = jax.lax.scan(
                    lambda c, sliced: (mamba(c, *sliced), None), x,
                    (params[f"mamba{run}"], layers))[0]
                run, m = run + 1, m + count
            else:
                for _ in range(count):
                    x = attention(x, params[f"attention{a}"], jnp.int32(a))
                    a += 1
        return x

    # ----- full forward ----------------------------------------------------- #
    def apply(self, params, state, input, *, training=False, rng=None):
        x = self._over_layers(
            params, self._embed(params, input),
            lambda x, p, _: self._forward_layer(self.mamba_layer, p, x),
            lambda x, p, _: self._forward_layer(self.attention_layer, p, x))
        return self._logits(params, x), state

    # ----- paged generation -------------------------------------------------- #
    def paged_state_spec(self, dtype=jnp.float32):
        """Every leaf of the generation state with its kind, in the pool's
        layout (an entry stands for all layers of its kind: the stacked
        leaf has the layer axis in front).  ``float32``, the engine's word
        for a cache that is not quantized, means this model's own
        ``dtype`` for K, V and the convolution's tail; the recurrent state
        is float32 whatever is asked."""
        if jnp.dtype(dtype) == jnp.dtype(jnp.int8):
            raise NotImplementedError(
                "GraniteHybrid keeps K, V and the convolution's tail in "
                "the model's dtype")
        return {"mamba": self.mamba_layer["op"].state_spec(self.dtype),
                "attention": self.attention_layer["op"].state_spec(
                    self.dtype)}

    def init_paged_cache(self, num_blocks: int, block_size: int,
                         dtype=jnp.float32, slots: int = 0):
        pool = allocate(self.paged_state_spec(dtype), num_blocks, block_size,
                        slots)
        stacked = lambda n: lambda leaf: jnp.zeros((n,) + leaf.shape,
                                                   leaf.dtype)
        return {"mamba": jax.tree.map(stacked(self.mamba_layers),
                                      pool["mamba"]),
                "attention": jax.tree.map(stacked(self.attention_layers),
                                          pool["attention"])}

    def _paged_step(self, params, input, pool, tables, pos, lengths, slots,
                    logits_at):
        """``apply_paged`` for rows that run together."""
        def mamba(carry, p, layer):
            x, state, conv, kv = carry
            x, new, _ = self._paged_layer(
                self.mamba_layer, p, x, {"state": state, "conv": conv},
                slots, pos, lengths, None, layer=layer)
            return x, new["state"], new["conv"], kv

        def attention(carry, p, layer):
            x, state, conv, kv = carry
            x, kv, _ = self._paged_layer(
                self.attention_layer, p, x, kv, tables, pos, lengths, None,
                layer=layer)
            return x, state, conv, kv

        x, state, conv, kv = self._over_layers(
            params, (self._embed(params, input), pool["mamba"]["state"],
                     pool["mamba"]["conv"], pool["attention"]),
            mamba, attention)
        return self._logits(params, x, logits_at), {
            "mamba": {"state": state, "conv": conv}, "attention": kv}

    def apply_paged(self, params, input, pool, tables, *, pos, lengths=None,
                    slots=None, logits_at=None):
        """A step of paged generation (``TransformerLM.apply_paged``'s
        contract) for a model with per-slot state: row ``i`` is slot
        ``slots[i]``, the trash slot (the slot leaves' last row) if the
        row is padding or not live.  ``logits_at (N,)`` asks for the logits
        of one position a row, ``(N, 1, V)``."""
        if slots is None:
            raise ValueError("a model with per-slot state has to be told "
                             "which slot each row is (slots=)")
        i32 = lambda a: jnp.asarray(a, jnp.int32)
        tables, pos, slots = i32(tables), i32(pos), i32(slots)
        n, per = input.shape[0], self.chunk_rows
        if lengths is None or n <= per or n % per:
            lengths = None if lengths is None else i32(lengths)
            return self._paged_step(params, input, pool, tables, pos,
                                    lengths, slots, logits_at)
        # the rows of a chunk step share no slot and no block: ``per`` of
        # them at a time, the pool carried from one group to the next
        rows = (input, tables, pos, i32(lengths), slots) \
            + (() if logits_at is None else (i32(logits_at),))

        def group(pool, rows):
            input, tables, pos, lengths, slots, *at = rows
            logits, pool = self._paged_step(
                params, input, pool, tables, pos, lengths, slots,
                at[0] if at else None)
            return pool, logits

        pool, logits = jax.lax.scan(group, pool, jax.tree.map(
            lambda a: a.reshape((n // per, per) + a.shape[1:]), rows))
        return logits.reshape((n,) + logits.shape[2:]), pool
