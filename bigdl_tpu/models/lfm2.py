"""LFM2-style hybrid language model (LiquidAI ``lfm2_moe``): gated
short-convolution and grouped-query attention layers, each followed by a
gated MLP (the leading ``num_dense_layers``) or a dropless mixture of
experts, RMSNorm before each, a tied embedding.

    h = x + Op_l(RMSNorm(x));   y = h + FFN_l(RMSNorm(h))

``layer_types[l]`` is ``"conv"`` or ``"full_attention"``.  Built for the
training path (``Optimizer(...).optimize()``); ``experts_held = (first,
count)`` makes the model one chip's share of an expert-parallel job (see
``nn.DroplessMoE``), and ``vocab_size`` is then that chip's slice.

Parameter tree::

    embed (V, D)    norm_f {weight}
    layer{i}: op_norm {weight}  ffn_norm {weight}
              op:  {in_weight (3D, D), kernel (L, D), out_weight (D, D)}
                or {qkv_weight ((H + 2 Hkv) Dh, D), q_norm (Dh,),
                    k_norm (Dh,), out_weight (D, D)}
              ffn: {w1 (F, D), w3 (F, D), w2 (D, F)}
                or {router_weight (E, D), router_bias (E,),
                    w1 (held, D, Fe), w3 (held, D, Fe), w2 (held, Fe, D)}

Model state: ``{"moe_load": int32[4]}``, the expert layers' routing counts
summed (``nn.DroplessMoE``; ``state_spans`` hands them to the trainer's
loop), or ``{}`` for a model without an expert layer.
"""

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.attention import GroupedQueryAttention
from bigdl_tpu.nn.gated import GatedMLP, GatedShortConv
from bigdl_tpu.nn.module import Container, child_rng
from bigdl_tpu.nn.moe import DroplessMoE
from bigdl_tpu.nn.normalization import RMSNorm


class LFM2(Container):
    """Decoder-only hybrid LM: ``(N, T)`` token ids -> ``(N, T, V)``
    logits against the tied embedding.

    ``remat`` recomputes each layer's forward in the backward pass (only
    the layers' inputs are kept).  ``kv_heads_per_call`` is handed to the
    attention layers (``nn.GroupedQueryAttention``)."""

    state_spans = DroplessMoE.state_spans

    def __init__(self, vocab_size: int, hidden_size: int,
                 layer_types: Sequence[str], num_dense_layers: int,
                 intermediate_size: int, moe_intermediate_size: int,
                 num_heads: int, num_kv_heads: int, num_experts: int,
                 num_experts_per_tok: int, experts_held=None,
                 conv_L_cache: int = 3, norm_eps: float = 1e-5,
                 rope_theta: float = 1e6, norm_topk_prob: bool = True,
                 routed_scaling_factor: float = 1.0, remat: bool = True,
                 kv_heads_per_call: Optional[int] = None, name=None):
        super().__init__(name)
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.layer_types = tuple(layer_types)
        self.remat = remat
        self.layers = []
        for i, kind in enumerate(self.layer_types):
            if kind == "conv":
                op = GatedShortConv(hidden_size, conv_L_cache)
            elif kind == "full_attention":
                op = GroupedQueryAttention(
                    hidden_size, num_heads, num_kv_heads, rope_theta,
                    norm_eps, kv_heads_per_call)
            else:
                raise ValueError(f"unknown layer type {kind!r}")
            if i < num_dense_layers:
                ffn = GatedMLP(hidden_size, intermediate_size)
            else:
                ffn = DroplessMoE(hidden_size, moe_intermediate_size,
                                  num_experts, num_experts_per_tok,
                                  experts_held, norm_topk_prob,
                                  routed_scaling_factor)
            layer = {"op_norm": RMSNorm(hidden_size, norm_eps), "op": op,
                     "ffn_norm": RMSNorm(hidden_size, norm_eps), "ffn": ffn}
            self.layers.append(layer)
            for m in layer.values():
                self.add(m)
        self.norm_f = RMSNorm(hidden_size, norm_eps)
        self.add(self.norm_f)

    def setup(self, rng, input_spec):
        d = self.hidden_size
        spec = jax.ShapeDtypeStruct(tuple(input_spec.shape) + (d,),
                                    jnp.float32)
        params = {"embed": 0.02 * jax.random.normal(
            child_rng(rng, 0), (self.vocab_size, d), jnp.float32)}
        state = {}
        for i, layer in enumerate(self.layers):
            made = {k: m.setup(child_rng(child_rng(rng, 1 + i), j), spec)
                    for j, (k, m) in enumerate(layer.items())}
            params[f"layer{i}"] = {k: p for k, (p, _) in made.items()}
            if made["ffn"][1] != ():
                state = made["ffn"][1]
        params["norm_f"], _ = self.norm_f.setup(child_rng(rng, 99), spec)
        return params, state

    def _layer(self, layer, p, x):
        """One layer; returns ``(y, the ffn's state)``."""
        mixer = "state_mixer" if isinstance(layer["op"], GatedShortConv) \
            else "attention"
        with jax.named_scope("operator"), jax.named_scope(mixer):
            h, _ = layer["op_norm"].apply(p["op_norm"], (), x)
            h, _ = layer["op"].apply(p["op"], (), h, training=True)
            x = x + h
        ffn = "moe" if isinstance(layer["ffn"], DroplessMoE) else "mlp"
        with jax.named_scope("ffn"), jax.named_scope(ffn):
            h, _ = layer["ffn_norm"].apply(p["ffn_norm"], (), x)
            h, st = layer["ffn"].apply(p["ffn"], (), h, training=True)
            return x + h, st

    def apply(self, params, state, input, *, training=False, rng=None):
        embed = params["embed"]
        with jax.named_scope("embed"):
            x = jnp.take(embed, input.astype(jnp.int32), axis=0)
        loads = []
        for i, layer in enumerate(self.layers):
            run = lambda p, x, _layer=layer: self._layer(_layer, p, x)
            if training and self.remat:
                run = jax.checkpoint(run)
            x, st = run(params[f"layer{i}"], x)
            if st != ():
                loads.append(st["moe_load"])
        new_state = {"moe_load": sum(loads)} if loads else {}
        with jax.named_scope("head"):
            x, _ = self.norm_f.apply(params["norm_f"], (), x)
            return x @ embed.astype(x.dtype).T, new_state
