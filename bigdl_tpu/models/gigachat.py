"""GigaChat3.5-style hybrid language model (ai-sage, ``model_type
gigachat3_5``): gated delta-rule layers (``nn.GatedDeltaNet``: a decay a
value head, 64 value heads over 32 key heads) with a latent-attention
layer (``nn.LatentAttention`` with a query latent, YaRN and an
element-wise output gate) at ``full_attention_layers``; each followed by
a gated MLP (the layers before ``first_k_dense_replace``) or a dropless
mixture of sigmoid-scored experts with a shared expert and the SwiGLU
clamp (``nn.DroplessMoE``).  Every norm is zero-centred and gated
(``nn.ZeroCenteredGatedNorm``: RMSNorm times ``2 sigmoid(w)``), before AND
after each sublayer (``layernorm_type pre_post``); an untied head.

    h = x + N(Op_l(N(x)));   y = h + N(FFN_l(N(h)))

``layer_types[l]`` is ``"gated_delta"`` or ``"latent_attention"``.  The
layers run one after the other (``ServedLM``'s methods, as ``Ling``'s);
``experts_held``, ``vocab_size`` and ``dtype`` as in ``models/ling.py``:
the residual stream, the router's input and the logits are float32.  A
chunk step of more than ``chunk_rows`` rows runs that many at a time
inside its program, so that a full house of admissions holds no more
temporaries than ``chunk_rows`` prompts' chunks.

Generation state, one entry a layer: a delta-rule layer's ``state (64,
128, 128)`` float32 and ``conv`` per SLOT, the latent layer's ``latent``
row per TOKEN (576 values in 640 columns); ``moe_load`` what the expert
layers of a step counted.

Parameter tree::

    embed (V, D)   head (V, D)   norm_f {weight}
    layer{i}: op_norm, op_post_norm, ffn_norm, ffn_post_norm {weight}
              op:  nn.GatedDeltaNet's or nn.LatentAttention's leaves
              ffn: {w1 (F, D), w3 (F, D), w2 (D, F)} or nn.DroplessMoE's
"""

import functools
from typing import Sequence

import jax.numpy as jnp

from bigdl_tpu.models.ling import ServedLM
from bigdl_tpu.nn.gated import GatedMLP
from bigdl_tpu.nn.latent_attention import LatentAttention
from bigdl_tpu.nn.linear_attention import GatedDeltaNet
from bigdl_tpu.nn.moe import DroplessMoE
from bigdl_tpu.nn.normalization import ZeroCenteredGatedNorm


class GigaChat35(ServedLM):
    """Decoder-only hybrid LM: ``(N, T)`` token ids -> ``(N, T, V)``
    float32 logits."""

    #: rows of a chunk step that run together: at 512 tokens a row a group
    #: is 2048 tokens, whose 7168-wide float32 stream is 59 MB and whose
    #: dense MLP's 18432-wide products are 151 MB
    chunk_rows = 4

    def __init__(self, vocab_size: int, hidden_size: int,
                 layer_types: Sequence[str], num_dense_layers: int,
                 intermediate_size: int, moe_intermediate_size: int,
                 num_experts: int, num_experts_per_tok: int,
                 experts_held=None, shared_width: int = 0,
                 routed_scaling_factor: float = 1.0,
                 swiglu_limit: float = None, num_heads: int = 64,
                 q_rank: int = 1536, kv_rank: int = 512, nope_dim: int = 128,
                 rope_dim: int = 64, v_dim: int = 128,
                 rope_theta: float = 1e5, rope_scaling=None,
                 linear_key_heads: int = 32, linear_value_heads: int = 64,
                 linear_head_dim: int = 128, conv_kernel: int = 4,
                 linear_gate_scale: float = 2.0, norm_gate: float = 2.0,
                 norm_eps: float = 1e-6, max_len: int = 8192,
                 dtype=jnp.float32, use_kernel: str = "auto", name=None):
        super().__init__(name)
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.layer_types = tuple(layer_types)
        self.max_len = max_len
        self.dtype = jnp.dtype(dtype)
        norm = functools.partial(ZeroCenteredGatedNorm, gate=norm_gate)
        self.layers = []
        for i, kind in enumerate(self.layer_types):
            if kind == "gated_delta":
                op = GatedDeltaNet(hidden_size, linear_key_heads,
                                   linear_value_heads, linear_head_dim,
                                   conv_kernel, norm_eps, linear_gate_scale,
                                   use_kernel)
            elif kind == "latent_attention":
                op = LatentAttention(
                    hidden_size, num_heads, kv_rank, nope_dim, rope_dim,
                    v_dim, rope_theta, norm_eps, gate="element",
                    qk_norm=False, rope_interleave=True, row_align=128,
                    q_rank=q_rank, rope_scaling=rope_scaling,
                    use_kernel=use_kernel)
            else:
                raise ValueError(f"unknown layer type {kind!r}")
            if i < num_dense_layers:
                ffn = GatedMLP(hidden_size, intermediate_size)
            else:
                ffn = DroplessMoE(hidden_size, moe_intermediate_size,
                                  num_experts, num_experts_per_tok,
                                  experts_held, True, routed_scaling_factor,
                                  use_kernel, shared_width=shared_width,
                                  swiglu_limit=swiglu_limit)
            self.layers.append(self._new_layer(op, ffn, norm_eps, norm,
                                               post=True))
        self.norm_f = norm(hidden_size, norm_eps)
        self.add(self.norm_f)
