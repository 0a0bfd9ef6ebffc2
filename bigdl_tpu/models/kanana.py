"""DeepSeek-V3-shaped language model as kanana-2-30b-a3b (kakaocorp)
publishes it: latent attention (``nn.LatentAttention`` without a query
latent, gate or q/k norms, interleaved rotary pairs) on EVERY layer, each
followed by a gated MLP (the leading ``num_dense_layers``) or a dropless
mixture of sigmoid-scored experts chosen on score plus bias, with shared
experts (``nn.DroplessMoE``); RMSNorm before each, an untied head.

    h = x + MLA_l(RMSNorm(x));   y = h + FFN_l(RMSNorm(h))

Built for the serving path (``ServingEngine(model, kv_cache="paged")
.generate()``), with a full forward (``apply``) beside it that the tests
hold it to; ``experts_held``, ``vocab_size`` and ``dtype`` as in
``models/ling.py``, whose residual stream, FFN step, logits and
state plumbing this model shares (``ServedLM``).

The expert layers are identical, so they are ONE ``lax.scan`` over
stacked parameters (``scan_layers``; unrolled, the same parameters run
layer by layer, for the tests): a program holds one layer's code
whatever the depth.  Generation state (``paged_state_spec``): every leaf
is per TOKEN, in blocks behind the block tables, so a prompt's full
blocks are shared and copied on write as ``TransformerLM``'s are: a
dense layer's ``latent`` leaf ``(NB + 1, bs, W)`` by itself, the expert
layers' as one stacked leaf ``(L, NB + 1, bs, W)`` that rides the scan's
carry and is written and read at ``(layer, block)`` where it lies (never
sliced out or copied: PERF.md section 6, PR 34), ``W`` the 576 values of
a row in 640 columns (``nn/latent_attention.py``, ``row_align``);
``moe_load`` is what the expert layers of a step counted, summed through
the scan.

Parameter tree::

    embed (V, D)   head (V, D)   norm_f {weight}
    layer{i}  (i < num_dense_layers), and ``layers`` with every leaf
    stacked over the expert layers:
        op_norm {weight}  ffn_norm {weight}
        op:  nn.LatentAttention's leaves (no q_norm, kr_norm, gate_weight)
        ffn: {w1 (F, D), w3 (F, D), w2 (D, F)} or nn.DroplessMoE's
"""

import jax
import jax.numpy as jnp

from bigdl_tpu.models.ling import ServedLM
from bigdl_tpu.nn.gated import GatedMLP
from bigdl_tpu.nn.generation_state import allocate
from bigdl_tpu.nn.latent_attention import LatentAttention
from bigdl_tpu.nn.module import child_rng
from bigdl_tpu.nn.moe import DroplessMoE
from bigdl_tpu.nn.normalization import RMSNorm


class Kanana(ServedLM):
    """Decoder-only LM: ``(N, T)`` token ids -> ``(N, T, V)`` float32
    logits."""

    def __init__(self, vocab_size: int, hidden_size: int, num_layers: int,
                 num_dense_layers: int, intermediate_size: int,
                 moe_intermediate_size: int, num_heads: int,
                 num_experts: int, num_experts_per_tok: int,
                 experts_held=None, shared_width: int = 0,
                 routed_scaling_factor: float = 1.0, kv_rank: int = 512,
                 nope_dim: int = 128, rope_dim: int = 64, v_dim: int = 128,
                 rope_theta: float = 1e6, rope_interleave: bool = True,
                 norm_eps: float = 1e-6, max_len: int = 16384,
                 dtype=jnp.float32, scan_layers: bool = True,
                 use_kernel: str = "auto", name=None):
        super().__init__(name)
        assert 0 <= num_dense_layers < num_layers
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_dense_layers = num_dense_layers
        self.num_expert_layers = num_layers - num_dense_layers
        self.max_len = max_len
        self.dtype = jnp.dtype(dtype)
        self.scan_layers = scan_layers

        def mixer():
            return LatentAttention(
                hidden_size, num_heads, kv_rank, nope_dim, rope_dim, v_dim,
                rope_theta, norm_eps, gate=False, qk_norm=False,
                rope_interleave=rope_interleave, row_align=128,
                use_kernel=use_kernel)

        self.dense_layers = [
            self._new_layer(mixer(), GatedMLP(hidden_size, intermediate_size),
                            norm_eps)
            for _ in range(num_dense_layers)]
        #: the one expert layer every scanned step runs
        self.expert_layer = self._new_layer(
            mixer(), DroplessMoE(hidden_size, moe_intermediate_size,
                                 num_experts, num_experts_per_tok,
                                 experts_held, True, routed_scaling_factor,
                                 use_kernel, shared_width=shared_width),
            norm_eps)
        self.norm_f = RMSNorm(hidden_size, norm_eps)
        self.add(self.norm_f)

    def setup(self, rng, input_spec):
        params, spec = self._setup_tables(rng, input_spec)
        for i, layer in enumerate(self.dense_layers):
            params[f"layer{i}"] = self._setup_layer(
                layer, child_rng(rng, 1 + i), spec)
        each = [self._setup_layer(self.expert_layer,
                                  child_rng(rng, 1 + self.num_dense_layers + i),
                                  spec)
                for i in range(self.num_expert_layers)]
        params["layers"] = jax.tree.map(lambda *a: jnp.stack(a), *each)
        return self._stored(params), ()

    def _over_expert_layers(self, body, carry, params):
        """``carry = body(carry, a layer's parameters, its index)`` over the
        expert layers: one ``lax.scan``, or the same steps unrolled."""
        layers = jnp.arange(self.num_expert_layers, dtype=jnp.int32)
        # ``moe_weights`` names what hands a layer its parameters: the
        # slices out of the stacked leaves (the expert weights are most of
        # their bytes), which inside a scan are the scan's own; what a
        # layer does with them names itself further in
        if self.scan_layers:
            with jax.named_scope("moe_weights"):
                return jax.lax.scan(
                    lambda c, sliced: (body(c, *sliced), None), carry,
                    (params["layers"], layers))[0]
        for i in range(self.num_expert_layers):
            with jax.named_scope("moe_weights"):
                p = jax.tree.map(lambda a: a[i], params["layers"])
            carry = body(carry, p, layers[i])
        return carry

    # ----- full forward ----------------------------------------------------- #
    def apply(self, params, state, input, *, training=False, rng=None):
        x = self._embed(params, input)
        for i, layer in enumerate(self.dense_layers):
            x = self._forward_layer(layer, params[f"layer{i}"], x)
        x = self._over_expert_layers(
            lambda x, p, _: self._forward_layer(self.expert_layer, p, x),
            x, params)
        return self._logits(params, x), state

    # ----- paged generation -------------------------------------------------- #
    def paged_state_spec(self, dtype=jnp.float32):
        """Every leaf of the generation state with its kind, in the pool's
        layout (the ``layers`` entry stands for all expert layers: the
        stacked leaf has the layer axis in front).  ``float32``, the
        engine's word for a cache that is not quantized, means this
        model's own ``dtype``."""
        self._check_cache_dtype(dtype, "Kanana")
        spec = {f"layer{i}": layer["op"].state_spec(self.dtype)
                for i, layer in enumerate(self.dense_layers)}
        spec["layers"] = self.expert_layer["op"].state_spec(self.dtype)
        spec["moe_load"] = self.moe_load_spec
        return spec

    def init_paged_cache(self, num_blocks: int, block_size: int,
                         dtype=jnp.float32):
        pool = allocate(self.paged_state_spec(dtype), num_blocks, block_size)
        pool["layers"] = jax.tree.map(
            lambda leaf: jnp.zeros((self.num_expert_layers,) + leaf.shape,
                                   leaf.dtype), pool["layers"])
        return pool

    def apply_paged(self, params, input, pool, tables, *, pos, lengths=None,
                    logits_at=None):
        """A step of paged generation (``TransformerLM.apply_paged``'s
        contract).  A row whose table starts on the trash block is padding
        or not live, and so is a chunk's token past its row's length: they
        go to no routed expert.  ``logits_at (N,)`` asks for the logits of
        one position a row, ``(N, 1, V)``.  The pool's ``moe_load`` comes
        back as this step's counts, summed over the expert layers."""
        tables = jnp.asarray(tables, jnp.int32)
        pos = jnp.asarray(pos, jnp.int32)
        stacked = pool["layers"]["latent"]
        live = jnp.broadcast_to(
            (tables[:, 0] != stacked.shape[1] - 1)[:, None], input.shape)
        if lengths is not None:
            lengths = jnp.asarray(lengths, jnp.int32)
            live &= jnp.arange(input.shape[1])[None, :] < lengths[:, None]
        x = self._embed(params, input)
        new_pool = {}
        for i, layer in enumerate(self.dense_layers):
            key = f"layer{i}"
            x, new_pool[key], _ = self._paged_layer(
                layer, params[key], x, pool[key], tables, pos, lengths, live)

        def body(carry, p, layer):
            # the stacked leaf rides in the carry, whole, and is addressed
            # at (layer, block): as xs/ys the loop would slice a layer's
            # leaf out and copy the pool (PERF.md section 6, PR 34)
            x, leaf, counted = carry
            x, new, load = self._paged_layer(
                self.expert_layer, p, x, {"latent": leaf}, tables, pos,
                lengths, live, layer=layer)
            return x, new["latent"], counted + load

        x, stacked, counted = self._over_expert_layers(
            body, (x, stacked, jnp.zeros_like(pool["moe_load"])), params)
        new_pool["layers"] = {"latent": stacked}
        new_pool["moe_load"] = counted
        return self._logits(params, x, logits_at), new_pool
