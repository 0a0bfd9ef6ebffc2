"""Ling-3.0-flash-style hybrid language model (inclusionAI): delta-rule
linear-attention layers (``nn.KimiDeltaAttention``) with one latent-
attention layer (``nn.LatentAttention``) closing every group, each
followed by a gated MLP (the leading ``num_dense_layers``) or a dropless
mixture of experts with group-limited selection and a shared expert
(``nn.DroplessMoE``); RMSNorm before each, an untied head.

    h = x + Op_l(RMSNorm(x));   y = h + FFN_l(RMSNorm(h))

``layer_types[l]`` is ``"kda"`` or ``"latent_attention"``.  Built for the
serving path (``ServingEngine(model, kv_cache="paged").generate()``), with
a full forward (``apply``) beside it that the tests hold it to.
``experts_held = (first, count)`` makes the model one chip's share of an
expert-parallel replica and ``vocab_size`` that chip's slice.  ``dtype``
is what the matrices are stored and multiplied in (norms, the router, the
decay's ``A_log`` and ``dt_bias`` stay float32) and what the per-token
cache rows are kept in; the residual stream, the router's input and the
logits are float32 whatever it is.

Generation state, one entry a layer (``paged_state_spec``): a delta-rule
layer's ``state`` and ``conv`` are per SLOT, a latent layer's ``latent``
is per TOKEN, in blocks behind the block tables; ``moe_load`` is what the
expert layers of a step counted (``nn/generation_state.py``).

Parameter tree::

    embed (V, D)   head (V, D)   norm_f {weight}
    layer{i}: op_norm {weight}  ffn_norm {weight}
              op:  nn.KimiDeltaAttention's or nn.LatentAttention's leaves
              ffn: {w1 (F, D), w3 (F, D), w2 (D, F)} or nn.DroplessMoE's
"""

from typing import Sequence

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.gated import GatedMLP
from bigdl_tpu.nn.generation_state import (COUNTER, StateSpec, allocate,
                                           has_slot_state)
from bigdl_tpu.nn.latent_attention import LatentAttention
from bigdl_tpu.nn.linear_attention import KimiDeltaAttention
from bigdl_tpu.nn.module import Container, child_rng
from bigdl_tpu.nn.moe import DroplessMoE
from bigdl_tpu.nn.normalization import RMSNorm

#: leaves that stay float32 whatever ``dtype`` is
FULL_PRECISION = ("weight", "router_weight", "router_bias", "A_log",
                  "dt_bias", "D", "o_norm", "q_norm", "kv_norm", "kr_norm",
                  "qa_norm")


class ServedLM(Container):
    """What the served language models of this package share
    (``Ling`` here, ``models/kanana.py``, ``models/granite.py``): pre-norm
    residual blocks of a token mixer and a gated MLP or a dropless
    mixture, a float32 residual stream, matrices stored and multiplied in
    ``self.dtype``, a head, and the generation-state plumbing.  A subclass
    sets ``vocab_size``, ``hidden_size``, ``max_len``, ``dtype`` and
    ``norm_f``, and builds its layers with ``_new_layer``.  The model
    methods here run ``self.layers`` one after the other (``Ling``,
    ``models/gigachat.py``); a model that scans like layers
    (``models/kanana.py``, ``models/granite.py``) gives its own and, for
    ``apply_paged``, a ``_paged_step``.

    A layer's norms are before its two sublayers; with ``post`` also
    after each, on the sublayer's output before the residual (``h = x +
    N_post(F(N_pre(x)))``), and every norm is of the kind ``_new_layer``
    is given.

    The head is untied (a ``head`` table beside ``embed``) unless the
    subclass sets ``tied_head``: the logits are then taken against
    ``embed`` itself and the tree holds no ``head``.  The four multipliers
    of the Granite family are class attributes that read 1 here, and a
    multiplier of 1 adds no operation to a program.

    What a mixer keeps between the steps of generation is what its
    ``state_spec`` declares (``nn/generation_state.py``): per-token
    ``block`` leaves (K and V, a latent row) or per-sequence ``slot``
    leaves: a delta-rule or state-space layer's float32 recurrent
    ``state`` and ``conv``, the tail of its short convolution's input.
    ``_paged_layer`` hands a mixer its leaves and the rows' block tables
    or slot ids and takes the new leaves back; a model that scans like
    layers hands the stacked leaf and the ``layer``."""

    #: logits against ``embed`` itself; no ``head`` leaf
    tied_head = False
    #: ``x0 = embedding_multiplier * embed[ids]``; every branch enters the
    #: residual stream times ``residual_multiplier``; the logits are
    #: divided by ``logits_scaling``
    embedding_multiplier = 1.0
    residual_multiplier = 1.0
    logits_scaling = 1.0

    #: the ``counter`` leaves of the generation state, in the pool's
    #: order: the span a tick records for each, and its attributes
    tick_counters = {"moe_load": DroplessMoE.generate_counts}
    #: ``apply_paged`` takes ``logits_at``: a chunk's step asks for the
    #: logits of the one position a row it samples from
    paged_logits_at = True
    #: rows of a chunk step that run together: a step of more rows (a
    #: whole number of such groups) runs them this many at a time inside
    #: the one program, the pool carried from group to group; None: all
    chunk_rows = None

    def _new_layer(self, op, ffn, norm_eps, norm=RMSNorm, post=False):
        """A layer's modules, registered: its two sublayers with a
        ``norm`` before each, and with ``post`` one after each too."""
        d = self.hidden_size
        layer = {"op_norm": norm(d, norm_eps), "op": op,
                 "ffn_norm": norm(d, norm_eps), "ffn": ffn}
        if post:
            layer.update(op_post_norm=norm(d, norm_eps),
                         ffn_post_norm=norm(d, norm_eps))
        for m in layer.values():
            self.add(m)
        return layer

    def _setup_layer(self, layer, rng, spec):
        return {k: m.setup(child_rng(rng, j), spec)[0]
                for j, (k, m) in enumerate(layer.items())}

    def _setup_tables(self, rng, input_spec):
        """``(embed, head and norm_f, the layers' input spec)``."""
        d = self.hidden_size
        spec = jax.ShapeDtypeStruct(tuple(input_spec.shape) + (d,),
                                    jnp.float32)
        table = lambda i: 0.02 * jax.random.normal(
            child_rng(rng, i), (self.vocab_size, d), jnp.float32)
        params = {"embed": table(0)}
        if not self.tied_head:
            params["head"] = table(98)
        params["norm_f"], _ = self.norm_f.setup(child_rng(rng, 99), spec)
        return params, spec

    def _stored(self, params):
        """Every leaf in the dtype it is kept in."""
        def stored(path, leaf):
            keep = getattr(path[-1], "key", None) in FULL_PRECISION
            return leaf if keep else leaf.astype(self.dtype)

        return jax.tree_util.tree_map_with_path(stored, params)

    # The residual stream is float32 (it is small beside the weights, and
    # rounding it to bfloat16 after every layer is what moves a router's
    # choice); every matrix is multiplied in ``dtype``.

    def _embed(self, params, input):
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], input.astype(jnp.int32),
                         axis=0).astype(jnp.float32)
            return x if self.embedding_multiplier == 1 \
                else self.embedding_multiplier * x

    def _residual(self, x, branch):
        """The stream with a branch's output added, float32."""
        branch = branch.astype(jnp.float32)
        return x + (branch if self.residual_multiplier == 1
                    else self.residual_multiplier * branch)

    @staticmethod
    def _post_norm(layer, p, key, branch):
        """A branch's output through the layer's norm ``key`` (float32),
        where the layer has one after the sublayer."""
        if key not in layer:
            return branch
        return layer[key].apply(p[key], (), branch.astype(jnp.float32))[0]

    def _ffn(self, layer, p, x, live=None):
        """``(x + FFN(RMSNorm(x)), the expert layer's counts or None)``;
        tokens that are not ``live (N, T)`` go to no routed expert."""
        experts = isinstance(layer["ffn"], DroplessMoE)
        with jax.named_scope("moe" if experts else "mlp"):
            h, _ = layer["ffn_norm"].apply(p["ffn_norm"], (), x)
            if experts:
                h, load = layer["ffn"].generate(p["ffn"], h, live)
            else:
                h, _ = layer["ffn"].apply(p["ffn"], (), h.astype(self.dtype))
                load = None
            return self._residual(
                x, self._post_norm(layer, p, "ffn_post_norm", h)), load

    @staticmethod
    def _mixer_scope(op):
        """The scope of a layer's token mixer with its norm and residual:
        ``state_mixer`` for a delta-rule or state-space layer (a state a
        sequence, nothing a token), ``attention`` otherwise."""
        return jax.named_scope("state_mixer" if has_slot_state(
            op.state_spec(jnp.float32)) else "attention")

    def _forward_layer(self, layer, p, x):
        """A layer of the full forward."""
        with self._mixer_scope(layer["op"]):
            h, _ = layer["op_norm"].apply(p["op_norm"], (), x)
            h, _ = layer["op"].apply(p["op"], (), h.astype(self.dtype))
            x = self._residual(x, self._post_norm(layer, p, "op_post_norm",
                                                  h))
        return self._ffn(layer, p, x)[0]

    def _paged_layer(self, block, p, x, pool, by, pos, lengths, live, **kw):
        """A layer (``block``: its four modules) of a step of paged
        generation: ``(x, the layer's new state, the expert layer's counts
        or None)``; ``kw`` goes to the mixer (the ``layer`` of a stacked
        leaf)."""
        with self._mixer_scope(block["op"]):
            h, _ = block["op_norm"].apply(p["op_norm"], (), x)
            h, new = block["op"].apply_paged(p["op"], h.astype(self.dtype),
                                             pool, by, pos, lengths, **kw)
            x = self._residual(x, self._post_norm(block, p, "op_post_norm",
                                                  h))
        x, load = self._ffn(block, p, x, live)
        return x, new, load

    def _logits(self, params, x, logits_at=None):
        """Float32 logits, of the one position ``logits_at (N,)`` a row
        where given: ``(N, 1, V)``."""
        with jax.named_scope("head"):
            if logits_at is not None:
                x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
            x, _ = self.norm_f.apply(params["norm_f"], (), x)
            table = params["embed" if self.tied_head else "head"]
            logits = jnp.einsum("ntd,vd->ntv", x.astype(self.dtype),
                                table.astype(self.dtype),
                                preferred_element_type=jnp.float32)
            return logits if self.logits_scaling == 1 \
                else logits / self.logits_scaling

    @staticmethod
    def _check_cache_dtype(dtype, who):
        if jnp.dtype(dtype) == jnp.dtype(jnp.int8):
            raise NotImplementedError(
                f"{who} keeps its latent cache in the model's dtype; an int8 "
                "block layout exists for per-head K and V only")

    #: the counter leaf of a model with expert layers
    moe_load_spec = StateSpec(COUNTER, (len(DroplessMoE.generate_counts),),
                              jnp.int32)

    # ----- the layers in order (``self.layers``) ---------------------------- #
    def setup(self, rng, input_spec):
        params, spec = self._setup_tables(rng, input_spec)
        for i, layer in enumerate(self.layers):
            params[f"layer{i}"] = self._setup_layer(
                layer, child_rng(rng, 1 + i), spec)
        return self._stored(params), ()

    def apply(self, params, state, input, *, training=False, rng=None):
        x = self._embed(params, input)
        for i, layer in enumerate(self.layers):
            x = self._forward_layer(layer, params[f"layer{i}"], x)
        return self._logits(params, x), state

    def paged_state_spec(self, dtype=jnp.float32):
        """Every leaf of the generation state with its kind.  ``float32``,
        the engine's word for a cache that is not quantized, means this
        model's own ``dtype`` for the per-token rows and the convolution's
        tail; the recurrent state is float32 whatever is asked."""
        self._check_cache_dtype(dtype, type(self).__name__)
        spec = {f"layer{i}": layer["op"].state_spec(self.dtype)
                for i, layer in enumerate(self.layers)}
        if any(isinstance(layer["ffn"], DroplessMoE)
               for layer in self.layers):
            spec["moe_load"] = self.moe_load_spec
        return spec

    def init_paged_cache(self, num_blocks: int, block_size: int,
                         dtype=jnp.float32, slots: int = 0):
        return allocate(self.paged_state_spec(dtype), num_blocks, block_size,
                        slots)

    def apply_paged(self, params, input, pool, tables, *, pos, lengths=None,
                    slots=None, logits_at=None):
        """A step of paged generation (``TransformerLM.apply_paged``'s
        contract) for a model with per-slot state: row ``i`` is slot
        ``slots[i]``, the trash slot (the slot leaves' last row) if the
        row is padding or not live.  ``logits_at (N,)`` asks for the logits
        of one position a row, ``(N, 1, V)``.  The pool's counters
        (``tick_counters``) come back as this step's counts, summed over
        the expert layers; over the row groups of ``chunk_rows`` summed
        too, but for a peak (``DroplessMoE.generate_peaks``), of which the
        largest group's is kept: a lower bound of the whole step's.  An
        expert that two groups touch counts in ``experts_touched`` twice,
        as its weights are read twice."""
        if slots is None:
            raise ValueError("a model with per-slot state has to be told "
                             "which slot each row is (slots=)")
        i32 = lambda a: jnp.asarray(a, jnp.int32)
        tables, pos, slots = i32(tables), i32(pos), i32(slots)
        n, per = input.shape[0], self.chunk_rows
        if lengths is None or per is None or n <= per or n % per:
            lengths = None if lengths is None else i32(lengths)
            return self._paged_step(params, input, pool, tables, pos,
                                    lengths, slots, logits_at)
        # the rows of a chunk step share no slot and no block: ``per`` of
        # them at a time, the pool carried from one group to the next
        rows = (input, tables, pos, i32(lengths), slots) \
            + (() if logits_at is None else (i32(logits_at),))
        counters = [k for k in self.tick_counters if k in pool]

        def group(pool, rows):
            input, tables, pos, lengths, slots, *at = rows
            logits, pool = self._paged_step(
                params, input, pool, tables, pos, lengths, slots,
                at[0] if at else None)
            return pool, (logits, [pool[k] for k in counters])

        pool, (logits, counts) = jax.lax.scan(group, pool, jax.tree.map(
            lambda a: a.reshape((n // per, per) + a.shape[1:]), rows))
        # a peak (the busiest expert) is the largest group's, not a sum
        peak = {k: jnp.asarray([a in DroplessMoE.generate_peaks
                                for a in self.tick_counters[k]])
                for k in counters}
        pool.update({k: jnp.where(peak[k], c.max(0), c.sum(0))
                     for k, c in zip(counters, counts)})
        return logits.reshape((n,) + logits.shape[2:]), pool

    def _paged_step(self, params, input, pool, tables, pos, lengths, slots,
                    logits_at):
        """``apply_paged`` for rows that run together: every layer in
        order, a mixer with slot state told the rows' slots, one with
        per-token state their block tables."""
        x = self._embed(params, input)
        slotted = [has_slot_state(layer["op"].state_spec(self.dtype))
                   for layer in self.layers]
        # a row on the trash slot is padding or not live; so is a chunk's
        # token past its row's length
        trash = [pool[f"layer{i}"]["state"].shape[0] - 1
                 for i, s in enumerate(slotted) if s]
        live = jnp.broadcast_to(
            (slots != trash[0])[:, None] if trash else True, input.shape)
        if lengths is not None:
            live &= jnp.arange(input.shape[1])[None, :] < lengths[:, None]
        new_pool, loads = {}, []
        for i, layer in enumerate(self.layers):
            key = f"layer{i}"
            by = slots if slotted[i] else tables
            x, new_pool[key], load = self._paged_layer(
                layer, params[key], x, pool[key], by, pos, lengths, live)
            if load is not None:
                loads.append(load)
        if loads:
            new_pool["moe_load"] = sum(loads)
        return self._logits(params, x, logits_at), new_pool


class Ling(ServedLM):
    """Decoder-only hybrid LM: ``(N, T)`` token ids -> ``(N, T, V)``
    float32 logits."""

    def __init__(self, vocab_size: int, hidden_size: int,
                 layer_types: Sequence[str], num_dense_layers: int,
                 intermediate_size: int, moe_intermediate_size: int,
                 num_heads: int, head_dim: int, num_experts: int,
                 num_experts_per_tok: int, experts_held=None,
                 n_group: int = 1, topk_group: int = 1,
                 shared_width: int = 0, routed_scaling_factor: float = 1.0,
                 kv_rank: int = 512, nope_dim: int = 128, rope_dim: int = 64,
                 v_dim: int = 128, rope_theta: float = 10000.0,
                 conv_kernel: int = 4, kda_lower_bound: float = -5.0,
                 norm_eps: float = 1e-6, max_len: int = 8192,
                 dtype=jnp.float32, use_kernel: str = "auto", name=None):
        super().__init__(name)
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.layer_types = tuple(layer_types)
        self.max_len = max_len
        self.dtype = jnp.dtype(dtype)
        self.layers = []
        for i, kind in enumerate(self.layer_types):
            if kind == "kda":
                op = KimiDeltaAttention(hidden_size, num_heads, head_dim,
                                        conv_kernel, kda_lower_bound,
                                        norm_eps, use_kernel)
            elif kind == "latent_attention":
                op = LatentAttention(hidden_size, num_heads, kv_rank,
                                     nope_dim, rope_dim, v_dim, rope_theta,
                                     norm_eps)
            else:
                raise ValueError(f"unknown layer type {kind!r}")
            if i < num_dense_layers:
                ffn = GatedMLP(hidden_size, intermediate_size)
            else:
                ffn = DroplessMoE(hidden_size, moe_intermediate_size,
                                  num_experts, num_experts_per_tok,
                                  experts_held, True, routed_scaling_factor,
                                  use_kernel, n_group, topk_group,
                                  shared_width)
            self.layers.append(self._new_layer(op, ffn, norm_eps))
        self.norm_f = RMSNorm(hidden_size, norm_eps)
        self.add(self.norm_f)
