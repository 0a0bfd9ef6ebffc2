"""Mixture-of-Experts layers (Switch/Mixtral-style top-k routing).

No reference analogue (SURVEY.md section 2.4: expert parallelism absent) --
built the canonical TPU way: expert parameters are *stacked* on a leading
expert dimension and the dispatch/compute/combine path is three dense
einsums with a static capacity, so the whole layer is MXU-shaped with no
dynamic shapes.  Sharding the expert dimension over an ``expert`` mesh axis
(parallel/ep.py) turns the dispatch/combine einsums into XLA all-to-alls
over ICI -- expert parallelism falls out of GSPMD annotations.

Routing: top-k gating with softmax probs, capacity ``C = ceil(T/E * cf)``
per expert; overflowing tokens are dropped (standard Switch behaviour) and
the load-balancing auxiliary loss (Shazeer et al.) keeps the router honest.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn.initialization import Xavier
from bigdl_tpu.nn.module import Module, child_rng
from bigdl_tpu.nn.normalization import LayerNorm


class MoE(Module):
    """Top-k routed expert MLP: (N, T, D) -> (N, T, D).

    apply() returns ``(out, {"aux_loss": scalar})`` -- the train step adds
    ``aux_weight * aux_loss`` to the task loss.
    """

    def __init__(self, hidden_size: int, num_experts: int, k: int = 2,
                 mlp_ratio: int = 4, capacity_factor: float = 1.25,
                 name=None):
        super().__init__(name)
        self.hidden_size = hidden_size
        self.num_experts = num_experts
        self.k = min(k, num_experts)
        self.mlp_ratio = mlp_ratio
        self.capacity_factor = capacity_factor

    def setup(self, rng, input_spec):
        d, f, e = (self.hidden_size, self.mlp_ratio * self.hidden_size,
                   self.num_experts)
        init = Xavier()
        w1 = jnp.stack([init.init(child_rng(rng, 2 + i), (d, f), d, f)
                        for i in range(e)])
        w2 = jnp.stack([init.init(child_rng(rng, 100 + i), (f, d), f, d)
                        for i in range(e)])
        return {
            "gate": init.init(child_rng(rng, 0), (d, e), d, e),
            "w1": w1,                      # (E, D, F) expert-stacked
            "b1": jnp.zeros((e, f), jnp.float32),
            "w2": w2,                      # (E, F, D)
            "b2": jnp.zeros((e, d), jnp.float32),
        }, ()

    def _capacity(self, tokens: int) -> int:
        # k*tokens routing assignments share E expert slots
        return max(
            self.k,
            int(math.ceil(
                self.k * tokens / self.num_experts * self.capacity_factor)))

    def apply(self, params, state, input, *, training=False, rng=None):
        n, t, d = input.shape
        e, k = self.num_experts, self.k
        tokens = n * t
        cap = self._capacity(tokens)
        x = input.reshape(tokens, d)

        logits = (x @ params["gate"].astype(x.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)              # (T, E)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)       # (T, k)
        gate_vals = gate_vals / jnp.clip(
            gate_vals.sum(-1, keepdims=True), 1e-9)

        # position of each (token, choice) within its expert's capacity
        sel = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)  # (T, k, E)
        # rank within expert: cumulative count over (token, choice) pairs in
        # routing priority order (choice-major so 1st choices beat 2nd)
        flat_sel = sel.transpose(1, 0, 2).reshape(k * tokens, e)
        pos = jnp.cumsum(flat_sel, axis=0) - flat_sel          # (k*T, E)
        pos = (pos * flat_sel).sum(-1)                         # (k*T,)
        fits = pos < cap
        pos = pos.reshape(k, tokens).transpose(1, 0)           # (T, k)
        fits = fits.reshape(k, tokens).transpose(1, 0)

        gate_vals = gate_vals * fits.astype(jnp.float32)
        # dispatch/combine tensors (T, E, C)
        combine = jnp.einsum(
            "tk,tke,tkc->tec", gate_vals, sel,
            jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.float32) *
            fits[..., None].astype(jnp.float32))
        dispatch = (combine > 0).astype(x.dtype)

        # expert compute, all MXU einsums over the stacked expert dim
        ex_in = jnp.einsum("tec,td->ecd", dispatch, x)
        h = jnp.einsum("ecd,edf->ecf", ex_in,
                       params["w1"].astype(x.dtype))
        h = h + params["b1"][:, None, :].astype(x.dtype)
        h = jax.nn.gelu(h)
        h = jnp.einsum("ecf,efd->ecd", h, params["w2"].astype(x.dtype))
        h = h + params["b2"][:, None, :].astype(x.dtype)
        out = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), h)

        # load-balance aux loss: E * mean(fraction_routed) . mean(prob)
        frac = sel[:, 0, :].mean(0)            # first-choice assignment share
        mean_prob = probs.mean(0)
        aux = (frac * mean_prob).sum() * e
        return out.reshape(n, t, d), {"aux_loss": aux}


def _no_cotangent(x):
    return np.zeros(x.shape, jax.dtypes.float0)


@jax.custom_vjp
def _take_rows(x, take, readers):
    """``y[i] = x[take[i]]`` (a row of zeros where ``take[i]`` is
    ``len(x)``), with the backward written as a gather too: row ``r`` of
    ``x`` is read by exactly the outputs ``readers[r, :]`` (``len(y)``
    where fewer read it), so its cotangent is their sum and no scatter
    runs."""
    return x.at[take].get(mode="fill", fill_value=0)


def _take_rows_fwd(x, take, readers):
    return _take_rows(x, take, readers), (take, readers)


def _take_rows_bwd(res, g):
    take, readers = res
    dx = g.at[readers].get(mode="fill", fill_value=0).sum(1)
    return dx.astype(g.dtype), _no_cotangent(take), _no_cotangent(readers)


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


class DroplessMoE(Module):
    """Mixture of gated-MLP experts that drops no token, as one chip's
    share of an expert-parallel layer: ``(N, T, D) -> (N, T, D)``.

    The router scores every token against ALL ``num_experts`` in float32
    (``s = sigmoid(u W_g)``), chooses the top ``k`` of ``s + b`` (``b``,
    the selection bias, chooses and does not weigh; it gets no gradient:
    a balancing rule outside the loss moves it) and weighs the chosen
    with ``s`` over ``(their sum + 1e-6)``.  With ``n_group > 1`` the
    choice is group-limited: the experts lie in ``n_group`` equal groups,
    a group's score is the sum of its two best ``s + b``, only the best
    ``topk_group`` groups stay, and the top ``k`` are taken among their
    experts.  ``shared_width > 0`` adds one gated MLP of that width that
    every token passes through, whatever the router says (every share of
    an expert-parallel layer computes it alike: count it once).
    ``swiglu_limit`` clamps every expert's two inputs to its SiLU product,
    routed and shared: ``silu(min(W_1 u, L)) * clip(W_3 u, -L, L)``.  Of the
    ``num_experts`` this
    layer HOLDS ``experts_held = (first, count)``: the assignments to held
    experts are sorted by expert into a buffer sized for the worst case,
    three grouped products (``ops/grouped_matmul.py``) run over the rows
    in use, and each token gets back the weighted rows of its held
    experts.  What the absent experts would add is left out: on a mesh
    the other shares add it (``sum`` over the expert axis); on one chip
    nothing stands in for them.

    apply() returns ``(out, {"moe_load": int32[4]})``: assignments routed,
    assignments to experts held here, those of the busiest held expert,
    and ``rows_here`` over the experts held (whole rows).  The trainer's
    loop puts them into a ``moe_load`` span (``state_spans``).
    ``generate()`` is the same layer for the steps of generation: tiles
    sized for the few rows an expert gets there, and a fifth count, the
    held experts that got a row at all.
    """

    #: leaves that the train step keeps in float32 under a compute dtype
    full_precision_params = ("router_weight",)
    #: model state the trainer's loop records after each synced step: a
    #: span of the key's name with these attributes, one fetch a key
    state_spans = {"moe_load": ("rows_routed", "rows_here",
                                "rows_busiest_expert", "rows_mean_expert")}

    def __init__(self, hidden_size: int, expert_width: int, num_experts: int,
                 k: int, experts_held=None, norm_topk_prob: bool = True,
                 routed_scaling_factor: float = 1.0,
                 use_kernel: str = "auto", n_group: int = 1,
                 topk_group: int = 1, shared_width: int = 0,
                 swiglu_limit: float = None, name=None):
        super().__init__(name)
        self.swiglu_limit = swiglu_limit
        assert use_kernel in ("auto", "never", "interpret")
        assert num_experts % n_group == 0 and 1 <= topk_group <= n_group
        self.n_group, self.topk_group = n_group, topk_group
        self.shared_width = shared_width
        self.hidden_size = hidden_size
        self.expert_width = expert_width
        self.num_experts = num_experts
        self.k = k
        self.experts_held = tuple(experts_held or (0, num_experts))
        first, count = self.experts_held
        assert 0 <= first and first + count <= num_experts and count >= 1
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.use_kernel = use_kernel

    def setup(self, rng, input_spec):
        d, f, e = self.hidden_size, self.expert_width, self.num_experts
        held = self.experts_held[1]
        init = Xavier()

        def stack(seed, shape, fan_in, fan_out):
            return jnp.stack([init.init(child_rng(rng, seed + i), shape,
                                        fan_in, fan_out)
                              for i in range(held)])

        params = {
            "router_weight": init.init(child_rng(rng, 0), (e, d), d, e),
            "router_bias": jnp.zeros((e,), jnp.float32),
            "w1": stack(1000, (d, f), d, f),       # (held, D, F)
            "w3": stack(2000, (d, f), d, f),
            "w2": stack(3000, (f, d), f, d),       # (held, F, D)
        }
        if self.shared_width:
            fs = self.shared_width
            params["shared"] = {
                "w1": init.init(child_rng(rng, 4000), (fs, d), d, fs),
                "w3": init.init(child_rng(rng, 4001), (fs, d), d, fs),
                "w2": init.init(child_rng(rng, 4002), (d, fs), fs, d)}
        return params, {"moe_load": jnp.zeros((4,), jnp.int32)}

    def route(self, params, x):
        """``(expert ids (T, k), weights (T, k))`` in float32."""
        logits = jnp.einsum("td,ed->te", x.astype(jnp.float32),
                            params["router_weight"].astype(jnp.float32),
                            precision="highest")
        scores = jax.nn.sigmoid(logits)
        chosen = scores + jax.lax.stop_gradient(
            params["router_bias"].astype(jnp.float32))
        if self.n_group > 1:
            grouped = chosen.reshape(chosen.shape[0], self.n_group, -1)
            best_two = jax.lax.top_k(grouped, 2)[0].sum(-1)
            _, keep = jax.lax.top_k(best_two, self.topk_group)
            kept = jnp.zeros(best_two.shape, bool).at[
                jnp.arange(keep.shape[0])[:, None], keep].set(True)
            chosen = jnp.where(kept[..., None], grouped,
                               -jnp.inf).reshape(chosen.shape)
        _, idx = jax.lax.top_k(chosen, self.k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        if self.norm_topk_prob:
            w = w / (w.sum(-1, keepdims=True) + 1e-6)
        return idx, w * self.routed_scaling_factor

    def _products(self, on_tpu, rows_an_expert=None):
        """``(rows a tile, the grouped product)``.  A tile is
        ``ops.grouped_matmul.BLOCK_ROWS`` on the chip; generation, where
        an expert gets ``rows_an_expert`` rows on average and every
        expert with a row costs a whole tile, takes the power of two
        next above that, from 16 (one bf16 tile) up."""
        from bigdl_tpu.ops import grouped_matmul as gm

        kernel = self.use_kernel == "interpret" or (
            self.use_kernel == "auto" and on_tpu)
        block = gm.BLOCK_ROWS if on_tpu else 8
        if on_tpu and rows_an_expert is not None:
            block = min(block, max(16, 1 << max(
                0, int(rows_an_expert) - 1).bit_length()))
        if kernel:
            interpret = self.use_kernel == "interpret"
            return block, lambda a, w, sizes: gm.grouped_matmul(
                a, w, sizes, block_rows=block, interpret=interpret)
        return block, lambda a, w, sizes: gm.grouped_matmul_reference(
            a, w, sizes, block)

    def _swiglu(self, gate, up):
        """``silu(gate) * up``, each clamped where the layer has a
        ``swiglu_limit``; ``gate`` and ``up`` are thunks, computed in that
        order (the programs without a limit lower as they always have)."""
        limit = self.swiglu_limit
        gate = gate() if limit is None else jnp.minimum(gate(), limit)
        gate = jax.nn.silu(gate)
        up = up() if limit is None else jnp.clip(up(), -limit, limit)
        return gate * up

    def apply(self, params, state, input, *, training=False, rng=None):
        out, sizes, assigned = self._run(params, input)
        held = self.experts_held[1]
        load = jnp.stack([jnp.int32(assigned), sizes.sum(), sizes.max(),
                          sizes.sum() // held])
        return out.reshape(input.shape), {"moe_load": load}

    #: what ``generate()`` counts, in order
    generate_counts = ("rows_routed", "rows_here", "rows_busiest_expert",
                       "rows_mean_expert", "experts_touched")
    #: of those, the counts that are a largest one and not a sum
    generate_peaks = ("rows_busiest_expert",)

    def generate(self, params, input, live=None):
        """The layer inside a step of generation: ``(out, int32[5])``, the
        counts ``generate_counts`` names.  The router reads ``input`` as it
        comes (a float32 residual stream's norm keeps its choice from
        flipping on a rounding); the experts multiply in the dtype their
        weights are stored in, and ``out`` is in that dtype.  ``live (N,
        T)`` bool says which tokens are real: a padding token or a row
        that is not live is routed to no expert (it gets the shared
        expert's part alone, and nobody reads it) and is not counted."""
        n, t, _ = input.shape
        out, sizes, assigned = self._run(
            params, input, rows_an_expert=-(-n * t * self.k
                                            // self.num_experts),
            dt=params["w1"].dtype, live=live)
        held = self.experts_held[1]
        return out.reshape(input.shape), jnp.stack([
            jnp.asarray(assigned, jnp.int32), sizes.sum(), sizes.max(),
            sizes.sum() // held, (sizes > 0).sum().astype(jnp.int32)])

    def _run(self, params, input, rows_an_expert=None, dt=None, live=None):
        """``(out (tokens, D), rows of every held expert (held,),
        assignments)``."""
        from bigdl_tpu.nn.attention import _on_tpu
        from bigdl_tpu.ops.grouped_matmul import buffer_rows, group_layout

        n, t, d = input.shape
        tokens, k = n * t, self.k
        first, held = self.experts_held
        block, product = self._products(_on_tpu(), rows_an_expert)
        x = input.reshape(tokens, d)
        with jax.named_scope("moe_router"):
            idx, weights = self.route(params, x)
        if dt is not None:
            x = x.astype(dt)
        dt = x.dtype
        with jax.named_scope("moe_dispatch"):
            # assignments (token-major) sorted by held expert, each
            # expert's rows from a multiple of the tile; those of absent
            # experts get no slot.  One stable sort and one running count:
            # no scatter, here or in the backward.
            assigned = tokens * k
            rows = buffer_rows(assigned, held, block)
            local = idx.reshape(assigned) - first
            here = (local >= 0) & (local < held)
            if live is not None:
                here &= jnp.repeat(live.reshape(tokens), k)
            group = jnp.where(here, local, held)
            member = (group[None] == jnp.arange(held + 1)[:, None])
            count = jnp.cumsum(member.astype(jnp.int32), axis=1)
            sizes_all = count[:, -1]
            sizes = sizes_all[:held]
            offsets, tile_group, _ = group_layout(sizes, rows, block)
            rank = jnp.take_along_axis(count, group[None], axis=0)[0] - 1
            slot_of = jnp.where(group < held,
                                jnp.append(offsets, 0)[group] + rank, rows)
            order = jnp.argsort(group, stable=True).astype(jnp.int32)
            starts = jnp.cumsum(sizes_all) - sizes_all
            slot = jnp.arange(rows, dtype=jnp.int32)
            slot_group = jnp.repeat(tile_group, block,
                                    total_repeat_length=rows)
            within = slot - offsets[slot_group]
            assignment_at = jnp.where(
                within < sizes[slot_group],
                order[jnp.minimum(starts[slot_group] + within,
                                  assigned - 1)], assigned)
            token_at = jnp.where(assignment_at < assigned,
                                 assignment_at // k, tokens)
            xs = _take_rows(x, token_at, slot_of.reshape(tokens, k))
        with jax.named_scope("moe_experts"):
            hidden = self._swiglu(
                lambda: product(xs, params["w1"].astype(dt), sizes),
                lambda: product(xs, params["w3"].astype(dt), sizes))
            ys = product(hidden, params["w2"].astype(dt), sizes)
        with jax.named_scope("moe_combine"):
            picked = _take_rows(ys, slot_of, assignment_at[:, None])
            out = (picked.reshape(tokens, k, d)
                   * weights[..., None].astype(dt)).sum(1)
        if self.shared_width:
            with jax.named_scope("moe_shared"):
                p = params["shared"]
                out = out + self._swiglu(
                    lambda: x @ p["w1"].astype(dt).T,
                    lambda: x @ p["w3"].astype(dt).T) @ p["w2"].astype(dt).T
        if live is not None:
            assigned = live.sum().astype(jnp.int32) * k
        return out, sizes, assigned


class MoETransformerBlock(Module):
    """Pre-LN block with MoE in place of the dense MLP."""

    def __init__(self, hidden_size, num_heads, num_experts, k=2,
                 mlp_ratio=4, capacity_factor=1.25, causal=True, name=None):
        super().__init__(name)
        from bigdl_tpu.nn.attention import MultiHeadAttention
        self.ln1 = LayerNorm(hidden_size)
        self.attn = MultiHeadAttention(hidden_size, num_heads, causal)
        self.ln2 = LayerNorm(hidden_size)
        self.moe = MoE(hidden_size, num_experts, k, mlp_ratio,
                       capacity_factor)

    def setup(self, rng, input_spec):
        params = {}
        for i, (key, m) in enumerate([("ln1", self.ln1), ("attn", self.attn),
                                      ("ln2", self.ln2), ("moe", self.moe)]):
            p, _ = m.setup(child_rng(rng, i), input_spec)
            params[key] = p
        return params, ()

    def apply(self, params, state, input, *, training=False, rng=None):
        h, _ = self.ln1.apply(params["ln1"], (), input)
        a, _ = self.attn.apply(params["attn"], (), h, training=training,
                               rng=child_rng(rng, 0))
        x = input + a
        h, _ = self.ln2.apply(params["ln2"], (), x)
        h, st = self.moe.apply(params["moe"], (), h, training=training)
        return x + h, st


class MoETransformerLM(Module):
    """Decoder-only MoE LM; apply() -> (logits, {"aux_loss": total})."""

    def __init__(self, vocab_size, hidden_size, num_heads, num_layers,
                 num_experts, k=2, max_len=2048, mlp_ratio=4,
                 capacity_factor=1.25, name=None):
        super().__init__(name)
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.max_len = max_len
        self.blocks = [
            MoETransformerBlock(hidden_size, num_heads, num_experts, k,
                                mlp_ratio, capacity_factor)
            for _ in range(num_layers)]
        self.ln_f = LayerNorm(hidden_size)

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
        for i, b in enumerate(self.blocks):
            params[f"block{i}"], _ = b.setup(child_rng(rng, 3 + i), hid_spec)
        params["ln_f"], _ = self.ln_f.setup(child_rng(rng, 99), hid_spec)
        return params, ()

    def apply(self, params, state, input, *, training=False, rng=None):
        t = input.shape[1]
        x = jnp.take(params["wte"], input.astype(jnp.int32), axis=0)
        x = x + params["wpe"][:t][None]
        aux = jnp.float32(0.0)
        for i, b in enumerate(self.blocks):
            x, st = b.apply(params[f"block{i}"], (), x, training=training,
                            rng=child_rng(rng, i))
            aux = aux + st["aux_loss"]
        x, _ = self.ln_f.apply(params["ln_f"], (), x)
        logits = x @ params["head"].astype(x.dtype).T
        return logits, {"aux_loss": aux}
