"""Ling-3.0-flash-VL's language model (inclusionAI) for the benchmark, as ONE
CHIP'S SHARE of a four-chip expert-parallel serving replica: the program's
model built at the configuration's sizes, weights from a seed, the work a
token needs from shapes, and a plain reference that is given the same
share.  No vision tower (the catalog's row has no settings for one): ids
are text ids.

Pre-norm residual blocks, RMSNorm (eps 1e-6)::

    h = x + Op_l(RMSNorm(x));   y = h + FFN_l(RMSNorm(h))

Published layer ``i`` is a LATENT-ATTENTION layer when ``(i + 1) %
layer_group_size == 0`` and a DELTA-RULE layer otherwise; the first
``first_k_dense_replace`` layers have a dense gated MLP, the rest the
mixture.  What the config does not fix is listed under ``assumed`` in
``configs/ling-3.0-flash-vl.json``.

Delta-rule layer (KDA, as published for Kimi Linear), ``H`` heads of
``d = 128``; for token ``x_t``::

    q, k, v = SiLU(conv4(W_qkv x))      causal depthwise over 4 tokens
    q, k    = l2norm(q), l2norm(k) a head;   q *= d^-1/2
    g_t     = kda_lower_bound * sigmoid(exp(A_log) * (W_a x_t + dt_bias))
    beta_t  = sigmoid(W_b x_t)          a head
    S_t     = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t     = S_t^T q_t                 S (d, d) a head, float32
    out     = W_o (sigmoid(W_g x_t) * RMSNorm_head(o_t))

Latent-attention layer (MLA without a query latent), ``H`` heads::

    q        = RMSNorm_head(W_q x)      128 no-position + 64 rotary a head
    [c, k_r] = W_kva x                  512 + 64
    c, k_r   = RMSNorm(c), RoPE(RMSNorm(k_r))       theta 6e6, rotate-half
    [k_n, v] = W_kvb c                  128 + 128 a head
    k        = [k_n, k_r]               k_r shared by all heads
    o        = causal softmax(q k^T / sqrt(192)) v
    out      = W_o (sigmoid(W_gate x)_h * o_h)      one gate value a head

Mixture: ``s = sigmoid(W_r x)`` over all ``router_width`` experts in
float32; selection on ``s + b``: ``n_group`` groups, a group's score the
sum of its best two, the best ``topk_group`` groups kept, the top ``k``
among their experts; weights ``s`` of the chosen over ``(their sum +
1e-6)``, times ``routed_scaling_factor``; gated-SiLU experts, of which the
chip holds ``experts_held`` (what the others would add is left out, here
as in the program); plus one shared expert on every token.

Three parts, kept apart (as ``gpt2-medium.py``): ``program_*`` are the
only functions that import ``bigdl_tpu``; ``make_params`` and the work
functions are the benchmark's own; ``reference_*`` are plain ``jax.numpy``
float32 at matmul precision ``highest``: a full forward over a whole
sequence, no cache, no kernels, the recurrence token by token, attention
blocked over queries and the mixture over experts so that 8192 tokens fit
beside the weights.

Parameter tree (the layout ``bigdl_tpu.models.ling.Ling`` uses)::

    embed (V, D)   head (V, D)   norm_f {weight}
    layer{i}: op_norm {weight}  ffn_norm {weight}
              op:  {qkv_weight (3C, D), conv_kernel (4, 3C), ag_weight (2C, D),
                    dt_bias (C,), A_log (H,), b_weight (H, D), o_norm (d,),
                    out_weight (D, C)}                        C = H d
                or {q_weight (H 192, D), q_norm (192,), kva_weight (576, D),
                    kv_norm (512,), kr_norm (64,), kvb_weight (H 256, 512),
                    gate_weight (H, D), out_weight (D, H 128)}
              ffn: {w1 (F, D), w3 (F, D), w2 (D, F)}
                or {router_weight (E, D), router_bias (E,),
                    w1 (held, D, Fe), w3 (held, D, Fe), w2 (held, Fe, D),
                    shared {w1 (Fs, D), w3 (Fs, D), w2 (D, Fs)}}
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from harness.precision import rounded, through
from harness.traffic import prng_key

#: leaves kept in float32 whatever the configuration's weight dtype
FULL_PRECISION = ("weight", "router_weight", "router_bias", "A_log",
                  "dt_bias", "o_norm", "q_norm", "kv_norm", "kr_norm")
L2_EPS = 1e-6
QUERY_BLOCK = 512


# --------------------------------------------------------------------- #
# sizes
# --------------------------------------------------------------------- #

def sizes(cfg):
    group = cfg["layer_group_size"]
    kinds = ["latent_attention" if (i + 1) % group == 0 else "kda"
             for i in cfg["layers_run"]]
    assert len(kinds) == cfg["num_hidden_layers"]
    first, held = cfg["experts_held"]
    assert held == cfg["num_experts"]
    assert cfg["qk_rope_head_dim"] == cfg["rotary_dim"]
    return dict(
        V=cfg["vocab_size"], D=cfg["hidden_size"],
        H=cfg["num_attention_heads"], d=cfg["head_dim"],
        F=cfg["intermediate_size"], Fe=cfg["moe_intermediate_size"],
        Fs=cfg["moe_shared_expert_intermediate_size"],
        E=cfg["router_width"], first=first, held=held,
        k=cfg["num_experts_per_tok"], groups=cfg["n_group"],
        topk_group=cfg["topk_group"], taps=cfg["short_conv_kernel_size"],
        rank=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], vd=cfg["v_head_dim"],
        dense=cfg["first_k_dense_replace"], kinds=kinds,
        eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"]),
        lower=float(cfg["kda_lower_bound"]),
        scaling=float(cfg["routed_scaling_factor"]),
        P=cfg["n_positions"],
        dtype=cfg.get("program", {}).get("dtype", "float32"))


def param_shapes(cfg):
    s = sizes(cfg)
    D, H, d = s["D"], s["H"], s["d"]
    C = H * d
    norm = lambda: {"weight": (D,)}
    out = {"embed": (s["V"], D), "head": (s["V"], D), "norm_f": norm()}
    for i, kind in enumerate(s["kinds"]):
        if kind == "kda":
            op = {"qkv_weight": (3 * C, D), "conv_kernel": (s["taps"], 3 * C),
                  "ag_weight": (2 * C, D), "dt_bias": (C,), "A_log": (H,),
                  "b_weight": (H, D), "o_norm": (d,), "out_weight": (D, C)}
        else:
            qd = s["nope"] + s["rope"]
            op = {"q_weight": (H * qd, D), "q_norm": (qd,),
                  "kva_weight": (s["rank"] + s["rope"], D),
                  "kv_norm": (s["rank"],), "kr_norm": (s["rope"],),
                  "kvb_weight": (H * (s["nope"] + s["vd"]), s["rank"]),
                  "gate_weight": (H, D), "out_weight": (D, H * s["vd"])}
        if i < s["dense"]:
            ffn = {"w1": (s["F"], D), "w3": (s["F"], D), "w2": (D, s["F"])}
        else:
            ffn = {"router_weight": (s["E"], D), "router_bias": (s["E"],),
                   "w1": (s["held"], D, s["Fe"]),
                   "w3": (s["held"], D, s["Fe"]),
                   "w2": (s["held"], s["Fe"], D),
                   "shared": {"w1": (s["Fs"], D), "w3": (s["Fs"], D),
                              "w2": (D, s["Fs"])}}
        out[f"layer{i}"] = {"op_norm": norm(), "ffn_norm": norm(),
                            "op": op, "ffn": ffn}
    return out


def _is_shape(x):
    return isinstance(x, tuple)


def param_count(cfg):
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        param_shapes(cfg), is_leaf=_is_shape))


def _last(path):
    return getattr(path[-1], "key", str(path[-1]))


def param_dtypes(cfg):
    """The dtype every leaf is stored in: the configuration's for the
    matrices, float32 for the leaves ``FULL_PRECISION`` names."""
    dt = jnp.dtype(sizes(cfg)["dtype"])
    return jax.tree_util.tree_map_with_path(
        lambda path, _: jnp.dtype(jnp.float32)
        if _last(path) in FULL_PRECISION else dt,
        param_shapes(cfg), is_leaf=_is_shape)


# --------------------------------------------------------------------- #
# weights from the seed, on the device, a leaf at a time
# --------------------------------------------------------------------- #

def _leaf_rule(path, cfg):
    """(mean, std) of a leaf by its place in the tree."""
    last = _last(path)
    if last in ("weight", "o_norm", "q_norm", "kv_norm", "kr_norm"):
        return 1.0, 0.02
    if last == "conv_kernel":
        return 0.0, 0.3
    if last == "router_weight":     # logits of spread about 1
        return 0.0, 1.0 / math.sqrt(cfg["hidden_size"])
    if last == "A_log":
        return 0.0, 0.3
    if last == "dt_bias":           # decays of 0.8-0.99 a token, mostly
        return -4.0, 1.0
    return 0.0, 0.02


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _draw(key, mean, std, shape, dtype):
    return (mean + std * jax.random.normal(key, shape, jnp.float32)) \
        .astype(dtype)


def make_params(cfg, seed):
    """The weights from ``seed``, drawn in float32 and stored in the
    configuration's dtype, one jitted call a leaf (the key and the rule
    are arguments: one compiled program a shape serves every seed): at
    the cell's sizes the tree is 10.5 GB, and drawn in one call its
    float32 draft would not fit beside it."""
    key = prng_key(seed)
    leaves, treedef = jax.tree.flatten_with_path(param_shapes(cfg),
                                                 is_leaf=_is_shape)
    dtypes = jax.tree.leaves(param_dtypes(cfg))
    out = []
    for i, ((path, shape), dt) in enumerate(zip(leaves, dtypes)):
        mean, std = _leaf_rule(path, cfg)
        out.append(_draw(jax.random.fold_in(key, i), mean, std, shape, dt))
    return jax.tree.unflatten(treedef, out)


# --------------------------------------------------------------------- #
# required work, from shapes
# --------------------------------------------------------------------- #

def held_share(cfg):
    """Expert-rows a token brings to THIS chip at the balanced
    expectation: ``k * held / router_width`` (8 x 128 / 512 = 2)."""
    s = sizes(cfg)
    return s["k"] * s["held"] / s["E"]


def expert_layers(cfg):
    s = sizes(cfg)
    return len(s["kinds"]) - s["dense"]


def kda_layers(cfg):
    return sizes(cfg)["kinds"].count("kda")


def forward_flops(cfg, context_lengths):
    """Floating-point operations a forward pass needs on this chip for
    tokens whose causal context lengths are given: the matmuls (2 per
    multiply-add) of mixers, FFNs, router, shared expert and head; the
    delta rule's four passes over a head's ``d x d`` state (decay, read,
    write, read-out: 2 each an element); latent attention as the
    EXPANDED form counts it (two matmuls a key over 192- and 128-wide
    heads, and ``W_kvb`` once a token), which is the least either path
    needs; and the ROUTED work of the experts held here AT THE BALANCED
    EXPECTATION (``held_share`` expert-rows a token)."""
    s = sizes(cfg)
    D, H, d = s["D"], s["H"], s["d"]
    C = H * d
    ctx = np.asarray(context_lengths, np.float64)
    per_token = 2.0 * D * s["V"]
    per_key = 0.0
    for i, kind in enumerate(s["kinds"]):
        if kind == "kda":
            per_token += 2 * D * (3 * C + 2 * C + H) + 2 * C * D \
                + 2 * s["taps"] * 3 * C + 8 * H * d * d
        else:
            per_token += 2 * D * (H * (s["nope"] + s["rope"]) + s["rank"]
                                  + s["rope"] + H) \
                + 2 * s["rank"] * H * (s["nope"] + s["vd"]) \
                + 2 * H * s["vd"] * D
            per_key += 2 * H * (s["nope"] + s["rope"] + s["vd"])
        if i < s["dense"]:
            per_token += 3 * 2 * D * s["F"]
        else:
            per_token += 2 * D * s["E"] + 3 * 2 * D * s["Fs"] \
                + held_share(cfg) * 3 * 2 * D * s["Fe"]
    return ctx.size * per_token + per_key * float(ctx.sum())


def kernel_work(cfg, mix, name):
    """FLOPs and bytes ONE call of a kernel needs at the mix's shapes."""
    s = sizes(cfg)
    act = jnp.dtype(s["dtype"]).itemsize
    if name == "kda_decode":
        # one call a delta-rule layer a decode tick: each LIVE slot's
        # state (H x d x d float32) read once and written once; the
        # kernel skips the slots that are not live, so the reader
        # (``grouped_roofline``) multiplies by the live slots the
        # decode_prep spans counted, which are a tick's and not summed
        # over layers: ``layers`` 1, and nothing is read once a call
        state = s["H"] * s["d"] * s["d"] * 4
        return {"flops_per_row": 8.0 * s["H"] * s["d"] * s["d"],
                "bytes_per_row": 2.0 * state,
                "bytes_per_call": 0.0, "layers": 1}
    if name == "grouped_matmul":
        # one grouped product over the rows of one expert layer (w1, w3
        # and w2 are all D x Fe or Fe x D): a row's FLOPs and bytes, and
        # the weights of ONE expert, which a call reads once for every
        # expert that got a row (the moe_load spans' experts_touched)
        return {"flops_per_row": 2.0 * s["D"] * s["Fe"],
                "bytes_per_row": float((s["D"] + s["Fe"]) * act),
                "bytes_per_expert": float(s["D"] * s["Fe"] * act),
                "calls_per_layer": 3, "layers": expert_layers(cfg)}
    raise KeyError(name)


# --------------------------------------------------------------------- #
# the program's model (the ONLY part that imports the program)
# --------------------------------------------------------------------- #

def program_model(cfg, params, batch_spec):
    """``bigdl_tpu.models.ling.Ling`` at the configuration's sizes with
    the benchmark's weights installed, in the dtypes they are stored in."""
    from bigdl_tpu.models.ling import Ling

    s = sizes(cfg)
    model = Ling(
        s["V"], s["D"], s["kinds"], s["dense"], s["F"], s["Fe"], s["H"],
        s["d"], s["E"], s["k"], experts_held=(s["first"], s["held"]),
        n_group=s["groups"], topk_group=s["topk_group"],
        shared_width=s["Fs"], routed_scaling_factor=s["scaling"],
        kv_rank=s["rank"], nope_dim=s["nope"], rope_dim=s["rope"],
        v_dim=s["vd"], rope_theta=s["theta"], conv_kernel=s["taps"],
        kda_lower_bound=s["lower"], norm_eps=s["eps"], max_len=s["P"],
        dtype=s["dtype"])
    expect, _ = jax.eval_shape(lambda k: model.setup(k, batch_spec),
                               jax.random.key(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), expect)
    if got != want:
        raise RuntimeError("the benchmark's parameter tree does not match "
                           f"the program's: {got} != {want}")
    model.set_parameters(params)
    model.set_state(())
    return model


# --------------------------------------------------------------------- #
# the plain reference
# --------------------------------------------------------------------- #

def _matrix_mode(mode):
    """What the matmuls' inputs are rounded through: ``bf16_state`` rounds
    none of them (it keeps the delta rule's state and decay in bfloat16,
    nothing else)."""
    return "f32" if mode == "bf16_state" else mode


def _mm(a, b, mode):
    """a @ b.T in float32; ``mode`` rounds both inputs first."""
    mode = _matrix_mode(mode)
    return jnp.einsum("...i,oi->...o", through(a, mode),
                      through(b.astype(jnp.float32), mode),
                      precision="highest")


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * weight


def _bf16(x):
    return rounded(x, lambda v: v.astype(jnp.bfloat16).astype(jnp.float32))


def _rope(x, theta):
    """Rotate-half rotary embedding on ``(B, T, H, Dh)``, positions
    ``0..T-1``."""
    t, dh = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None]
    x1, x2 = jnp.split(x, 2, -1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _kda(u, p, s, mode):
    """The delta-rule layer, token by token."""
    b, t, _ = u.shape
    H, d, taps = s["H"], s["d"], s["taps"]
    proj = jnp.pad(_mm(u, p["qkv_weight"], mode),
                   ((0, 0), (taps - 1, 0), (0, 0)))
    kernel = p["conv_kernel"].astype(jnp.float32)
    conv = sum(kernel[j] * proj[:, j:j + t] for j in range(taps))
    q, k, v = [a.reshape(b, t, H, d)
               for a in jnp.split(jax.nn.silu(conv), 3, -1)]
    l2 = lambda a: a * jax.lax.rsqrt(
        jnp.sum(jnp.square(a), -1, keepdims=True) + L2_EPS)
    q, k = l2(q) / math.sqrt(d), l2(k)
    a, gate = jnp.split(_mm(u, p["ag_weight"], mode), 2, -1)
    g = s["lower"] * jax.nn.sigmoid(
        jnp.exp(p["A_log"])[:, None] * (a + p["dt_bias"]).reshape(b, t, H, d))
    beta = jax.nn.sigmoid(_mm(u, p["b_weight"], mode))
    keep = _bf16 if mode == "bf16_state" else (lambda x: x)

    def step(S, xs):
        qt, kt, vt, gt, bt = xs                    # (b, H, d), bt (b, H)
        S = S * keep(jnp.exp(gt))[..., None]
        held = jnp.einsum("bhk,bhkv->bhv", kt, S, precision="highest")
        S = keep(S + jnp.einsum("bhk,bhv->bhkv", bt[..., None] * kt,
                                vt - held, precision="highest"))
        return S, jnp.einsum("bhk,bhkv->bhv", qt, S, precision="highest")

    tm = lambda x: jnp.moveaxis(x, 1, 0)
    _, o = jax.lax.scan(step, jnp.zeros((b, H, d, d), jnp.float32),
                        (tm(q), tm(k), tm(v), tm(g), tm(beta)))
    o = _rms(jnp.moveaxis(o, 0, 1), p["o_norm"], s["eps"])
    return _mm(jax.nn.sigmoid(gate) * o.reshape(b, t, H * d),
               p["out_weight"], mode)


def _mla(u, p, s, mode):
    """Latent attention, expanded: every token's keys and values formed,
    plain causal attention over heads, ``QUERY_BLOCK`` queries at a time."""
    b, t, _ = u.shape
    H, nope, rope, vd = s["H"], s["nope"], s["rope"], s["vd"]
    mm = _matrix_mode(mode)
    q = _rms(_mm(u, p["q_weight"], mode).reshape(b, t, H, nope + rope),
             p["q_norm"], s["eps"])
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], s["theta"])], -1)
    kva = _mm(u, p["kva_weight"], mode)
    c = _rms(kva[..., :s["rank"]], p["kv_norm"], s["eps"])
    k_r = _rope(_rms(kva[..., s["rank"]:], p["kr_norm"],
                     s["eps"])[:, :, None], s["theta"])
    kv = _mm(c, p["kvb_weight"], mode).reshape(b, t, H, nope + vd)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r, (b, t, H, rope))], -1)
    v = kv[..., nope:]
    block = min(QUERY_BLOCK, t)
    blocks = -(-t // block)
    # queries padded to whole blocks (the padding's rows are cut off below)
    qp = jnp.pad(q, ((0, 0), (0, blocks * block - t), (0, 0), (0, 0)))

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(qp, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", through(qb, mm),
                            through(k, mm), precision="highest") \
            / math.sqrt(nope + rope)
        seen = jnp.arange(t)[None, :] <= (start + jnp.arange(block))[:, None]
        w = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", through(w, mm), through(v, mm),
                          precision="highest")

    o = jax.lax.map(rows, jnp.arange(blocks) * block)   # (blocks, b, block, H, vd)
    o = jnp.moveaxis(o, 0, 1).reshape(b, blocks * block, H, vd)[:, :t]
    gate = jax.nn.sigmoid(_mm(u, p["gate_weight"], mode))
    return _mm((o * gate[..., None]).reshape(b, t, H * vd),
               p["out_weight"], mode)


def _gated(u, w1, w3, w2, mode):
    return _mm(jax.nn.silu(_mm(u, w1, mode)) * _mm(u, w3, mode), w2, mode)


def route(u, p, s):
    """``(expert ids (..., k), weights (..., k))``: float32 whatever the
    control's precision (the program keeps the router float32 too)."""
    logits = jnp.einsum("...i,oi->...o", u,
                        p["router_weight"].astype(jnp.float32),
                        precision="highest")
    scores = jax.nn.sigmoid(logits)
    chosen = scores + jax.lax.stop_gradient(p["router_bias"])
    if s["groups"] > 1:
        grouped = chosen.reshape(chosen.shape[:-1] + (s["groups"], -1))
        best_two = jax.lax.top_k(grouped, 2)[0].sum(-1)
        threshold = jax.lax.top_k(best_two, s["topk_group"])[0][..., -1:]
        grouped = jnp.where((best_two >= threshold)[..., None], grouped,
                            -jnp.inf)
        chosen = grouped.reshape(chosen.shape)
    _, idx = jax.lax.top_k(chosen, s["k"])
    w = jnp.take_along_axis(scores, idx, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-6)
    return idx, w * s["scaling"]


def _moe(u, p, s, mode):
    """Routed part of the experts HELD, one expert after the other over
    every token (each weighed by the router's weight for it, nought where
    it was not chosen), plus the shared expert."""
    idx, w = route(u, p, s)

    def one(acc, e_and_w):
        e, w1, w3, w2 = e_and_w
        weight = jnp.sum(jnp.where(idx == s["first"] + e, w, 0.0), -1)
        # stored (D, Fe) and (Fe, D): transposed to ``_mm``'s (out, in)
        return acc + weight[..., None] * _gated(u, w1.T, w3.T, w2.T,
                                                mode), None

    sh = p["shared"]
    routed, _ = jax.lax.scan(
        one, jnp.zeros(u.shape, jnp.float32),
        (jnp.arange(s["held"]), p["w1"], p["w3"], p["w2"]))
    return routed + _gated(u, sh["w1"], sh["w3"], sh["w2"], mode)


def reference_logits(params, tokens, cfg, mode="f32"):
    """(B, T) token ids -> (B, T, V) float32 logits.  ``mode`` is the
    control's precision: ``f32`` (the reference); ``fp8`` (inputs of every
    matmul rounded to e4m3 with a per-tensor scale, the router float32);
    ``bf16`` (matmul inputs and the residual stream rounded to bfloat16);
    ``bf16_state`` (only the delta rule's state and decay kept in
    bfloat16)."""
    s = sizes(cfg)
    stream = _bf16 if mode == "bf16" else (lambda x: x)
    x = stream(params["embed"].astype(jnp.float32)[tokens])
    for i, kind in enumerate(s["kinds"]):
        p = params[f"layer{i}"]
        op = _kda if kind == "kda" else _mla
        x = stream(x + op(_rms(x, p["op_norm"]["weight"], s["eps"]),
                          p["op"], s, mode))
        h = _rms(x, p["ffn_norm"]["weight"], s["eps"])
        f = p["ffn"]
        x = stream(x + (_gated(h, f["w1"], f["w3"], f["w2"], mode)
                        if i < s["dense"] else _moe(h, f, s, mode)))
    return _mm(_rms(x, params["norm_f"]["weight"], s["eps"]),
               params["head"], mode)
