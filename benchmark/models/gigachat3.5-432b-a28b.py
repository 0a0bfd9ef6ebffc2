"""GigaChat3.5-432B-A28B (ai-sage; ``model_type: gigachat3_5``) for the
benchmark, as ONE CHIP'S SHARE of a sixteen-chip serving deployment
(routed experts by expert parallelism, the vocabulary sliced eight ways,
mixers whole with attention data-parallel): the program's model built at
the configuration's sizes, weights from a seed, the work a token needs
from shapes, and a plain reference that is given the same share.

Every sublayer is normed before AND after (``layernorm_type pre_post``),
every norm the zero-centred gated one, ``N(x) = RMSNorm(x) * 2 sigmoid(w)``
(eps 1e-6); an untied head::

    h = x + N(Op_l(N(x)));   y = h + N(FFN_l(N(h)));   logits = N(x_L) W_head^T

Published layer ``i`` is a LATENT-ATTENTION layer when it is in
``full_attention_layers`` and a GATED DELTA-RULE layer otherwise; the
first ``first_k_dense_replace`` layers have a dense gated MLP, the rest
the mixture.  What the config does not fix is listed under ``assumed`` in
``configs/gigachat3.5-432b-a28b.json``.

Gated delta-rule layer, 32 key heads and 64 value heads of ``d = 128``
(value head ``h`` reads key head ``h // 2``); for a token ``x_t``::

    q, k, v = SiLU(conv4([W_q x; W_k x; W_v x]))    4096 + 4096 + 8192, causal depthwise
    q, k    = l2norm(q), l2norm(k) a key head;  q *= d^-1/2
    g_h     = -exp(A_log_h) * softplus(W_a x_t + dt_bias)_h      a value head
    beta_h  = sigmoid(W_b x_t)_h                                 a value head
    S_h     = exp(g_h) S_h + beta_h k (v_h - (exp(g_h) S_h)^T k)^T    (d, d) float32
    o_h     = S_h^T q
    out     = W_o (RMSNorm_d(o_h) * (1 + w_o) * 2 sigmoid(W_z x_t))

Latent-attention layer (MLA with a query latent), 64 heads::

    q        = W_qb RMSNorm_1536(W_qa x)      128 no-position + 64 rotary a head
    [c, k_r] = W_kva x                        512 + 64
    c        = RMSNorm(c);  k_r = RoPE(k_r)   shared by all heads
    [k_n, v] = W_kvb c                        128 + 128 a head
    o        = causal softmax(q k^T * 192^-1/2 * mscale^2) v
    out      = W_o (sigmoid(W_gate x) * o)    one gate value a channel
    RoPE: interleaved pairs (x_2i, x_2i+1), theta 1e5, YaRN (factor 8 over
    32768, beta_fast 32, beta_slow 1); mscale = 0.1 ln 8 + 1

Mixture (``noaux_tc``, no group limit): ``s = sigmoid(W_r x)`` over all 256
experts in float32; the top 8 of ``s + b``; weights ``s`` of the chosen
over ``(their sum + 1e-6)`` times 2.5; experts ``W_2(SiLU(min(W_1 u, 10))
* clip(W_3 u, -10, 10))``, of which the chip holds ``experts_held`` (what
the others would add is left out, here as in the program); plus the
shared expert on every token, clamped alike and unweighted.

Three parts, kept apart (as ``gpt2-medium.py``): ``program_*`` are the
only functions that import ``bigdl_tpu``; ``make_params`` and the work
functions are the benchmark's own; ``reference_*`` are plain ``jax.numpy``
float32 at matmul precision ``highest``: a full forward over a whole row,
no cache, no kernels, the recurrence token by token, attention blocked
over queries and the mixture over experts, so that 8192 tokens fit beside
the weights (in float32 they would be 18.9 GB).

Parameter tree (the layout ``bigdl_tpu.models.gigachat.GigaChat35``
uses)::

    embed (V, D)   head (V, D)   norm_f {weight}
    layer{i}: op_norm, op_post_norm, ffn_norm, ffn_post_norm {weight}
              op:  {qkv_weight (Cq, D), conv_kernel (4, Cq), ab_weight (2 Hv, D),
                    dt_bias (Hv,), A_log (Hv,), z_weight (Cv, D), o_norm (d,),
                    out_weight (D, Cv)}           Cq = (2 Hk + Hv) d, Cv = Hv d
                or {qa_weight (1536, D), qa_norm (1536,), qb_weight (H 192, 1536),
                    kva_weight (576, D), kv_norm (512,), kvb_weight (H 256, 512),
                    gate_weight (H 128, D), out_weight (D, H 128)}
              ffn: {w1 (F, D), w3 (F, D), w2 (D, F)}
                or {router_weight (E, D), router_bias (E,),
                    w1 (held, D, Fe), w3 (held, D, Fe), w2 (held, Fe, D),
                    shared {w1 (Fs, D), w3 (Fs, D), w2 (D, Fs)}}
"""

import functools
import importlib.util
import math

import jax
import jax.numpy as jnp
import numpy as np

from harness.precision import rounded, through
from harness.traffic import prng_key

#: leaves kept in float32 whatever the configuration's weight dtype
FULL_PRECISION = ("weight", "router_weight", "router_bias", "A_log",
                  "dt_bias", "o_norm", "kv_norm", "qa_norm")
PROGRAM_CLASS = "bigdl_tpu.models.gigachat.GigaChat35"
L2_EPS = 1e-6
QUERY_BLOCK = 256


# --------------------------------------------------------------------- #
# sizes
# --------------------------------------------------------------------- #

def sizes(cfg):
    assert cfg["n_group"] == 1 and cfg["topk_group"] == 1
    assert cfg["layernorm_type"] == "pre_post" and cfg["gated_attention"]
    assert cfg["qk_head_dim"] == cfg["qk_nope_head_dim"] \
        + cfg["qk_rope_head_dim"]
    assert cfg["rope_scaling"]["type"] == "yarn"
    assert not cfg["use_shared_expert_sigmoid"]
    assert not cfg["tie_word_embeddings"]
    assert cfg["num_nextn_predict_layers"] == 0
    first, held = cfg["experts_held"]
    assert held == cfg["n_routed_experts"]
    full = set(cfg["full_attention_layers"])
    kinds = ["latent_attention" if i in full else "gated_delta"
             for i in cfg["layers_run"]]
    assert len(kinds) == cfg["num_hidden_layers"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    d = cfg["linear_key_head_dim"]
    assert d == cfg["linear_value_head_dim"] and hv % hk == 0
    return dict(
        V=cfg["vocab_size"], D=cfg["hidden_size"],
        H=cfg["num_attention_heads"], F=cfg["intermediate_size"],
        Fe=cfg["moe_intermediate_size"],
        Fs=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        E=cfg["router_width"], first=first, held=held,
        k=cfg["num_experts_per_tok"], rank=cfg["kv_lora_rank"],
        q_rank=cfg["q_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], vd=cfg["v_head_dim"],
        dense=cfg["first_k_dense_replace"], kinds=kinds,
        Hk=hk, Hv=hv, d=d, Cq=(2 * hk + hv) * d, Cv=hv * d,
        taps=cfg["linear_conv_kernel_dim"],
        gate_scale=float(cfg["linear_sigmoid_gate_scale"]),
        norm_gate=float(cfg["layernorm_gating_weight"]),
        eps=cfg["rms_norm_eps"], o_eps=cfg["linear_attn_o_norm_eps"],
        theta=float(cfg["rope_theta"]), yarn=dict(cfg["rope_scaling"]),
        scaling=float(cfg["routed_scaling_factor"]),
        limit=float(cfg["swiglu_limit"]), P=cfg["n_positions"],
        dtype=cfg.get("program", {}).get("dtype", "float32"))


def layer_shapes(cfg, kind, dense):
    s = sizes(cfg)
    D, H = s["D"], s["H"]
    if kind == "gated_delta":
        op = {"qkv_weight": (s["Cq"], D), "conv_kernel": (s["taps"], s["Cq"]),
              "ab_weight": (2 * s["Hv"], D), "dt_bias": (s["Hv"],),
              "A_log": (s["Hv"],), "z_weight": (s["Cv"], D),
              "o_norm": (s["d"],), "out_weight": (D, s["Cv"])}
    else:
        op = {"qa_weight": (s["q_rank"], D), "qa_norm": (s["q_rank"],),
              "qb_weight": (H * (s["nope"] + s["rope"]), s["q_rank"]),
              "kva_weight": (s["rank"] + s["rope"], D),
              "kv_norm": (s["rank"],),
              "kvb_weight": (H * (s["nope"] + s["vd"]), s["rank"]),
              "gate_weight": (H * s["vd"], D),
              "out_weight": (D, H * s["vd"])}
    if dense:
        ffn = {"w1": (s["F"], D), "w3": (s["F"], D), "w2": (D, s["F"])}
    else:
        ffn = {"router_weight": (s["E"], D), "router_bias": (s["E"],),
               "w1": (s["held"], D, s["Fe"]), "w3": (s["held"], D, s["Fe"]),
               "w2": (s["held"], s["Fe"], D),
               "shared": {"w1": (s["Fs"], D), "w3": (s["Fs"], D),
                          "w2": (D, s["Fs"])}}
    norm = lambda: {"weight": (D,)}
    return {"op_norm": norm(), "op_post_norm": norm(), "ffn_norm": norm(),
            "ffn_post_norm": norm(), "op": op, "ffn": ffn}


def _is_shape(x):
    return isinstance(x, tuple)


def param_shapes(cfg):
    s = sizes(cfg)
    out = {"embed": (s["V"], s["D"]), "head": (s["V"], s["D"]),
           "norm_f": {"weight": (s["D"],)}}
    for i, kind in enumerate(s["kinds"]):
        out[f"layer{i}"] = layer_shapes(cfg, kind, i < s["dense"])
    return out


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


def memory_bytes(cfg, mix):
    """What the cell's device holds, from shapes alone: ``(weights, slot
    state and convolution tails, latent pool)`` in bytes; the latent row
    of ``rank + rope`` values is stored in the next multiple of 128
    columns."""
    s = sizes(cfg)
    e = mix["engine"]
    act = jnp.dtype(s["dtype"]).itemsize
    weights = sum(int(np.prod(shape)) * d.itemsize for shape, d in zip(
        jax.tree.leaves(param_shapes(cfg), is_leaf=_is_shape),
        jax.tree.leaves(param_dtypes(cfg))))
    delta = s["kinds"].count("gated_delta")
    a_slot = delta * (s["Hv"] * s["d"] * s["d"] * 4
                      + (s["taps"] - 1) * s["Cq"] * act)
    row = -(-(s["rank"] + s["rope"]) // 128) * 128
    a_block = s["kinds"].count("latent_attention") * e["kv_block_size"] \
        * row * act
    return (weights, (e["decode_slots"] + 1) * a_slot,
            (e["kv_blocks"] + 1) * a_block)


# --------------------------------------------------------------------- #
# weights from the seed, on the device, a leaf at a time
# --------------------------------------------------------------------- #

def _leaf_rule(path, cfg):
    """(distribution, a, b) of a leaf by its name."""
    last = _last(path)
    if last in ("weight", "o_norm"):    # zero-centred: a scale of about 1
        return "normal", 0.0, 0.02
    if last in ("qa_norm", "kv_norm"):  # plain RMSNorm weights
        return "normal", 1.0, 0.02
    if last == "conv_kernel":
        return "normal", 0.0, 0.3
    if last == "router_weight":         # logits of spread about 1
        return "normal", 0.0, 1.0 / math.sqrt(cfg["hidden_size"])
    if last == "A_log":                 # exp(A_log) = uniform(1, 4)
        return "log_uniform_width", 1.0, 4.0
    if last == "dt_bias":               # softplus(dt_bias) in [1e-3, 1e-2]
        return "inverse_softplus", 1e-3, 1e-2
    return "normal", 0.0, 0.02


@functools.partial(jax.jit, static_argnames=("rule", "shape", "dtype"))
def _draw(key, a, b, rule, shape, dtype):
    f32 = jnp.float32
    if rule == "normal":
        x = a + b * jax.random.normal(key, shape, f32)
    elif rule == "log_uniform_width":   # log of a uniform draw
        x = jnp.log(jax.random.uniform(key, shape, f32, a, b))
    else:                               # inverse softplus of a log-uniform
        step = jnp.exp(jax.random.uniform(key, shape, f32, jnp.log(a),
                                          jnp.log(b)))
        x = step + jnp.log(-jnp.expm1(-step))
    return x.astype(dtype)


def make_params(cfg, seed):
    """The weights from ``seed``, drawn in float32 and stored in the
    configuration's dtype, one jitted call a leaf (the key and the rule's
    numbers are arguments: one compiled program a shape serves every
    seed): at the cell's sizes the tree is 9.46 GB, and drawn in one call
    its float32 draft would not fit beside it.  A program without the
    model's module (a commit from before it) is told so here, before any
    weight is drawn for it."""
    module = cfg.get("program", {}).get("class", PROGRAM_CLASS) \
        .rsplit(".", 1)[0]
    if importlib.util.find_spec(module) is None:
        raise ModuleNotFoundError(f"the program has no {module}")
    key = prng_key(seed)
    leaves, treedef = jax.tree.flatten_with_path(param_shapes(cfg),
                                                 is_leaf=_is_shape)
    dtypes = jax.tree.leaves(param_dtypes(cfg))
    out = []
    for i, ((path, shape), dt) in enumerate(zip(leaves, dtypes)):
        rule, a, b = _leaf_rule(path, cfg)
        out.append(_draw(jax.random.fold_in(key, i), a, b, rule, shape, dt))
    return jax.tree.unflatten(treedef, out)


# --------------------------------------------------------------------- #
# required work, from shapes
# --------------------------------------------------------------------- #

def held_share(cfg):
    """Expert-rows a token brings to THIS chip at the balanced
    expectation: ``k * held / router_width`` (8 x 16 / 256 = 0.5, a
    sixteenth of the routed work)."""
    s = sizes(cfg)
    return s["k"] * s["held"] / s["E"]


def expert_layers(cfg):
    s = sizes(cfg)
    return len(s["kinds"]) - s["dense"]


def forward_flops(cfg, context_lengths):
    """Floating-point operations a forward pass needs on this chip for
    tokens whose causal context lengths are given: the matmuls (2 per
    multiply-add) of mixers, FFNs, router, shared expert and head; the
    convolution's taps; the delta rule's four passes over a value head's
    ``d x d`` state (decay, read, write, read-out: 2 each an element);
    latent attention as the EXPANDED form counts it (two matmuls a key
    over 192- and 128-wide heads, and ``W_kvb`` once a token); and the
    ROUTED work of the experts held here AT THE BALANCED EXPECTATION
    (``held_share`` expert-rows a token)."""
    s = sizes(cfg)
    D, H = s["D"], s["H"]
    ctx = np.asarray(context_lengths, np.float64)
    per_token = 2.0 * D * s["V"]
    per_key = 0.0
    for i, kind in enumerate(s["kinds"]):
        if kind == "gated_delta":
            per_token += 2 * D * (s["Cq"] + 2 * s["Hv"] + s["Cv"]) \
                + 2 * s["Cv"] * D + 2 * s["taps"] * s["Cq"] \
                + 8 * s["Hv"] * s["d"] * s["d"]
        else:
            per_token += 2 * D * s["q_rank"] \
                + 2 * s["q_rank"] * H * (s["nope"] + s["rope"]) \
                + 2 * D * (s["rank"] + s["rope"]) \
                + 2 * s["rank"] * H * (s["nope"] + s["vd"]) \
                + 2 * D * H * s["vd"] + 2 * H * s["vd"] * D
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
    if name == "gated_delta_decode":
        # one call a delta-rule layer a decode tick: each LIVE slot's
        # state (Hv x d x d float32, 64 x 128 x 128) read once and written
        # once; the kernel skips the slots that are not live, so the
        # reader (``grouped_roofline``) multiplies by the live slots the
        # decode_prep spans counted, which are a tick's and not summed
        # over layers: ``layers`` 1, and nothing is read once a call
        state = s["Hv"] * s["d"] * s["d"] * 4
        return {"flops_per_row": 8.0 * s["Hv"] * s["d"] * s["d"],
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
    """``bigdl_tpu.models.gigachat.GigaChat35`` at the configuration's
    sizes with the benchmark's weights installed, in the dtypes they are
    stored in."""
    from bigdl_tpu.models.gigachat import GigaChat35

    s = sizes(cfg)
    model = GigaChat35(
        s["V"], s["D"], s["kinds"], s["dense"], s["F"], s["Fe"], s["E"],
        s["k"], experts_held=(s["first"], s["held"]), shared_width=s["Fs"],
        routed_scaling_factor=s["scaling"], swiglu_limit=s["limit"],
        num_heads=s["H"], q_rank=s["q_rank"], kv_rank=s["rank"],
        nope_dim=s["nope"], rope_dim=s["rope"], v_dim=s["vd"],
        rope_theta=s["theta"], rope_scaling=s["yarn"],
        linear_key_heads=s["Hk"], linear_value_heads=s["Hv"],
        linear_head_dim=s["d"], conv_kernel=s["taps"],
        linear_gate_scale=s["gate_scale"], norm_gate=s["norm_gate"],
        norm_eps=s["eps"], max_len=s["P"], dtype=s["dtype"])
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

def _mm(a, b, mode):
    """a @ b.T in float32; ``mode`` rounds both inputs first."""
    return jnp.einsum("...i,oi->...o", through(a, mode),
                      through(b.astype(jnp.float32), mode),
                      precision="highest")


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * weight


def _norm(x, p, s):
    """The zero-centred gated norm: RMSNorm times ``2 sigmoid(w)``."""
    return _rms(x, s["norm_gate"] * jax.nn.sigmoid(p["weight"]), s["eps"])


def _bf16(x):
    return rounded(x, lambda v: v.astype(jnp.bfloat16).astype(jnp.float32))


#: the controls that lower the delta rule's recurrence alone
SCAN_CONTROLS = ("bf16_state", "bf16_scan")


def _gated_delta(u, p, s, mode, scan=None):
    """The gated delta-rule layer, token by token.  ``scan`` lowers the
    recurrence alone: ``bf16_state`` keeps the state in bfloat16 between
    tokens, ``bf16_scan`` rounds the inputs of its three products to
    bfloat16 (what a TPU's default matmul precision does)."""
    rs = _bf16 if scan == "bf16_scan" else (lambda x: x)
    keep = _bf16 if scan == "bf16_state" else (lambda x: x)
    b, t, _ = u.shape
    Hk, Hv, d, taps = s["Hk"], s["Hv"], s["d"], s["taps"]
    proj = jnp.pad(_mm(u, p["qkv_weight"], mode),
                   ((0, 0), (taps - 1, 0), (0, 0)))
    kernel = p["conv_kernel"].astype(jnp.float32)
    conv = jax.nn.silu(sum(kernel[j] * proj[:, j:j + t]
                           for j in range(taps)))
    q = conv[..., :Hk * d].reshape(b, t, Hk, d)
    k = conv[..., Hk * d:2 * Hk * d].reshape(b, t, Hk, d)
    v = conv[..., 2 * Hk * d:].reshape(b, t, Hv, d)
    l2 = lambda a: a * jax.lax.rsqrt(
        jnp.sum(jnp.square(a), -1, keepdims=True) + L2_EPS)
    q, k = l2(q) / math.sqrt(d), l2(k)
    # value head h reads key head h // (Hv / Hk)
    q, k = (jnp.repeat(a, Hv // Hk, axis=2) for a in (q, k))
    ab = _mm(u, p["ab_weight"], mode)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ab[..., :Hv] + p["dt_bias"])
    beta = jax.nn.sigmoid(ab[..., Hv:])

    def step(S, xs):
        qt, kt, vt, gt, bt = xs                 # (b, Hv, d), gt bt (b, Hv)
        S = S * jnp.exp(gt)[..., None, None]
        held = jnp.einsum("bhk,bhkv->bhv", rs(kt), rs(S), precision="highest")
        S = keep(S + jnp.einsum("bhk,bhv->bhkv", rs(bt[..., None] * kt),
                                rs(vt - held), precision="highest"))
        return S, jnp.einsum("bhk,bhkv->bhv", rs(qt), rs(S),
                             precision="highest")

    tm = lambda x: jnp.moveaxis(x, 1, 0)
    _, o = jax.lax.scan(step, jnp.zeros((b, Hv, d, d), jnp.float32),
                        (tm(q), tm(k), tm(v), tm(g), tm(beta)))
    o = _rms(jnp.moveaxis(o, 0, 1), 1.0 + p["o_norm"], s["o_eps"])
    gate = s["gate_scale"] * jax.nn.sigmoid(_mm(u, p["z_weight"], mode))
    return _mm(gate * o.reshape(b, t, Hv * d), p["out_weight"], mode)


def yarn_frequencies(dim, theta, yarn):
    """DeepSeek-V3's YaRN: the frequency of rotary pair ``i`` is
    ``f_i`` where the pair turns more than ``beta_fast`` times over the
    original context, ``f_i / factor`` where it turns fewer than
    ``beta_slow`` times, and a linear blend between."""
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    orig = yarn["original_max_position_embeddings"]

    def pair_turning(turns):      # the pair index that turns ``turns`` times
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(pair_turning(yarn["beta_fast"])), 0)
    hi = min(math.ceil(pair_turning(yarn["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return (f * (1 - ramp) + f / yarn["factor"] * ramp).astype(np.float32)


def softmax_scale(s):
    m = 0.1 * s["yarn"]["mscale_all_dim"] * math.log(s["yarn"]["factor"]) \
        + 1.0
    return (s["nope"] + s["rope"]) ** -0.5 * m * m


def rope(x, s):
    """Interleaved rotary embedding with YaRN's frequencies on ``(B, T,
    H, Dh)``, positions ``0..T-1``: the pair ``(x_2i, x_2i+1)`` is turned
    by angle ``i`` and stays where it is."""
    t, dh = x.shape[1], x.shape[-1]
    f = jnp.asarray(yarn_frequencies(dh, s["theta"], s["yarn"]))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * f[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _mla(u, p, s, mode):
    """Latent attention, expanded: every token's keys and values formed,
    plain causal attention over heads, ``QUERY_BLOCK`` queries at a time."""
    b, t, _ = u.shape
    H, nope, rp, vd = s["H"], s["nope"], s["rope"], s["vd"]
    q = _mm(_rms(_mm(u, p["qa_weight"], mode), p["qa_norm"], s["eps"]),
            p["qb_weight"], mode).reshape(b, t, H, nope + rp)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], s)], -1)
    kva = _mm(u, p["kva_weight"], mode)
    c = _rms(kva[..., :s["rank"]], p["kv_norm"], s["eps"])
    k_r = rope(kva[..., s["rank"]:][:, :, None], s)
    kv = _mm(c, p["kvb_weight"], mode).reshape(b, t, H, nope + vd)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r, (b, t, H, rp))], -1)
    v = kv[..., nope:]
    block = min(QUERY_BLOCK, t)
    blocks = -(-t // block)
    # queries padded to whole blocks (the padding's rows are cut off below)
    qp = jnp.pad(q, ((0, 0), (0, blocks * block - t), (0, 0), (0, 0)))
    scale = softmax_scale(s)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(qp, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", through(qb, mode),
                            through(k, mode), precision="highest") * scale
        seen = jnp.arange(t)[None, :] <= (start + jnp.arange(block))[:, None]
        w = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", through(w, mode),
                          through(v, mode), precision="highest")

    o = jax.lax.map(rows, jnp.arange(blocks) * block)   # (blocks, b, block, H, vd)
    o = jnp.moveaxis(o, 0, 1).reshape(b, blocks * block, H * vd)[:, :t]
    gate = jax.nn.sigmoid(_mm(u, p["gate_weight"], mode))
    return _mm(gate * o, p["out_weight"], mode)


def _swiglu(u, w1, w3, w2, mode, limit=None):
    """``W_2(SiLU(W_1 u) * W_3 u)``; with ``limit`` the clamp
    ``W_2(SiLU(min(W_1 u, L)) * clip(W_3 u, -L, L))``."""
    a, up = _mm(u, w1, mode), _mm(u, w3, mode)
    if limit is not None:
        a, up = jnp.minimum(a, limit), jnp.clip(up, -limit, limit)
    return _mm(jax.nn.silu(a) * up, w2, mode)


def route(u, p, s):
    """``(expert ids (..., k), weights (..., k))``: float32 whatever the
    control's precision (the program keeps the router float32 too)."""
    logits = jnp.einsum("...i,oi->...o", u,
                        p["router_weight"].astype(jnp.float32),
                        precision="highest")
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(
        scores + jax.lax.stop_gradient(p["router_bias"]), s["k"])
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
        return acc + weight[..., None] * _swiglu(
            u, w1.T, w3.T, w2.T, mode, s["limit"]), None

    sh = p["shared"]
    routed, _ = jax.lax.scan(
        one, jnp.zeros(u.shape, jnp.float32),
        (jnp.arange(s["held"]), p["w1"], p["w3"], p["w2"]))
    return routed + _swiglu(u, sh["w1"], sh["w3"], sh["w2"], mode,
                            s["limit"])


def reference_logits(params, tokens, cfg, mode="f32"):
    """(B, T) token ids -> (B, T, V) float32 logits.  ``mode`` is the
    control's precision: ``f32`` (the reference); ``fp8`` (inputs of every
    matmul rounded to e4m3 with a per-tensor scale, the router float32);
    ``bf16`` (matmul inputs and the residual stream rounded to
    bfloat16); ``bf16_state`` and ``bf16_scan`` (the delta rule's
    recurrence alone lowered, as ``_gated_delta`` says; all else
    float32)."""
    s = sizes(cfg)
    scan, mode = (mode, "f32") if mode in SCAN_CONTROLS else (None, mode)
    stream = _bf16 if mode == "bf16" else (lambda x: x)
    x = stream(params["embed"].astype(jnp.float32)[tokens])
    for i, kind in enumerate(s["kinds"]):
        p = params[f"layer{i}"]
        u = _norm(x, p["op_norm"], s)
        y = _gated_delta(u, p["op"], s, mode, scan) \
            if kind == "gated_delta" else _mla(u, p["op"], s, mode)
        x = stream(x + _norm(y, p["op_post_norm"], s))
        h = _norm(x, p["ffn_norm"], s)
        f = p["ffn"]
        ffn = _swiglu(h, f["w1"], f["w3"], f["w2"], mode) \
            if i < s["dense"] else _moe(h, f, s, mode)
        x = stream(x + _norm(ffn, p["ffn_post_norm"], s))
    return _mm(_norm(x, params["norm_f"], s), params["head"], mode)
