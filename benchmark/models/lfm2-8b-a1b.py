"""LFM2-8B-A1B (LiquidAI, ``lfm2_moe``) for the benchmark, as ONE CHIP'S
SHARE of a four-chip expert-parallel training job: the program's model
built at the configuration's sizes, weights from a seed, the work a step
needs from shapes, and a plain reference that is given the same share.

    h = x + Op_l(RMSNorm(x));   y = h + FFN_l(RMSNorm(h))

``Op_l`` is the gated short convolution or grouped-query attention (q/k
RMSNorm, rotary positions), ``FFN_l`` the gated MLP (leading dense layers)
or the mixture: ``s = sigmoid(u W_g)`` over all ``router_width`` experts in
float32, the top ``k`` of ``s + b``, weights ``s`` over ``(their sum +
1e-6)``; of those experts the chip holds ``experts_held`` and what the
others would add is left out, here as in the program.

Three parts, kept apart (as ``gpt2-medium.py``): ``program_*`` are the
only functions that import ``bigdl_tpu``; ``make_params``/``draw_params``
and the work functions are the benchmark's own; ``reference_*`` are plain
``jax.numpy`` float32 at matmul precision ``highest``.

Parameter tree (the layout ``bigdl_tpu.models.lfm2.LFM2`` uses)::

    embed (V, D)    norm_f {weight}
    layer{i}: op_norm {weight}  ffn_norm {weight}
              op:  {in_weight (3D, D), kernel (L, D), out_weight (D, D)}
                or {qkv_weight ((H + 2 Hkv) Dh, D), q_norm (Dh,),
                    k_norm (Dh,), out_weight (D, D)}
              ffn: {w1 (F, D), w3 (F, D), w2 (D, F)}
                or {router_weight (E, D), router_bias (E,),
                    w1 (held, D, Fe), w3 (held, D, Fe), w2 (held, Fe, D)}
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from harness.precision import through
from harness.traffic import prng_key


# --------------------------------------------------------------------- #
# sizes
# --------------------------------------------------------------------- #

def sizes(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kinds = [cfg["layer_types"][i] for i in cfg["layers_run"]]
    assert len(kinds) == cfg["num_hidden_layers"]
    first, held = cfg["experts_held"]
    assert held == cfg["num_experts"]
    return dict(V=cfg["vocab_size"], D=d, H=h,
                Hkv=cfg["num_key_value_heads"],
                Dh=cfg.get("head_dim") or d // h,
                F=cfg["intermediate_size"], Fe=cfg["moe_intermediate_size"],
                E=cfg["router_width"], first=first, held=held,
                k=cfg["num_experts_per_tok"], taps=cfg["conv_L_cache"],
                dense=cfg["num_dense_layers"], kinds=kinds,
                eps=cfg["norm_eps"], theta=float(cfg["rope_theta"]),
                scaling=float(cfg["routed_scaling_factor"]))


def param_shapes(cfg):
    s = sizes(cfg)
    D, Dh = s["D"], s["Dh"]
    norm = lambda: {"weight": (D,)}
    out = {"embed": (s["V"], D), "norm_f": norm()}
    for i, kind in enumerate(s["kinds"]):
        if kind == "conv":
            op = {"in_weight": (3 * D, D), "kernel": (s["taps"], D),
                  "out_weight": (D, D)}
        else:
            op = {"qkv_weight": ((s["H"] + 2 * s["Hkv"]) * Dh, D),
                  "q_norm": (Dh,), "k_norm": (Dh,), "out_weight": (D, D)}
        if i < s["dense"]:
            ffn = {"w1": (s["F"], D), "w3": (s["F"], D), "w2": (D, s["F"])}
        else:
            ffn = {"router_weight": (s["E"], D), "router_bias": (s["E"],),
                   "w1": (s["held"], D, s["Fe"]),
                   "w3": (s["held"], D, s["Fe"]),
                   "w2": (s["held"], s["Fe"], D)}
        out[f"layer{i}"] = {"op_norm": norm(), "ffn_norm": norm(),
                            "op": op, "ffn": ffn}
    return out


def _is_shape(x):
    return isinstance(x, tuple)


def param_count(cfg):
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        param_shapes(cfg), is_leaf=_is_shape))


# --------------------------------------------------------------------- #
# weights from the seed, on the device, in one jitted call
# --------------------------------------------------------------------- #

def _leaf_rule(path, cfg):
    """(mean, std) of a leaf by its place in the tree."""
    last = getattr(path[-1], "key", str(path[-1]))
    if last == "weight" or last in ("q_norm", "k_norm"):
        return 1.0, 0.02
    if last == "kernel":
        return 0.0, 0.3
    if last == "router_weight":     # logits of spread about 1
        return 0.0, 1.0 / math.sqrt(cfg["hidden_size"])
    if last == "router_bias":       # a seeded constant: selects, no gradient
        return 0.0, 0.02
    return 0.0, 0.02


def draw_params(cfg, key):
    """The model's fp32 weights from a PRNG key (traceable: the key is an
    argument, so one compiled program serves every seed)."""
    leaves, treedef = jax.tree.flatten_with_path(param_shapes(cfg),
                                                 is_leaf=_is_shape)
    out = []
    for i, (path, shape) in enumerate(leaves):
        mean, std = _leaf_rule(path, cfg)
        out.append(mean + std * jax.random.normal(
            jax.random.fold_in(key, i), shape, jnp.float32))
    return jax.tree.unflatten(treedef, out)


def make_params(cfg, seed):
    """The weights from ``seed``: one jitted call, made on the default
    device, no host copy."""
    return jax.jit(lambda key: draw_params(cfg, key))(prng_key(seed))


# --------------------------------------------------------------------- #
# comparison units: what "by the worst unit" runs over
# --------------------------------------------------------------------- #

def unit_sq_norms(tree, other=None):
    """Squared norms of ``tree`` (or of ``tree - other``) by unit: every
    leaf, the fused qkv leaf cut into its q, k and v rows (q has as many
    rows as the model is wide; k and v share the rest), and an expert's
    three matrices TOGETHER as one unit, so that a top-k flip
    between two nearly equal scores (bfloat16 against float32 inputs)
    moves a unit little."""
    diff = tree if other is None else jax.tree.map(
        lambda a, b: a - b, tree, other)
    sq = lambda a: jnp.sum(jnp.square(a))
    out = {}
    for name, node in diff.items():
        if not name.startswith("layer"):
            out[name] = jax.tree.map(sq, node)
            continue
        layer = {"op_norm": sq(node["op_norm"]["weight"]),
                 "ffn_norm": sq(node["ffn_norm"]["weight"])}
        op = dict(node["op"])
        if "qkv_weight" in op:
            w = op.pop("qkv_weight")
            d = w.shape[1]
            kv = (w.shape[0] - d) // 2
            layer["op"] = {"q": sq(w[:d]), "k": sq(w[d:d + kv]),
                           "v": sq(w[d + kv:])}
        else:
            layer["op"] = {}
        layer["op"].update({k: sq(v) for k, v in op.items()})
        ffn = node["ffn"]
        if "router_weight" in ffn:
            per = lambda a: jnp.sum(jnp.square(a), axis=(1, 2))
            layer["ffn"] = {"router_weight": sq(ffn["router_weight"]),
                            "router_bias": sq(ffn["router_bias"]),
                            "expert": per(ffn["w1"]) + per(ffn["w3"])
                            + per(ffn["w2"])}
        else:
            layer["ffn"] = {k: sq(v) for k, v in ffn.items()}
        out[name] = layer
    return out


# --------------------------------------------------------------------- #
# required work, from shapes (recompute never counted)
# --------------------------------------------------------------------- #

def held_share(cfg):
    """Expert-rows a token brings to THIS chip at the balanced
    expectation: ``k * held / router_width`` (4 x 8 / 32 = 1)."""
    s = sizes(cfg)
    return s["k"] * s["held"] / s["E"]


def forward_flops(cfg, context_lengths):
    """Floating-point operations a forward pass needs on this chip for
    tokens whose causal context lengths are given: the matmuls (2 per
    multiply-add) of operators, FFNs, router and head, attention's two
    matmuls per key, and the ROUTED work of the experts held here AT THE
    BALANCED EXPECTATION (``held_share`` expert-rows a token), whatever
    the router of a seed really sends."""
    s = sizes(cfg)
    D = s["D"]
    ctx = np.asarray(context_lengths, np.float64)
    per_token = 2.0 * D * s["V"]
    attention_layers = 0
    for i, kind in enumerate(s["kinds"]):
        if kind == "conv":
            per_token += 2 * D * 3 * D + 2 * D * D + 2 * s["taps"] * D
        else:
            per_token += 2 * D * (s["H"] + 2 * s["Hkv"]) * s["Dh"] \
                + 2 * D * D
            attention_layers += 1
        if i < s["dense"]:
            per_token += 3 * 2 * D * s["F"]
        else:
            per_token += 2 * D * s["E"] \
                + held_share(cfg) * 3 * 2 * D * s["Fe"]
    return ctx.size * per_token \
        + attention_layers * 4 * s["H"] * s["Dh"] * float(ctx.sum())


def train_step_flops(cfg, batch, seq):
    """Forward + backward of one optimizer step on ``batch`` sequences of
    ``seq`` tokens: three times the forward; routed work at the balanced
    expectation (``forward_flops``)."""
    ctx = np.tile(np.arange(1, seq + 1), batch)
    return 3.0 * forward_flops(cfg, ctx)


def expert_layers(cfg):
    s = sizes(cfg)
    return len(s["kinds"]) - s["dense"]


def kernel_work(cfg, mix, name):
    """FLOPs and bytes ONE call of a kernel needs at the mix's shapes."""
    s = sizes(cfg)
    batch, seq = int(mix["batch"]), int(mix["data"]["seq_len"])
    act = 2 if mix.get("compute_dtype") == "bfloat16" else 4
    if name == "attention_fwd":
        # one call takes ``kv_heads_per_call`` (row, KV head) pairs, each
        # with its group of query heads; K and V are read once a query
        # head, as the kernel is handed them
        pairs = cfg["program"].get("kv_heads_per_call") or batch * s["Hkv"]
        heads = pairs * s["H"] // s["Hkv"]
        return {"flops": 4.0 * s["Dh"] * heads * seq * (seq + 1) / 2,
                "bytes": 4.0 * heads * seq * s["Dh"] * act}
    if name in ("cross_entropy_fwd", "cross_entropy_bwd"):
        n = batch * seq * s["V"]
        return {"fwd": {"flops": 5.0 * n, "bytes": 4.0 * n},
                "bwd": {"flops": 3.0 * n, "bytes": 8.0 * n}}[name[-3:]]
    if name == "grouped_matmul":
        # one grouped product over the rows of one expert layer: every
        # row against a D x Fe matrix (w1, w3, w2 and their two backward
        # products each are all D x Fe or Fe x D); a step runs 3 forward,
        # 3 in the remat recompute and 6 backward a layer
        return {"flops_per_row": 2.0 * s["D"] * s["Fe"],
                "bytes_per_row": float((s["D"] + s["Fe"]) * act),
                "bytes_per_call": float(s["held"] * s["D"] * s["Fe"] * act),
                "calls_per_layer": 12, "layers": expert_layers(cfg)}
    raise KeyError(name)


# --------------------------------------------------------------------- #
# the program's model (the ONLY part that imports the program)
# --------------------------------------------------------------------- #

def program_model(cfg, params, batch_spec):
    """``bigdl_tpu.models.lfm2.LFM2`` at the configuration's sizes with
    the benchmark's weights installed."""
    from bigdl_tpu.models.lfm2 import LFM2

    s = sizes(cfg)
    prog = cfg["program"]
    model = LFM2(s["V"], s["D"], s["kinds"], s["dense"], s["F"], s["Fe"],
                 s["H"], s["Hkv"], s["E"], s["k"],
                 experts_held=(s["first"], s["held"]), conv_L_cache=s["taps"],
                 norm_eps=s["eps"], rope_theta=s["theta"],
                 norm_topk_prob=cfg["norm_topk_prob"],
                 routed_scaling_factor=s["scaling"], remat=prog["remat"],
                 kv_heads_per_call=prog.get("kv_heads_per_call"))
    expect, state = jax.eval_shape(lambda k: model.setup(k, batch_spec),
                                   jax.random.key(0))
    got = jax.tree.map(lambda a: a.shape, params)
    want = jax.tree.map(lambda a: a.shape, expect)
    if got != want:
        raise RuntimeError("the benchmark's parameter tree does not match "
                           f"the program's: {got} != {want}")
    model.set_parameters(params)
    model.set_state(jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                                 state))
    return model


def program_training(cfg, traffic):
    """Criterion and optimizer as the GPT-2 cell builds them."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim

    o = traffic["optimizer"]
    if o["name"] != "adam":
        raise ValueError(f"lfm2 trains with adam, not {o['name']!r}")
    criterion = nn.TimeDistributedCriterion(
        nn.FusedSoftmaxCrossEntropyCriterion())
    method = optim.Adam(learning_rate=o["learning_rate"], beta1=o["beta1"],
                        beta2=o["beta2"], epsilon=o["epsilon"])
    return criterion, method


# --------------------------------------------------------------------- #
# the plain reference
# --------------------------------------------------------------------- #

def _mm(a, b, mode):
    """a @ b.T in float32; ``mode`` rounds both inputs first."""
    return jnp.einsum("...i,oi->...o", through(a, mode), through(b, mode),
                      precision="highest")


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * weight


def _rope(x, theta):
    """Rotate-half rotary embedding on ``(B, T, H, Dh)``."""
    t, dh = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None]
    x1, x2 = jnp.split(x, 2, -1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _conv(u, p, s, mode):
    t = u.shape[1]
    gate_b, gate_c, x = jnp.split(_mm(u, p["in_weight"], mode), 3, -1)
    z = jnp.pad(gate_b * x, ((0, 0), (s["taps"] - 1, 0), (0, 0)))
    c = sum(p["kernel"][j] * z[:, j:j + t] for j in range(s["taps"]))
    return _mm(gate_c * c, p["out_weight"], mode)


def _attention(u, p, s, mode, query_block=1024):
    b, t, d = u.shape
    H, Hkv, Dh = s["H"], s["Hkv"], s["Dh"]
    qkv = _mm(u, p["qkv_weight"], mode)
    q, k, v = jnp.split(qkv, [H * Dh, (H + Hkv) * Dh], -1)
    q = _rope(_rms(q.reshape(b, t, H, Dh), p["q_norm"], s["eps"]),
              s["theta"])
    k = _rope(_rms(k.reshape(b, t, Hkv, Dh), p["k_norm"], s["eps"]),
              s["theta"])
    v = v.reshape(b, t, Hkv, Dh)
    # every KV head serves H // Hkv query heads
    q = q.reshape(b, t, Hkv, H // Hkv, Dh)
    k, v = through(k, mode), through(v, mode)
    kpos = jnp.arange(t)

    @jax.checkpoint
    def block(qb, start):
        """A block of queries against all keys (the scores of a block are
        the only (T, T)-sized array alive)."""
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", through(qb, mode), k,
                            precision="highest") / math.sqrt(Dh)
        qpos = start + jnp.arange(qb.shape[1])
        scores = jnp.where(kpos[None] <= qpos[:, None], scores, -jnp.inf)
        w = jax.nn.softmax(scores, -1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", through(w, mode), v,
                          precision="highest")

    if t <= query_block:
        o = block(q, 0)
    else:
        assert t % query_block == 0
        n = t // query_block
        qs = q.reshape(b, n, query_block, Hkv, H // Hkv, Dh) \
            .transpose(1, 0, 2, 3, 4, 5)
        o = jax.lax.map(lambda a: block(*a),
                        (qs, jnp.arange(n) * query_block))
        o = o.transpose(1, 0, 2, 3, 4, 5)
    return _mm(o.reshape(b, t, d), p["out_weight"], mode)


def _operator(u, p, s, kind, mode):
    return _conv(u, p, s, mode) if kind == "conv" \
        else _attention(u, p, s, mode)


def _gated_mlp(u, w1, w3, w2, mode):
    return _mm(jax.nn.silu(_mm(u, w1, mode)) * _mm(u, w3, mode), w2, mode)


def _router_scores(u, p):
    """Float32 in every mode: the configuration states the router's
    precision apart from the compute dtype, and the control lowers only
    the latter."""
    return jax.nn.sigmoid(jnp.einsum("...d,ed->...e", u, p["router_weight"],
                                     precision="highest"))


def _mixture(u, p, s, mode):
    """Routes over all ``E`` experts, computes those held here on every
    token (the weight of a token an expert was not chosen for is nought),
    leaves the rest out."""
    scores = _router_scores(u, p)
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(p["router_bias"]),
                           s["k"])
    w = jnp.take_along_axis(scores, idx, -1)
    w = s["scaling"] * w / (w.sum(-1, keepdims=True) + 1e-6)

    @jax.checkpoint
    def expert(acc, e_and_weights):
        e, w1, w3, w2 = e_and_weights
        mine = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        # (D, Fe) and (Fe, D) as stored: _mm takes (out, in)
        y = _gated_mlp(u, w1.T, w3.T, w2.T, mode)
        return acc + mine[..., None] * y, None

    held = s["first"] + jnp.arange(s["held"])
    out, _ = jax.lax.scan(expert, jnp.zeros_like(u),
                          (held, p["w1"], p["w3"], p["w2"]))
    return out


def _ffn(u, p, s, i, mode):
    if i < s["dense"]:
        return _gated_mlp(u, p["w1"], p["w3"], p["w2"], mode)
    return _mixture(u, p, s, mode)


def reference_logits(params, tokens, cfg, mode="f32"):
    """(B, T) token ids -> (B, T, V) float32 logits.  ``mode`` is the
    control's precision: ``f32`` (the reference), ``bf16`` or ``fp8``
    (the inputs of every matmul but the router's rounded)."""
    s = sizes(cfg)
    x = params["embed"][tokens]
    for i, kind in enumerate(s["kinds"]):

        @jax.checkpoint
        def layer(x, p, i=i, kind=kind):
            x = x + _operator(_rms(x, p["op_norm"]["weight"], s["eps"]),
                              p["op"], s, kind, mode)
            return x + _ffn(_rms(x, p["ffn_norm"]["weight"], s["eps"]),
                            p["ffn"], s, i, mode)

        x = layer(x, params[f"layer{i}"])
    x = _rms(x, params["norm_f"]["weight"], s["eps"])
    return _mm(x, params["embed"], mode)


def reference_loss(params, batch, cfg, mode="f32"):
    """Mean next-token cross-entropy over every position of the batch, as
    ``TimeDistributedCriterion(CrossEntropy)`` averages it; no auxiliary
    term."""
    x, y = batch
    logits = reference_logits(params, x, cfg, mode)
    logp = jax.nn.log_softmax(logits, -1)
    picked = jnp.take_along_axis(logp, y[..., None].astype(jnp.int32), -1)
    return -jnp.mean(picked)
