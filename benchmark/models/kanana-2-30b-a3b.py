"""kanana-2-30b-a3b-instruct-2601 (kakaocorp; ``model_type: deepseek_v3``)
for the benchmark, as ONE CHIP'S SHARE of an eight-chip serving replica
(routed experts by expert parallelism, attention data-parallel): the
program's model built at the configuration's sizes, weights from a seed,
the work a token needs from shapes, and a plain reference that is given
the same share.

Pre-norm residual blocks, RMSNorm (eps 1e-6), untied embedding and head::

    h = x + MLA(RMSNorm(x));   y = h + FFN(RMSNorm(h))

The first ``first_k_dense_replace`` layers have a dense gated-SiLU MLP, the
rest the mixture (``moe_layer_freq`` 1).  What the config does not fix is
listed under ``assumed`` in ``configs/kanana-2-30b-a3b.json``.

Latent attention (MLA, ``q_lora_rank: null``), ``H`` heads::

    q        = W_q x                    128 no-position + 64 rotary a head, no norm
    [c, k_r] = W_kva x                  512 + 64
    c        = RMSNorm(c)
    k_r      = RoPE(k_r)                one rotary key shared by all heads;
                                        theta 1e6, no scaling, INTERLEAVED pairs
                                        (x_2i, x_2i+1) turned by angle i
    [k_n, v] = W_kvb c                  128 + 128 a head
    k        = [k_n, k_r]
    o        = causal softmax(q k^T / sqrt(192)) v
    out      = W_o o                    no gate

The cache row of a token is ``[c, RoPE(k_r)]``, 576 values.

Mixture (DeepSeek-V3's ``noaux_tc``): ``s = sigmoid(W_r x)`` over all
``router_width`` experts in float32; the top ``k`` of ``s + b`` (``n_group``
1, ``topk_group`` 1: no group limit; ``b`` a seeded constant); weights ``s``
of the chosen over ``(their sum + 1e-6)`` (``norm_topk_prob``), times
``routed_scaling_factor``; gated-SiLU experts, of which the chip holds
``experts_held`` (what the others would add is left out, here as in the
program); plus the ``n_shared_experts`` shared experts as one gated MLP of
their summed width on every token, unweighted.

Three parts, kept apart (as ``gpt2-medium.py``): ``program_*`` are the
only functions that import ``bigdl_tpu``; ``make_params`` and the work
functions are the benchmark's own; ``reference_*`` are plain ``jax.numpy``
float32 at matmul precision ``highest``: one full forward over a whole
row, no cache, no kernels, layer after layer (no scan over them),
attention blocked over queries and the mixture over experts so that 16384
tokens fit beside the weights.

Parameter tree (the layout ``bigdl_tpu.models.kanana.Kanana`` uses)::

    embed (V, D)   head (V, D)   norm_f {weight}
    layer{i}  (i < first_k_dense_replace), and ``layers`` with every leaf
    stacked over the L expert layers:
        op_norm {weight}  ffn_norm {weight}
        op:  {q_weight (H 192, D), kva_weight (576, D), kv_norm (512,),
              kvb_weight (H 256, 512), out_weight (D, H 128)}
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
FULL_PRECISION = ("weight", "router_weight", "router_bias", "kv_norm")
QUERY_BLOCK = 512


# --------------------------------------------------------------------- #
# sizes
# --------------------------------------------------------------------- #

def sizes(cfg):
    first, held = cfg["experts_held"]
    assert held == cfg["n_routed_experts"]
    assert cfg["n_group"] == 1 and cfg["topk_group"] == 1
    assert cfg["moe_layer_freq"] == 1 and cfg["q_lora_rank"] is None
    assert cfg["qk_head_dim"] == cfg["qk_nope_head_dim"] \
        + cfg["qk_rope_head_dim"]
    return dict(
        V=cfg["vocab_size"], D=cfg["hidden_size"],
        H=cfg["num_attention_heads"], F=cfg["intermediate_size"],
        Fe=cfg["moe_intermediate_size"],
        Fs=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        E=cfg["router_width"], first=first, held=held,
        k=cfg["num_experts_per_tok"], rank=cfg["kv_lora_rank"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        vd=cfg["v_head_dim"], dense=cfg["first_k_dense_replace"],
        layers=cfg["num_hidden_layers"], eps=cfg["rms_norm_eps"],
        theta=float(cfg["rope_theta"]),
        interleave=bool(cfg["rope_interleave"]),
        scaling=float(cfg["routed_scaling_factor"]), P=cfg["n_positions"],
        dtype=cfg.get("program", {}).get("dtype", "float32"))


def layer_shapes(cfg, dense):
    s = sizes(cfg)
    D, H = s["D"], s["H"]
    op = {"q_weight": (H * (s["nope"] + s["rope"]), D),
          "kva_weight": (s["rank"] + s["rope"], D), "kv_norm": (s["rank"],),
          "kvb_weight": (H * (s["nope"] + s["vd"]), s["rank"]),
          "out_weight": (D, H * s["vd"])}
    if dense:
        ffn = {"w1": (s["F"], D), "w3": (s["F"], D), "w2": (D, s["F"])}
    else:
        ffn = {"router_weight": (s["E"], D), "router_bias": (s["E"],),
               "w1": (s["held"], D, s["Fe"]), "w3": (s["held"], D, s["Fe"]),
               "w2": (s["held"], s["Fe"], D),
               "shared": {"w1": (s["Fs"], D), "w3": (s["Fs"], D),
                          "w2": (D, s["Fs"])}}
    return {"op_norm": {"weight": (D,)}, "ffn_norm": {"weight": (D,)},
            "op": op, "ffn": ffn}


def _is_shape(x):
    return isinstance(x, tuple)


def param_shapes(cfg):
    s = sizes(cfg)
    out = {"embed": (s["V"], s["D"]), "head": (s["V"], s["D"]),
           "norm_f": {"weight": (s["D"],)}}
    for i in range(s["dense"]):
        out[f"layer{i}"] = layer_shapes(cfg, True)
    stacked = s["layers"] - s["dense"]
    out["layers"] = jax.tree.map(lambda shape: (stacked,) + shape,
                                 layer_shapes(cfg, False), is_leaf=_is_shape)
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


# --------------------------------------------------------------------- #
# weights from the seed, on the device, a leaf at a time
# --------------------------------------------------------------------- #

def _leaf_rule(path, cfg):
    """(mean, std) of a leaf by its place in the tree."""
    last = _last(path)
    if last in ("weight", "kv_norm"):
        return 1.0, 0.02
    if last == "router_weight":     # logits of spread about 1
        return 0.0, 1.0 / math.sqrt(cfg["hidden_size"])
    return 0.0, 0.02


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _draw(key, mean, std, shape, dtype):
    return (mean + std * jax.random.normal(key, shape, jnp.float32)) \
        .astype(dtype)


def make_params(cfg, seed):
    """The weights from ``seed``, drawn in float32 and stored in the
    configuration's dtype, one jitted call a leaf (the key and the rule
    are arguments: one compiled program a shape serves every seed)."""
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
    expectation: ``k * held / router_width`` (6 x 16 / 128 = 0.75, an
    eighth of the routed work)."""
    s = sizes(cfg)
    return s["k"] * s["held"] / s["E"]


def expert_layers(cfg):
    s = sizes(cfg)
    return s["layers"] - s["dense"]


def forward_flops(cfg, context_lengths):
    """Floating-point operations a forward pass needs on this chip for
    tokens whose causal context lengths are given: the matmuls (2 per
    multiply-add) of the latent mixers, FFNs, router, shared experts and
    head; latent attention as the EXPANDED form counts it (two matmuls a
    key over 192- and 128-wide heads, and ``W_kvb`` once a token), which
    is the least either path needs; and the ROUTED work of the experts
    held here AT THE BALANCED EXPECTATION (``held_share`` expert-rows a
    token)."""
    s = sizes(cfg)
    D, H = s["D"], s["H"]
    ctx = np.asarray(context_lengths, np.float64)
    mixer = 2 * D * (H * (s["nope"] + s["rope"]) + s["rank"] + s["rope"]) \
        + 2 * s["rank"] * H * (s["nope"] + s["vd"]) + 2 * H * s["vd"] * D
    per_token = 2.0 * D * s["V"] + s["layers"] * mixer \
        + s["dense"] * 3 * 2 * D * s["F"] \
        + expert_layers(cfg) * (2 * D * s["E"] + 3 * 2 * D * s["Fs"]
                                + held_share(cfg) * 3 * 2 * D * s["Fe"])
    per_key = s["layers"] * 2 * H * (s["nope"] + s["rope"] + s["vd"])
    return ctx.size * per_token + per_key * float(ctx.sum())


def kernel_work(cfg, mix, name):
    """FLOPs and bytes ONE call of a kernel needs at the mix's shapes."""
    s = sizes(cfg)
    act = jnp.dtype(s["dtype"]).itemsize
    if name == "latent_decode":
        # one call a layer a decode tick; a "row" is a CONTEXT TOKEN of a
        # live slot (the decode_prep spans' context_tokens: a tick's, not
        # summed over layers, so ``layers`` 1): its cache row of rank +
        # rope values read once for all heads, and every head's score
        # over the whole row and weighted sum over its first ``rank``
        # values.  The least the mathematics needs: live tokens, not whole
        # blocks, and not the 640 columns a row is stored in.
        width = s["rank"] + s["rope"]
        return {"flops_per_row": 2.0 * s["H"] * (width + s["rank"]),
                "bytes_per_row": float(width * act),
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
    """``bigdl_tpu.models.kanana.Kanana`` at the configuration's sizes
    with the benchmark's weights installed, in the dtypes they are stored
    in."""
    from bigdl_tpu.models.kanana import Kanana

    s = sizes(cfg)
    model = Kanana(
        s["V"], s["D"], s["layers"], s["dense"], s["F"], s["Fe"], s["H"],
        s["E"], s["k"], experts_held=(s["first"], s["held"]),
        shared_width=s["Fs"], routed_scaling_factor=s["scaling"],
        kv_rank=s["rank"], nope_dim=s["nope"], rope_dim=s["rope"],
        v_dim=s["vd"], rope_theta=s["theta"],
        rope_interleave=s["interleave"], norm_eps=s["eps"], max_len=s["P"],
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
    """What the matmuls' inputs are rounded through: ``fp8_latent`` rounds
    none of them (it rounds the cached latent rows, nothing else)."""
    return "f32" if mode == "fp8_latent" else mode


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


def rope(x, theta, interleave):
    """Rotary embedding on ``(B, T, H, Dh)``, positions ``0..T-1``, in
    place: with ``interleave`` the pair ``(x_2i, x_2i+1)`` is turned by
    angle ``i`` and stays where it is; without, the pair ``(x_i,
    x_i+Dh/2)`` (rotate-half)."""
    t, dh = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    if interleave:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         -1).reshape(x.shape)
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mla(u, p, s, mode):
    """Latent attention, expanded: every token's keys and values formed,
    plain causal attention over heads, ``QUERY_BLOCK`` queries at a time."""
    b, t, _ = u.shape
    H, nope, rp, vd = s["H"], s["nope"], s["rope"], s["vd"]
    mm = _matrix_mode(mode)
    q = _mm(u, p["q_weight"], mode).reshape(b, t, H, nope + rp)
    q = jnp.concatenate(
        [q[..., :nope], rope(q[..., nope:], s["theta"], s["interleave"])], -1)
    kva = _mm(u, p["kva_weight"], mode)
    c = _rms(kva[..., :s["rank"]], p["kv_norm"], s["eps"])
    k_r = rope(kva[..., s["rank"]:][:, :, None], s["theta"], s["interleave"])
    if mode == "fp8_latent":
        # what a float8 cache would hold: the row [c, k_r] rounded whole
        row = through(jnp.concatenate([c, k_r[:, :, 0]], -1), "fp8")
        c, k_r = row[..., :s["rank"]], row[..., s["rank"]:][:, :, None]
    kv = _mm(c, p["kvb_weight"], mode).reshape(b, t, H, nope + vd)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r, (b, t, H, rp))], -1)
    v = kv[..., nope:]
    block = min(QUERY_BLOCK, t)
    blocks = -(-t // block)
    # queries padded to whole blocks (the padding's rows are cut off below)
    qp = jnp.pad(q, ((0, 0), (0, blocks * block - t), (0, 0), (0, 0)))

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(qp, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", through(qb, mm),
                            through(k, mm), precision="highest") \
            / math.sqrt(nope + rp)
        seen = jnp.arange(t)[None, :] <= (start + jnp.arange(block))[:, None]
        w = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", through(w, mm), through(v, mm),
                          precision="highest")

    o = jax.lax.map(rows, jnp.arange(blocks) * block)   # (blocks, b, block, H, vd)
    o = jnp.moveaxis(o, 0, 1).reshape(b, blocks * block, H, vd)[:, :t]
    return _mm(o.reshape(b, t, H * vd), p["out_weight"], mode)


def _gated(u, w1, w3, w2, mode):
    return _mm(jax.nn.silu(_mm(u, w1, mode)) * _mm(u, w3, mode), w2, mode)


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


def _moe(u, p, s, mode, first=None, shared=True):
    """Routed part of the ``held`` experts from ``first`` on (the chip's
    share by default), one expert after the other over every token (each
    weighed by the router's weight for it, nought where it was not
    chosen), plus, with ``shared``, the shared experts."""
    idx, w = route(u, p, s)
    first = s["first"] if first is None else first

    def one(acc, e_and_w):
        e, w1, w3, w2 = e_and_w
        weight = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        # stored (D, Fe) and (Fe, D): transposed to ``_mm``'s (out, in)
        return acc + weight[..., None] * _gated(u, w1.T, w3.T, w2.T,
                                                mode), None

    out, _ = jax.lax.scan(
        one, jnp.zeros(u.shape, jnp.float32),
        (jnp.arange(p["w1"].shape[0]), p["w1"], p["w3"], p["w2"]))
    if shared:
        sh = p["shared"]
        out = out + _gated(u, sh["w1"], sh["w3"], sh["w2"], mode)
    return out


def layer_params(params, cfg):
    """The layers' parameters in order, the stacked ones one by one."""
    s = sizes(cfg)
    for i in range(s["layers"]):
        if i < s["dense"]:
            yield params[f"layer{i}"]
        else:
            yield jax.tree.map(lambda a: a[i - s["dense"]], params["layers"])


def reference_logits(params, tokens, cfg, mode="f32"):
    """(B, T) token ids -> (B, T, V) float32 logits.  ``mode`` is the
    control's precision: ``f32`` (the reference); ``fp8`` (inputs of every
    matmul rounded to e4m3 with a per-tensor scale, the router float32);
    ``bf16`` (matmul inputs and the residual stream rounded to bfloat16);
    ``fp8_latent`` (only the latent rows a cache would hold, ``[c,
    RoPE(k_r)]``, rounded to e4m3)."""
    s = sizes(cfg)
    stream = _bf16 if mode == "bf16" else (lambda x: x)
    x = stream(params["embed"].astype(jnp.float32)[tokens])
    for i, p in enumerate(layer_params(params, cfg)):
        x = stream(x + _mla(_rms(x, p["op_norm"]["weight"], s["eps"]),
                            p["op"], s, mode))
        h = _rms(x, p["ffn_norm"]["weight"], s["eps"])
        f = p["ffn"]
        x = stream(x + (_gated(h, f["w1"], f["w3"], f["w2"], mode)
                        if i < s["dense"] else _moe(h, f, s, mode)))
    return _mm(_rms(x, params["norm_f"]["weight"], s["eps"]),
               params["head"], mode)
