"""granite-4.0-h-micro (ibm-granite; ``model_type: granitemoehybrid``, no
routed experts) for the benchmark, WHOLE on one chip: the program's model
built at the configuration's sizes, weights from a seed, the work a token
needs from shapes, and a plain reference.

Pre-norm residual blocks, RMSNorm (eps 1e-5), a TIED head, the family's
four multipliers::

    x0     = 12 * embed[ids]                                  embedding_multiplier
    h      = x + 0.22 * Mixer_l(RMSNorm(x))                   residual_multiplier
    y      = h + 0.22 * W_2(SiLU(W_1 RMSNorm(h)) * W_3 RMSNorm(h))
    logits = (RMSNorm(x_L) @ embed^T) / 8                     logits_scaling

Mixer ``attention``: 32 query heads over 8 key/value heads of 64 (query
head ``g`` reads KV head ``g // 4``), no bias, NO positional encoding
(``nope``), scores times 0.015625 (``attention_multiplier``), causal
softmax.

Mixer ``mamba`` (Mamba-2; ``d_inner = 64 heads x 64``, state 128, one
group, 4 taps)::

    [z, xBC] = W_in u;  dt = W_dt u          the published in_proj's rows
    xBC      = SiLU(causal depthwise conv_4(xBC) + conv_bias)
    [x, B, C] = xBC                          B and C shared by all heads
    dt_h     = softplus(dt_h + dt_bias_h);   A_h = -exp(A_log_h)
    S_h,t    = exp(dt_h,t A_h) S_h,t-1 + dt_h,t x_h,t (x) B_t     (64, 128) float32
    y_h,t    = S_h,t C_t + D_h x_h,t
    out      = W_out (RMSNorm_4096(y * SiLU(z)) * w)              gate BEFORE the norm

What the config does not fix is listed under ``assumed`` in
``configs/granite-4.0-h-micro.json``.

Three parts, kept apart (as ``gpt2-medium.py``): ``program_*`` are the
only functions that import ``bigdl_tpu``; ``make_params`` and the work
functions are the benchmark's own; ``reference_*`` are plain ``jax.numpy``
float32 at matmul precision ``highest``: one full forward over a whole
row, no cache, no kernels, no chunks: the recurrence runs TOKEN BY TOKEN
(a ``lax.scan`` over the tokens of a row).  The Mamba layers of a run are
taken one after the other by a ``lax.scan`` over their stacked weights,
each lifted to float32 inside its own step, so that no more than a layer
is ever held in float32.

Parameter tree (the layout ``bigdl_tpu.models.granite.GraniteHybrid``
uses; a run is the Mamba layers between two attention layers)::

    embed (V, D)   norm_f {weight}
    mamba{r}: every leaf stacked over run r's layers
    attention{j}:
        op_norm {weight}  ffn_norm {weight}
        op:  {in_weight (2 d_inner + 2 N, D), dt_weight (H, D),
              conv_kernel (taps, d_inner + 2 N), conv_bias, dt_bias (H,),
              A_log (H,), D (H,), o_norm (d_inner,), out_weight (D, d_inner)}
          or {qkv_weight ((Hq + 2 Hkv) 64, D), out_weight (D, D)}
        ffn: {w1 (F, D), w3 (F, D), w2 (D, F)}
"""

import functools
import importlib.util
from itertools import groupby

import jax
import jax.numpy as jnp
import numpy as np

from harness.precision import rounded, through
from harness.traffic import prng_key

#: leaves kept in float32 whatever the configuration's weight dtype
FULL_PRECISION = ("weight", "A_log", "dt_bias", "D", "o_norm")


# --------------------------------------------------------------------- #
# sizes
# --------------------------------------------------------------------- #

def sizes(cfg):
    assert cfg["mamba_n_groups"] == 1 and cfg["num_local_experts"] == 0
    assert cfg["position_embedding_type"] == "nope"
    assert cfg["tie_word_embeddings"] and not cfg["attention_bias"]
    assert cfg["mamba_conv_bias"] and not cfg["mamba_proj_bias"]
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    assert H * P == cfg["mamba_expand"] * cfg["hidden_size"]
    kinds = tuple(cfg["layer_types"])
    assert len(kinds) == cfg["num_hidden_layers"]
    Hq = cfg["num_attention_heads"]
    return dict(
        V=cfg["vocab_size"], D=cfg["hidden_size"],
        F=cfg["shared_intermediate_size"], Hq=Hq,
        Hkv=cfg["num_key_value_heads"], dh=cfg["hidden_size"] // Hq,
        H=H, P=P, N=cfg["mamba_d_state"], taps=cfg["mamba_d_conv"],
        inner=H * P, conv=H * P + 2 * cfg["mamba_d_state"],
        chunk=cfg["mamba_chunk_size"], kinds=kinds,
        runs=[(k, len(list(g))) for k, g in groupby(kinds)],
        mamba=kinds.count("mamba"), attention=kinds.count("attention"),
        eps=cfg["rms_norm_eps"],
        scale=float(cfg["attention_multiplier"]),
        embed_x=float(cfg["embedding_multiplier"]),
        resid_x=float(cfg["residual_multiplier"]),
        logits_div=float(cfg["logits_scaling"]), P_ctx=cfg["n_positions"],
        dtype=cfg.get("program", {}).get("dtype", "float32"))


def layer_shapes(cfg, kind):
    s = sizes(cfg)
    D, F = s["D"], s["F"]
    if kind == "mamba":
        op = {"in_weight": (s["inner"] + s["conv"], D),
              "dt_weight": (s["H"], D),
              "conv_kernel": (s["taps"], s["conv"]),
              "conv_bias": (s["conv"],), "dt_bias": (s["H"],),
              "A_log": (s["H"],), "D": (s["H"],), "o_norm": (s["inner"],),
              "out_weight": (D, s["inner"])}
    else:
        op = {"qkv_weight": ((s["Hq"] + 2 * s["Hkv"]) * s["dh"], D),
              "out_weight": (D, D)}
    return {"op_norm": {"weight": (D,)}, "ffn_norm": {"weight": (D,)},
            "op": op,
            "ffn": {"w1": (F, D), "w3": (F, D), "w2": (D, F)}}


def _is_shape(x):
    return isinstance(x, tuple)


def param_shapes(cfg):
    s = sizes(cfg)
    out = {"embed": (s["V"], s["D"]), "norm_f": {"weight": (s["D"],)}}
    run = att = 0
    for kind, count in s["runs"]:
        if kind == "mamba":
            out[f"mamba{run}"] = jax.tree.map(
                lambda shape: (count,) + shape, layer_shapes(cfg, kind),
                is_leaf=_is_shape)
            run += 1
        else:
            for _ in range(count):
                out[f"attention{att}"] = layer_shapes(cfg, kind)
                att += 1
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
    state, K and V pool)`` in bytes."""
    s = sizes(cfg)
    e = mix["engine"]
    act = jnp.dtype(s["dtype"]).itemsize
    weights = sum(int(np.prod(shape)) * d.itemsize for shape, d in zip(
        jax.tree.leaves(param_shapes(cfg), is_leaf=_is_shape),
        jax.tree.leaves(param_dtypes(cfg))))
    a_slot = s["mamba"] * (s["H"] * s["P"] * s["N"] * 4
                           + (s["taps"] - 1) * s["conv"] * act)
    a_block = s["attention"] * 2 * e["kv_block_size"] * s["Hkv"] * s["dh"] \
        * act
    return (weights, (e["decode_slots"] + 1) * a_slot,
            (e["kv_blocks"] + 1) * a_block)


# --------------------------------------------------------------------- #
# weights from the seed, on the device, a leaf at a time
# --------------------------------------------------------------------- #

def _leaf_rule(path):
    """(distribution, a, b) of a leaf by its name."""
    last = _last(path)
    if last in ("weight", "o_norm"):
        return "normal", 1.0, 0.02
    if last == "D":
        return "normal", 1.0, 0.0
    if last == "A_log":                 # A = -uniform(1, 16)
        return "log_uniform_width", 1.0, 16.0
    if last == "dt_bias":               # softplus(dt_bias) in [1e-3, 1e-1]
        return "inverse_softplus", 1e-3, 1e-1
    if last == "conv_kernel":
        return "uniform", -0.5, 0.5
    return "normal", 0.0, 0.02


@functools.partial(jax.jit, static_argnames=("rule", "shape", "dtype"))
def _draw(key, a, b, rule, shape, dtype):
    f32 = jnp.float32
    if rule == "normal":
        x = a + b * jax.random.normal(key, shape, f32)
    elif rule == "uniform":
        x = jax.random.uniform(key, shape, f32, a, b)
    elif rule == "log_uniform_width":   # log of a uniform draw
        x = jnp.log(jax.random.uniform(key, shape, f32, a, b))
    else:                               # inverse softplus of a log-uniform
        step = jnp.exp(jax.random.uniform(key, shape, f32, jnp.log(a),
                                          jnp.log(b)))
        x = step + jnp.log(-jnp.expm1(-step))
    return x.astype(dtype)


PROGRAM_CLASS = "bigdl_tpu.models.granite.GraniteHybrid"


def make_params(cfg, seed):
    """The weights from ``seed``, drawn in float32 and stored in the
    configuration's dtype, one jitted call a leaf (the key and the rule's
    numbers are arguments: one compiled program a shape serves every
    seed).  A program without the model's module (a commit from before it)
    is told so here, before 6.4 GB are drawn for it."""
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
        rule, a, b = _leaf_rule(path)
        out.append(_draw(jax.random.fold_in(key, i), a, b, rule, shape, dt))
    return jax.tree.unflatten(treedef, out)


# --------------------------------------------------------------------- #
# required work, from shapes
# --------------------------------------------------------------------- #

def forward_flops(cfg, context_lengths):
    """Floating-point operations a forward pass needs for tokens whose
    causal context lengths are given: the matmuls (2 per multiply-add) of
    the mixers' projections, the MLPs and the tied head; the convolution's
    taps; the state-space recurrence as its least form needs it, a
    multiply-add a state element to update and another to read out (4 H P
    N a token a layer: the chunked scan's products, which prefill runs,
    are more; the decode step's are these); and attention's scores and
    weighted sums, two matmuls a key over 32 heads of 64."""
    s = sizes(cfg)
    D = s["D"]
    ctx = np.asarray(context_lengths, np.float64)
    mamba = 2 * D * (s["inner"] + s["conv"] + s["H"]) + 2 * s["inner"] * D \
        + 2 * s["taps"] * s["conv"] + 4 * s["H"] * s["P"] * s["N"]
    attention = 2 * D * (s["Hq"] + 2 * s["Hkv"]) * s["dh"] + 2 * D * D
    per_token = 2.0 * D * s["V"] + s["mamba"] * mamba \
        + s["attention"] * attention \
        + len(s["kinds"]) * 3 * 2 * D * s["F"]
    per_key = s["attention"] * 2 * 2 * s["Hq"] * s["dh"]
    return ctx.size * per_token + per_key * float(ctx.sum())


def kernel_work(cfg, mix, name):
    """FLOPs and bytes ONE call of a kernel needs at the mix's shapes."""
    s = sizes(cfg)
    if name == "ssd_decode":
        # one call a Mamba layer a decode tick: each LIVE slot's state
        # (H x P x N float32) read once and written once; the kernel skips
        # the slots that are not live, so the reader (``grouped_roofline``)
        # multiplies by the live slots the decode_prep spans counted,
        # which are a tick's and not summed over layers: ``layers`` 1, and
        # nothing is read once a call
        state = s["H"] * s["P"] * s["N"]
        return {"flops_per_row": 4.0 * state, "bytes_per_row": 8.0 * state,
                "bytes_per_call": 0.0, "layers": 1}
    raise KeyError(name)


# --------------------------------------------------------------------- #
# the program's model (the ONLY part that imports the program)
# --------------------------------------------------------------------- #

def program_model(cfg, params, batch_spec):
    """``bigdl_tpu.models.granite.GraniteHybrid`` at the configuration's
    sizes with the benchmark's weights installed, in the dtypes they are
    stored in."""
    from bigdl_tpu.models.granite import GraniteHybrid

    s = sizes(cfg)
    model = GraniteHybrid(
        s["V"], s["D"], s["kinds"], s["F"], s["Hq"], s["Hkv"], s["H"],
        s["P"], state_dim=s["N"], conv_kernel=s["taps"],
        chunk_size=s["chunk"], attention_multiplier=s["scale"],
        embedding_multiplier=s["embed_x"], residual_multiplier=s["resid_x"],
        logits_scaling=s["logits_div"], norm_eps=s["eps"],
        max_len=s["P_ctx"], dtype=s["dtype"])
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
    none of them (it rounds the recurrent state, nothing else)."""
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


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _mamba(u, p, s, mode):
    """The Mamba-2 mixer over whole rows ``u (B, T, D)``: projections and
    the convolution for every token at once, then the recurrence one token
    after the other from a zero state."""
    b, t, _ = u.shape
    H, P, N, taps, inner = s["H"], s["P"], s["N"], s["taps"], s["inner"]
    proj = _mm(u, p["in_weight"], mode)
    z, xbc = proj[..., :inner], proj[..., inner:]
    dt = jax.nn.softplus(_mm(u, p["dt_weight"], mode) + p["dt_bias"])
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(p["conv_kernel"][j] * padded[:, j:j + t] for j in range(taps))
    xbc = jax.nn.silu(conv + p["conv_bias"])
    x = xbc[..., :inner].reshape(b, t, H, P)
    B, C = xbc[..., inner:inner + N], xbc[..., inner + N:]
    A = -jnp.exp(p["A_log"])
    keep = _bf16 if mode == "bf16_state" else (lambda v: v)

    def token(S, now):
        x_t, dt_t, B_t, C_t = now            # (b, H, P) (b, H) (b, N) (b, N)
        S = jnp.exp(dt_t * A)[..., None, None] * S \
            + (dt_t[..., None] * x_t)[..., None] * B_t[:, None, None, :]
        S = keep(S)
        y = jnp.sum(S * C_t[:, None, None, :], -1) + p["D"][:, None] * x_t
        return S, y

    _, y = jax.lax.scan(
        token, jnp.zeros((b, H, P, N), jnp.float32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C)))
    y = jnp.moveaxis(y, 0, 1).reshape(b, t, inner) * jax.nn.silu(z)
    return _mm(_rms(y, p["o_norm"], s["eps"]), p["out_weight"], mode)


def _attention(u, p, s, mode):
    """Grouped-query attention with no positions: plain causal softmax
    over whole rows, every KV head read by its four query heads."""
    b, t, _ = u.shape
    Hq, Hkv, dh = s["Hq"], s["Hkv"], s["dh"]
    mm = _matrix_mode(mode)
    qkv = _mm(u, p["qkv_weight"], mode)
    q = qkv[..., :Hq * dh].reshape(b, t, Hkv, Hq // Hkv, dh)
    k = qkv[..., Hq * dh:(Hq + Hkv) * dh].reshape(b, t, Hkv, dh)
    v = qkv[..., (Hq + Hkv) * dh:].reshape(b, t, Hkv, dh)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", through(q, mm), through(k, mm),
                        precision="highest") * s["scale"]
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    w = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", through(w, mm), through(v, mm),
                   precision="highest")
    return _mm(o.reshape(b, t, Hq * dh), p["out_weight"], mode)


def _layer(x, p, s, mode, mixer, stream):
    """A layer on the residual stream; ``p`` in the dtype it is stored
    in, lifted to float32 here."""
    p = _f32(p)
    x = stream(x + s["resid_x"] * mixer(
        _rms(x, p["op_norm"]["weight"], s["eps"]), p["op"], s, mode))
    h = _rms(x, p["ffn_norm"]["weight"], s["eps"])
    f = p["ffn"]
    return stream(x + s["resid_x"] * _mm(
        jax.nn.silu(_mm(h, f["w1"], mode)) * _mm(h, f["w3"], mode), f["w2"],
        mode))


def reference_logits(params, tokens, cfg, mode="f32"):
    """(B, T) token ids -> (B, T, V) float32 logits.  ``mode`` is the
    control's precision: ``f32`` (the reference); ``fp8`` (inputs of every
    matmul rounded to e4m3 with a per-tensor scale); ``bf16`` (matmul
    inputs and the residual stream rounded to bfloat16); ``bf16_state``
    (only the recurrent state, rounded to bfloat16 after every token, as a
    state kept in the model's dtype would be)."""
    s = sizes(cfg)
    stream = _bf16 if mode == "bf16" else (lambda x: x)
    embed = params["embed"].astype(jnp.float32)
    x = stream(s["embed_x"] * embed[tokens])
    run = att = 0
    for kind, count in s["runs"]:
        if kind == "mamba":
            x, _ = jax.lax.scan(
                lambda x, p: (_layer(x, p, s, mode, _mamba, stream), None),
                x, params[f"mamba{run}"])
            run += 1
        else:
            for _ in range(count):
                x = _layer(x, params[f"attention{att}"], s, mode,
                           _attention, stream)
                att += 1
    return _mm(_rms(x, params["norm_f"]["weight"], s["eps"]), embed,
               mode) / s["logits_div"]
