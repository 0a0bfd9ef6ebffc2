"""GPT-2 (Radford et al. 2019) for the benchmark: the program's model built
at the configuration's sizes, weights from a seed, the work a step needs
from shapes, and a plain reference.

Three parts, kept apart:

- ``program_*``: the only functions that import the program
  (``bigdl_tpu``).  They build what a user builds and nothing else.
- ``make_params`` / ``required_*``: the benchmark's own; no import of the
  program.
- ``reference_*``: plain ``jax.numpy`` float32 at matmul precision
  ``highest``; imports nothing of the program and takes the weights the
  benchmark made.

Parameter tree (the layout ``TransformerLM(scan_layers=True)`` uses; every
leaf under ``blocks`` has a leading layer axis)::

    wte (V, D)  wpe (P, D)  head (V, D)  ln_f {weight, bias}
    blocks: ln1 {weight, bias}  attn {qkv_weight (3D, D), qkv_bias,
            out_weight (D, D), out_bias}  ln2 {...}
            fc1 {weight (F, D), bias}  fc2 {weight (D, F), bias}
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from harness.precision import rounded, through
from harness.traffic import prng_key

LN_EPS = 1e-6        # the program's LayerNorm default (GPT-2 states 1e-5)


# --------------------------------------------------------------------- #
# sizes
# --------------------------------------------------------------------- #

def sizes(cfg):
    d = cfg["n_embd"]
    return dict(V=cfg["vocab_size"], P=cfg["n_positions"], D=d,
                L=cfg["n_layer"], H=cfg["n_head"],
                F=cfg.get("n_inner") or 4 * d)


def param_shapes(cfg):
    s = sizes(cfg)
    V, P, D, L, F = s["V"], s["P"], s["D"], s["L"], s["F"]
    ln = lambda lead: {"weight": lead + (D,), "bias": lead + (D,)}
    return {
        "wte": (V, D), "wpe": (P, D), "head": (V, D), "ln_f": ln(()),
        "blocks": {
            "ln1": ln((L,)), "ln2": ln((L,)),
            "attn": {"qkv_weight": (L, 3 * D, D), "qkv_bias": (L, 3 * D),
                     "out_weight": (L, D, D), "out_bias": (L, D)},
            "fc1": {"weight": (L, F, D), "bias": (L, F)},
            "fc2": {"weight": (L, D, F), "bias": (L, D)},
        },
    }


def param_count(cfg):
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


# --------------------------------------------------------------------- #
# weights from the seed, on the device, in one jitted call
# --------------------------------------------------------------------- #

def _leaf_rule(path):
    """(mean, std) of a leaf by its place in the tree."""
    names = [getattr(k, "key", str(k)) for k in path]
    last = names[-1]
    if names[0] == "wpe":
        return 0.0, 0.01
    if last == "weight" and names[-2].startswith("ln"):
        return 1.0, 0.02
    return 0.0, 0.02


def draw_params(cfg, key):
    """The model's fp32 weights from a PRNG key (traceable: the key is an
    argument, so one compiled program serves every seed)."""
    leaves, treedef = jax.tree.flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(leaves):
        mean, std = _leaf_rule(path)
        out.append(mean + std * jax.random.normal(
            jax.random.fold_in(key, i), shape, jnp.float32))
    return jax.tree.unflatten(treedef, out)


def make_params(cfg, seed):
    """The weights from ``seed``: one jitted call, made on the default
    device, no host copy."""
    return jax.jit(lambda key: draw_params(cfg, key))(prng_key(seed))


# --------------------------------------------------------------------- #
# comparison units: what "by the worst leaf" runs over
# --------------------------------------------------------------------- #

def unit_sq_norms(tree, other=None):
    """Squared norms of ``tree`` (or of ``tree - other``) by unit: every
    leaf outside the blocks, and inside them every layer's slice of every
    leaf, the fused qkv leaves cut into their q, k and v thirds (the key
    bias has no gradient under softmax, and must not hide in a leaf that
    has one)."""
    diff = tree if other is None else jax.tree.map(
        lambda a, b: a - b, tree, other)

    def outer(a):
        return jnp.sum(jnp.square(a))

    def per_layer(a):
        return jnp.sum(jnp.square(a.reshape(a.shape[0], -1)), -1)

    def per_layer_qkv(a):
        return jnp.sum(jnp.square(a.reshape(a.shape[0], 3, -1)), -1)

    out = {k: jax.tree.map(outer, v) for k, v in diff.items()
           if k != "blocks"}
    blocks = {k: jax.tree.map(per_layer, v)
              for k, v in diff["blocks"].items() if k != "attn"}
    attn = diff["blocks"]["attn"]
    blocks["attn"] = {k: (per_layer_qkv if k.startswith("qkv")
                          else per_layer)(v) for k, v in attn.items()}
    out["blocks"] = blocks
    return out


# --------------------------------------------------------------------- #
# required work, from shapes (recompute never counted)
# --------------------------------------------------------------------- #

def forward_flops(cfg, context_lengths):
    """Floating-point operations a forward pass needs for tokens whose
    causal context lengths (number of keys each query attends, itself
    included) are given: matmuls of the blocks and the head (2 per
    multiply-add), and attention's two matmuls per key."""
    s = sizes(cfg)
    D, L, F, V = s["D"], s["L"], s["F"], s["V"]
    ctx = np.asarray(context_lengths, np.float64)
    n = ctx.size
    per_token = L * (2 * D * 3 * D + 2 * D * D + 2 * 2 * D * F) + 2 * D * V
    attention = L * 4 * D * float(ctx.sum())
    return n * per_token + attention


def train_step_flops(cfg, batch, seq):
    """Forward + backward of one optimizer step on ``batch`` sequences of
    ``seq`` tokens: three times the forward (the backward is two matmuls
    for each of the forward's)."""
    ctx = np.tile(np.arange(1, seq + 1), batch)
    return 3.0 * forward_flops(cfg, ctx)


def attention_fwd_work(cfg, batch, seq, dtype_bytes):
    """Causal attention forward for one layer at (batch, seq): FLOPs over
    the lower triangle (diagonal included) and the bytes of q, k, v read
    and the output written once."""
    s = sizes(cfg)
    D = s["D"]
    pairs = batch * seq * (seq + 1) / 2
    return {"flops": 4.0 * D * pairs,
            "bytes": 4.0 * batch * seq * D * dtype_bytes}


def cross_entropy_work(cfg, rows, logits_bytes):
    """Softmax cross-entropy over (rows, V): forward reads the logits once;
    forward + backward read them twice and write the gradient once.  About
    5 FLOPs an element forward (max, subtract, exp, sum, pick) and 3
    backward; memory bounds it by far."""
    V = sizes(cfg)["V"]
    return {"fwd": {"flops": 5.0 * rows * V, "bytes": rows * V * logits_bytes},
            "bwd": {"flops": 3.0 * rows * V,
                    "bytes": 2.0 * rows * V * logits_bytes}}


def kernel_work(cfg, mix, name):
    """FLOPs and bytes ONE call of a kernel needs at the mix's shapes."""
    batch, seq = int(mix["batch"]), int(mix["data"]["seq_len"])
    act = 2 if mix.get("compute_dtype") == "bfloat16" else 4
    if name == "attention_fwd":
        return attention_fwd_work(cfg, batch, seq, act)
    if name in ("cross_entropy_fwd", "cross_entropy_bwd"):
        # the step casts the logits to float32 before the criterion
        return cross_entropy_work(cfg, batch * seq, 4)[name[-3:]]
    raise KeyError(name)


# --------------------------------------------------------------------- #
# the program's model (the ONLY part that imports the program)
# --------------------------------------------------------------------- #

def program_model(cfg, params, batch_spec):
    """``TransformerLM`` at the configuration's sizes with the benchmark's
    weights installed."""
    from bigdl_tpu.nn.attention import TransformerLM

    s = sizes(cfg)
    model = TransformerLM(s["V"], s["D"], s["H"], s["L"], max_len=s["P"],
                          mlp_ratio=s["F"] // s["D"],
                          scan_layers=cfg["program"]["scan_layers"])
    expect = jax.eval_shape(lambda k: model.setup(k, batch_spec)[0],
                            jax.random.key(0))
    got = jax.tree.map(lambda a: a.shape, params)
    want = jax.tree.map(lambda a: a.shape, expect)
    if got != want:
        raise RuntimeError("the benchmark's parameter tree does not match "
                           f"the program's: {got} != {want}")
    model.set_parameters(params)
    model.set_state(())
    return model


def program_training(cfg, traffic):
    """Criterion and optimizer as ``models/run.py``'s transformer-train
    builds them."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim

    o = traffic["optimizer"]
    if o["name"] != "adam":
        raise ValueError(f"gpt2 trains with adam, not {o['name']!r}")
    criterion = nn.TimeDistributedCriterion(
        nn.FusedSoftmaxCrossEntropyCriterion())
    method = optim.Adam(learning_rate=o["learning_rate"], beta1=o["beta1"],
                        beta2=o["beta2"], epsilon=o["epsilon"])
    return criterion, method


# --------------------------------------------------------------------- #
# the plain reference
# --------------------------------------------------------------------- #

def _stream(x, act_dtype):
    """The residual stream as the control keeps it."""
    if act_dtype == jnp.float32:
        return x
    return rounded(x, lambda v: v.astype(act_dtype).astype(jnp.float32))


def _mm(a, b, mode):
    """a @ b.T in float32; ``mode`` rounds both inputs first."""
    return jnp.einsum("...i,oi->...o", through(a, mode), through(b, mode),
                      precision="highest")


def _ln(x, p):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["weight"] + p["bias"]


def _block(x, p, heads, mode, act_dtype):
    b, t, d = x.shape
    h = _ln(x, p["ln1"])
    qkv = _mm(h, p["attn"]["qkv_weight"], mode) + p["attn"]["qkv_bias"]
    q, k, v = [a.reshape(b, t, heads, d // heads)
               for a in jnp.split(qkv, 3, -1)]
    scores = jnp.einsum("bqhd,bkhd->bhqk", through(q, mode), through(k, mode),
                        precision="highest") / math.sqrt(d // heads)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    w = jax.nn.softmax(scores, -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", through(w, mode), through(v, mode),
                   precision="highest").reshape(b, t, d)
    x = x + _mm(o, p["attn"]["out_weight"], mode) + p["attn"]["out_bias"]
    x = _stream(x, act_dtype)
    h = _ln(x, p["ln2"])
    h = jax.nn.gelu(_mm(h, p["fc1"]["weight"], mode) + p["fc1"]["bias"],
                    approximate=True)
    x = x + _mm(h, p["fc2"]["weight"], mode) + p["fc2"]["bias"]
    return _stream(x, act_dtype)


def reference_logits(params, tokens, cfg, mode="f32"):
    """(B, T) token ids -> (B, T, V) float32 logits: embed, the blocks one
    after the other (a scan over the layer axis, each layer rematerialised
    in a backward pass so that the reference fits beside nothing else),
    final LayerNorm, head.  ``mode`` is the control's precision: ``f32``
    (the reference), ``bf16`` (inputs of every matmul AND the residual
    stream rounded to bfloat16) or ``fp8`` (matmul inputs rounded to
    e4m3 with a per-tensor scale)."""
    heads = sizes(cfg)["H"]
    act_dtype = jnp.bfloat16 if mode == "bf16" else jnp.float32
    t = tokens.shape[1]
    x = params["wte"][tokens] + params["wpe"][:t][None]
    x = _stream(x, act_dtype)

    @jax.checkpoint
    def layer(x, p):
        return _block(x, p, heads, mode, act_dtype)

    x, _ = jax.lax.scan(lambda x, p: (layer(x, p), None), x,
                        params["blocks"])
    return _mm(_ln(x, params["ln_f"]), params["head"], mode)


def reference_loss(params, batch, cfg, mode="f32"):
    """Mean next-token cross-entropy over every position of the batch
    (``x``: (B, T) inputs, ``y``: (B, T) targets), as
    ``TimeDistributedCriterion(CrossEntropy)`` averages it."""
    x, y = batch
    logits = reference_logits(params, x, cfg, mode)
    logp = jax.nn.log_softmax(logits, -1)
    picked = jnp.take_along_axis(logp, y[..., None].astype(jnp.int32), -1)
    return -jnp.mean(picked)
