"""ResNet-50 (He et al. 2015) for the benchmark: the program's model at
the configuration's sizes, weights from a seed, the work a step needs from
shapes, and a plain reference.  Same three parts as every model file:
``program_*`` (the only importers of ``bigdl_tpu``), the benchmark's own
weights and work, and ``reference_*`` in plain ``jax.numpy`` float32.

The parameter tree is the one ``bigdl_tpu.models.resnet.ResNet`` builds:
a ``Sequential`` keyed by child index as a string, ``()`` where a child has
no parameters::

    '0' stem conv {weight (7,7,3,64)}   '1' bn {weight, bias}
    '2' relu ()   '3' maxpool ()
    '4'..'19' bottlenecks: {'0': {'0': main, '1': shortcut}, '1': (), '2': ()}
        main: '0' conv1x1 '1' bn '2' () '3' conv3x3 '4' bn '5' () '6' conv1x1 '7' bn
        shortcut: {'0' conv1x1, '1' bn} where shape or stride changes, else ()
    '20' global pool ()   '21' classifier {weight (classes, 2048), bias}

The state tree mirrors it with ``{running_mean, running_var}`` at every
BatchNorm.  The rehearsal sizes are NOT ResNet-50 (one block a stage):
they exist to rehearse the path, and ``program_model`` builds the program's
model from the same stage lists.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from harness.precision import through
from harness.traffic import prng_key

BN_EPS = 1e-5


# --------------------------------------------------------------------- #
# the architecture as a plan: the one description both the weights and
# the reference follow
# --------------------------------------------------------------------- #

def plan(cfg):
    """``[(key, block)]`` for the bottlenecks: each block is
    ``(n_in, planes, n_out, stride, projected)``."""
    blocks, n_in, key = [], cfg["stem_channels"], 4
    for stage, (planes, count) in enumerate(zip(cfg["stage_planes"],
                                                cfg["stage_blocks"])):
        for i in range(count):
            stride = 2 if (stage > 0 and i == 0) else 1
            n_out = planes * cfg["expansion"]
            blocks.append((str(key), (n_in, planes, n_out, stride,
                                      n_in != n_out or stride != 1)))
            n_in, key = n_out, key + 1
    return blocks, n_in, key


def _bn_shapes(n):
    return {"weight": (n,), "bias": (n,)}


def param_shapes(cfg):
    c, stem = cfg["image_channels"], cfg["stem_channels"]
    tree = {"0": {"weight": (7, 7, c, stem)}, "1": _bn_shapes(stem),
            "2": (), "3": ()}
    blocks, n_last, key = plan(cfg)
    for k, (n_in, planes, n_out, _stride, projected) in blocks:
        main = {"0": {"weight": (1, 1, n_in, planes)},
                "1": _bn_shapes(planes), "2": (),
                "3": {"weight": (3, 3, planes, planes)},
                "4": _bn_shapes(planes), "5": (),
                "6": {"weight": (1, 1, planes, n_out)},
                "7": _bn_shapes(n_out)}
        short = {"0": {"weight": (1, 1, n_in, n_out)},
                 "1": _bn_shapes(n_out)} if projected else ()
        tree[k] = {"0": {"0": main, "1": short}, "1": (), "2": ()}
    tree[str(key)] = ()
    tree[str(key + 1)] = {"weight": (cfg["num_classes"], n_last),
                          "bias": (cfg["num_classes"],)}
    return tree


def _is_shape(x):
    return isinstance(x, tuple) and len(x) > 0 and all(
        isinstance(d, int) for d in x)


def param_count(cfg):
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        param_shapes(cfg), is_leaf=_is_shape))


def draw_params(cfg, key):
    """fp32 weights from a PRNG key (traceable: the key is an argument,
    so one compiled program serves every seed)."""
    leaves, treedef = jax.tree.flatten_with_path(param_shapes(cfg),
                                                 is_leaf=_is_shape)
    out = []
    for i, (path, shape) in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        last = getattr(path[-1], "key", "")
        if len(shape) == 4:                          # He normal, fan-out
            std = math.sqrt(2.0 / (shape[0] * shape[1] * shape[3]))
            out.append(std * jax.random.normal(k, shape, jnp.float32))
        elif len(shape) == 2:
            out.append(0.01 * jax.random.normal(k, shape, jnp.float32))
        elif last == "weight":                       # BatchNorm scale
            out.append(1.0 + 0.02 * jax.random.normal(k, shape))
        else:
            out.append(0.02 * jax.random.normal(k, shape, jnp.float32))
    return jax.tree.unflatten(treedef, out)


def make_params(cfg, seed):
    """The weights from ``seed`` in one jitted call on the device."""
    return jax.jit(lambda key: draw_params(cfg, key))(prng_key(seed))


def make_state(cfg):
    """BatchNorm running statistics as the program starts them (mean 0,
    variance 1), ``()`` at every other child."""
    def of(node):
        if not isinstance(node, dict):
            return ()
        if set(node) == {"weight", "bias"} and len(node["weight"]) == 1:
            n = node["weight"][0]                    # a BatchNorm
            return {"running_mean": jnp.zeros((n,), jnp.float32),
                    "running_var": jnp.ones((n,), jnp.float32)}
        if "weight" in node:                         # conv or classifier
            return ()
        return {k: of(v) for k, v in node.items()}

    return of(param_shapes(cfg))


# --------------------------------------------------------------------- #
# required work, from shapes
# --------------------------------------------------------------------- #

def forward_flops_per_image(cfg):
    """2 x multiply-adds of every convolution and the classifier."""
    size = cfg["image_size"]
    c, stem = cfg["image_channels"], cfg["stem_channels"]
    hw = math.ceil(size / 2)
    total = 2 * hw * hw * 49 * c * stem
    hw = math.ceil(hw / 2)                           # max pool
    blocks, n_last, _ = plan(cfg)
    for _k, (n_in, planes, n_out, stride, projected) in blocks:
        total += 2 * hw * hw * n_in * planes         # 1x1 at the input size
        out = math.ceil(hw / stride)
        total += 2 * out * out * 9 * planes * planes
        total += 2 * out * out * planes * n_out
        if projected:
            total += 2 * out * out * n_in * n_out
        hw = out
    return total + 2 * n_last * cfg["num_classes"]


def train_step_flops(cfg, batch, *_):
    """Forward + backward: three times the forward."""
    return 3.0 * batch * forward_flops_per_image(cfg)


# --------------------------------------------------------------------- #
# the program's model
# --------------------------------------------------------------------- #

def program_model(cfg, params, batch_spec):
    from bigdl_tpu.models import resnet

    if cfg["stage_blocks"] == [3, 4, 6, 3] and cfg["stem_channels"] == 64:
        model = resnet.ResNet(depth=cfg["program"]["depth"],
                              class_num=cfg["num_classes"])
    else:                                            # rehearsal sizes only
        import bigdl_tpu.nn as nn
        from bigdl_tpu.nn.initialization import MsraFiller

        stem = cfg["stem_channels"]
        model = (nn.Sequential()
                 .add(nn.SpatialConvolution(
                     cfg["image_channels"], stem, 7, 7, 2, 2, 3, 3,
                     with_bias=False, data_format="NHWC",
                     weight_init=MsraFiller(False)))
                 .add(nn.SpatialBatchNormalization(stem)).add(nn.ReLU())
                 .add(nn.SpatialMaxPooling(3, 3, 2, 2, 1, 1)))
        blocks, n_last, _ = plan(cfg)
        for _k, (n_in, planes, _n_out, stride, _p) in blocks:
            model.add(resnet.bottleneck(n_in, planes, stride,
                                        cfg["expansion"]))
        model.add(nn.GlobalAveragePooling2D())
        model.add(nn.Linear(n_last, cfg["num_classes"]))
    expect = jax.eval_shape(lambda k: model.setup(k, batch_spec)[0],
                            jax.random.key(0))
    got = jax.tree.map(lambda a: a.shape, params)
    want = jax.tree.map(lambda a: a.shape, expect)
    if got != want:
        raise RuntimeError("the benchmark's parameter tree does not match "
                           "the program's")
    model.set_parameters(params)
    model.set_state(make_state(cfg))
    return model


def program_training(cfg, traffic):
    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim

    o = traffic["optimizer"]
    if o["name"] != "sgd":
        raise ValueError(f"resnet trains with sgd, not {o['name']!r}")
    method = optim.SGD(learning_rate=o["learning_rate"],
                       momentum=o["momentum"], dampening=0.0,
                       weight_decay=o.get("weight_decay", 0.0))
    return nn.CrossEntropyCriterion(), method


# --------------------------------------------------------------------- #
# the plain reference
# --------------------------------------------------------------------- #

def _conv(x, w, stride, pad, mode):
    return jax.lax.conv_general_dilated(
        through(x, mode), through(w, mode), (stride, stride),
        [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision="highest")


def _bn(x, p):
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), (0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + BN_EPS) * p["weight"] + p["bias"]


def _bottleneck(x, p, stride, mode):
    main, short = p["0"]["0"], p["0"]["1"]
    h = jax.nn.relu(_bn(_conv(x, main["0"]["weight"], 1, 0, mode),
                        main["1"]))
    h = jax.nn.relu(_bn(_conv(h, main["3"]["weight"], stride, 1, mode),
                        main["4"]))
    h = _bn(_conv(h, main["6"]["weight"], 1, 0, mode), main["7"])
    if short != ():
        x = _bn(_conv(x, short["0"]["weight"], stride, 0, mode), short["1"])
    return jax.nn.relu(h + x)


def reference_logits(params, images, cfg, mode="f32"):
    """(N, H, W, C) float32 images -> (N, classes) logits, BatchNorm on
    the batch's own statistics (training mode); every bottleneck is
    rematerialised in a backward pass so that the whole batch fits."""
    x = jax.nn.relu(_bn(_conv(images, params["0"]["weight"], 2, 3, mode),
                        params["1"]))
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        [(0, 0), (1, 1), (1, 1), (0, 0)])
    blocks, _n_last, key = plan(cfg)
    for k, (_n_in, _planes, _n_out, stride, _p) in blocks:
        x = jax.checkpoint(
            lambda x, p, s=stride: _bottleneck(x, p, s, mode))(x, params[k])
    x = jnp.mean(x, (1, 2))
    head = params[str(key + 1)]
    return jnp.einsum("ni,oi->no", through(x, mode),
                      through(head["weight"], mode),
                      precision="highest") + head["bias"]


def reference_loss(params, batch, cfg, mode="f32"):
    """Mean cross-entropy over the batch (labels 0-based)."""
    images, labels = batch
    logp = jax.nn.log_softmax(reference_logits(params, images, cfg, mode))
    return -jnp.mean(jnp.take_along_axis(
        logp, labels[:, None].astype(jnp.int32), -1))
