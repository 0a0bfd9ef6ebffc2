"""Synthetic-data throughput drivers.

Reference: models/utils/LocalOptimizerPerf.scala,
models/utils/DistriOptimizerPerf.scala:82 and nn/mkldnn/Perf.scala:56-126 —
log imgs/sec (or iters/sec) on synthetic data for the standard models.

    python -m bigdl_tpu.models.perf --model resnet50 -b 32 -i 20
    python -m bigdl_tpu.models.perf --model vgg16 --distributed

Unlike the reference (threads x replica fwd/bwd), the measured unit here is
the fused jitted train step (fwd + bwd + update in one XLA program); the
first iteration is excluded as compile time.
"""

import argparse
import time

import numpy as np


# image models: (module, ctor, input shape, classes)
MODELS = {
    "lenet": ("bigdl_tpu.models.lenet", "LeNet5", (28, 28, 1), 10),
    "alexnet": ("bigdl_tpu.models.alexnet", "AlexNetOWT", (224, 224, 3), 1000),
    "vgg16": ("bigdl_tpu.models.vgg", "Vgg16", (224, 224, 3), 1000),
    "vgg19": ("bigdl_tpu.models.vgg", "Vgg19", (224, 224, 3), 1000),
    "resnet50": ("bigdl_tpu.models.resnet", "ResNet", (224, 224, 3), 1000),
    "inception_v1": ("bigdl_tpu.models.inception",
                     "InceptionV1NoAuxClassifier", (224, 224, 3), 1000),
    "inception_v2": ("bigdl_tpu.models.inception", "InceptionV2",
                     (224, 224, 3), 1000),
}


# token models (the reference's "SimpleRNN LM sample throughput" row and
# the transformer flagship): (module, ctor, ctor args/kwargs, vocab, seq_len)
TOKEN_MODELS = {
    "simplernn": ("bigdl_tpu.models.rnn", "SimpleRNN",
                  (4000, 40, 4000), {}, 4000, 25),
    "lstm_lm": ("bigdl_tpu.models.rnn", "LSTMLanguageModel",
                (10000, 128, 256), {}, 10000, 35),
    "transformer": ("bigdl_tpu.nn.attention", "TransformerLM",
                    (8000, 256, 4, 4), {"max_len": 256}, 8000, 256),
}


def _resolve(mod_name, fn_name):
    import importlib
    return getattr(importlib.import_module(mod_name), fn_name)


def build_model(name):
    mod_name, fn_name, shape, classes = MODELS[name]
    return _resolve(mod_name, fn_name)(), shape, classes


def build_token_model(name):
    mod_name, fn_name, args, kwargs, vocab, seq_len = TOKEN_MODELS[name]
    return _resolve(mod_name, fn_name)(*args, **kwargs), vocab, seq_len


def run_perf(model_name="resnet50", batch=32, iterations=20,
             distributed=False, fused=False):
    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim
    from bigdl_tpu.optim.train_step import make_train_step

    rng = np.random.default_rng(0)
    if model_name in TOKEN_MODELS:
        if distributed:
            raise NotImplementedError(
                "--distributed drives the image-model DistriOptimizer "
                "path; token models run the single-chip fused step")
        # LM perf (reference: models/rnn/README.md throughput log + the
        # transformer flagship): (N, T) tokens -> per-token NLL
        model, vocab, seq_len = build_token_model(model_name)
        x = jnp.asarray(rng.integers(0, vocab, size=(batch, seq_len)),
                        jnp.int32)
        target = jnp.asarray(rng.integers(0, vocab, size=(batch, seq_len)))
        if model_name == "transformer":
            # TimeDistributed flattens (N,T,V)->(N*T,V), which is the
            # shape that engages the Pallas fused-CE kernel
            criterion = nn.TimeDistributedCriterion(
                nn.FusedSoftmaxCrossEntropyCriterion())
        else:
            criterion = nn.TimeDistributedCriterion(nn.ClassNLLCriterion())
    else:
        model, shape, classes = build_model(model_name)
        x = jnp.asarray(rng.normal(size=(batch,) + shape), jnp.float32)
        target = jnp.asarray(rng.integers(0, classes, size=batch))
        criterion = nn.ClassNLLCriterion()
    method = optim.SGD(learning_rate=0.01)
    if fused:
        # one flat-vector update kernel (docs/performance.md op accounting)
        method = optim.Fused(method)

    if distributed:
        # DistriOptimizerPerf equivalent: run the sharded DistriOptimizer
        # loop on synthetic data and report its per-iteration throughput.
        from bigdl_tpu.dataset import SampleToMiniBatch, array_dataset
        from bigdl_tpu.optim import DistriOptimizer, Trigger

        n = batch * 4
        xs = np.asarray(rng.normal(size=(n,) + shape), np.float32)
        ys = rng.integers(0, classes, size=n)
        ds = array_dataset(xs, ys) >> SampleToMiniBatch(batch)
        opt = DistriOptimizer(model, ds, criterion, method)
        opt.set_end_when(Trigger.max_iteration(iterations))
        t0 = time.perf_counter()
        opt.optimize()
        dt = time.perf_counter() - t0
        rate = batch * iterations / dt
        print(f"[{model_name}] distributed batch {batch}: "
              f"{rate:.1f} records/sec incl. compile")
        return rate

    model.build(jax.ShapeDtypeStruct(x.shape, x.dtype))
    params, mstate = model.parameters()[0], model.state()
    opt_state = method.init_state(params)
    step = jax.jit(make_train_step(model, criterion, method),
                   donate_argnums=(0, 1, 2))

    key = jax.random.key(0)
    # compile (excluded)
    params, mstate, opt_state, loss = step(params, mstate, opt_state, x,
                                           target, key)
    jax.block_until_ready(loss)

    times = []
    for i in range(iterations):
        t0 = time.perf_counter()
        params, mstate, opt_state, loss = step(params, mstate, opt_state, x,
                                               target, jax.random.fold_in(key, i))
        jax.block_until_ready(loss)
        times.append(time.perf_counter() - t0)
        print(f"iter {i + 1}/{iterations}: "
              f"{batch / times[-1]:.1f} records/sec, loss {float(loss):.4f}")

    med = float(np.median(times))
    print(f"[{model_name}] batch {batch}: median {batch / med:.1f} records/sec "
          f"({med * 1e3:.1f} ms/iter)")
    return batch / med


def main(argv=None):
    p = argparse.ArgumentParser(prog="bigdl_tpu.models.perf")
    p.add_argument("--model", default="resnet50",
                   choices=sorted(MODELS) + sorted(TOKEN_MODELS))
    p.add_argument("-b", "--batchSize", type=int, default=32, dest="batch")
    p.add_argument("-i", "--iteration", type=int, default=20,
                   dest="iterations")
    p.add_argument("--distributed", action="store_true")
    p.add_argument("--fused", action="store_true",
                   help="flat fused optimizer update (optim.Fused)")
    args = p.parse_args(argv)
    run_perf(args.model, args.batch, args.iterations, args.distributed,
             fused=args.fused)


if __name__ == "__main__":
    main()
