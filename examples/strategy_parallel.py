"""Selecting a parallel strategy from the Optimizer facade.

No reference analogue (the reference's only topology is Spark data
parallelism); this is the round-5 productization of the tp/pp/sp/ep
engines behind the one factory (docs/distributed-training.md).  Runs on
a virtual CPU mesh out of the box:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/strategy_parallel.py --strategy tp
    ... --strategy pp --schedule 1f1b
    ... --strategy pp-cnn           # heterogeneous Sequential pipeline
    ... --strategy sp               # ring-attention sequence parallelism
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--strategy", default="tp",
                   choices=["tp", "pp", "pp-cnn", "sp"])
    p.add_argument("--schedule", default="gpipe",
                   choices=["gpipe", "1f1b"])
    p.add_argument("--maxIteration", type=int, default=4)
    args = p.parse_args()

    import logging
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(levelname)-5s %(message)s")

    import numpy as np

    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim
    from bigdl_tpu.dataset import SampleToMiniBatch, array_dataset
    from bigdl_tpu.nn.attention import TransformerLM
    from bigdl_tpu.optim import Optimizer, Trigger
    from bigdl_tpu.utils.random_generator import RNG

    n_dev = jax.device_count()
    if n_dev < 2:
        raise SystemExit(
            "need >=2 devices; set JAX_PLATFORMS=cpu "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    n_dev = 2 * (n_dev // 2)       # largest even prefix: meshes are 2 x k
    RNG.set_seed(0)
    rng = np.random.default_rng(0)

    if args.strategy == "pp-cnn":
        # heterogeneous pipeline: a CNN Sequential with uneven stages
        # (<=4 pipeline stages; the 7-child model can't fill more)
        pipe = 4 if n_dev % 4 == 0 else 2
        mesh = jax.sharding.Mesh(
            np.asarray(jax.devices()[:n_dev]).reshape(-1, pipe),
            ("data", "pipe"))
        model = (nn.Sequential()
                 .add(nn.SpatialConvolution(3, 8, 3, 3, 1, 1, 1, 1))
                 .add(nn.ReLU())
                 .add(nn.SpatialConvolution(8, 16, 3, 3, 1, 1, 1, 1))
                 .add(nn.ReLU())
                 .add(nn.SpatialMaxPooling(2, 2, 2, 2))
                 .add(nn.Flatten())
                 .add(nn.Linear(16 * 8 * 8, 10)))
        # batch = microbatches x data shards x 2 samples each
        batch = 2 * 2 * (n_dev // pipe)
        x = rng.standard_normal((batch, 16, 16, 3)).astype(np.float32)
        y = rng.integers(0, 10, batch).astype(np.int32)
        crit = nn.CrossEntropyCriterion()
        opt = Optimizer(model,
                        array_dataset(x, y) >> SampleToMiniBatch(batch),
                        crit, optim.SGD(learning_rate=0.05),
                        strategy="pp", mesh=mesh, n_microbatches=2)
    else:
        axis = {"tp": "model", "pp": "pipe", "sp": "seq"}[args.strategy]
        # the model axis must divide the 4 attention heads / 4 blocks:
        # largest of 4/2/1 that fits the device count
        k = next(c for c in (4, 2, 1) if (n_dev // 2) % c == 0
                 and c <= n_dev // 2)
        mesh = jax.sharding.Mesh(
            np.asarray(jax.devices()[:2 * k]).reshape(2, k),
            ("data", axis))
        model = TransformerLM(
            256, 64, 4, num_layers=4, max_len=128,
            seq_axis_name="seq" if args.strategy == "sp" else None)
        x = rng.integers(0, 256, (8, 32)).astype(np.int32)
        y = rng.integers(0, 256, (8, 32)).astype(np.int32)
        crit = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion())
        kw = ({"n_microbatches": 2, "schedule": args.schedule}
              if args.strategy == "pp" else {})
        opt = Optimizer(model, array_dataset(x, y) >> SampleToMiniBatch(8),
                        crit, optim.SGD(learning_rate=0.05),
                        strategy=args.strategy, mesh=mesh, **kw)

    opt.set_end_when(Trigger.max_iteration(args.maxIteration))
    opt.optimize()
    print(f"{args.strategy} on {mesh.shape}: "
          f"final loss {opt.driver_state['loss']:.4f}")


if __name__ == "__main__":
    main()
