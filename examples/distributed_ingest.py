"""Spark-style partitioned ingest + the engine seam, end to end.

Reference analogue: the lenet Train example consuming
``DataSet.rdd(sc.parallelize(...))`` (models/lenet/Train.scala) — here
any partitioned source (a pyspark RDD when pyspark is installed, a
partition list otherwise) feeds per-host shards into DistriOptimizer,
and ``BIGDL_ENGINE_TYPE=ir`` routes the model through the IR engine
seam (``ConversionUtils.convert`` analogue).

Run:  python examples/distributed_ingest.py [--records N] [--engine ir]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--records", type=int, default=256)
    parser.add_argument("--batch", type=int, default=64)
    parser.add_argument("--epochs", type=int, default=2)
    parser.add_argument("--engine", default=None,
                        help="xla (default) | ir | unset=keep env")
    args = parser.parse_args(argv)
    if args.engine:
        os.environ["BIGDL_ENGINE_TYPE"] = args.engine

    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim
    from bigdl_tpu.dataset import (ListPartitionSource, PartitionedDataSet,
                                   Sample, SampleToMiniBatch)
    from bigdl_tpu.models.lenet import LeNet5
    from bigdl_tpu.optim import DistriOptimizer, Trigger
    from bigdl_tpu.utils.engine import Engine

    rng = np.random.default_rng(0)
    n = args.records
    samples = [Sample(x, y) for x, y in zip(
        rng.standard_normal((n, 28, 28, 1)).astype(np.float32),
        rng.integers(0, 10, n).astype(np.int32))]

    # a pyspark RDD works the same: PartitionedDataSet(sc.parallelize(
    # samples, 8)); partitions land on the host that consumes them
    parts = 8
    k = max(n // parts, 1)
    source = ListPartitionSource(
        [samples[i * k:(i + 1) * k] for i in range(parts)])

    train = PartitionedDataSet(source) >> SampleToMiniBatch(args.batch)
    model = LeNet5()
    opt = DistriOptimizer(model, train, nn.ClassNLLCriterion(),
                          optim.SGD(learning_rate=0.2, momentum=0.9,
                                    dampening=0.0),
                          mesh=Engine.build_mesh())
    opt.set_end_when(Trigger.max_epoch(args.epochs))
    opt.optimize()
    print(f"trained {opt.driver_state['neval'] - 1} steps over "
          f"{parts} partitions; final loss "
          f"{opt.driver_state['loss']:.4f} "
          f"(engine={os.environ.get('BIGDL_ENGINE_TYPE', 'xla')})")
    return opt.driver_state["loss"]


if __name__ == "__main__":
    main()
