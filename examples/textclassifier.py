"""CNN text classifier.

Reference: example/textclassification (GloVe embeddings + temporal CNN over
news20).  Synthetic version: class-dependent token distributions, a
LookupTable embedding and Conv1D tower — same architecture shape, no
downloads.

    python examples/textclassifier.py --iters 25
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import argparse

import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--vocab", type=int, default=500)
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--classes", type=int, default=4)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--iters", type=int, default=25)
    args = p.parse_args()

    import bigdl_tpu.nn as nn
    from bigdl_tpu.nn import ops as nnops
    from bigdl_tpu import optim
    from bigdl_tpu.dataset import SampleToMiniBatch, array_dataset
    from bigdl_tpu.optim import LocalOptimizer, Top1Accuracy, Trigger

    rng = np.random.default_rng(1)
    n = 1024
    y = rng.integers(0, args.classes, n)
    # class c draws tokens near c * vocab/classes
    centers = (y * (args.vocab // args.classes))[:, None]
    x = (centers + rng.integers(0, args.vocab // args.classes,
                                (n, args.seq_len))) % args.vocab

    model = (nn.Sequential()
             .add(nn.LookupTable(args.vocab, 32))
             .add(nn.Conv1D(32, 64, 5))
             .add(nn.ReLU())
             .add(nnops.ReduceMax(1))
             .add(nn.Linear(64, args.classes))
             .add(nn.LogSoftMax()))

    ds = array_dataset(x, y) >> SampleToMiniBatch(args.batch)
    opt = LocalOptimizer(model, ds, nn.ClassNLLCriterion(),
                         optim.Adam(learning_rate=1e-3))
    opt.set_end_when(Trigger.max_iteration(args.iters))
    opt.set_validation(Trigger.every_epoch(),
                       array_dataset(x[:256], y[:256]) >>
                       SampleToMiniBatch(args.batch), [Top1Accuracy()])
    opt.optimize()
    print("final loss:", opt.driver_state["loss"])


if __name__ == "__main__":
    main()
