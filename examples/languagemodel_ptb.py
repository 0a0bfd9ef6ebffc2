"""PTB-style LSTM language model.

Reference: example/languagemodel (PTBModel: 2-layer LSTM LM trained with
TimeDistributedCriterion(CrossEntropy)).  Synthetic corpus built from a
repeating-ngram distribution so the loss visibly drops without a download.

    python examples/languagemodel_ptb.py --iters 30
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import argparse

import numpy as np


def main():
    import jax
    p = argparse.ArgumentParser()
    p.add_argument("--vocab", type=int, default=200)
    p.add_argument("--seq-len", type=int, default=24)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--iters", type=int, default=30)
    args = p.parse_args()

    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim
    from bigdl_tpu.dataset import SampleToMiniBatch, array_dataset
    from bigdl_tpu.models.rnn import LSTMLanguageModel
    from bigdl_tpu.optim import LocalOptimizer, Trigger

    rng = np.random.default_rng(0)
    # markov-ish synthetic corpus: next token = (token * 7 + noise) % vocab
    n = 512
    toks = np.zeros((n, args.seq_len + 1), np.int64)
    toks[:, 0] = rng.integers(0, args.vocab, n)
    for t in range(args.seq_len):
        toks[:, t + 1] = (toks[:, t] * 7 + rng.integers(0, 3, n)) % args.vocab
    x, y = toks[:, :-1], toks[:, 1:]

    model = LSTMLanguageModel(args.vocab, 64, 128)
    ds = array_dataset(x, y) >> SampleToMiniBatch(args.batch)
    opt = LocalOptimizer(
        model, ds,
        nn.TimeDistributedCriterion(nn.ClassNLLCriterion()),
        optim.Adam(learning_rate=3e-3))
    opt.set_end_when(Trigger.max_iteration(args.iters))
    opt.optimize()
    print("final loss:", opt.driver_state["loss"])


if __name__ == "__main__":
    main()
