"""Train an imported TensorFlow graph end-to-end (Session training).

Reference: example/tensorflow (loads a GraphDef and either trains it with
BigDL's optimizer via BigDLSessionImpl -- utils/tf/Session.scala:105 -- or
runs transfer learning on imported frozen weights).

    python examples/tensorflow_training.py path/to/graph.pb logits
    python examples/tensorflow_training.py            # in-process demo

With no arguments it builds a small classifier GraphDef with the tensorflow
package (present in the test image), freezes it, re-imports it with
trainable variables, and fits it on a synthetic 3-class problem.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

def _demo_graph(path):
    import numpy as np
    import tensorflow as tf

    rng = np.random.default_rng(0)
    g = tf.Graph()
    with g.as_default():
        x = tf.compat.v1.placeholder(tf.float32, (None, 8), name="x")
        w1 = tf.compat.v1.Variable(
            rng.standard_normal((8, 32)).astype(np.float32) * 0.2, name="w1")
        b1 = tf.compat.v1.Variable(np.zeros(32, np.float32), name="b1")
        w2 = tf.compat.v1.Variable(
            rng.standard_normal((32, 3)).astype(np.float32) * 0.2, name="w2")
        h = tf.nn.relu(tf.matmul(x, w1) + b1)
        tf.identity(tf.matmul(h, w2), name="logits")
    with open(path, "wb") as f:
        f.write(g.as_graph_def().SerializeToString())
    return path


def main(argv=None):
    import numpy as np

    from bigdl_tpu import optim
    from bigdl_tpu.dataset import SampleToMiniBatch, array_dataset
    from bigdl_tpu.interop.tf_session import TFSession
    from bigdl_tpu.nn import CrossEntropyCriterion
    from bigdl_tpu.optim.trigger import Trigger
    from bigdl_tpu.optim.validation import Top1Accuracy

    p = argparse.ArgumentParser()
    p.add_argument("pb", nargs="?", help="frozen GraphDef path")
    p.add_argument("output", nargs="?", default="logits",
                   help="output node name")
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--epochs", type=int, default=30)
    args = p.parse_args(argv)

    if args.pb is None:
        args.pb = _demo_graph("/tmp/tf_training_demo.pb")
        print(f"no GraphDef given; built demo classifier at {args.pb}")

    # synthetic, linearly separable-ish 3-class data
    rng = np.random.default_rng(1)
    n = 512
    labels = rng.integers(0, 3, n)
    centers = rng.standard_normal((3, 8)) * 2.0
    feats = (centers[labels] + rng.standard_normal((n, 8))).astype(np.float32)

    sess = TFSession(args.pb, binary=True)
    print("placeholders:", sess.placeholders())

    dataset = array_dataset(feats, labels.astype(np.int32)) >> \
        SampleToMiniBatch(args.batch)
    model = sess.train(
        outputs=[args.output],
        dataset=dataset,
        optim_method=optim.Adam(learning_rate=0.01),
        criterion=CrossEntropyCriterion(),
        end_when=Trigger.max_epoch(args.epochs),
    )

    from bigdl_tpu.optim.predictor import evaluate
    acc = evaluate(model, dataset, [Top1Accuracy()])[0]
    print(f"train-set top-1 after {args.epochs} epochs: "
          f"{acc.result()[0]:.3f}")


if __name__ == "__main__":
    main()
