"""Run a LIVE Keras model on the bigdl-tpu backend.

Reference workflow: pyspark/bigdl/examples (keras integration) — build
and compile a model with real Keras, then hand it to
``with_bigdl_backend`` to train/serve on this stack.

    python examples/keras_backend.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def main(argv=None):
    import keras
    from keras import layers

    from bigdl.keras.backend import with_bigdl_backend

    km = keras.Sequential([
        layers.Input(shape=(20,)),
        layers.Dense(32, activation="relu"),
        layers.Dense(4, activation="softmax"),
    ])
    km.compile(optimizer=keras.optimizers.SGD(learning_rate=0.1),
               loss="categorical_crossentropy", metrics=["accuracy"])

    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 20)).astype(np.float32)
    w = rng.normal(size=(20, 4)).astype(np.float32)
    labels = (x @ w).argmax(-1)
    y = np.eye(4, dtype=np.float32)[labels]

    model = with_bigdl_backend(km)
    model.fit(x, y, batch_size=32, nb_epoch=5, validation_data=(x, y))
    acc = model.evaluate(x, y, batch_size=32)[0]
    print(f"accuracy on the bigdl backend: {acc:.3f}")
    assert acc > 0.5, "the separable synthetic task should be learnable"


if __name__ == "__main__":
    main()
