"""int8 post-training quantization (reference: example/mkldnn int8 +
AbstractModule.quantize -- BigQuant path; here int8 weights ride the MXU
via lax.dot_general with preferred_element_type, nn/quantized.py).

    python examples/quantize_int8.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

def main(argv=None):
    import time

    import numpy as np
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models.lenet import LeNet5
    from bigdl_tpu.nn.quantized import quantize

    model = LeNet5()
    x = jnp.asarray(np.random.rand(64, 28, 28).astype(np.float32))
    model.evaluate()
    y_fp = np.asarray(model.forward(x))

    qmodel = quantize(model)
    y_q = np.asarray(qmodel.forward(x))

    agree = (y_fp.argmax(1) == y_q.argmax(1)).mean()
    err = np.abs(y_fp - y_q).max()
    print(f"fp32 vs int8: top-1 agreement {agree:.2%}, max |diff| {err:.4f}")

    # micro-benchmark both paths
    for name, m in (("fp32", model), ("int8", qmodel)):
        fn = jax.jit(lambda p, s, xx, m=m: m.apply(p, s, xx)[0])
        fn(m._params, m._state, x).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(20):
            out = fn(m._params, m._state, x)
        out.block_until_ready()
        print(f"{name}: {(time.perf_counter() - t0) / 20 * 1e3:.2f} ms/batch")


if __name__ == "__main__":
    main()
