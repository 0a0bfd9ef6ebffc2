"""Concurrent serving with PredictionService.

Reference: example/udfpredictor (SQL UDF serving) +
optim/PredictionService.scala:56 (thread-safe model-instance pool).  Here a
thread pool fires concurrent single-record predictions against the service
twice: the semaphore-serial baseline, then the coalescing engine
(``coalesce=True`` -- concurrent requests share one padded, bucketed,
precompiled device batch per dispatch tick; docs/performance.md,
"Inference serving").

    python examples/udf_predictor.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from concurrent.futures import ThreadPoolExecutor

import numpy as np


def main():
    import jax.numpy as jnp
    import bigdl_tpu.nn as nn
    from bigdl_tpu.optim.predictor import PredictionService
    from bigdl_tpu.models.lenet import LeNet5

    model = LeNet5()
    model.forward(jnp.zeros((1, 28, 28, 1)))   # build
    model.evaluate()
    service = PredictionService(model, num_threads=4)

    rng = np.random.default_rng(0)
    # PER-SAMPLE activities: the service adds the batch axis (serial
    # path) or stacks requests into one tick (coalesced path) -- a
    # pre-batched (1, 28, 28, 1) query would stack to a rank the
    # precompile()-warmed executables never see
    queries = [jnp.asarray(rng.normal(size=(28, 28, 1)), jnp.float32)
               for _ in range(32)]
    with ThreadPoolExecutor(8) as pool:
        results = list(pool.map(service.predict, queries))
    preds = [int(np.asarray(r).argmax()) for r in results]
    print("served", len(preds), "predictions:", preds[:10])

    # the high-throughput path: same request surface, but concurrent
    # callers coalesce into one bucketed device batch per dispatch tick
    with PredictionService(model, coalesce=True, max_batch_size=8,
                           max_wait_ms=2.0) as coalesced:
        coalesced.precompile()             # warm the bucket ladder
        with ThreadPoolExecutor(8) as pool:
            results2 = list(pool.map(coalesced.predict, queries))
    # cross-bucket logits agree to float rounding (different executable
    # shapes pick different XLA blockings), so compare logits, not a
    # potentially tie-broken argmax
    assert all(np.allclose(a, b, rtol=1e-5, atol=1e-6)
               for a, b in zip(results, results2))
    preds2 = [int(np.asarray(r).argmax()) for r in results2]
    print("coalesced serving agrees:", preds2[:10])


if __name__ == "__main__":
    main()
