"""Subprocess serving worker for the distributed-tracing acceptance
test (tests/test_tracing.py).

Boots a tiny ``TransformerLM`` behind the ``serving/worker.py`` socket
protocol with a worker-local ``StepTelemetry`` whose ``traces.jsonl``
sink is the cross-process half of the trace story: engine spans for
requests whose sampled context crossed the wire land HERE, and
``tools/trace_report.py`` stitches them back to the driver's fleet
spans by trace_id.  The port-file handshake is atomic (written only
after precompile, like tools/serve_fleet.py), so a returned worker is
ready to serve.  ``--slowMs`` delays every predict -- the lever that
holds a request in flight long enough for the driver to SIGKILL this
process mid-request (the trace-continuity-under-failure drill).
"""

import argparse
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def build_model():
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.nn.attention import TransformerLM

    # tiny and single-layer: the whole compile budget of a 3-worker
    # spawn must stay inside the tier-1 clock
    m = TransformerLM(vocab_size=32, hidden_size=16, num_heads=4,
                      num_layers=1, max_len=32)
    m.build(jax.ShapeDtypeStruct((2, 8), jnp.int32),
            rng=jax.random.PRNGKey(0))
    return m


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--replicaId", type=int, required=True)
    ap.add_argument("--portFile", required=True)
    ap.add_argument("--slowMs", type=float, default=0.0)
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    from bigdl_tpu.observability import StepTelemetry
    from bigdl_tpu.serving import BucketLadder, ServingEngine
    from bigdl_tpu.serving.worker import ReplicaServer

    tel = StepTelemetry(
        os.path.join(args.out, f"worker_{args.replicaId}"),
        run_name=f"worker_{args.replicaId}", trace=False)
    model = build_model()
    eng = ServingEngine(model, max_batch_size=2, max_wait_ms=1.0,
                        ladder=BucketLadder(2, min_size=1),
                        telemetry=tel, decode_slots=2,
                        decode_max_len=32)
    example = np.zeros((8,), np.int32)
    eng.precompile(example_feature=example)

    srv = ReplicaServer(eng, port=0)
    if args.slowMs > 0:
        # hold every predict in flight: the SIGKILL drill needs a
        # window where the request is accepted but unanswered
        inner = srv._op_predict

        def slow_predict(req):
            time.sleep(args.slowMs / 1e3)
            return inner(req)

        srv._op_predict = slow_predict
    tmp = args.portFile + ".tmp"
    with open(tmp, "w") as f:       # atomic: a half-written port file
        f.write(str(srv.port))      # must never be readable
    os.replace(tmp, args.portFile)
    print(f"[trace-worker {args.replicaId}] port {srv.port}",
          file=sys.stderr, flush=True)
    srv.serve_forever()
    return 0


if __name__ == "__main__":
    sys.exit(main())
