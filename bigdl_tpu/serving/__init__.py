"""High-throughput inference serving (the TPU-native redesign of the
reference's ``optim/PredictionService.scala`` instance pool).

- ``ServingEngine`` -- request coalescing behind a bounded queue with a
  ``max_batch_size`` / ``max_wait_ms`` deadline policy, bucketed shape
  padding (closed executable set, ``precompile()`` warms it), and
  sharded multi-device predict over a mesh's data axis with host-side
  round-robin as the fallback.
- ``BucketLadder`` -- the shape ladder (batch and, for sequence
  models, length buckets).
- ``ServingEngine(quantize=True, accuracy_gate=...)`` -- the int8
  serving path: the model's post-training-quantized twin serves on the
  same machinery, fp32 checkpoints quantize at ``refresh_params`` swap
  time, and an ``optim.validation.AccuracyDeltaGate`` rejects swaps
  whose fp32-vs-int8 divergence exceeds tolerance.

- ``ServingEngine.generate()`` (``serving/generation.py``) --
  autoregressive generation: paged-KV chunk-prefill/decode steps
  compiled once (pool donated in place), a slot-based
  continuous-batching scheduler (sequences join/leave a fixed
  decode-slot pool mid-flight with zero steady-state recompiles),
  per-request ``max_new_tokens``/EOS stops, and streaming
  ``GenerateFuture`` handles that yield tokens as decode ticks
  complete.
- ``PagedGenerateScheduler`` / ``BlockAllocator``
  (``serving/generation.py`` + ``serving/paging.py``) -- the KV
  cache: a fixed device block pool with host-side block tables,
  refcounted prefix sharing (content-hashed full blocks, LRU-cached,
  copy-on-write on divergence), chunked prefill interleaved with
  decode ticks, typed ``BlockPoolExhausted`` admission sheds, and
  in-jit ``SamplingParams`` temperature/top-k/top-p sampling
  (``serving/sampling.py``).
- ``ModelRegistry`` / ``RolloutController`` (``serving/deploy.py``) --
  the train->serve loop closed: versioned hot-swap with shadow/canary
  staged exposure, atomic cutover, automatic rollback to the retained
  previous version, durable ``kind: "deploy"`` audit events.
- ``ServingFleet`` (``serving/fleet.py``) -- N replicas (in-process
  engines and/or ``serving/worker.py`` subprocess workers) behind
  health-aware least-loaded routing with per-replica circuit breakers,
  deadline-budgeted retries, tail-latency hedging and load shedding;
  ``FleetSupervisor`` restarts dead workers from the registry's
  committed version, and the ``RolloutController`` performs ROLLING
  deploys across a fleet (drain -> gate -> commit -> undrain, one
  replica at a time).
- ``serving/transport.py`` -- the fleet's binary wire: versioned
  magic+type+length frames with typed refusals, zero-copy tensor
  frames (``np.frombuffer`` on receive, no array transits pickle),
  persistent ``WirePool`` connections with request-id multiplexing, a
  digest-authed handshake (``BIGDL_RUN_TOKEN``), and blockwise-int8
  weight distribution (``quantize_tree_for_wire``) for staging
  traffic.  The PR 14 pickle wire survives behind
  ``transport="pickle"``.

See docs/performance.md ("Inference serving", "Int8 inference",
"Fleet transport"),
docs/robustness.md ("Continuous deployment", "Serving fleets") and
docs/observability.md (extended ``kind: "inference"`` event schema,
serving-precision + version header stamp, the ``deploy``/``fleet``
event schemas).
"""

from bigdl_tpu.serving.buckets import BucketLadder
from bigdl_tpu.serving.deploy import (ModelRegistry, ModelVersion,
                                      RolloutController, snapshot_digest)
from bigdl_tpu.serving.engine import (EngineDraining, ServeFuture,
                                      ServingEngine)
from bigdl_tpu.serving.fleet import (CircuitBreaker, FleetOverloadedError,
                                     FleetSupervisor,
                                     FleetUnavailableError,
                                     InProcessReplica, ServingFleet,
                                     SubprocessReplica)
from bigdl_tpu.serving.generation import (GenerateFuture,
                                          PagedGenerateScheduler)
from bigdl_tpu.serving.paging import BlockAllocator, BlockPoolExhausted
from bigdl_tpu.serving.sampling import SamplingParams
from bigdl_tpu.serving.transport import (ReplicaCallError, WireAuthError,
                                         WireClient, WireError,
                                         WireFrameError, WirePool,
                                         WireProtocolError,
                                         WireVersionError)

__all__ = ["BlockAllocator", "BlockPoolExhausted", "BucketLadder",
           "CircuitBreaker", "EngineDraining", "FleetOverloadedError",
           "FleetSupervisor", "FleetUnavailableError", "GenerateFuture",
           "InProcessReplica", "ModelRegistry",
           "ModelVersion", "PagedGenerateScheduler", "ReplicaCallError",
           "RolloutController", "SamplingParams", "ServeFuture",
           "ServingEngine", "ServingFleet", "SubprocessReplica",
           "WireAuthError", "WireClient", "WireError", "WireFrameError",
           "WirePool", "WireProtocolError", "WireVersionError",
           "snapshot_digest"]
