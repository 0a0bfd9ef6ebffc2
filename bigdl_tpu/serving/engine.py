"""Dynamic-batched inference serving: request coalescing over a
bucketed, precompiled eval step.

Reference: optim/PredictionService.scala:56 keeps an instance pool of
model clones behind a blocking queue -- concurrency there means more
JVM threads each running their own forward.  On TPU one compiled
program already saturates the chip, so concurrency is won by BATCHING:
concurrent callers submit single activities to a bounded queue, a
dispatcher thread drains it under a ``max_batch_size`` /
``max_wait_ms`` deadline policy, and every tick runs ONE padded device
batch instead of N serialized batch-1 dispatches.  The pad target
comes from a bucket ladder (``buckets.BucketLadder``) so the compiled
executable cache has a small, closed, warmable key set -- steady-state
serving performs zero XLA compiles (``precompile``).

Three device layouts behind one engine:

- single device (default): the model's own placement, like Predictor;
- sharded (``mesh=``): the batch axis splits over the mesh's data axis
  (``parallel/zero.stage_batch_global`` -- the dp driver's staging
  path) with params replicated once, so one tick runs data-parallel
  over every chip;
- host-side round-robin (``round_robin=True``): the fallback when no
  mesh program is wanted -- whole ticks rotate across local devices
  with per-device weight replicas, the literal analogue of the
  reference's cloned-instance pool.

Every tick emits a ``kind: "inference"`` telemetry event extended with
queue depth, bucket id, batch fill fraction, pad waste and the
per-request latencies (``tools/obs_report.py`` "Serving" section).
"""

import collections
import logging
import threading
import time
from concurrent.futures import Future, TimeoutError as FutureTimeoutError
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.dataset.minibatch import PaddingParam, Sample, \
    samples_to_minibatch
from bigdl_tpu.observability.spans import span
from bigdl_tpu.optim.validation import compiled_eval_step
from bigdl_tpu.serving.buckets import (BucketLadder, ladder_or_default,
                                       pad_batch_axis, pad_length_axis,
                                       slice_batch_axis, walk_length_leaves)

log = logging.getLogger("bigdl_tpu.serving")


class EngineDraining(RuntimeError):
    """``submit()`` refused because the engine is draining: it stopped
    ADMITTING requests (``drain()``) while the dispatcher finishes the
    queue it already accepted.  The typed error lets a fleet router
    distinguish "this replica is mid-deploy, pick another" from a real
    serving failure -- a drained replica is healthy, just closed for
    business until ``undrain()``."""


class ServeFuture(Future):
    """Per-request handle: ``result(timeout)`` plus, once served, the
    ``bucket`` the request rode in and its end-to-end ``latency_s``."""

    def __init__(self):
        super().__init__()
        self.bucket: Optional[int] = None
        self.latency_s: Optional[float] = None
        self._t_submit = time.perf_counter()
        self._trace = None           # sampled TraceContext, or None


# --------------------------------------------------------------------------- #
# Eval backends: where a tick's padded batch actually runs.
# --------------------------------------------------------------------------- #

class _LocalEval:
    """Default single-device layout -- the model's own placement."""

    kind = "local"
    align = 1
    replicas = 1

    def __init__(self, model, compute_dtype=None):
        self.model = model
        self.step = compiled_eval_step(model, compute_dtype)

    def stage(self, params, mstate):
        # uncommitted jnp leaves, like init-time weights: a numpy tree
        # would key the jit cache differently and force one spurious
        # recompile on the first tick that serves it
        import jax.numpy as jnp

        return (jax.tree.map(jnp.asarray, params), mstate)

    def install(self, staged):
        # the local layout serves from the model's own tree (the engine
        # points the model at the staged params); nothing device-side
        pass

    def capture(self):
        return (self.model.weights(), self.model.state())

    def eval(self, x, tick=0, weights=None):
        if weights is not None:
            return self.step(weights[0], weights[1], x)
        params, mstate = self.model.weights(), self.model.state()
        return self.step(params, mstate, x)

    def precompile(self, sample_spec, buckets):
        params, mstate = self.model.weights(), self.model.state()
        return self.step.precompile(params, mstate, sample_spec, buckets)


class _ShardedEval:
    """Data-parallel eval over the mesh's data axis: the batch axis is
    split across devices (the dp driver's ``_shard_batch`` staging
    path, ``parallel/zero.stage_batch_global``), params/state are
    replicated ON DEVICE once at construction (call ``refresh_params``
    after mutating the model's weights)."""

    kind = "sharded"

    def __init__(self, model, mesh, axis="data", compute_dtype=None):
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.model = model
        self.mesh = mesh
        self.axis = axis
        self.align = int(mesh.shape[axis])
        self.replicas = int(mesh.shape[axis])
        self.step = compiled_eval_step(model, compute_dtype)
        self._batch_sharding = NamedSharding(mesh, P(axis))
        self._rep = NamedSharding(mesh, P())
        self.refresh_params()

    def refresh_params(self):
        self.install(self.stage(self.model.weights(),
                                self.model.state()))

    def stage(self, params, mstate):
        staged_p = jax.device_put(params, self._rep)
        staged_m = mstate if not jax.tree.leaves(mstate) else \
            jax.device_put(mstate, self._rep)
        return (staged_p, staged_m)

    def install(self, staged):
        # one tuple unpack = the atomic pointer swap a cutover rides on
        self._params, self._mstate = staged

    def capture(self):
        return (self._params, self._mstate)

    def _stage(self, x):
        from bigdl_tpu.parallel.zero import stage_batch_global

        return stage_batch_global(x, self._batch_sharding)

    def eval(self, x, tick=0, weights=None):
        params, mstate = weights if weights is not None \
            else (self._params, self._mstate)
        return self.step(params, mstate, self._stage(x))

    def precompile(self, sample_spec, buckets):
        return self.step.precompile(self._params, self._mstate, sample_spec,
                                    buckets, stage=self._stage)


class _RoundRobinEval:
    """Whole ticks rotate across local devices, each holding its own
    weight replica -- the host-side fallback when no mesh program is
    available, and the literal TPU analogue of the reference's pooled
    model clones (PredictionService.scala:64-77: N instances, each
    serving whole requests)."""

    kind = "round_robin"
    align = 1

    def __init__(self, model, devices=None, compute_dtype=None):
        self.model = model
        self.devices = list(devices) if devices else jax.local_devices()
        self.replicas = len(self.devices)
        self.step = compiled_eval_step(model, compute_dtype)
        self.refresh_params()

    def refresh_params(self):
        # per-device replicas (the "clone pool"), remade on demand
        self.install(self.stage(self.model.weights(),
                                self.model.state()))

    def stage(self, params, mstate):
        return [jax.device_put((params, mstate), d) for d in self.devices]

    def install(self, staged):
        self._replicas = staged        # one list swap = atomic cutover

    def capture(self):
        return self._replicas

    def eval(self, x, tick=0, weights=None):
        dev = self.devices[tick % len(self.devices)]
        replicas = weights if weights is not None else self._replicas
        params, mstate = replicas[tick % len(self.devices)]
        return self.step(params, mstate, jax.device_put(x, dev))

    def precompile(self, sample_spec, buckets):
        # jax keys executables on placement too: warm every device
        total = 0
        for dev, (params, mstate) in zip(self.devices, self._replicas):
            total += self.step.precompile(
                params, mstate, sample_spec, buckets,
                stage=lambda t, _d=dev: jax.device_put(t, _d))
        return total


# --------------------------------------------------------------------------- #
# The engine.
# --------------------------------------------------------------------------- #


def _tree_spec(tree):
    """(labels, per-leaf (shape, dtype), treedef) of a weight tree --
    the structural contract refresh_params validates against.  Reads
    shape/dtype ATTRIBUTES only: no ``np.asarray`` on the leaves, so
    validating gigabytes of device-resident params moves zero bytes."""
    from jax.tree_util import keystr, tree_flatten_with_path

    leaves_with_path, treedef = tree_flatten_with_path(tree)
    labels = [keystr(p) for p, _ in leaves_with_path]

    def dtype_of(l):
        dt = getattr(l, "dtype", None)
        return str(dt if dt is not None else np.result_type(l))

    specs = [(tuple(np.shape(l)), dtype_of(l))
             for _, l in leaves_with_path]
    return labels, specs, treedef


def _spec_mismatch(expect, got, what):
    """First structural/shape/dtype difference between two _tree_spec
    results as a human-readable reason, or None when they match.

    Always names the FIRST mismatched tree path with both sides'
    shapes/dtypes (where each side has that leaf at all): the
    half-written-checkpoint drill's operator needs to know WHICH plane
    broke, not just that one did (docs/robustness.md, "Serving
    survives a bad refresh")."""
    e_labels, e_specs, e_def = expect
    g_labels, g_specs, g_def = got
    if e_def != g_def:
        e_map = dict(zip(e_labels, e_specs))
        g_map = dict(zip(g_labels, g_specs))
        for label in e_labels:          # first contract leaf not offered
            if label not in g_map:
                e = e_map[label]
                return (f"{what} tree structure differs at {label}: "
                        f"serving contract expects shape {e[0]} dtype "
                        f"{e[1]}, leaf missing from the incoming tree")
        for label in g_labels:          # first offered leaf not expected
            if label not in e_map:
                g = g_map[label]
                return (f"{what} tree structure differs at {label}: "
                        f"incoming tree carries an unexpected leaf "
                        f"(shape {g[0]} dtype {g[1]}) the serving "
                        f"contract has no plane for")
        for label, e in zip(e_labels, e_specs):   # same leaves, reshaped
            g = g_map.get(label)
            if g is not None and e != g:
                return (f"{what} leaf {label}: expected shape {e[0]} "
                        f"dtype {e[1]}, got shape {g[0]} dtype {g[1]}")
        return (f"{what} tree structure differs: same leaves, "
                f"different nesting (first leaf "
                f"{e_labels[0] if e_labels else '<empty tree>'})")
    for label, e, g in zip(e_labels, e_specs, g_specs):
        if e != g:
            return (f"{what} leaf {label}: expected shape {e[0]} "
                    f"dtype {e[1]}, got shape {g[0]} dtype {g[1]}")
    return None


class ServingEngine:
    """Coalescing, bucketed, (optionally) sharded inference server.

    >>> eng = ServingEngine(model, max_batch_size=32, max_wait_ms=2.0)
    >>> eng.precompile()                  # warm the whole bucket ladder
    >>> y = eng.predict(feature)          # blocking single request
    >>> fut = eng.submit(feature)         # or async; fut.result()

    Deadline policy: a tick dispatches as soon as ``max_batch_size``
    requests are pending, or when the OLDEST pending request has waited
    ``max_wait_ms`` -- the knob trading batch fill (throughput) against
    added latency at low offered load (docs/performance.md, "Inference
    serving").  ``queue_capacity`` bounds pending requests; a full
    queue back-pressures ``submit`` instead of growing without bound.

    A tick that raises (poisoned input, device error) fails only that
    tick's requests -- the exception is set on each of its futures (so
    every affected caller sees it) and the dispatcher keeps serving
    subsequent traffic.

    ``quantize=True`` serves the model's int8 post-training-quantized
    twin (``nn.quantized.quantize_model``) instead of the fp32 original
    on the SAME layout/ladder/precompile machinery: ~4x smaller device
    weights, int8 MXU matmuls, zero steady-state recompiles.  The fp32
    model object stays untouched and remains the refresh contract:
    ``refresh_params`` takes fp32 checkpoints and quantizes them at swap
    time (on the sharded mesh the staged replica tree is the int8
    payload+scales -- the blockwise-int8 wire stance of the PR 4
    collectives applied to the weight gather, EQuARX-style -- with the
    moved bytes recorded on the ``param_refresh`` audit event).  Pass a
    callable to use it as the quantizer's allow/deny ``select``
    predicate.  ``accuracy_gate`` (an
    ``optim.validation.AccuracyDeltaGate``, or a dict of its kwargs)
    compares fp32-vs-int8 outputs on a held-out batch at construction
    AND at every refresh: a swap whose divergence exceeds the tolerance
    is rejected through the ``param_refresh`` rejected-with-reason path
    and the engine keeps serving its current weights.

    ``kv_cache_dtype="int8"`` stores the paged generation pool as int8
    payloads plus per-(position, head) fp32 scales (~3.6x less KV
    memory at head_dim 32; the ledger's ``kv_cache`` split reports the
    real narrow bytes).  ``speculative=k`` decodes with the int8 twin
    drafting ``k`` tokens per tick and ONE fp32 forward verifying them
    -- the output stream is bit-identical to fp32-only decoding
    (greedy and seeded sampling both), it's just emitted 1..k+1 tokens
    per verify step.  ``accuracy_gate`` composes with ``speculative``
    to gate the drafter the same way it gates an int8 serving twin
    (docs/performance.md, "Generation serving").
    """

    def __init__(self, model, max_batch_size: int = 32,
                 max_wait_ms: float = 2.0, queue_capacity: int = 1024,
                 ladder: Optional[BucketLadder] = None,
                 length_ladder: Optional[BucketLadder] = None,
                 length_select=None,
                 feature_padding: Optional[PaddingParam] = None,
                 compute_dtype=None, mesh=None, axis: str = "data",
                 round_robin: bool = False, telemetry=None,
                 max_executables: Optional[int] = None,
                 quantize=False, accuracy_gate=None,
                 decode_slots: Optional[int] = None,
                 decode_max_len: Optional[int] = None,
                 kv_cache: str = "paged", kv_block_size: int = 16,
                 kv_blocks: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 kv_cache_dtype: str = "fp32",
                 speculative: int = 0):
        if not model.is_built():
            raise ValueError("build the model (or train it) before serving")
        if max_batch_size < 1:
            raise ValueError(f"max_batch_size must be >= 1, got "
                             f"{max_batch_size}")
        if queue_capacity < 1:
            # 0 would make the first submit() wait on _not_full forever
            raise ValueError(f"queue_capacity must be >= 1, got "
                             f"{queue_capacity}")
        self.model = model
        self._compute_dtype = compute_dtype
        # the serving contract frozen at construction: refresh_params
        # validates any later weight swap against THIS tree structure +
        # shapes BEFORE touching the device caches, so a half-written
        # checkpoint mid-retrain raises cleanly and the engine keeps
        # serving the old weights (docs/robustness.md).  The contract is
        # always the FP32 tree -- a quantized engine still swaps fp32
        # checkpoints in, quantizing them itself at staging time.
        self._params_spec = _tree_spec(model.weights())
        self._mstate_spec = _tree_spec(model.state())
        self._quantized = bool(quantize)
        self._qselect = quantize if callable(quantize) else None
        if speculative < 0:
            raise ValueError(
                f"speculative must be >= 0 (draft tokens per verify "
                f"step; 0 disables), got {speculative}")
        self.speculative = int(speculative)
        if accuracy_gate is not None and not self._quantized \
                and not self.speculative:
            raise ValueError(
                "accuracy_gate compares the fp32 model against its int8 "
                "twin; it needs quantize=... (int8 serving) or "
                "speculative=k (int8 drafter) to have a candidate to "
                "gate")
        self._gate = self._make_gate(accuracy_gate)
        if self._quantized or self.speculative:
            from bigdl_tpu.nn.quantized import quantize_model

            # the int8 twin: same module tree, quantized params, its
            # own compiled-step cache; self.model stays fp32.  On a
            # quantized engine it SERVES; with speculative=k it DRAFTS
            # (verification always runs the fp32 original, so the
            # generated stream stays bit-identical to fp32 decoding)
            self._qmodel, _ = quantize_model(model, select=self._qselect)
        else:
            self._qmodel = None
        serve_model = self._qmodel if self._quantized else model
        if mesh is not None and int(mesh.shape[axis]) > 1:
            self._backend = _ShardedEval(serve_model, mesh, axis,
                                         compute_dtype)
        elif round_robin and len(jax.local_devices()) > 1:
            self._backend = _RoundRobinEval(serve_model,
                                            compute_dtype=compute_dtype)
        else:
            self._backend = _LocalEval(serve_model, compute_dtype)
        align = self._backend.align
        self.max_batch_size = -(-int(max_batch_size) // align) * align
        self.max_wait_s = float(max_wait_ms) / 1e3
        self.queue_capacity = int(queue_capacity)
        self.ladder = ladder_or_default(ladder, self.max_batch_size, align)
        if self.ladder.max < self.max_batch_size:
            self.ladder.add(self.max_batch_size)
        if self.ladder.min > self.max_batch_size:
            raise ValueError(
                f"ladder's smallest rung {self.ladder.min} exceeds "
                f"max_batch_size {self.max_batch_size}: a tick can never "
                f"hold that many requests, so every dispatch would pad "
                f"past the largest batch it can ever fill")
        # copied like the batch ladder (ladder_or_default): over-max
        # lengths grow this ladder under traffic, and that growth must
        # not leak into a ladder the caller shares with other engines
        self.length_ladder = None if length_ladder is None \
            else length_ladder.copy()
        self.length_select = length_select
        self.feature_padding = feature_padding
        self.telemetry = telemetry
        self._explicit_bound = max_executables is not None
        if self._explicit_bound:
            # the bound lives on the per-(model, dtype) compiled step,
            # which validate()/Predictor/other engines on the same model
            # share -- it governs that one shared cache (last writer
            # wins), because the executable count being bounded IS the
            # shared jit cache's
            self._backend.step.max_executables = max_executables
        else:
            self._fit_bound(len(self.ladder))
        self._pending = collections.deque()
        self._lock = threading.Lock()
        self._not_empty = threading.Condition(self._lock)
        self._not_full = threading.Condition(self._lock)
        self._running = True
        self._tick = 0
        self._served = 0
        # drain seam (serving/fleet.py rolling deploys): _draining stops
        # ADMISSION only; the dispatcher keeps ticking until every
        # already-accepted future is resolved.  _in_tick counts requests
        # claimed off the queue but not yet resolved, so drain() can
        # wait for true quiescence (queue empty AND no tick in flight).
        self._draining = False
        self._in_tick = 0
        self._idle = threading.Condition(self._lock)
        self._gate_detail = None
        # staged-exposure seams (serving/deploy.py): a canary routes a
        # traffic fraction's ticks onto a staged candidate's weights; a
        # shadow mirrors a fraction of ticks (batch + live outputs) to
        # an off-request-path observer.  Written by the rollout
        # controller's thread, read once per tick by the dispatcher --
        # single-attribute assignment keeps each swap atomic.
        self._canary = None           # (staged handle, fraction, version)
        self._canary_acc = 0.0
        self._canary_ticks = 0        # ticks served on the candidate
        self._canary_rows = 0         # real rows served on the candidate
        self._canary_failures = 0     # candidate ticks that raised
        self._shadow = None           # (fn, fraction)
        self._shadow_acc = 0.0
        self._version_info = None     # {"version", "digest"} when deployed
        # autoregressive generation (serving/generation.py): a slot
        # pool this size decodes with KV caches behind ``generate()``.
        # None = AUTO (8 slots when the served model has a decode mode,
        # off otherwise); 0 disables explicitly.  The scheduler is
        # built lazily on first use, but unlike the first paged-cache
        # cut, precompile() warms generation whenever the model has
        # a decode mode (the zero-steady-state-recompile contract: the
        # first generate() after precompile must not pay compiles,
        # whether or not decode_slots was spelled out).
        if decode_slots is None:
            decode_slots = 8 if hasattr(model, "init_paged_cache") else 0
        self.decode_slots = int(decode_slots)
        self.decode_max_len = decode_max_len
        # the generation cache is the paged block pool (serving/paging.py:
        # prefix sharing, chunked prefill, in-jit sampling), the one KV
        # cache there is; ``kv_cache`` names it and takes no other value
        if kv_cache != "paged":
            raise ValueError(
                f"kv_cache must be 'paged', the one KV cache (the "
                f"contiguous one was removed), got {kv_cache!r}")
        if kv_cache_dtype not in ("fp32", "int8"):
            raise ValueError(
                f"kv_cache_dtype must be 'fp32' or 'int8', got "
                f"{kv_cache_dtype!r}")
        if (kv_cache_dtype != "fp32" or self.speculative) \
                and not hasattr(model, "init_paged_cache"):
            raise TypeError(
                f"{type(model).__name__} has no init_paged_cache(): "
                f"int8 KV blocks and speculative decoding need the "
                f"paged decode mode (TransformerLM has one)")
        self.kv_cache_dtype = kv_cache_dtype
        self.kv_block_size = int(kv_block_size)
        self.kv_blocks = kv_blocks
        self.prefill_chunk = prefill_chunk
        self._gen = None
        self._gen_lock = threading.Lock()
        self._memory_ledger = None
        if self._gate is not None:
            # the INITIAL quantization must clear the same bar a later
            # hot-swap would: a model this quantizer damages beyond
            # tolerance never starts serving int8 at all
            ok, detail = self._check_accuracy(model.weights(),
                                              model.state())
            self._gate_detail = detail
            if not ok:
                self._record_refresh("rejected", detail.get("reason"),
                                     accuracy_gate=detail)
                raise ValueError(
                    f"accuracy gate refused the initial int8 "
                    f"quantization ({detail.get('reason')}); serve fp32 "
                    f"or relax the gate tolerances")
        self._stamp_serving_info()
        self._dispatcher = threading.Thread(
            target=self._loop, name="bigdl-serving-dispatcher", daemon=True)
        self._dispatcher.start()

    # ----- request surface -------------------------------------------------- #
    def submit(self, feature, timeout: Optional[float] = None,
               trace=None) -> ServeFuture:
        """Enqueue one activity (array tree or ``Sample``); returns a
        future.  Blocks when ``queue_capacity`` requests are pending;
        with ``timeout``, a queue still full after that many seconds
        raises ``concurrent.futures.TimeoutError`` instead of waiting
        for the backlog to drain.  ``trace`` (an already-sampled
        ``TraceContext``) rides the future: the serving tick records
        queue-wait/device spans for it (docs/observability.md,
        "Request tracing")."""
        fut = ServeFuture()
        fut._trace = trace
        deadline = None if timeout is None \
            else time.perf_counter() + timeout
        with self._lock:
            if not self._running:
                raise RuntimeError("ServingEngine is closed")
            if self._draining:
                raise EngineDraining(
                    "ServingEngine is draining (admission closed until "
                    "undrain()); already-accepted requests will still "
                    "be served")
            while self._running and not self._draining and \
                    len(self._pending) >= self.queue_capacity:
                remaining = None if deadline is None \
                    else deadline - time.perf_counter()
                if remaining is not None and remaining <= 0:
                    raise FutureTimeoutError(
                        f"submit timed out after {timeout}s: queue full "
                        f"({self.queue_capacity} requests pending)")
                self._not_full.wait(timeout=remaining)
            if not self._running:
                raise RuntimeError("ServingEngine is closed")
            if self._draining:
                # drain began while this caller waited on a full queue:
                # admission is closed now, whatever space opened up
                raise EngineDraining(
                    "ServingEngine began draining while this submit "
                    "waited for queue space; request not accepted")
            self._pending.append((feature, fut))
            self._not_empty.notify()
        return fut

    def predict(self, feature, timeout: Optional[float] = None,
                trace=None):
        """Blocking single-request predict (the PredictionService
        surface): submit, wait, return this request's output rows.
        ``timeout`` bounds the WHOLE call -- admission into a full
        queue spends from the same budget as waiting for the result.
        A timed-out request is cancelled: if still pending, its tick
        drops it (a timeout/retry loop must not fill the queue with
        zombie requests nobody will read)."""
        t0 = time.perf_counter()
        fut = self.submit(feature, timeout=timeout, trace=trace)
        remaining = None if timeout is None \
            else max(0.0, timeout - (time.perf_counter() - t0))
        try:
            return fut.result(remaining)
        except FutureTimeoutError:
            self._abandon(fut)
            raise

    def predict_many(self, features, timeout: Optional[float] = None):
        """Submit a burst and wait for every result.  Like ``predict``,
        ``timeout`` bounds the WHOLE call (queue admission of each
        request and all the result waits draw down one shared budget --
        N requests never wait N times the timeout) and a timeout
        cancels every still-pending request of the burst."""
        deadline = None if timeout is None \
            else time.perf_counter() + timeout

        def remaining():
            return None if deadline is None \
                else max(0.0, deadline - time.perf_counter())

        futs: List[ServeFuture] = []
        try:
            for f in features:
                futs.append(self.submit(f, timeout=remaining()))
            return [f.result(remaining()) for f in futs]
        except FutureTimeoutError:
            for f in futs:
                self._abandon(f)     # no-op on already-served futures
            raise

    def _abandon(self, fut: ServeFuture):
        """Cancel a timed-out request AND free its queue slot now: a
        cancelled entry left in ``_pending`` would keep counting toward
        capacity / tick fill / the oldest-request deadline until a tick
        drained it, blocking the very retry the caller is about to
        make.  A ``GenerateFuture`` routes to ITS queue -- the
        generation scheduler's, not the predict deque."""
        from bigdl_tpu.serving.generation import GenerateFuture

        if isinstance(fut, GenerateFuture):
            if self._gen is not None:
                self._gen._abandon(fut)
            return
        if not fut.cancel():         # already claimed by a tick (or done)
            return
        with self._lock:
            for entry in self._pending:
                if entry[1] is fut:
                    self._pending.remove(entry)
                    self._not_full.notify()
                    break

    # ----- autoregressive generation (serving/generation.py) ----------------- #
    def _generation(self):
        """The lazily-built generation scheduler (slot pool + compiled
        prefill/decode steps).  Serves the SAME model the eval path
        serves: on a quantized engine that is the int8 twin, so
        generation rides the identical ``AccuracyDeltaGate``-guarded
        weight set every refresh_params swap validates."""
        if self._gen is None:
            with self._gen_lock:
                if self._gen is None:
                    if self.decode_slots < 1:
                        raise ValueError(
                            "generation is disabled on this engine "
                            "(decode_slots=0); construct with "
                            "decode_slots >= 1")
                    from bigdl_tpu.serving.generation import (
                        PagedGenerateScheduler, SpeculativeScheduler)

                    serve_model = self._qmodel if self._quantized \
                        else self.model
                    cache_dtype = {"fp32": jnp.float32,
                                   "int8": jnp.int8}[self.kv_cache_dtype]
                    paged_kw = dict(
                        slots=self.decode_slots,
                        max_len=self.decode_max_len,
                        queue_capacity=self.queue_capacity,
                        cache_dtype=cache_dtype,
                        telemetry=self.telemetry,
                        admission_check=self._gen_admission_check,
                        exhausted_hook=self._on_pool_exhausted,
                        block_size=self.kv_block_size,
                        num_blocks=self.kv_blocks,
                        prefill_chunk=self.prefill_chunk)
                    if self.speculative:
                        # verifier = the fp32 original (the stream must
                        # stay bit-identical to fp32 decoding), drafter
                        # = the gated int8 twin
                        self._gen = SpeculativeScheduler(
                            self.model, self._qmodel,
                            spec_k=self.speculative, **paged_kw)
                    else:
                        self._gen = PagedGenerateScheduler(
                            serve_model, **paged_kw)
        return self._gen

    def _gen_admission_check(self):
        """Runs under the SCHEDULER's lock right before a generation
        enqueues: the engine-side lifecycle re-check that closes the
        race where drain() observes an idle scheduler between
        generate()'s early check and the actual enqueue."""
        if not self._running:
            raise RuntimeError("ServingEngine is closed")
        if self._draining:
            raise EngineDraining(
                "ServingEngine began draining while this generate "
                "was being admitted; request not accepted")

    def generate(self, prompt, max_new_tokens: int = 16,
                 eos_id: Optional[int] = None,
                 timeout: Optional[float] = None, trace=None,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, seed: Optional[int] = None):
        """Autoregressive generation: enqueue a prompt (1-D token ids)
        onto the continuous-batching decode scheduler; returns a
        streaming ``GenerateFuture`` (``.stream()`` yields tokens as
        decode ticks complete, ``.result()`` returns the full list).
        Generation stops at ``eos_id`` (included in the output) or
        after ``max_new_tokens``.

        Decoding is greedy by default; ``temperature > 0`` samples
        in-jit (optionally truncated by ``top_k`` / nucleus ``top_p``),
        with an explicit ``seed`` making the stream deterministic per
        (seed, prompt).

        Admission honors the engine's lifecycle exactly like
        ``submit``: a draining engine raises ``EngineDraining``, a
        closed one ``RuntimeError``; ``timeout`` bounds the wait for a
        queue slot."""
        with self._lock:
            if not self._running:
                raise RuntimeError("ServingEngine is closed")
            if self._draining:
                raise EngineDraining(
                    "ServingEngine is draining (admission closed until "
                    "undrain()); in-flight generations still complete")
        sampling = None
        if temperature > 0.0 or top_k > 0 or top_p < 1.0 \
                or seed is not None:
            from bigdl_tpu.serving.sampling import SamplingParams

            sampling = SamplingParams(temperature=temperature,
                                      top_k=top_k, top_p=top_p,
                                      seed=seed)
        return self._generation().submit(prompt,
                                         max_new_tokens=max_new_tokens,
                                         eos_id=eos_id, timeout=timeout,
                                         trace=trace, sampling=sampling)

    def predict_at(self, feature, bucket: int):
        """UNBATCHED reference predict: this one request, padded to
        ``bucket``, evaluated synchronously outside the queue.  Within
        one bucket shape XLA's reduction order is fixed and eval-mode
        rows are independent, so this is bit-exact to the same request
        served in a coalesced tick of the same bucket (the
        identical-outputs witness of the tests)."""
        x = self._form_batch([feature], bucket)
        y = self._backend.eval(x, tick=0)
        return jax.tree.map(lambda a: np.asarray(a)[0], y)

    def _fit_bound(self, n_buckets):
        """Raise the shared step's eviction-free executable bound to fit
        this engine's closed shape set (batch rungs x length rungs, x
        per-device replicas for round-robin) plus headroom for
        validation's own batch shape -- the default bound is sized for a
        single ladder and would cry "shape leak" on a legitimately
        warmed larger one.  No-op when the caller set an explicit
        ``max_executables`` (their bound, their warnings)."""
        if self._explicit_bound:
            return
        combos = n_buckets * (len(self.length_ladder)
                              if self.length_ladder is not None else 1)
        if isinstance(self._backend, _RoundRobinEval):
            combos *= len(self._backend.devices)
        step = self._backend.step
        step.max_executables = max(step.max_executables, combos + 8)

    # ----- warmup ----------------------------------------------------------- #
    def _sample_spec(self, example_feature=None):
        if example_feature is not None:
            feat = example_feature.feature \
                if isinstance(example_feature, Sample) else example_feature
            return jax.tree.map(np.asarray, feat)
        spec = getattr(self.model, "_build_spec", None)
        if spec is None:
            raise ValueError(
                "precompile() needs the per-sample feature shape: the "
                "model records none (built lazily?) -- pass "
                "example_feature=")
        # the build spec is batched: drop the leading batch axis
        return jax.tree.map(
            lambda s: np.zeros(tuple(s.shape[1:]), dtype=s.dtype), spec)

    def precompile(self, buckets=None, example_feature=None) -> int:
        """Compile the eval step for every bucket BEFORE traffic
        arrives; returns the number of backend compiles performed.
        After this, a workload of mixed request sizes within the
        ladder performs zero XLA compiles (the acceptance contract,
        pinned by tests/test_serving.py via ``RecompileWatchdog``).

        With a ``length_ladder``, every (batch bucket x length rung)
        combination is warmed -- each bucketed feature leaf's leading
        (time) axis is set to the rung, mirroring what
        ``pad_length_axis`` does to traffic (``length_select`` excludes
        fixed side inputs from both, and is always called with a
        BATCHED-rank leaf so a shape-based predicate selects the same
        leaves at warmup as under traffic).  A request mixing different
        rungs across bucketed leaves would still compile once on first
        sight."""
        spec = self._sample_spec(example_feature)
        if buckets is None:
            buckets = list(self.ladder)
        else:
            buckets = [int(b) for b in buckets]
            # the ladder= path validates this in ladder_or_default; an
            # explicit bucket list must not sneak past it into an opaque
            # sharding error when the batch can't split over the mesh
            bad = [b for b in buckets
                   if b < 1 or b % self._backend.align]
            if bad:
                raise ValueError(
                    f"buckets {bad} not divisible by the device alignment "
                    f"{self._backend.align} (sharded predict splits the "
                    f"batch axis evenly)")
        self._fit_bound(len(buckets))
        # generation's shape set (decode step + prefill rungs) warms
        # alongside the eval ladder, so one precompile() closes BOTH
        # executable sets before traffic.  Warm whenever the served
        # model HAS a decode mode: the old gate (explicit decode_slots=
        # or a scheduler already built) silently skipped AUTO-mode
        # engines, so their first generate() after "precompile" still
        # paid every generation compile (tests/test_paged.py pins this)
        gen_compiles = 0
        served = self._qmodel if self._quantized else self.model
        if self.decode_slots > 0 and hasattr(served, "init_paged_cache"):
            gen_compiles = self._generation().precompile()
        if self.length_ladder is None:
            return self._backend.precompile(spec, buckets) + gen_compiles

        total = gen_compiles
        for rung in self.length_ladder:
            # the same walker pad_length_axis uses under traffic, on
            # sample-rank spec leaves (batched=False): identical leaf
            # numbering, rank gate, and length_select semantics, so the
            # warmed shapes are exactly the ones ticks will produce
            at_rung = walk_length_leaves(
                spec, self.length_select,
                lambda a, _r=int(rung): np.zeros((_r,) + a.shape[1:],
                                                 a.dtype),
                batched=False)
            total += self._backend.precompile(at_rung, buckets)
        return total

    # ----- dispatcher ------------------------------------------------------- #
    def _loop(self):
        # a queue_capacity below max_batch_size caps how full a tick can
        # ever get -- waiting for more would stall EVERY tick for the
        # whole max_wait_ms at saturation (submitters blocked on a full
        # queue can never raise _pending past capacity)
        fill = min(self.max_batch_size, self.queue_capacity)
        while True:
            with self._lock:
                while self._running and not self._pending:
                    self._idle.notify_all()   # quiescent: drain() waiters
                    self._not_empty.wait()
                if not self._running and not self._pending:
                    self._idle.notify_all()
                    return
                # deadline anchored on the OLDEST pending request; a
                # draining engine dispatches immediately -- no new
                # requests can arrive, so waiting out max_wait_ms for a
                # fuller batch only delays the drain
                deadline = self._pending[0][1]._t_submit + self.max_wait_s
                while self._running and not self._draining \
                        and len(self._pending) < fill:
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0:
                        break
                    self._not_empty.wait(timeout=remaining)
                take = min(self.max_batch_size, len(self._pending))
                reqs = [self._pending.popleft() for _ in range(take)]
                qdepth = len(self._pending)
                self._in_tick += len(reqs)
                self._not_full.notify_all()
            # claim each future (PENDING -> RUNNING) so a caller's
            # cancel() can no longer race the result-setting below --
            # set_result on a CANCELLED future raises InvalidStateError,
            # which would kill the dispatcher thread and hang the engine
            claimed = [r for r in reqs
                       if r[1].set_running_or_notify_cancel()]
            try:
                if claimed:
                    self._tick += 1
                    self._run_tick(claimed, qdepth)
            finally:
                with self._lock:
                    self._in_tick -= len(reqs)
                    self._served += len(claimed)
                    if not self._pending and not self._in_tick:
                        self._idle.notify_all()

    def _form_batch(self, features, bucket):
        samples = [f if isinstance(f, Sample) else Sample(f)
                   for f in features]
        mb = samples_to_minibatch(samples,
                                  feature_padding=self.feature_padding)
        x = pad_batch_axis(mb.get_input(), bucket)
        if self.length_ladder is not None:
            x = pad_length_axis(x, self.length_ladder, self.length_select)
        return x

    def _executables(self):
        """Current executable count of the shared compiled step (the
        per-tick delta is the live recompile signal: nonzero after
        ``precompile()`` means a shape leaked past the ladder).
        ``CompiledEvalStep.executables()`` already owns the
        can't-report fallback (None where jax lacks the cache API)."""
        return self._backend.step.executables() or 0

    def _run_tick(self, reqs, qdepth):
        t0 = time.perf_counter()
        feats = [r[0] for r in reqs]
        futs: List[ServeFuture] = [r[1] for r in reqs]
        execs_before = self._executables() \
            if self.telemetry is not None else 0
        # canary routing decided up front (error-diffusion accumulator:
        # a fraction f serves ~f of ticks on the candidate, spread
        # evenly, deterministically); the canary tuple is read ONCE so
        # a concurrent set_canary(None) cannot tear this tick
        canary = self._canary
        on_canary = False
        if canary is not None:
            self._canary_acc += canary[1]
            if self._canary_acc >= 1.0 - 1e-9:
                self._canary_acc -= 1.0
                on_canary = True
        reached_eval = False
        try:
            with span("serve_tick", tick=self._tick, records=len(reqs)):
                n = len(feats)
                bucket = self.ladder.bucket_for(n)
                if bucket is None:        # can't happen: take <= ladder.max
                    bucket = self.ladder.add(n)
                x = self._form_batch(feats, bucket)
                t_formed = time.perf_counter()
                reached_eval = True
                # weights= passed only on canary ticks: callers (and
                # tests) may substitute eval callables that predate
                # the override kwarg
                y = self._backend.eval(
                    x, tick=self._tick,
                    weights=canary[0]["staged"]) if on_canary \
                    else self._backend.eval(x, tick=self._tick)
                y = jax.tree.map(np.asarray, y)        # host sync + gather
        except Exception as e:
            # the failure belongs to THIS tick's callers only: surface
            # it on each future and keep the dispatcher serving
            log.exception("serving tick %d failed (%d requests)",
                          self._tick, len(futs))
            if on_canary and reached_eval:
                # a crashing candidate EVAL is canary evidence (the
                # rollout controller's rejection trigger); a malformed
                # request failing batch formation is the client's
                # fault on any tick and must not veto the rollout
                self._canary_failures += 1
            for fut in futs:
                if not fut.done():
                    fut.set_exception(e)
            return
        t_done = time.perf_counter()
        for i, fut in enumerate(futs):
            fut.bucket = bucket
            fut.latency_s = t_done - fut._t_submit
            fut.set_result(jax.tree.map(lambda a: a[i], y))
        if on_canary:
            self._canary_ticks += 1
            self._canary_rows += n
        # shadow mirroring AFTER the results are delivered: the
        # observer gets the tick's padded batch + live outputs and must
        # only enqueue (the candidate eval runs on the controller's
        # shadow worker, never on the request path)
        shadow = self._shadow
        if shadow is not None:
            self._shadow_acc += shadow[1]
            if self._shadow_acc >= 1.0 - 1e-9:
                self._shadow_acc -= 1.0
                try:
                    shadow[0](x, y, bucket, n, self._tick)
                except Exception:
                    log.exception("shadow observer failed (tick %d)",
                                  self._tick)
        if self.telemetry is not None:
            try:
                wall = t_done - t0
                event = dict(
                    step=self._tick, wall_s=wall,
                    data_wait_s=t_formed - t0, device_s=t_done - t_formed,
                    records=n, records_per_s=n / max(wall, 1e-9),
                    queue_depth=qdepth, queue_capacity=self.queue_capacity,
                    bucket=bucket, batch_fill=n / bucket,
                    pad_waste=(bucket - n) / bucket,
                    request_latency_s=[round(f.latency_s, 6) for f in futs])
                if on_canary:
                    # which ticks rode the candidate: the per-version
                    # SLO cut of the canary window reads this
                    event["canary"] = True
                    event["canary_version"] = canary[2]
                compiles = self._executables() - execs_before
                if compiles > 0:
                    # a tick that compiled: after precompile() this is
                    # a shape leak -- scrapeable live as
                    # bigdl_serving_recompiles_total
                    event["compiles"] = compiles
                traced = [f for f in futs if f._trace is not None]
                if traced:
                    # parallel trace-id list (null for untraced rows):
                    # the metrics bridge zips it with request_latency_s
                    # so latency-histogram buckets carry exemplars
                    event["request_traces"] = [
                        f._trace.trace_id if f._trace is not None
                        else None for f in futs]
                self.telemetry.record("inference", **event)
                if traced:
                    self._record_tick_trace(traced, t0, t_formed,
                                            t_done, bucket)
            except Exception:     # results are already delivered --
                log.exception(    # never let telemetry kill the dispatcher
                    "serving telemetry record failed (tick %d)", self._tick)

    def _record_tick_trace(self, traced, t0, t_formed, t_done, bucket):
        """Request-trace spans for one serving tick
        (docs/observability.md, "Request tracing"): one
        ``engine_request`` span per traced request (queue wait + device
        time under its own trace_id) and ONE ``serve_tick`` span
        carrying links to every trace riding the batch -- continuous
        batching means N request spans share one device dispatch."""
        emit = getattr(self.telemetry, "record_trace", None)
        if emit is None:
            return
        from bigdl_tpu.observability.tracing import TraceContext

        now = time.time()
        links = []
        for f in traced:
            ctx = f._trace.child()
            links.append(ctx.trace_id)
            emit("engine_request", ctx, now - f.latency_s, f.latency_s,
                 queue_wait_s=round(max(0.0, t0 - f._t_submit), 6),
                 device_s=round(t_done - t_formed, 6),
                 tick=self._tick, bucket=int(bucket))
        emit("serve_tick", TraceContext.mint(), now - (t_done - t0),
             t_done - t0, links=links, records=len(traced),
             tick=self._tick, bucket=int(bucket))

    # ----- int8 path: gate + staging helpers -------------------------------- #
    @property
    def quantized(self) -> bool:
        """Whether this engine serves the int8 twin (the precision that
        actually answers requests -- stamped on the telemetry header)."""
        return self._quantized

    def serving_model_bytes(self) -> int:
        """Bytes of the weight tree the backend serves from (the int8
        payload+scales tree when quantized, the fp32 tree otherwise)."""
        from bigdl_tpu.nn.quantized import model_bytes

        src = self._qmodel if self._quantized else self.model
        return model_bytes(src.weights())

    # ----- device-memory ledger (observability/memory.py) -------------------- #
    def memory_ledger(self, registry=None):
        """The engine's ``MemoryLedger``: params (plus the retained
        fp32 twin on a quantized engine), the KV block pool with its
        active/prefix-cached/free split, and -- when a deploy
        ``ModelRegistry`` is passed -- the staged-version buffers.
        Built lazily, attached to this engine's telemetry; call
        ``record_memory()`` to put a snapshot on the timeline.
        Re-calling with ``registry`` (re)binds the staged source."""
        if self._memory_ledger is None:
            from bigdl_tpu.observability.memory import MemoryLedger

            led = MemoryLedger()
            led.register("params", self.serving_model_bytes)
            if self._quantized:
                # the fp32 tree is retained for gate evals and as the
                # refresh_params source -- real bytes, own them
                def fp32_bytes():
                    from bigdl_tpu.nn.quantized import model_bytes
                    return model_bytes(self.model.weights())
                led.register("params_fp32", fp32_bytes)
            led.register("kv_cache", self._kv_cache_bytes)
            if self.telemetry is not None:
                led.attach(self.telemetry)
            self._memory_ledger = led
        if registry is not None:
            self._memory_ledger.register(
                "staged", lambda: registry.retained_bytes())
        return self._memory_ledger

    def _kv_cache_bytes(self):
        """Ledger source for the generation KV pool: total device bytes
        plus the allocator's block split (zero until the first
        ``generate()`` builds the scheduler)."""
        gen = self._gen
        if gen is None:
            return 0
        rec = {"bytes": gen.cache_bytes()}
        alloc = getattr(gen, "_alloc", None)
        if alloc is not None:
            st = alloc.stats()
            total = st.get("blocks_total") or 0
            # the allocator-reported bytes behind one addressable
            # block: measured from the device pool it fronts (payload
            # AND scale leaves at the pool's ACTUAL storage dtype), so
            # an int8 pool's split reports real narrow bytes instead
            # of compute-dtype hand-math overstating it ~4x
            per_block = st.get("bytes_per_block")
            if per_block is None:
                per_block = rec["bytes"] / total if total else 0
            rec.update(
                blocks_total=total,
                blocks_active=st.get("blocks_used"),
                blocks_cached=st.get("blocks_cached"),
                blocks_free=st.get("blocks_free"),
                kv_dtype=st.get("kv_dtype"),
                active_bytes=int(st.get("blocks_used", 0) * per_block),
                cached_bytes=int(st.get("blocks_cached", 0) * per_block),
                free_bytes=int(st.get("blocks_free", 0) * per_block))
        return rec

    def memory_headroom(self):
        """The admission/autoscaling capacity signal: allocator
        headroom (None on backends without memory stats) plus the KV
        pool's block occupancy, which is meaningful everywhere --
        ``BlockPoolExhausted`` sheds and autoscaler decisions cite
        these measured numbers."""
        snap = self.memory_ledger().snapshot()
        out = {"headroom_bytes": snap["headroom_bytes"],
               "headroom_fraction": snap["headroom_fraction"],
               "attributed_bytes": snap["attributed_bytes"],
               "live_bytes": snap["live_bytes"]}
        gen = self._gen
        alloc = getattr(gen, "_alloc", None) if gen is not None else None
        if alloc is not None:
            st = alloc.stats()
            total = st.get("blocks_total") or 0
            free = st.get("blocks_free", 0) + st.get("blocks_cached", 0)
            out["kv_blocks_total"] = total
            # cached blocks are reclaimable (LRU-evictable), so they
            # count as admission headroom even while they hold prefixes
            out["kv_blocks_free"] = free
            out["kv_fill"] = round(1.0 - free / total, 6) if total else 0.0
        return out

    def record_memory(self, **extra):
        """Snapshot the ledger onto the telemetry timeline (a durable
        ``kind: "memory"`` event, bridged to the
        ``bigdl_memory_bytes{device,subsystem}`` gauges)."""
        return self.memory_ledger().record(tick=self._tick, **extra)

    def _on_pool_exhausted(self, exc):
        """Generation's ``BlockPoolExhausted`` forensics hook: dump the
        full ledger + block occupancy + last ticks ONCE, durably,
        before/while the shed propagates to callers."""
        try:
            self.memory_ledger().handle_allocation_failure(
                exc, detail={"kv": self._kv_cache_bytes()},
                reason="kv_block_pool_exhausted")
        except Exception:
            log.exception("memory forensics dump failed")

    @staticmethod
    def _make_gate(accuracy_gate):
        if accuracy_gate is None:
            return None
        from bigdl_tpu.optim.validation import AccuracyDeltaGate

        if isinstance(accuracy_gate, AccuracyDeltaGate):
            return accuracy_gate
        if isinstance(accuracy_gate, dict):
            return AccuracyDeltaGate(**accuracy_gate)
        raise ValueError(
            f"accuracy_gate must be an AccuracyDeltaGate or a dict of "
            f"its kwargs, got {type(accuracy_gate).__name__}")

    def _gate_eval(self, step, params, mstate):
        """Bind ``step`` into the gate's ``x -> logits`` callable.  The
        held-out batch is padded to its ladder bucket (and the result
        sliced back), so the int8 side reuses a precompiled executable
        where possible -- gate evals run at swap time, never on the
        request path."""
        def run(x):
            x = jax.tree.map(np.asarray, x)
            n = jax.tree.leaves(x)[0].shape[0]
            bucket = self.ladder.bucket_for(n)
            xb = x if bucket is None or bucket == n \
                else pad_batch_axis(x, bucket)
            y = step(params, mstate, xb)
            return jax.tree.map(lambda a: np.asarray(a)[:n], y)
        return run

    def _check_accuracy(self, fp_params, fp_mstate, qparams=None):
        """fp32-vs-int8 gate on a CANDIDATE weight set (nothing is
        committed here): quantize ``fp_params`` unless the int8 tree is
        supplied, run both eval steps on the held-out batch, return
        ``(ok, detail)``."""
        if qparams is None:
            from bigdl_tpu.nn.quantized import quantize_params

            qparams = quantize_params(self.model, fp_params, self._qselect)
        from bigdl_tpu.optim.validation import compiled_eval_step

        ref_step = compiled_eval_step(self.model, self._compute_dtype)
        # the int8 side: the serving backend's step on a quantized
        # engine; on a speculative-only engine (fp32 serving, int8
        # drafter) the backend is fp32, so the gate evals the twin's
        # own compiled step instead
        q_step = self._backend.step if self._quantized \
            else compiled_eval_step(self._qmodel, self._compute_dtype)
        ok, detail = self._gate.check(
            self._gate_eval(ref_step, fp_params, fp_mstate),
            self._gate_eval(q_step, qparams, fp_mstate))
        return ok, detail

    def _stamp_serving_info(self):
        """Satellite of the int8 path: the telemetry header (or a
        standalone ``serving_info`` event when the header already went
        out) states which precision served this run -- quantized flag,
        weight dtype, serving-tree bytes (and the fp32 bytes it
        replaced), backend layout (docs/observability.md, "Serving
        telemetry")."""
        if self.telemetry is None:
            return
        from bigdl_tpu.nn.quantized import model_bytes

        info = {"quantized": self._quantized,
                "weight_dtype": "int8" if self._quantized else "float32",
                "model_bytes": self.serving_model_bytes(),
                "backend": self._backend.kind,
                "replicas": self._backend.replicas}
        if self.decode_slots > 0:
            info["decode_slots"] = self.decode_slots
            info["kv_cache"] = "paged"
            info["kv_block_size"] = self.kv_block_size
            info["kv_cache_dtype"] = self.kv_cache_dtype
            if self.speculative:
                info["speculative"] = self.speculative
        if self._version_info is not None:
            # WHICH checkpoint this replica serves: version id + the
            # snapshot's manifest digest (set_serving_version)
            info["version"] = self._version_info["version"]
            info["digest"] = self._version_info["digest"]
        if self._quantized:
            info["model_bytes_fp32"] = model_bytes(self.model.weights())
        if self._gate_detail is not None:
            info["accuracy_gate"] = self._gate_detail
        try:
            self.telemetry.set_serving_info(info)
        except Exception:
            log.exception("serving_info telemetry stamp failed")

    def _weights_swapped(self):
        """After a weight swap lands: build the generation scheduler's
        served tree from the new weights now, before its next tick
        (``ServedWeights``), and drop the paged scheduler's prefix
        cache.  Cached K/V was computed under the OLD weights -- serving
        it to a new prompt would silently mix checkpoints (live
        sequences keep their blocks and finish mid-flight, the PR 15
        trade)."""
        gen = self._gen
        served = getattr(gen, "served", None)
        if served is not None:
            served.rebuild()
        flush = getattr(gen, "flush_prefix_cache", None)
        if flush is not None:
            flush()

    # ----- staged deployment surface (serving/deploy.py) --------------------- #
    def stage_weights(self, params, mstate=None, src_layout=None):
        """Validate + device-stage a CANDIDATE weight set WITHOUT
        committing anything: the engine keeps serving its current
        weights while the candidate's device buffers sit staged beside
        them.  Returns an opaque staged handle the rollout machinery
        threads through shadow evaluation (``eval_staged``), canary
        routing (``set_canary``) and the eventual atomic
        ``commit_staged`` -- or retains for a pointer-swap rollback.

        Same front door as ``refresh_params``: ``src_layout``
        redistributes a cross-layout checkpoint onto the serving tree
        first, then the structure/shape contract check runs -- a
        half-written checkpoint raises here, before any staging.  On a
        quantized engine the candidate is quantized ONCE at staging
        (the handle carries the int8 payload+scales); a later commit or
        rollback of this handle never re-quantizes or re-stages."""
        if src_layout is not None:
            from bigdl_tpu.parallel.reshard import to_model_layout

            params = to_model_layout(params, src_layout, self.model,
                                     telemetry=self.telemetry,
                                     what="deploy-stage")
        reason = self._validate_incoming(params, mstate)
        if reason is not None:
            raise ValueError(
                f"stage_weights rejected the candidate ({reason}); "
                f"nothing was staged -- is the source checkpoint "
                f"half-written or from a different model?")
        from bigdl_tpu.nn.quantized import model_bytes
        import jax.numpy as jnp

        # normalize to UNCOMMITTED jnp leaves here, so the tree a later
        # commit points the model at keys the jit cache exactly like
        # the init-time weights it replaces (a raw-numpy checkpoint
        # tree would force one spurious recompile on the first
        # post-cutover tick -- the zero-steady-state-recompile pin)
        params = jax.tree.map(jnp.asarray, params)
        if mstate is not None:
            mstate = jax.tree.map(jnp.asarray, mstate)
        stage_mstate = mstate if mstate is not None else self.model.state()
        qparams = None
        if self._quantized:
            from bigdl_tpu.nn.quantized import quantize_params

            qparams = quantize_params(self.model, params, self._qselect)
        serve_tree = qparams if qparams is not None else params
        return {"params": params, "mstate": mstate, "qparams": qparams,
                "staged": self._backend.stage(serve_tree, stage_mstate),
                "model_bytes": model_bytes(serve_tree),
                "quantized": self._quantized}

    def capture_staged(self):
        """The CURRENTLY serving weights as a staged handle -- what a
        rollout controller retains before a cutover so rollback is a
        pointer swap back to live device buffers, never a re-quantize
        or a re-stage."""
        from bigdl_tpu.nn.quantized import model_bytes

        qparams = self._qmodel.weights() if self._quantized else None
        serve_tree = qparams if qparams is not None \
            else self.model.weights()
        # the CURRENT model state rides the handle: a rollback must
        # restore it too, or a stateful model (BatchNorm running
        # stats) would serve previous params mixed with the rejected
        # candidate's state -- not the bit-for-bit re-serve promised
        return {"params": self.model.weights(),
                "mstate": self.model.state(), "qparams": qparams,
                "staged": self._backend.capture(),
                "model_bytes": model_bytes(serve_tree),
                "quantized": self._quantized}

    def commit_staged(self, handle, version=None, digest=None):
        """The atomic cutover: point the engine at an already-staged
        handle.  The serving-visible swap is ONE attribute assignment
        (the backend's committed weights pointer / the served model's
        params dict), so a tick observes either the old weights or the
        new ones, never a torn mix -- and because the handle's device
        buffers already exist, this is equally the ROLLBACK primitive:
        committing a retained previous handle re-serves it bit-for-bit
        with no re-quantize, no re-stage, no gate.

        No gate runs here by design -- staged-exposure verdicts
        (shadow comparison, canary SLO + accuracy gate) belong to the
        rollout controller BEFORE it commits
        (docs/robustness.md, "Continuous deployment")."""
        if handle.get("quantized") != self._quantized:
            raise ValueError(
                "staged handle precision does not match this engine "
                "(was it staged on a different engine?)")
        if handle["qparams"] is not None:
            self._qmodel.set_parameters(handle["qparams"])
        self.model.set_parameters(handle["params"])
        if handle.get("mstate") is not None:
            self.model.set_state(handle["mstate"])
            if self._qmodel is not None:
                self._qmodel.set_state(handle["mstate"])
        self._backend.install(handle["staged"])
        if version is not None:
            self.set_serving_version(version, digest)
        audit = {"model_bytes": handle.get("model_bytes"), "staged": True}
        if self._quantized:
            audit["quantized"] = True
        if handle.get("wire_bytes") is not None:
            # weights that crossed the fleet wire record what the
            # TRANSPORT measured (int8 distribution ships ~4x fewer
            # bytes than model_bytes claims) -- the honest number for
            # the param_refresh trail
            audit["wire_bytes"] = int(handle["wire_bytes"])
            audit["weight_wire"] = handle.get("weight_wire")
        self._record_refresh("ok", **audit)
        self._weights_swapped()
        self._stamp_serving_info()
        return self

    def eval_staged(self, handle, x, tick=0):
        """Run the serving eval step on a STAGED handle's weights --
        the shadow-evaluation path: same compiled executables as live
        traffic (identical shapes and placement, so zero new compiles
        for ladder-shaped batches), candidate outputs, nothing
        committed.  Runs on the caller's thread: keep it off the
        dispatcher (the shadow observer enqueues; a worker evals)."""
        y = self._backend.eval(x, tick=tick, weights=handle["staged"])
        return jax.tree.map(np.asarray, y)

    def set_canary(self, handle, fraction=0.1, version=None):
        """Route ``fraction`` of ticks onto a staged candidate's
        weights (error-diffused, so the fraction holds over any
        window); ``set_canary(None)`` ends the canary.  Stats reset on
        every call -- ``canary_stats()`` reads the current window."""
        if handle is not None and not 0.0 < float(fraction) <= 1.0:
            raise ValueError(
                f"canary fraction must be in (0, 1], got {fraction}")
        self._canary_acc = 0.0
        self._canary_ticks = 0
        self._canary_rows = 0
        self._canary_failures = 0
        self._canary = None if handle is None \
            else (handle, float(fraction), version)
        return self

    def canary_stats(self):
        """``{"ticks", "rows", "failures"}`` of the current canary
        window (since the last ``set_canary``)."""
        return {"ticks": self._canary_ticks, "rows": self._canary_rows,
                "failures": self._canary_failures}

    def set_shadow(self, fn, fraction=1.0):
        """Mirror ``fraction`` of ticks to ``fn(x_padded, y_live,
        bucket, n_real, tick)`` AFTER their results are delivered.
        The observer runs on the dispatcher thread and must only
        enqueue -- evaluate the candidate elsewhere (``eval_staged``).
        ``set_shadow(None)`` stops mirroring; observer exceptions are
        logged and swallowed (shadowing is best-effort, live traffic
        is not)."""
        if fn is not None and not 0.0 < float(fraction) <= 1.0:
            raise ValueError(
                f"shadow fraction must be in (0, 1], got {fraction}")
        self._shadow_acc = 0.0
        self._shadow = None if fn is None else (fn, float(fraction))
        return self

    def set_serving_version(self, version, digest=None):
        """Stamp WHICH model version this engine is serving: carried on
        the telemetry header's ``serving`` block (or a standalone
        ``serving_info`` event), every ``param_refresh`` audit event,
        and -- through the metrics bridge -- the
        ``bigdl_serving_version_info`` gauge, so an operator can always
        answer "which checkpoint is this replica serving?"."""
        self._version_info = {"version": int(version),
                             "digest": None if digest is None
                             else str(digest)}
        self._stamp_serving_info()
        return self

    def _validate_incoming(self, params, mstate):
        """First structure/shape/dtype mismatch of an incoming weight
        set against the construction-time serving contract, or None."""
        reason = _spec_mismatch(self._params_spec, _tree_spec(params),
                                "params")
        if reason is None and mstate is not None:
            reason = _spec_mismatch(self._mstate_spec, _tree_spec(mstate),
                                    "mstate")
        return reason

    # ----- lifecycle -------------------------------------------------------- #
    def refresh_from_snapshot(self, path):
        """Hot-swap weights straight from a TRAINING checkpoint written
        under ANY layout this stack trains (docs/robustness.md,
        "Portable resharding"): resolve the snapshot, read its manifest
        ``layout`` block, load the weights replicated on host under the
        snapshot's OWN layout, redistribute them onto the serving
        model's tree (``parallel/reshard.to_model_layout`` -- dp flat
        planes unravel, pp stage-stacked trees unstack, scan/unrolled
        block keyings interconvert, tp/ep trees pass through), and run
        the ordinary ``refresh_params`` -- structure check and
        ``accuracy_gate`` still in front, old weights keep serving on
        any rejection.

        ``path`` may be a snapshot itself (``checkpoint.<tag>.pkl`` /
        ``snap_<n>`` dir) or a checkpoint DIRECTORY, in which case the
        newest intact snapshot is resolved (corrupt ones quarantined,
        exactly like training resume)."""
        from bigdl_tpu.parallel.reshard import read_snapshot_layout

        p = self._resolve_snapshot(path)
        src = read_snapshot_layout(p)
        params, mstate = self._load_snapshot_weights(p, src)
        return self.refresh_params(params, mstate, src_layout=src)

    @staticmethod
    def _resolve_snapshot(path):
        """A concrete snapshot path from a file/dir/checkpoint-root
        (newest intact wins; every-candidate-corrupt raises)."""
        import os

        from bigdl_tpu.utils import file_io

        base = os.path.basename(str(path).rstrip("/"))
        if file_io.isdir(path) and not base.startswith("snap_"):
            intact, quarantined = file_io.scan_sharded_snapshots(path)
            if not intact:
                intact, q2 = file_io.scan_checkpoints(path)
                quarantined += q2
            if not intact:
                raise ValueError(
                    f"no intact snapshot under {path}"
                    + (f" (quarantined: {quarantined})" if quarantined
                       else ""))
            return intact[0]
        return path

    def _load_snapshot_weights(self, p, src_layout):
        """-> (params, mstate) of a snapshot, replicated on host under
        its OWN layout (the restore-under-own-layout contract the
        redistribution engine expects).  dp flat planes come back as
        the flat vector (``src_layout`` tells refresh_params to
        unravel); strategy snapshots as their native trees."""
        from bigdl_tpu.utils import file_io

        def clean_state(mstate):
            import jax
            return mstate if mstate is not None \
                and jax.tree.leaves(mstate) else None

        if not file_io.isdir(p):                   # pickle snapshot
            import jax.numpy as jnp

            payload = file_io.load(p)
            mp = payload["model_params"]
            if isinstance(mp, dict) and "model_params_flat" in mp:
                mp = mp["model_params_flat"]
            # uncommitted jnp leaves, exactly like the orbax branch
            # below: file_io.load hands back raw numpy, which keys the
            # serving jit cache differently than init-time weights and
            # would force one spurious recompile per bucket on the
            # first post-swap ticks
            mp = jax.tree.map(jnp.asarray, mp)
            return mp, clean_state(payload.get("model_state"))
        import orbax.checkpoint as ocp                  # sharded (orbax)

        with ocp.StandardCheckpointer() as ckptr:
            # no abstract tree: the snapshot's own structure/shapes ARE
            # the contract here (restore-under-own-layout); arrays come
            # back whole on the local device, host-addressable
            restored = ckptr.restore(p)
        # re-materialize as UNCOMMITTED arrays (host round trip): a
        # committed orbax-restored array -- or a raw numpy leaf -- keys
        # the serving jit cache differently than the init-time weights
        # it replaces and would force one spurious recompile on the
        # first post-swap tick (the zero-steady-state-recompile pin)
        import jax.numpy as jnp

        restored = jax.tree.map(lambda a: jnp.asarray(np.asarray(a)),
                                restored)
        if "params_flat" in restored:              # dp flat-plane payload
            return restored["params_flat"], clean_state(
                restored.get("mstate"))
        return restored["params"], None

    def refresh_params(self, params=None, mstate=None, src_layout=None):
        """Swap in retrained weights and re-replicate the device caches
        (sharded / round-robin layouts hold weights on device).

        With ``params`` (and optionally ``mstate``): validate the
        incoming tree's STRUCTURE and per-leaf shapes/dtypes against
        the serving model's, and only then ``set_parameters`` + refresh
        -- a refresh fed from a half-written checkpoint mid-retrain
        raises ``ValueError`` here and the engine keeps serving the old
        weights untouched.  Without arguments (the historical spelling:
        caller already mutated ``self.model``), the model's CURRENT
        params are validated against the engine's construction-time
        spec before the device caches re-replicate.

        On a quantized engine the incoming checkpoint is ALWAYS fp32
        (the training side's tree): it is quantized here at swap time,
        gated by ``accuracy_gate`` (a failing gate rejects the swap
        through the same rejected-with-reason audit path and the old
        weights keep serving), and the tree staged onto the devices is
        the int8 payload+scales -- the ``param_refresh`` event records
        ``model_bytes`` and the replica-staging ``wire_bytes`` it moved
        in that blockwise-int8 wire stance (docs/performance.md, "Int8
        inference").

        ``src_layout`` (a ``parallel.reshard.LayoutSpec`` or its
        manifest dict) names the layout the incoming ``params`` were
        SAVED under when it differs from the serving model's own tree:
        the weights are first redistributed onto the serving layout
        (``to_model_layout`` -- emitting the durable ``kind:"reshard"``
        audit event), and only then hit the structure check and the
        accuracy gate, so a tp/pp/dp training checkpoint hot-swaps into
        a replicated (or sharded-mesh) serving engine with the exact
        same guards in front."""
        incoming = params is not None
        if src_layout is not None:
            if not incoming:
                raise ValueError(
                    "src_layout describes an INCOMING params tree; "
                    "pass params= alongside it")
            from bigdl_tpu.parallel.reshard import to_model_layout

            params = to_model_layout(params, src_layout, self.model,
                                     telemetry=self.telemetry,
                                     what="serving-refresh")
        if incoming:
            reason = self._validate_incoming(params, mstate)
            if reason is not None:
                self._record_refresh("rejected", reason)
                raise ValueError(
                    f"refresh_params rejected the incoming weights "
                    f"({reason}); the engine keeps serving its current "
                    "weights -- is the source checkpoint half-written "
                    "or from a different model?")
        else:
            params = self.model.weights()
            reason = _spec_mismatch(self._params_spec, _tree_spec(params),
                                    "params")
            if reason is not None:
                self._record_refresh("rejected", reason)
                raise ValueError(
                    f"refresh_params: the model's weights no longer "
                    f"match the serving contract ({reason}); device "
                    "caches left untouched")
        from bigdl_tpu.nn.quantized import model_bytes

        qparams, gate_detail, audit = None, None, {}
        if self._quantized:
            from bigdl_tpu.nn.quantized import quantize_params

            # stage WITHOUT committing: quantize the candidate, gate it,
            # and only then touch the models / device caches
            qparams = quantize_params(self.model, params, self._qselect)
            stage_mstate = mstate if mstate is not None \
                else self.model.state()
            if self._gate is not None:
                ok, gate_detail = self._check_accuracy(params, stage_mstate,
                                                       qparams)
                if not ok:
                    reason = ("accuracy gate: "
                              + gate_detail.get("reason", "failed"))
                    self._record_refresh("rejected", reason,
                                         accuracy_gate=gate_detail)
                    raise ValueError(
                        f"refresh_params rejected the incoming weights "
                        f"({reason}); the engine keeps serving its "
                        "current weights")
                self._gate_detail = gate_detail
            audit["model_bytes"] = model_bytes(qparams)
            audit["quantized"] = True
        else:
            audit["model_bytes"] = model_bytes(params)
        # bytes the swap stages onto devices: one serving tree per
        # replica (mesh size for sharded, device count for round-robin)
        audit["wire_bytes"] = audit["model_bytes"] * self._backend.replicas
        if incoming:
            self.model.set_parameters(params)
            if mstate is not None:
                self.model.set_state(mstate)
        if qparams is not None:
            self._qmodel.set_parameters(qparams)
            # the twin shares the eval state tree; re-sync in case the
            # refresh (or the caller, in the no-arg spelling) moved it
            self._qmodel.set_state(self.model.state())
        refresh = getattr(self._backend, "refresh_params", None)
        if refresh is not None:
            refresh()
        if gate_detail is not None:
            audit["accuracy_gate"] = gate_detail
        self._record_refresh("ok", **audit)
        self._weights_swapped()
        self._stamp_serving_info()
        return self

    def _record_refresh(self, outcome, reason=None, **extra):
        """Weight-swap audit trail: every refresh_params outcome (ok or
        rejected) lands as a ``kind: "param_refresh"`` telemetry event
        -- the live counter behind it is how a retrain loop's hot-swap
        cadence (and its rejected half-written checkpoints) shows up on
        a /metrics scrape.  ``extra`` carries the int8 staging evidence:
        ``model_bytes`` / ``wire_bytes`` of the staged tree, the
        ``quantized`` stamp and the ``accuracy_gate`` detail."""
        if self.telemetry is None:
            return
        try:
            fields = {"tick": self._tick, "outcome": outcome,
                      "backend": self._backend.kind, **extra}
            if self._version_info is not None:
                fields.setdefault("version", self._version_info["version"])
                fields.setdefault("digest", self._version_info["digest"])
            if reason is not None:
                fields["reason"] = str(reason)[:300]
            self.telemetry.record("param_refresh", **fields)
        except Exception:
            log.exception("param_refresh telemetry record failed")

    @property
    def draining(self) -> bool:
        """True while admission is closed (``drain()`` .. ``undrain()``)."""
        return self._draining

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Gracefully quiesce WITHOUT closing: stop admitting (a new
        ``submit`` raises the typed ``EngineDraining``), let the
        dispatcher finish its in-flight tick and serve every
        already-queued request, and return once the engine is idle.

        The contract the fleet's rolling deploys ride on
        (docs/robustness.md, "Serving fleets"): NO accepted future is
        ever dropped -- every request admitted before ``drain()`` was
        called resolves normally (result or its tick's exception).
        Returns True when fully drained; False when ``timeout`` seconds
        passed with work still in flight (the engine KEEPS draining --
        call again to keep waiting, or ``undrain()`` to reopen).
        Idempotent; ``undrain()`` reopens admission."""
        deadline = None if timeout is None \
            else time.perf_counter() + timeout
        with self._lock:
            self._draining = True
            # wake the dispatcher out of its batch-fill wait AND any
            # submitter blocked on a full queue (it must see the drain
            # and raise instead of being admitted late)
            self._not_empty.notify_all()
            self._not_full.notify_all()
            while self._pending or self._in_tick:
                remaining = None if deadline is None \
                    else deadline - time.perf_counter()
                if remaining is not None and remaining <= 0:
                    return False
                self._idle.wait(timeout=remaining)
        if self._gen is not None:
            # in-flight generations are accepted work too: the no-
            # accepted-future-ever-dropped contract covers them, so the
            # drain waits for every live sequence to finish decoding
            remaining = None if deadline is None \
                else max(0.0, deadline - time.perf_counter())
            return self._gen.drain(timeout=remaining)
        return True

    def undrain(self):
        """Reopen admission after a ``drain()`` (the rolling deploy's
        per-replica drain -> cutover -> undrain step)."""
        with self._lock:
            self._draining = False
            self._not_full.notify_all()
        return self

    def stats(self):
        """Live engine occupancy -- the health/load signal a fleet
        router balances on: pending queue depth, requests claimed by
        the in-flight tick, lifetime ticks/requests served, and the
        drain flag."""
        with self._lock:
            stats = {"pending": len(self._pending),
                     "in_tick": self._in_tick,
                     "draining": self._draining,
                     "running": self._running,
                     "ticks": self._tick,
                     "served": self._served,
                     "queue_capacity": self.queue_capacity}
        if self._gen is not None:
            stats["generate"] = self._gen.stats()
        return stats

    def close(self, timeout: Optional[float] = 10.0):
        """Stop accepting requests, drain the queue, join the
        dispatcher; the generation scheduler gives its KV cache back to
        the device (a closed engine holds no cache, collected or not).
        Idempotent."""
        with self._lock:
            self._running = False
            self._not_empty.notify_all()
            self._not_full.notify_all()
        self._dispatcher.join(timeout)
        if self._gen is not None:
            self._gen.close(timeout)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
