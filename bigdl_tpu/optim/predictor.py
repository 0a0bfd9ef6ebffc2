"""Batch inference + concurrent serving.

Reference: optim/Predictor.scala:35,154 (RDD batch inference with broadcast
weights), optim/LocalPredictor.scala (thread-parallel local variant),
optim/PredictionService.scala:56 (instance pool of model clones behind a
blocking queue).

TPU-native: one jitted eval step; "broadcast" is simply device residency.
Concurrency is won by BATCHING, not threading: ``PredictionService``
keeps the reference's bounded-concurrency contract with a semaphore
(the serial baseline), and ``coalesce=True`` routes requests through
``bigdl_tpu.serving.ServingEngine`` -- concurrent small requests share
one padded device batch per dispatch tick instead of serializing
batch-1 evals through the semaphore.

Shape discipline: every ragged batch (the tail of a dataset, a
partially-filled serving tick) is padded up to a bucket before
dispatch, so the compiled-executable set is closed and steady state
never recompiles (docs/performance.md, "Inference serving").
"""

import threading
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.dataset.dataset import AbstractDataSet
from bigdl_tpu.dataset.minibatch import Sample, samples_to_minibatch
from bigdl_tpu.observability.spans import span
from bigdl_tpu.optim.validation import compiled_eval_step


class Predictor:
    """Batched prediction over a DataSet or array of Samples
    (reference: optim/Predictor.scala:154).

    ``telemetry``: optional ``StepTelemetry`` -- each batch appends a
    ``kind: "inference"`` JSONL event with the same split-timer keys as
    training steps (plus the bucket/fill/pad-waste fields), and batch
    fetch/eval land in the host span trace.

    ``ladder``: optional ``serving.BucketLadder`` controlling how a
    ragged batch pads.  The default (None) pads a short batch up to the
    largest batch size seen this run -- for a uniform-batch dataset
    that is a single-rung ladder, so the whole predict pass uses
    EXACTLY ONE compiled executable (previously the ragged tail
    silently compiled a second one).  Pass a multi-rung ladder to trade
    a couple of extra warmable executables for less pad compute on
    small tails.
    """

    def __init__(self, model, batch_size: int = 128, compute_dtype=None,
                 telemetry=None, ladder=None):
        if not model.is_built():
            raise ValueError("build the model (or train it) before predicting")
        self.model = model
        self.batch_size = batch_size
        self.telemetry = telemetry
        # copied: _bucket_for grows the ladder past its max, and that
        # growth must not leak into a caller-shared ladder
        self.ladder = None if ladder is None else ladder.copy()
        # shared per-(model, dtype) compiled step: a Predictor built for
        # an already-validated model reuses validation's executable
        self._eval = compiled_eval_step(model, compute_dtype)

    def predict_minibatch(self, batch):
        x = jax.device_put(batch.get_input())   # one async tree transfer
        return self._eval(self.model.parameters()[0], self.model.state(), x)

    def _bucket_for(self, n: int, run_max: int) -> int:
        """The pad target for an ``n``-row batch: the caller-supplied
        ladder when one is set (auto-extended past its max so an
        oversized dataset batch becomes a rung the tail can pad to),
        else the largest batch size seen this run."""
        if self.ladder is not None:
            b = self.ladder.bucket_for(n)
            return b if b is not None else self.ladder.add(n)
        return max(n, run_max)

    def predict(self, data) -> List:
        """data: AbstractDataSet of MiniBatches, or list of Samples.
        Returns one output PER SAMPLE: an ndarray row, or -- for a
        table-output model (ConcatTable etc) -- the sample's output
        tree with ndarray leaves.

        The batch-k+1 fetch overlaps batch k's device execution (the
        eval dispatch is async; the host sync is the ``np.asarray``
        readback), mirroring the training loop's staging choreography.
        """
        outs = []
        it = self._batches(data)
        with span("predict_fetch"):
            batch = next(it, None)
        step = 0
        run_max = 0
        while batch is not None:
            t0 = time.perf_counter()
            step += 1
            n = batch.size()
            bucket = self._bucket_for(n, run_max)
            # ragged batches pad UP to the bucket so every dispatch
            # reuses a warm executable; padded rows are sliced off the
            # output below (targets are never read here, so they are
            # not padded).  Exotic batch types (padded-COO sparse
            # features) keep the historical unpadded dispatch -- the
            # fallback resolves BEFORE the span so the span's bucket
            # agrees with the inference event's
            try:
                staged = batch.pad_to(bucket, pad_target=False)
            except TypeError:
                staged, bucket = batch, n
            run_max = max(run_max, bucket)
            with span("predict_batch", step=step, bucket=bucket):
                y = self.predict_minibatch(staged)
                tf = time.perf_counter()
                with span("predict_fetch"):
                    next_batch = next(it, None)     # overlapped fetch
                data_wait = time.perf_counter() - tf
                # host sync FIRST, then numpy-slice the padded tail: a
                # device-side a[:n] would compile a fresh slice
                # executable per (bucket, tail) pair on the request path
                if isinstance(y, (tuple, list)):
                    # table-output model (ConcatTable etc): one output
                    # TREE per sample, not one list entry per branch
                    leaves, treedef = jax.tree.flatten(y)
                    leaves = [np.asarray(a)[:n] for a in leaves]
                    outs.extend(jax.tree.unflatten(treedef, rows)
                                for rows in zip(*leaves))
                else:
                    outs.extend(np.asarray(y)[:n])
            if self.telemetry is not None:
                wall = time.perf_counter() - t0
                self.telemetry.record(
                    "inference", step=step, wall_s=wall,
                    data_wait_s=data_wait, device_s=wall - data_wait,
                    records=n, records_per_s=n / max(wall, 1e-9),
                    bucket=bucket, batch_fill=n / bucket,
                    pad_waste=(bucket - n) / bucket)
            batch = next_batch
        return outs

    def predict_class(self, data) -> List[int]:
        """Reference: predictClass -- argmax over the last axis."""
        return [int(np.argmax(o, axis=-1)) for o in self.predict(data)]

    def _record_batches(self, records):
        from bigdl_tpu.dataset.minibatch import MiniBatch

        for i in range(0, len(records), self.batch_size):
            chunk = records[i:i + self.batch_size]
            if isinstance(chunk[0], Sample):
                yield samples_to_minibatch(chunk)
            else:
                yield MiniBatch(np.stack(chunk))

    def _batches(self, data):
        from bigdl_tpu.dataset.distributed import is_partitioned, source_of

        if isinstance(data, AbstractDataSet):
            yield from data.data(train=False)
            return
        if is_partitioned(data):
            # model.predict(rdd) analogue (reference: Predictor.scala:154
            # maps partitions under a broadcast model): THIS host predicts
            # the partitions congruent to its process index (the
            # PartitionedDataSet locality contract), batch by batch
            import jax

            src = source_of(data)
            n_hosts = jax.process_count()
            host = jax.process_index()
            for p in range(src.num_partitions()):
                if p % n_hosts != host:
                    continue
                yield from self._record_batches(list(src.partition(p)))
            return
        yield from self._record_batches(list(data))


class PredictionService:
    """Thread-safe concurrent serving (reference: optim/PredictionService.scala:56).

    ``num_threads`` bounds in-flight requests like the reference's instance
    pool (:64-77); all threads share one compiled XLA executable, which is
    the TPU-native equivalent of pooled clones sharing weights.

    ``coalesce=True`` replaces the serialize-through-the-semaphore data
    path with a ``ServingEngine``: concurrent requests coalesce into one
    padded, bucketed device batch per dispatch tick (``max_batch_size``
    / ``max_wait_ms``), optionally sharded over ``mesh``'s data axis --
    the high-throughput path.  NOTE: with coalescing, ``num_threads`` no longer bounds
    in-flight requests -- admission control moves to the engine's
    bounded queue (``queue_capacity``, default 1024, back-pressuring
    ``submit``), because queued requests are cheap host-side rows, not
    per-request device dispatches.  Call ``close()`` (or use as a
    context manager) to stop the engine's dispatcher thread.
    """

    def __init__(self, model, num_threads: int = 4, compute_dtype=None,
                 coalesce: bool = False, max_batch_size: int = 16,
                 max_wait_ms: float = 2.0, **engine_kw):
        self.predictor = Predictor(model, compute_dtype=compute_dtype)
        self._sem = threading.Semaphore(num_threads)
        self.engine = None
        if coalesce:
            from bigdl_tpu.serving import ServingEngine

            self.engine = ServingEngine(
                model, max_batch_size=max_batch_size,
                max_wait_ms=max_wait_ms, compute_dtype=compute_dtype,
                **engine_kw)
        elif engine_kw:
            raise TypeError(
                f"unexpected arguments {sorted(engine_kw)}: engine options "
                "require coalesce=True")

    def predict(self, activity):
        """Single-activity request -> output activity
        (reference: PredictionService.predict :79-126).

        A failure inside the guarded region (bad payload, device error)
        must both RELEASE the concurrency permit and surface to the
        caller -- a leaked permit would deadlock the service after
        num_threads failures.  The explicit acquire/try-finally makes
        that lifetime obvious to auditors, and the failing-batch
        concurrency test pins the contract (the previous ``with
        self._sem`` released on exception too; this is a clarity
        rewrite plus a regression pin, not a behavior change)."""
        if self.engine is not None:
            return self.engine.predict(activity)
        self._sem.acquire()
        try:
            with span("serve_request"):
                x = jax.tree.map(lambda a: jnp.asarray(a)[None], activity)
                y = self.predictor._eval(
                    self.predictor.model.parameters()[0],
                    self.predictor.model.state(), x)
                return jax.tree.map(lambda a: np.asarray(a)[0], y)
        finally:
            self._sem.release()

    def predict_bytes(self, data: bytes) -> bytes:
        """Byte-array request/response API (reference :128-255 uses protobuf
        Activity).  Format: npz-serialized arrays."""
        import io

        with io.BytesIO(data) as f:
            arrs = np.load(f, allow_pickle=False)
            activity = tuple(arrs[k] for k in sorted(arrs.files))
        if len(activity) == 1:
            activity = activity[0]
        out = self.predict(activity)
        buf = io.BytesIO()
        if isinstance(out, tuple):
            np.savez(buf, **{f"out{i}": np.asarray(o)
                             for i, o in enumerate(out)})
        else:
            np.savez(buf, out0=np.asarray(out))
        return buf.getvalue()

    def precompile(self, buckets=None, example_feature=None):
        """Warm the coalescing engine's bucket ladder (no-op for the
        semaphore path, whose single batch-1 shape warms on first
        use)."""
        if self.engine is not None:
            return self.engine.precompile(buckets, example_feature)
        return 0

    def close(self):
        if self.engine is not None:
            self.engine.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def evaluate(model, dataset, methods, compute_dtype=None):
    """model.evaluate facade (reference: AbstractModule.evaluate :855)."""
    from bigdl_tpu.optim.local_optimizer import validate

    return validate(model, model.parameters()[0], model.state(), dataset,
                    methods, compute_dtype)
