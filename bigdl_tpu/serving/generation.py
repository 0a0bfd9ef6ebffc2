"""Autoregressive generation serving: compiled KV-cache decode steps
and a slot-based continuous-batching scheduler.

The serving stack could only run FULL forwards: serving a transformer
token-by-token meant re-running the O(L^2) forward over the whole
prefix for every new token.  This module restructures the computation
so the compiler sees O(1) incremental work per token (the TVM lesson,
arxiv 1802.04799): the model layer's KV cache (``TransformerLM
.init_cache`` / ``apply(cache=, pos=)``, nn/attention.py) turns a
decode step into one token's projections plus a masked attention read
over fixed-shape buffers, and this module turns THAT into a serving
loop with a closed executable set:

- ``generate_steps(model)`` -- the jitted (prefill, decode) pair,
  compiled once per model and cached on the instance like
  ``optim.validation.compiled_eval_step``.  Both steps DONATE the slot
  cache, so XLA updates the K/V buffers in place instead of copying
  ``slots x max_len`` of cache every tick.
- ``GenerateScheduler`` -- continuous batching over a fixed pool of
  decode slots: prefill ticks admit waiting prompts into free slots
  (batch-bucketed and prompt-length-bucketed through the same
  ``BucketLadder`` machinery the eval path uses, so the compiled-shape
  set is closed and warmable); decode ticks advance EVERY occupied
  slot one token in a single fixed-shape step.  Sequences join and
  leave slots mid-flight without recompiling anything: the cache
  batch axis never changes, and a vacated row is simply garbage the
  per-row frontier mask keeps invisible until the next occupant's
  prefill overwrites it.  Row ``slots`` (one past the pool) is a TRASH
  slot: prefill padding rows scatter their K/V there, so a
  partially-filled prefill bucket can never corrupt a live sequence.
- ``GenerateFuture`` -- the streaming per-request handle: tokens are
  pushed as ticks complete (``stream()`` yields them live);
  ``result()`` waits for EOS / ``max_new_tokens`` and returns the full
  generated list.

Every tick lands as a ``kind:"inference"`` telemetry event stamped
with ``tick_kind`` ("prefill"/"decode"), ``tokens`` emitted, and slot
occupancy -- the fields behind ``bigdl_serving_tokens_total`` and the
slot-utilization gauge (docs/observability.md, "Serving telemetry").
In THIS scheduler decoding is greedy (argmax in-jit, so only token
ids cross the host boundary each tick).

``PagedGenerateScheduler`` (below) is the memory-scale successor: the
same dispatcher contract, but the cache is a PAGED block pool
addressed through per-sequence block tables (serving/paging.py) --
prefix blocks shared across requests, long prompts prefilled in
fixed-size chunks interleaved with decode ticks, and temperature /
top-k / top-p sampling drawn inside the decode step
(serving/sampling.py).  The contiguous scheduler stays as the greedy
baseline the paged one is compared against (docs/performance.md, "Paged
KV cache").
"""

import collections
import itertools
import logging
import os
import queue
import threading
import time
from concurrent.futures import Future, TimeoutError as FutureTimeoutError
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn.generation_state import (BLOCK, COUNTER, SLOT,
                                           kinds as state_kinds)
from bigdl_tpu.observability.spans import now_ns, record_span, span, to_ns
from bigdl_tpu.serving.buckets import BucketLadder

log = logging.getLogger("bigdl_tpu.serving")

#: ``request_id`` of the recorder's ``request`` spans: one per future
_REQUEST_IDS = itertools.count(1)


def _scatter_rows(slot_leaf, frag_leaf, slot_ids, t):
    """Write a prefill fragment's rows into the slot cache at
    ``slot_ids``, first ``t`` positions.  K/V leaves are ``(batch,
    max_len, heads, head_dim)`` -- the batch axis sits at ``ndim - 4``,
    which also lands on the right axis for the scan-stacked layout's
    extra leading layer dim."""
    if slot_leaf.ndim == 4:
        return slot_leaf.at[slot_ids, :t].set(frag_leaf)
    return slot_leaf.at[:, slot_ids, :t].set(frag_leaf)


def generate_steps(model, cache_dtype=jnp.float32):
    """The jitted ``(prefill, decode)`` pair for ``model``, compiled
    once per (model, cache dtype) and cached on the instance (same
    lifetime story as ``compiled_eval_step``: dropping the model drops
    its executables).

    - ``prefill(params, slot_cache, tokens (B, T), lengths (B,),
      slot_ids (B,)) -> (first_tokens (B,), new_slot_cache)``: one
      ragged-prompt prefill -- runs the cached forward over the padded
      prompt batch, scatters the K/V fragment into the slot cache rows
      named by ``slot_ids``, and reads each row's first generated
      token at its TRUE ``length - 1`` (padding rows point at the
      trash slot and are discarded).
    - ``decode(params, slot_cache, tokens (S,), pos (S,)) ->
      (next_tokens (S,), new_slot_cache)``: one fixed-shape step over
      the whole pool.

    Both donate the slot cache (argument 1): steady-state decode moves
    one token's activations, not the cache.
    """
    cache = model.__dict__.setdefault("_compiled_generate_steps", {})
    key = np.dtype(cache_dtype).name
    fns = cache.get(key)
    if fns is not None:
        return fns

    def prefill(params, slot_cache, tokens, lengths, slot_ids):
        n, t = tokens.shape
        local = model.init_cache(n, t, cache_dtype)
        logits, frag = model.apply(params, (), tokens, cache=local)
        idx = jnp.clip(lengths.astype(jnp.int32) - 1, 0, t - 1)
        row = jnp.take_along_axis(
            logits, idx[:, None, None], axis=1)[:, 0]
        first = jnp.argmax(row, axis=-1).astype(jnp.int32)
        new = jax.tree.map(
            lambda sc, fr: _scatter_rows(sc, fr, slot_ids, t),
            slot_cache, frag)
        return first, new

    def decode(params, slot_cache, tokens, pos):
        logits, new = model.apply(params, (), tokens[:, None],
                                  cache=slot_cache, pos=pos)
        nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        return nxt, new

    fns = (jax.jit(prefill, donate_argnums=(1,)),
           jax.jit(decode, donate_argnums=(1,)))
    cache[key] = fns
    return fns


class GenerateFuture(Future):
    """Per-request generation handle.  ``result(timeout)`` returns the
    full generated token list (EOS included when hit); ``stream()``
    yields tokens LIVE as decode ticks complete.  Once finished,
    ``finish_reason`` ("eos" / "length"), ``prompt_len`` and the
    end-to-end ``latency_s`` are set."""

    def __init__(self, prompt_len: int, max_new_tokens: int,
                 eos_id: Optional[int]):
        super().__init__()
        self.prompt_len = int(prompt_len)
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id
        self.finish_reason: Optional[str] = None
        self.latency_s: Optional[float] = None
        #: latency_s split at slot admission: time queued waiting for a
        #: free decode slot vs time actually prefilling/decoding (one
        #: mixed number hides queue pressure behind decode speed)
        self.queue_wait_s: Optional[float] = None
        self.decode_s: Optional[float] = None
        #: the request's stamps (``perf_counter`` seconds): submit, slot
        #: admission, first streamed token.  The recorder's ``request``
        #: span and every latency field are made of these and of one
        #: more reading when the future finishes
        self._t_submit = time.perf_counter()
        self._t_admit: Optional[float] = None
        self._t_first: Optional[float] = None
        self.request_id = next(_REQUEST_IDS)
        #: prefill device calls this prompt took (chunks, paged; else 1)
        self._chunks = 0
        #: sampled TraceContext from the submitting engine, or None
        self._trace = None
        #: SamplingParams for this request (None = greedy argmax);
        #: only the paged scheduler accepts non-greedy settings
        self.sampling = None
        #: prompt positions served straight from the prefix cache
        #: (paged scheduler only; 0 means every position was computed)
        self.prefix_hit_tokens = 0
        #: device bytes of per-slot state the request held (a model with
        #: such leaves; its ``request`` span then also says how many rows
        #: it wrote to the per-token cache), or None
        self._state_bytes = None
        self._stream: "queue.Queue" = queue.Queue()
        #: set by GenerateScheduler._abandon on a CLAIMED request: the
        #: dispatcher evicts the sequence at the next tick boundary
        self._abandoned = False

    def stream(self, timeout: Optional[float] = None):
        """Yield generated token ids as they are produced.  ``timeout``
        bounds the WHOLE stream; a tick that errors re-raises here."""
        deadline = None if timeout is None \
            else time.perf_counter() + timeout
        while True:
            remaining = None if deadline is None \
                else deadline - time.perf_counter()
            if remaining is not None and remaining <= 0:
                raise FutureTimeoutError(
                    f"token stream timed out after {timeout}s")
            try:
                item = self._stream.get(timeout=remaining)
            except queue.Empty:
                raise FutureTimeoutError(
                    f"token stream timed out after {timeout}s") from None
            if item is None:                      # completion sentinel
                return
            if isinstance(item, BaseException):
                raise item
            yield item


class _Slot:
    """One occupied decode slot: the request's future, its token tally
    and the cache frontier (``pos`` = where the NEXT token's K/V will
    be written; ``last`` = the token that decode step feeds in)."""

    __slots__ = ("fut", "tokens", "last", "pos")

    def __init__(self, fut, first_token, pos):
        self.fut = fut
        self.tokens = [first_token]
        self.last = first_token
        self.pos = pos


class GenerateScheduler:
    """Slot-based continuous batching over one model's KV cache.

    ``slots`` decode slots plus one trash row share a single
    fixed-shape cache (``model.init_cache(slots + 1, max_len)``).  The
    dispatcher thread alternates: a PREFILL tick admits up to
    ``len(free slots)`` waiting prompts (batch padded to the slot
    ladder, prompts padded to the prompt-length ladder), a DECODE tick
    advances every occupied slot one token.  Finished sequences free
    their slot immediately -- the next prefill reuses it without any
    recompile, because nothing about the compiled shapes depends on
    WHICH slots are live (the acceptance contract: zero new compiles
    after ``precompile()`` across a mixed-length closed-loop workload,
    pinned in tests/test_decode.py).

    ``params_fn`` is read once per tick, so an engine-level
    ``refresh_params`` hot-swap takes effect on the next tick; a
    sequence mid-flight finishes with its earlier tokens' K/V from the
    old weights (documented in docs/performance.md -- the alternative,
    draining generation for every swap, is a worse availability
    trade).
    """

    def __init__(self, model, slots: int = 8, max_len: Optional[int] = None,
                 prompt_ladder: Optional[BucketLadder] = None,
                 queue_capacity: int = 1024, cache_dtype=jnp.float32,
                 telemetry=None, params_fn=None, admission_check=None,
                 exhausted_hook=None, name: str = "generate"):
        if slots < 1:
            raise ValueError(f"need at least 1 decode slot, got {slots}")
        self.model = model
        self.slots = int(slots)
        model_max = getattr(model, "max_len", None)
        self.max_len = int(model_max if max_len is None
                           else min(max_len, model_max or max_len))
        self.queue_capacity = int(queue_capacity)
        self.telemetry = telemetry
        #: optional callable run under THIS scheduler's lock right
        #: before a request enqueues (raising refuses admission): the
        #: owning engine injects its draining/closed check here, so an
        #: engine.drain() that observed an idle scheduler can never
        #: race a generate() that already passed the engine-side check
        self._admission_check = admission_check
        #: optional callable(exc) invoked when the KV pool sheds a
        #: request (``BlockPoolExhausted``): the owning engine points
        #: this at its MemoryLedger's forensic dump so the first
        #: exhaustion leaves a durable memory_dump event
        self._exhausted_hook = exhausted_hook
        self._params = params_fn or (lambda: model.weights())
        # prompt lengths round up this ladder (rung = the padded prefill
        # T); a COPY like the engine's batch ladder, so growth stays ours
        self.prompt_ladder = prompt_ladder.copy() \
            if prompt_ladder is not None \
            else BucketLadder(self.max_len,
                              min_size=min(8, self.max_len))
        if self.prompt_ladder.max > self.max_len:
            raise ValueError(
                f"prompt ladder's largest rung {self.prompt_ladder.max} "
                f"exceeds the cache max_len {self.max_len}")
        # admission counts round up this one (prefill batch rungs)
        self.batch_ladder = BucketLadder(self.slots)
        #: slot pool + 1 trash row (prefill padding rows scatter there)
        self._trash = self.slots
        self._cache_dtype = cache_dtype
        self._setup_steps()      # compiled steps + self._cache (the
        #                          paged subclass swaps in pool + tables)
        self._slots = [None] * self.slots
        self._free = collections.deque(range(self.slots))
        self._pending = collections.deque()
        # requests popped off the queue but not yet slotted (or failed):
        # the engine predict path's _in_tick equivalent, so drain() can
        # wait for TRUE quiescence instead of missing a request that is
        # mid-prefill between queue-pop and slot assignment
        self._in_flight = 0
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._not_full = threading.Condition(self._lock)
        self._idle = threading.Condition(self._lock)
        self._running = True
        self._tick = 0
        self._served = 0
        self._tokens_out = 0
        self._dispatcher = threading.Thread(
            target=self._loop, name=f"bigdl-serving-{name}", daemon=True)
        self._dispatcher.start()

    #: set by PagedGenerateScheduler -- the contiguous scheduler's
    #: compiled steps only argmax, so non-greedy sampling is refused at
    #: submit instead of silently decoding greedy
    supports_sampling = False

    def _setup_steps(self):
        """Compile the step pair and allocate the device cache; the
        paged subclass overrides this with the pool + allocator."""
        if not hasattr(self.model, "init_cache"):
            raise TypeError(
                f"{type(self.model).__name__} has no init_cache(): "
                f"generation needs a KV-cache decode mode (TransformerLM "
                f"has one)")
        self._prefill_fn, self._decode_fn = generate_steps(
            self.model, self._cache_dtype)
        self._cache = self.model.init_cache(self.slots + 1, self.max_len,
                                            self._cache_dtype)

    def _reset_pool(self):
        """Reallocate the device cache after a failed (donating) tick."""
        self._cache = self.model.init_cache(self.slots + 1, self.max_len,
                                            self._cache_dtype)

    def cache_bytes(self) -> int:
        """Device bytes the KV cache actually holds (the same reading
        on both schedulers)."""
        return int(sum(leaf.size * leaf.dtype.itemsize
                       for leaf in jax.tree.leaves(self._cache)))

    # ----- request surface -------------------------------------------------- #
    def submit(self, prompt, max_new_tokens: int = 16,
               eos_id: Optional[int] = None,
               timeout: Optional[float] = None,
               trace=None, sampling=None) -> GenerateFuture:
        """Enqueue one prompt (1-D int token ids); returns the
        streaming future.  Blocks when ``queue_capacity`` requests are
        pending (``timeout`` bounds the wait, like engine.submit)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must hold at least one token")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if prompt.size + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the cache max_len "
                f"{self.max_len}; raise decode_max_len or trim the "
                f"request")
        if sampling is not None and not sampling.greedy \
                and not self.supports_sampling:
            raise ValueError(
                "temperature/top-k/top-p sampling needs the paged "
                "scheduler (ServingEngine kv_cache='paged'); the "
                "contiguous pool decodes greedy only")
        fut = GenerateFuture(prompt.size, max_new_tokens, eos_id)
        fut._trace = trace
        fut.sampling = sampling
        deadline = None if timeout is None \
            else time.perf_counter() + timeout
        with self._lock:
            if not self._running:
                raise RuntimeError("generation scheduler is closed")
            while self._running and \
                    len(self._pending) >= self.queue_capacity:
                remaining = None if deadline is None \
                    else deadline - time.perf_counter()
                if remaining is not None and remaining <= 0:
                    raise FutureTimeoutError(
                        f"generate submit timed out after {timeout}s: "
                        f"queue full ({self.queue_capacity} pending)")
                self._not_full.wait(timeout=remaining)
            if not self._running:
                raise RuntimeError("generation scheduler is closed")
            if self._admission_check is not None:
                self._admission_check()
            self._pending.append((prompt, fut))
            self._work.notify()
        return fut

    def _active(self):
        return [(i, s) for i, s in enumerate(self._slots) if s is not None]

    def _busy(self):
        """Work the dispatcher still owes: what ``_loop``, ``drain`` and
        ``close`` wait for besides the queue."""
        return bool(self._active())

    def stats(self):
        with self._lock:
            active = len(self._active())
            return {"pending": len(self._pending),
                    "in_flight": self._in_flight,
                    "slots": self.slots, "slots_active": active,
                    "ticks": self._tick, "served": self._served,
                    "tokens": self._tokens_out,
                    "running": self._running}

    # ----- warmup ----------------------------------------------------------- #
    def precompile(self) -> int:
        """Compile the whole generation shape set before traffic: the
        one decode executable plus every (admission rung x prompt-length
        rung) prefill.  Warmup runs on DUMMY caches (zeros_like the
        real one -- identical shapes key identical executables) so the
        live cache is never donated away.  Returns backend compiles
        performed."""
        from bigdl_tpu.observability.watchdogs import backend_compile_count

        params = self._params()
        before = backend_compile_count()
        dummy = jax.tree.map(jnp.zeros_like, self._cache)
        s = self.slots + 1
        nxt, dummy = self._decode_fn(params, dummy,
                                     np.zeros((s,), np.int32),
                                     np.zeros((s,), np.int32))
        jax.block_until_ready(nxt)
        for b in self.batch_ladder:
            for t in self.prompt_ladder:
                first, dummy = self._prefill_fn(
                    params, dummy, np.zeros((int(b), int(t)), np.int32),
                    np.ones((int(b),), np.int32),
                    np.full((int(b),), self._trash, np.int32))
                jax.block_until_ready(first)
        return backend_compile_count() - before

    # ----- dispatcher ------------------------------------------------------- #
    def _loop(self):
        while True:
            with self._lock:
                if self._running and not self._pending \
                        and not self._busy():
                    with span("dispatcher_idle"):
                        while self._running and not self._pending \
                                and not self._busy():
                            self._idle.notify_all()
                            self._work.wait()
                if not self._running and not self._pending \
                        and not self._busy():
                    self._idle.notify_all()
                    return
            with span("tick", tick=self._tick,
                      slots_total=self.slots) as tick:
                self._iterate(tick)

    def _iterate(self, tick):
        """One dispatcher iteration: admission, then the device work."""
        admit = []
        try:
            with span("admit") as adm:
                with self._lock:
                    if self._pending and self._free:
                        take = min(len(self._free), len(self._pending))
                        admit = [self._pending.popleft()
                                 for _ in range(take)]
                        self._in_flight += len(admit)
                        self._not_full.notify_all()
                    qdepth = len(self._pending)
                # a cancelled future's prompt is dropped here (its slot
                # was never assigned); claiming moves PENDING->RUNNING
                # so result-setting can't race a caller's cancel().  A
                # dropped future still gets the stream sentinel -- a
                # consumer blocked in stream() must see the end, not
                # hang on a request nobody will ever decode
                claimed = []
                for p, f in admit:
                    if f.set_running_or_notify_cancel():
                        claimed.append((p, f))
                    else:
                        f._stream.put(None)
                self._sweep_abandoned()
                if self._cache is None and claimed:
                    # a failed tick lost the pool and could not rebuild it
                    try:
                        self._reset_pool()
                    except Exception as lost:
                        for _p, f in claimed:
                            self._fail_request(f, lost)
                        claimed = []
                placed = self._admit(claimed) if claimed else None
                adm.set(requests=len(claimed))
            tick.set(queue_depth=qdepth, **self._occupancy(claimed))
            # by the time this returns, every claimed request is
            # slotted (visible to _active) or failed
            self._run_device(claimed, placed, qdepth)
        except Exception:
            # defensive: per-tick failures are already surfaced on
            # the affected futures; this keeps an unexpected
            # scheduler bug from silently killing the dispatcher
            log.exception("generation scheduler tick failed")
        finally:
            with self._lock:
                self._in_flight -= len(admit)
                if not self._pending and not self._in_flight \
                        and not self._busy():
                    self._idle.notify_all()

    def _admit(self, reqs):
        """Give each claimed request a free slot (queue wait ends here);
        returns the slot ids for ``_run_prefill``."""
        t_admit = time.perf_counter()
        with self._lock:
            slots = [self._free.popleft() for _ in reqs]
        for _p, f in reqs:
            f._t_admit = t_admit
        return slots

    def _occupancy(self, claimed):
        """The ``tick`` span's view of the slots once admission is done."""
        return {"slots_decoding": len(self._active()),
                "slots_prefilling": len(claimed)}

    def _run_device(self, claimed, slots, qdepth):
        if claimed:
            self._run_prefill(claimed, slots, qdepth)
        if self._active():
            self._run_decode_tick(qdepth)

    def _compiles(self):
        if self.telemetry is None:
            return None
        from bigdl_tpu.observability.watchdogs import backend_compile_count

        return backend_compile_count()

    def _run_prefill(self, reqs, slots, qdepth):
        execs_before = self._compiles()
        n = len(reqs)
        with span("prefill_prep", rows=n, slots_total=self.slots) as prep:
            bucket = self.batch_ladder.bucket_for(n) \
                or self.batch_ladder.add(n)
            longest = max(int(p.size) for p, _ in reqs)
            t_pad = self.prompt_ladder.bucket_for(longest) \
                or self.prompt_ladder.add(longest)
            tokens = np.zeros((bucket, t_pad), np.int32)
            lengths = np.ones((bucket,), np.int32)
            slot_ids = np.full((bucket,), self._trash, np.int32)
            for i, (p, _f) in enumerate(reqs):
                tokens[i, : p.size] = p
                lengths[i] = p.size
                slot_ids[i] = slots[i]
            prep.set(bucket=int(bucket),
                     prompt_tokens=int(lengths[:n].sum()))
        try:
            with span("generate_prefill", tick=self._tick, records=n):
                with span("launch"):
                    first, self._cache = self._prefill_fn(
                        self._params(), self._cache, tokens, lengths,
                        slot_ids)
                with span("fetch"):
                    first = np.asarray(first)        # host sync
        except Exception as e:
            log.exception("prefill tick failed (%d prompts)", n)
            self._tick_failed(e, [f for _p, f in reqs], slots)
            return
        done_lat = []
        with span("deliver", tokens=n) as dlv:
            for i, (p, f) in enumerate(reqs):
                f._chunks = 1
                slot = _Slot(f, int(first[i]), pos=int(p.size))
                self._slots[slots[i]] = slot
                self._deliver(slots[i], slot, done_lat)
            dlv.set(finished=len(done_lat))
        self._tick += 1
        self._record_tick("prefill", prep.start_ns, dlv.end_ns, records=n,
                          tokens=n, bucket=int(bucket),
                          prompt_bucket=int(t_pad), qdepth=qdepth,
                          execs_before=execs_before, latencies=done_lat,
                          riders=[f for _p, f in reqs])

    def _run_decode_tick(self, qdepth):
        execs_before = self._compiles()
        s = self.slots + 1
        active = self._active()
        with span("decode_prep", rows=len(active),
                  slots_total=self.slots) as prep:
            tokens = np.zeros((s,), np.int32)
            pos = np.zeros((s,), np.int32)
            for i, slot in active:
                tokens[i] = slot.last
                pos[i] = slot.pos
        try:
            with span("generate_decode", tick=self._tick,
                      records=len(active)):
                with span("launch"):
                    nxt, self._cache = self._decode_fn(
                        self._params(), self._cache, tokens, pos)
                with span("fetch"):
                    nxt = np.asarray(nxt)            # host sync
        except Exception as e:
            log.exception("decode tick failed (%d slots)", len(active))
            self._tick_failed(e, [], [])
            return
        done_lat = []
        with span("deliver", tokens=len(active)) as dlv:
            for i, slot in active:
                slot.pos += 1
                slot.last = int(nxt[i])
                slot.tokens.append(slot.last)
                self._deliver(i, slot, done_lat)
            dlv.set(finished=len(done_lat))
        self._tick += 1
        self._record_tick("decode", prep.start_ns, dlv.end_ns, records=0,
                          tokens=len(active), qdepth=qdepth,
                          execs_before=execs_before, latencies=done_lat,
                          slots_before=len(active),
                          riders=[slot.fut for _i, slot in active])

    def _tick_failed(self, e, futs, extra_free):
        """A failed tick is a POOL loss, not just this tick's: both
        compiled steps DONATE the slot cache, and jax invalidates
        donated buffers at call time -- after a runtime failure
        ``self._cache`` points at deleted arrays, so every live
        sequence's K/V is gone with it.  Fail the tick's own futures
        AND every still-active slot honestly, then reallocate a fresh
        zero cache so the scheduler keeps serving NEW prompts instead
        of raising 'Array has been deleted' forever."""
        failed = list(futs)
        for i, slot in self._active():
            failed.append(slot.fut)
            self._release_slot(i, slot)
        with self._lock:
            self._free.extend(extra_free)
        try:
            self._reset_pool()
        except Exception as lost:
            # the pool cannot be rebuilt (the device has no room left, or
            # is gone): nothing can be decoded until it can, so nobody
            # may be left waiting on it.  ``_iterate`` tries again before
            # it admits anything
            log.exception("the generation pool could not be rebuilt")
            self._release_cache()
            with self._lock:
                waiting = [f for _p, f in self._pending]
                self._pending.clear()
                self._not_full.notify_all()
            for f in waiting:
                if f.set_running_or_notify_cancel():
                    self._fail_request(f, lost)
                else:
                    f._stream.put(None)
        for f in failed:
            if not f.done():
                self._fail_request(f, e)

    def _abandon(self, fut):
        """Give up on a generation nobody will read (the sibling of
        ``ServingEngine._abandon``).  Still pending: cancel, free its
        queue slot now, end the stream.  Already CLAIMED: mark it for
        eviction -- the dispatcher frees the decode slot at the next
        tick boundary (``_sweep_abandoned``) instead of decoding the
        rest of ``max_new_tokens`` into a slot nobody reads, which is
        what lets a fleet deadline-retry on a sibling without
        double-booking decode slots for the whole sequence."""
        if not fut.cancel():         # already decoding (or done)
            fut._abandoned = True
            return
        fut._stream.put(None)
        with self._lock:
            for entry in self._pending:
                if entry[1] is fut:
                    self._pending.remove(entry)
                    self._not_full.notify()
                    break

    def _sweep_abandoned(self):
        """Evict abandoned mid-flight sequences: free the slot and
        resolve the future with the tokens decoded so far (a PARTIAL
        result, ``finish_reason: "abandoned"`` -- a success as far as
        replica health accounting goes: the replica worked, the caller
        left)."""
        for i, slot in self._active():
            fut = slot.fut
            if not fut._abandoned or fut.done():
                continue
            self._release_slot(i, slot)
            self._finish_request(fut, slot.tokens, "abandoned")

    def _release_slot(self, index, slot):
        """Return a slot to the free pool (every eviction path funnels
        here; the paged subclass also releases the sequence's blocks)."""
        self._slots[index] = None
        with self._lock:
            self._free.append(index)

    def _deliver(self, index, slot, done_lat):
        """Stream the slot's newest token; complete + free the slot on
        EOS or the request's token budget."""
        fut = slot.fut
        tok = slot.tokens[-1]
        if len(slot.tokens) == 1:
            fut._t_first = time.perf_counter()
        fut._stream.put(tok)
        reason = None
        if fut.eos_id is not None and tok == fut.eos_id:
            reason = "eos"
        elif len(slot.tokens) >= fut.max_new_tokens:
            reason = "length"
        if reason is None:
            return
        self._release_slot(index, slot)
        done_lat.append(fut)
        self._served += 1
        self._finish_request(fut, slot.tokens, reason)

    def _finish_request(self, fut, tokens, reason):
        """End a request with the tokens it has: the latency fields, the
        stream's sentinel, the result, and its records."""
        fut.finish_reason = reason
        self._stamp_request(fut, len(tokens))
        fut._stream.put(None)
        fut.set_result(list(tokens))
        self._record_request_trace(fut, len(tokens))

    def _fail_request(self, fut, e):
        """End a request with an exception (a lost pool, a shed)."""
        fut.finish_reason = "error:" + type(e).__name__
        self._stamp_request(fut, 0)
        fut._stream.put(e)
        fut._stream.put(None)
        fut.set_exception(e)

    @staticmethod
    def _stamp_request(fut, n_tokens):
        """One more clock reading, when the request ends: with the stamps
        the future already holds it gives ``latency_s`` and its split
        (admit stamp missing => the whole latency was a wait), and the
        recorder's ``request`` span, whose three parts add up to it."""
        now = time.perf_counter()
        admit = fut._t_admit if fut._t_admit is not None else now
        first = fut._t_first if fut._t_first is not None else now
        fut.latency_s = now - fut._t_submit
        fut.queue_wait_s = max(0.0, admit - fut._t_submit)
        fut.decode_s = max(0.0, now - admit)
        record_span(
            "request", to_ns(fut._t_submit), to_ns(now),
            request_id=fut.request_id, prompt_tokens=fut.prompt_len,
            new_tokens=n_tokens, prefix_hit_tokens=fut.prefix_hit_tokens,
            chunks=fut._chunks,
            queue_wait_ns=int(fut.queue_wait_s * 1e9),
            prefill_ns=int(max(0.0, first - admit) * 1e9),
            decode_ns=int(max(0.0, now - first) * 1e9),
            finish_reason=fut.finish_reason,
            **({} if fut._state_bytes is None else
               {"state_bytes": fut._state_bytes,
                "latent_tokens": fut.prompt_len + n_tokens}))

    def _record_request_trace(self, fut, n_tokens):
        """Completion span for one traced generation -- the decode-side
        mirror of the fleet's root span, carrying the queue-wait vs
        decode split and every token's tick story via the tick links."""
        if fut._trace is None or self.telemetry is None:
            return
        emit = getattr(self.telemetry, "record_trace", None)
        if emit is None:
            return
        try:
            kw = {}
            if fut.prefix_hit_tokens:
                # how much of this request's prompt the prefix cache
                # served -- ties a fast queue_wait/decode split to its
                # cause in the trace story
                kw["prefix_hit_tokens"] = fut.prefix_hit_tokens
            emit("generate_request", fut._trace.child(),
                 to_ns(fut._t_submit) * 1e-9, fut.latency_s or 0.0,
                 queue_wait_s=round(fut.queue_wait_s or 0.0, 6),
                 decode_s=round(fut.decode_s or 0.0, 6),
                 tokens=n_tokens, finish_reason=fut.finish_reason, **kw)
        except Exception:
            log.exception("generation trace record failed")

    def _record_tick(self, kind, start_ns, end_ns, records, tokens, qdepth,
                     execs_before, latencies, bucket=None,
                     prompt_bucket=None, slots_before=None,
                     riders=None, extra=None):
        """The tick's telemetry event and trace record; its times are the
        tick's spans' (start of its prep to end of its deliver)."""
        self._tokens_out += tokens
        if self.telemetry is None:
            return
        try:
            wall = (end_ns - start_ns) * 1e-9
            active = slots_before if slots_before is not None \
                else len(self._active())
            event = dict(step=self._tick, wall_s=wall, tick_kind=kind,
                         records=records, tokens=tokens,
                         tokens_per_s=tokens / max(wall, 1e-9),
                         slots_active=active, slots_total=self.slots,
                         queue_depth=qdepth,
                         queue_capacity=self.queue_capacity)
            if bucket is not None:
                event["bucket"] = bucket
                event["batch_fill"] = records / bucket
                event["pad_waste"] = (bucket - records) / bucket
            if extra:
                # paged-pool occupancy + prefix-hit fields (the metrics
                # bridge turns these into bigdl_serving_kv_blocks{state}
                # and bigdl_serving_prefix_hits_total)
                event.update(extra)
            if prompt_bucket is not None:
                event["prompt_bucket"] = prompt_bucket
            if latencies:
                # a DISTINCT field from predict's request_latency_s: a
                # multi-token generation is seconds where a predict is
                # milliseconds, and one mixed series would burn any
                # predict-tuned latency SLO (and its canary auto-
                # rollback) on perfectly healthy generate traffic.
                # queue-wait and decode time land as SEPARATE series:
                # one merged number read as "slow decode" when the real
                # story was slot starvation
                event["generate_latency_s"] = [round(f.latency_s, 6)
                                               for f in latencies]
                event["generate_queue_wait_s"] = [
                    round(f.queue_wait_s or 0.0, 6) for f in latencies]
                event["generate_decode_s"] = [
                    round(f.decode_s or 0.0, 6) for f in latencies]
                traces = [f._trace.trace_id if f._trace is not None
                          else None for f in latencies]
                if any(t is not None for t in traces):
                    # parallel to generate_latency_s: the metrics
                    # bridge zips the two for histogram exemplars
                    event["generate_traces"] = traces
            if riders:
                tids = [f._trace.trace_id for f in riders
                        if f._trace is not None]
                if tids:
                    # which traced sequences were RESIDENT this tick:
                    # obs_report attributes slot occupancy by trace
                    event["trace_ids"] = tids
            after = self._compiles()
            if after is not None and after - execs_before > 0:
                # nonzero after precompile() = a generation shape leak
                event["compiles"] = after - execs_before
            self.telemetry.record("inference", **event)
            self._record_tick_trace(kind, start_ns, wall, riders, records,
                                    tokens)
        except Exception:
            log.exception("generation telemetry record failed (tick %d)",
                          self._tick)

    def _record_tick_trace(self, kind, start_ns, wall, riders, records,
                           tokens):
        """One span per tick with links to every traced sequence that
        rode it -- the continuous-batching shape (one tick, N resident
        requests) is a links relationship, not parent/child, because
        the tick belongs to ALL of them equally."""
        emit = getattr(self.telemetry, "record_trace", None)
        if emit is None or not riders:
            return
        links = [f._trace.trace_id for f in riders
                 if f._trace is not None]
        if not links:
            return
        from bigdl_tpu.observability.tracing import TraceContext

        emit("%s_tick" % kind, TraceContext.mint(),
             start_ns * 1e-9, wall, links=links, tick=self._tick,
             records=records, tokens=tokens)

    # ----- lifecycle -------------------------------------------------------- #
    def drain(self, timeout: Optional[float] = None) -> bool:
        """Wait until no generation work is pending or mid-flight.
        ADMISSION gating belongs to the owning engine (its ``drain()``
        closes ``generate()`` before calling this); returns False when
        ``timeout`` passes with sequences still decoding."""
        deadline = None if timeout is None \
            else time.perf_counter() + timeout
        with self._lock:
            self._work.notify_all()
            while self._pending or self._in_flight or self._busy():
                remaining = None if deadline is None \
                    else deadline - time.perf_counter()
                if remaining is not None and remaining <= 0:
                    return False
                self._idle.wait(timeout=remaining)
        return True

    def close(self, timeout: Optional[float] = 10.0):
        """Stop the dispatcher, give the device cache back and let go of
        the owner's hooks.  The owning engine and this scheduler refer
        to each other (the hooks are its bound methods); left alone, a
        closed engine's cache would stay in device memory until a cycle
        collection, and the next engine of the process would not fit
        beside it."""
        with self._lock:
            self._running = False
            self._work.notify_all()
            self._not_full.notify_all()
        self._dispatcher.join(timeout)
        if not self._dispatcher.is_alive():
            self._release_cache()
            self._admission_check = self._exhausted_hook = None

    def _release_cache(self):
        self._cache = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def _state_kinds(model, cache_dtype):
    """The kinds of the model's generation-state leaves, as a tree like
    the pool's (nn/generation_state.py): every model with a paged cache
    declares them (``paged_state_spec``)."""
    return state_kinds(model.paged_state_spec(cache_dtype))


def _has_slot_leaves(kinds):
    return SLOT in jax.tree.leaves(kinds)


def paged_generate_steps(model, cache_dtype=jnp.float32):
    """The jitted step triple for PAGED generation, compiled once per
    (model, cache dtype) and cached on the instance like
    ``generate_steps``:

    - ``chunk_prefill(params, pool, tokens (B, Tc), start (B,),
      lengths (B,), tables (B, MB), temperature, top_k, top_p, seed
      (each (B,))) -> (first_tokens (B,), new_pool)``: one fixed-size
      prompt chunk per row, scattered into the block pool through the
      tables; row ``i``'s returned token is sampled from its LAST
      valid chunk position's logits -- only meaningful for rows whose
      chunk completes the prompt, garbage (and discarded) otherwise.
    - ``decode(params, pool, tokens (S,), pos (S,), tables (S, MB),
      temperature, top_k, top_p, seed (each (S,))) -> (next_tokens,
      new_pool)``: one fixed-shape step over the whole slot pool.
    - ``copy_block(pool, src, dst) -> new_pool``: the copy-on-write
      primitive -- physical block ``src`` duplicated into ``dst``
      across every layer, one executable regardless of which blocks.

    Sampling runs in-jit (serving/sampling.py): the knobs are runtime
    arrays, so greedy and sampled rows share each executable, and the
    RNG folds on (seed, token position) -- a request replays
    identically however it was chunked or slotted.  All three steps
    donate the pool.

    A model whose generation state has per-SLOT leaves
    (``paged_state_spec``, nn/generation_state.py) is also told which slot
    each row is: both steps take one more argument, ``slots (B,)`` or
    ``(S,)``, the trash slot for a row that is padding or not live.  A
    model without such leaves is never handed it, and compiles to the
    programs it always had.  A model that sets ``paged_logits_at`` is
    asked by the chunk step for the logits of one position a row
    (``apply_paged(..., logits_at=)``), with or without slot leaves.
    """
    from bigdl_tpu.serving.sampling import sample_tokens

    cache = model.__dict__.setdefault("_compiled_paged_steps", {})
    key = np.dtype(cache_dtype).name
    fns = cache.get(key)
    if fns is not None:
        return fns

    kinds = _state_kinds(model, cache_dtype)

    def chunk_prefill(params, pool, tokens, start, lengths, tables,
                      temperature, top_k, top_p, seed, slots=None):
        tc = tokens.shape[1]
        last = lambda: jnp.clip(lengths.astype(jnp.int32) - 1, 0, tc - 1)
        kw = {} if slots is None else {"slots": slots}
        if getattr(model, "paged_logits_at", False):
            # only the last valid position's logits are wanted: a model
            # that says so can be asked for one position a row (a chunk
            # of 512 tokens then forms no 512 x vocabulary logits)
            kw["logits_at"] = last()
        logits, new = model.apply_paged(params, tokens, pool, tables,
                                        pos=start, lengths=lengths, **kw)
        with jax.named_scope("sampler"):
            idx = jnp.zeros_like(kw["logits_at"]) if "logits_at" in kw \
                else last()
            row = jnp.take_along_axis(
                logits, idx[:, None, None], axis=1)[:, 0]
            # the sampled token OCCUPIES position start + lengths; folding
            # the RNG on that position makes the draw independent of how
            # the prompt was chunked
            first = sample_tokens(row, temperature, top_k, top_p, seed,
                                  start + lengths)
        return first, new

    def decode(params, pool, tokens, pos, tables, temperature, top_k,
               top_p, seed, slots=None):
        kw = {} if slots is None else {"slots": slots}
        logits, new = model.apply_paged(params, tokens[:, None], pool,
                                        tables, pos=pos, **kw)
        with jax.named_scope("sampler"):
            nxt = sample_tokens(logits[:, 0], temperature, top_k, top_p,
                                seed, pos + 1)
        return nxt, new

    def copy_block(pool, src, dst):
        def cp(leaf, kind):
            # block leaves are (NB, bs, H * Dh), scales (NB, bs, H); the
            # scan-stacked layout adds a leading layer axis -- the block
            # axis sits at ndim - 3 either way.  Per-slot leaves and
            # counters hold nothing of a block.
            if kind != BLOCK:
                return leaf
            if leaf.ndim == 3:
                return leaf.at[dst].set(leaf[src])
            return leaf.at[:, dst].set(leaf[:, src])
        return jax.tree.map(cp, pool, kinds)

    fns = (jax.jit(chunk_prefill, donate_argnums=(1,)),
           jax.jit(decode, donate_argnums=(1,)),
           jax.jit(copy_block, donate_argnums=(0,)))
    cache[key] = fns
    return fns


class _PagedSlot:
    """One admitted sequence in the paged scheduler.  While
    ``consumed < len(prompt)`` the slot is PREFILLING: chunk ticks
    advance ``consumed`` (which starts at the prefix-cache hit length,
    not 0).  The final chunk samples the first token and flips the
    slot to decoding, after which ``tokens`` and ``last`` mean exactly
    what ``_Slot``'s do.

    The dispatcher launches a tick before it has fetched the one
    before, so the slot is kept in two tenses.  ``consumed``, ``pos``
    and ``launched`` (tokens whose computation has been launched) say
    what the device has been ASKED for and move when a tick is
    launched; ``tokens`` and ``last`` say what has come back.
    ``riding`` counts the launched ticks that carry the slot's row and
    have not been fetched; ``ended`` marks a request that finished (EOS,
    abandoned) while one still did: its slot and blocks are given back
    when ``riding`` falls to nought."""

    __slots__ = ("fut", "prompt", "seq", "consumed", "tokens", "last",
                 "pos", "seed", "launched", "riding", "ended")

    def __init__(self, fut, prompt, seq, consumed, seed):
        self.fut = fut
        self.prompt = prompt
        self.seq = seq                    # BlockAllocator sequence id
        self.consumed = int(consumed)
        self.tokens = []
        self.last = None
        self.pos = None
        self.seed = int(seed)
        self.launched = 0
        self.riding = 0
        self.ended = False

    @property
    def prefilling(self):
        return self.consumed < self.prompt.size

    @property
    def decoding(self):
        """Wants a row in the next decode tick: its prompt is on the
        device (or on its way) and a token of its budget is still to be
        asked for.  A row that ends by length leaves here, exactly; one
        that may end on ``eos_id`` is found out a tick late."""
        return not self.ended and not self.prefilling \
            and self.launched < self.fut.max_new_tokens


class _Launched(NamedTuple):
    """A device call that has been launched and not fetched: what its
    fetch, deliver and telemetry need once the tokens are asked for."""

    kind: str                 # "prefill" | "decode"
    tick: int
    #: ``(slot index, slot, at)``, ``at`` the row's index in ``out`` or
    #: None where the call gives the row no token (a chunk that does not
    #: end its prompt)
    rows: list
    out: jax.Array            # the tokens, on the device
    counted: Optional[list]   # copies of the counter leaves
    start_ns: int             # of the call's prep span
    qdepth: int
    execs_before: Optional[int]
    event: dict               # the tick event's own fields


@jax.jit
def _feed_tokens(host, src, prev):
    """A decode tick's input tokens when some rows' newest token is still
    on the device: row ``i`` takes ``prev[src[i]]`` (the unfetched call's
    output) where ``src[i] >= 0``, else the host's ``host[i]``."""
    return jnp.where(src >= 0, prev[jnp.maximum(src, 0)], host)


@jax.jit
def _copy_leaves(leaves):
    """Copies that outlive the pool they were read from: the next call
    donates the pool, counters and all."""
    return [jnp.copy(leaf) for leaf in leaves]


class PagedGenerateScheduler(GenerateScheduler):
    """Continuous batching over a PAGED KV cache: the dispatcher
    contract (slots, futures, telemetry, drain/close) is inherited
    from ``GenerateScheduler``; what changes is where K/V live and how
    prompts arrive.

    - The cache is ``model.init_paged_cache(num_blocks, block_size)``
      -- memory scales with ``num_blocks``, not ``slots x max_len``
      worst case -- and every sequence addresses it through a
      ``BlockAllocator`` table (serving/paging.py).  Admission
      RESERVES the request's worst-case block need; a pool that can't
      hold it sheds the request with ``BlockPoolExhausted`` instead of
      letting decode corrupt a neighbour later.
    - Prompts whose leading full blocks hash-match an earlier request
      map the SHARED blocks (``prefix_hit_tokens``) and skip that much
      prefill compute and memory.
    - A long prompt prefills in ``prefill_chunk``-token chunks, ONE
      chunk per dispatcher iteration with a decode tick in between --
      so an admitted 10k-token prompt delays live streams by one
      chunk's latency per token, never head-of-line-blocks them.
    - Decode ticks sample in-jit per the request's ``SamplingParams``
      (greedy by default, bit-identical to the contiguous argmax).
    - The dispatcher LAUNCHES AHEAD, one deep: tick k+1 is prepared and
      launched before tick k's tokens are fetched, so the device's queue
      is never empty while there is work and the host's share of a call
      (arguments, dispatch, the transfer back, delivery) runs beside a
      program and not between two.  At most one call is launched and
      not fetched (``_inflight``).  A chunk tick needs nothing of the
      tick before it; a decode tick needs each row's newest token, and
      takes it on the device from the unfetched call's output
      (``_feed_tokens``).  Who rides tick k+1 is known without tick k's
      tokens for a row that ends by length; a row that may end on
      ``eos_id`` rides regardless, and if tick k ended it, its row of
      tick k+1 is computed and thrown away (``rows_wasted``).  A slot
      whose request ends while a launched tick still carries its row
      keeps its blocks and its per-slot state until that tick has been
      fetched.  Rows are independent and program order on the device is
      launch order, so the tokens are those of one tick at a time.

    The executable set stays closed and warmable: ONE decode shape,
    one chunk shape per admission-batch rung, one block-copy, and the
    token feed at each rung's length -- zero steady-state recompiles
    across mixed lengths, chunked prefill and sampled decoding (the
    acceptance contract, tests/test_paged.py).
    """

    supports_sampling = True

    def __init__(self, model, slots: int = 8, max_len: Optional[int] = None,
                 prompt_ladder: Optional[BucketLadder] = None,
                 queue_capacity: int = 1024, cache_dtype=jnp.float32,
                 telemetry=None, params_fn=None, admission_check=None,
                 exhausted_hook=None, name: str = "generate",
                 block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 prefill_chunk: Optional[int] = None):
        if not hasattr(model, "init_paged_cache"):
            raise TypeError(
                f"{type(model).__name__} has no init_paged_cache(): the "
                f"paged scheduler needs the block-pool decode mode "
                f"(TransformerLM has one); kv_cache='contiguous' works "
                f"with plain init_cache models")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.block_size = int(block_size)
        model_max = getattr(model, "max_len", None)
        eff_max = int(model_max if max_len is None
                      else min(max_len, model_max or max_len))
        #: table width: enough entries to map max_len positions
        self.max_blocks_per_seq = -(-eff_max // self.block_size)
        #: pool size; the default matches the contiguous pool's token
        #: capacity (slots x max_len) -- pass something smaller to
        #: actually cap memory (prefix sharing means a smaller pool
        #: still holds the same traffic)
        self.num_blocks = int(num_blocks) if num_blocks is not None \
            else int(slots) * self.max_blocks_per_seq
        if prefill_chunk is None:
            prefill_chunk = min(64, eff_max)
        if prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        self.prefill_chunk = int(min(prefill_chunk, eff_max))
        # admission-tick prefix-hit deltas, stamped on the next chunk
        # tick's telemetry event (prompt_tokens is the hit-rate
        # denominator: positions ADMITTED, hit or not)
        self._hits_delta = 0
        self._hit_tokens_delta = 0
        self._prompt_tokens_delta = 0
        self._seq_counter = 0
        #: the call that has been launched and not fetched, or None
        self._inflight = None
        self._launched_ahead = 0
        self._rows_wasted = 0
        super().__init__(model, slots=slots, max_len=max_len,
                         prompt_ladder=prompt_ladder,
                         queue_capacity=queue_capacity,
                         cache_dtype=cache_dtype, telemetry=telemetry,
                         params_fn=params_fn,
                         admission_check=admission_check,
                         exhausted_hook=exhausted_hook, name=name)

    def _setup_steps(self):
        self._chunk_fn, self._decode_fn, self._copy_fn = \
            paged_generate_steps(self.model, self._cache_dtype)
        #: the kinds of the pool's leaves (nn/generation_state.py)
        self._kinds = _state_kinds(self.model, self._cache_dtype)
        #: per-SLOT leaves beside the blocks: rows are then told their
        #: slot, a slot is reset by its sequence's first chunk, and no
        #: prompt is served from the prefix cache (a recurrent state
        #: cannot be rebuilt from the blocks a prefix shares)
        self._slot_state = _has_slot_leaves(self._kinds)
        self._build_pool()
        # the two small programs of launching ahead, at every shape a
        # tick can ask for: they are nobody's rung to warm
        host = np.zeros((self.slots,), np.int32)
        for n in self.batch_ladder:
            _feed_tokens(host, host - 1, np.zeros((int(n),), np.int32))
        self._counters()

    def _build_pool(self):
        kw = {"slots": self.slots} if self._slot_state else {}
        self._cache = self.model.init_paged_cache(
            self.num_blocks, self.block_size, self._cache_dtype, **kw)
        self._alloc = self._make_alloc()

    def _leaves_of(self, kind):
        """The pool's leaves of one kind."""
        return [leaf for leaf, k in zip(jax.tree.leaves(self._cache),
                                        jax.tree.leaves(self._kinds))
                if k == kind]

    def slot_state_bytes(self) -> int:
        """Device bytes of per-slot state ONE sequence holds."""
        return int(sum(leaf.size * leaf.dtype.itemsize
                       for leaf in self._leaves_of(SLOT))) \
            // (self.slots + 1)

    def kv_dtype(self) -> str:
        """Short storage-dtype name of the paged pool ("fp32"/"int8"),
        the spelling BlockAllocator namespaces prefix hashes with."""
        name = np.dtype(self._cache_dtype).name
        return {"float32": "fp32", "bfloat16": "bf16",
                "float16": "fp16"}.get(name, name)

    def _make_alloc(self):
        """Build the allocator for the pool JUST allocated: it learns
        the pool's storage dtype (prefix hashes refuse to cross
        storage formats) and the measured device bytes behind one
        addressable block -- every leaf, scales included -- so
        ``stats()`` reports real narrow bytes, not compute-dtype
        hand-math (ROADMAP item 3's rule)."""
        from bigdl_tpu.serving.paging import BlockAllocator

        pool_bytes = sum(leaf.size * leaf.dtype.itemsize
                         for leaf in self._leaves_of(BLOCK))
        return BlockAllocator(
            self.num_blocks, self.block_size, kv_dtype=self.kv_dtype(),
            bytes_per_block=int(pool_bytes) // (self.num_blocks + 1),
            share_prefixes=not self._slot_state)

    def _reset_pool(self):
        # a failed donating tick killed the device pool, so every
        # cached prefix block's CONTENT is gone too: fresh allocator,
        # empty registry (the base already released live sequences)
        self._build_pool()

    def flush_prefix_cache(self):
        """Invalidate cached prefix blocks (engine weight swaps call
        this -- K/V computed under old weights must not serve new
        prompts)."""
        self._alloc.flush_cached()

    def stats(self):
        st = super().stats()
        st["kv"] = self._alloc.stats()
        st["block_size"] = self.block_size
        st["prefill_chunk"] = self.prefill_chunk
        st["launched_ahead"] = self._launched_ahead
        st["rows_wasted"] = self._rows_wasted
        return st

    # ----- warmup ----------------------------------------------------------- #
    def precompile(self) -> int:
        """Warm the whole paged shape set: the one decode executable,
        one chunk-prefill per admission rung, and the COW block copy.
        Dummy pools only -- the live pool is never donated away."""
        from bigdl_tpu.observability.watchdogs import backend_compile_count

        params = self._params()
        before = backend_compile_count()
        dummy = jax.tree.map(jnp.zeros_like, self._cache)
        s = self.slots
        mb = self.max_blocks_per_seq
        trash = np.int32(self._alloc.trash)

        def knobs(n):
            return (np.zeros((n,), np.float32), np.zeros((n,), np.int32),
                    np.ones((n,), np.float32), np.zeros((n,), np.int32))

        nxt, dummy = self._decode_fn(
            params, dummy, np.zeros((s,), np.int32),
            np.zeros((s,), np.int32), np.full((s, mb), trash, np.int32),
            *knobs(s), *self._slot_rows(s))
        jax.block_until_ready(nxt)
        tc = self.prefill_chunk
        for b in self.batch_ladder:
            b = int(b)
            first, dummy = self._chunk_fn(
                params, dummy, np.zeros((b, tc), np.int32),
                np.zeros((b,), np.int32), np.ones((b,), np.int32),
                np.full((b, mb), trash, np.int32), *knobs(b),
                *self._slot_rows(b))
            jax.block_until_ready(first)
        dummy = self._copy_fn(dummy, np.int32(0), np.int32(0))
        jax.block_until_ready(jax.tree.leaves(dummy)[0])
        return backend_compile_count() - before

    # ----- dispatcher ticks -------------------------------------------------- #
    def _busy(self):
        return self._inflight is not None or super()._busy()

    def _release_slot(self, index, slot):
        if slot.riding:
            # a launched tick still writes this row's blocks and state:
            # ``_complete`` gives them back once it has been fetched
            slot.ended = True
            return
        self._alloc.free_sequence(slot.seq)
        super()._release_slot(index, slot)

    def _tick_failed(self, e, futs, extra_free):
        """The call in flight was launched on the pool that is lost, or is
        the one that failed: it is dropped untouched, and nothing rides
        any more, so every slot goes back now.  The riders of both calls
        are the active slots, and the base fails each once."""
        self._inflight = None
        for _i, slot in self._active():
            slot.riding = 0
        super()._tick_failed(e, futs, extra_free)

    def _kv_extra(self):
        st = self._alloc.stats()
        extra = {"kv_blocks_used": st["blocks_used"],
                 "kv_blocks_cached": st["blocks_cached"],
                 "kv_blocks_free": st["blocks_free"],
                 "kv_blocks_total": st["blocks_total"]}
        if self._prompt_tokens_delta:
            extra["prompt_tokens"] = self._prompt_tokens_delta
            self._prompt_tokens_delta = 0
        if self._hits_delta or self._hit_tokens_delta:
            extra["prefix_hits"] = self._hits_delta
            extra["prefix_hit_tokens"] = self._hit_tokens_delta
            self._hits_delta = 0
            self._hit_tokens_delta = 0
        return extra

    def _admit(self, reqs):
        """ADMISSION only (no device work): assign a slot, match the
        prefix cache, reserve the worst-case block need.  The actual
        prompt compute happens one chunk per dispatcher iteration in
        ``_run_device``, interleaved with decode ticks."""
        from bigdl_tpu.serving.paging import BlockPoolExhausted

        t_admit = time.perf_counter()
        for p, f in reqs:
            f._t_admit = t_admit     # queue wait ends at slot admission
        admitted = 0
        for p, f in reqs:
            sp = f.sampling
            seed = 0
            if sp is not None and not sp.greedy:
                seed = sp.seed if sp.seed is not None else \
                    int.from_bytes(os.urandom(4), "little") & 0x7fffffff
            seq = self._seq_counter
            self._seq_counter += 1
            with self._lock:
                idx = self._free.popleft()
            try:
                cached = self._alloc.begin_sequence(
                    seq, p.tolist(), int(p.size) + f.max_new_tokens)
            except BlockPoolExhausted as e:
                with self._lock:
                    self._free.append(idx)
                hook = self._exhausted_hook
                if hook is not None:
                    # forensics BEFORE the caller sees the failure: the
                    # dump must be on disk even if the shed cascades
                    try:
                        hook(e)
                    except Exception:
                        log.exception("exhausted_hook failed")
                self._fail_request(f, e)
                continue
            f.prefix_hit_tokens = cached
            self._hits_delta += cached // self.block_size
            self._hit_tokens_delta += cached
            self._prompt_tokens_delta += int(p.size)
            self._slots[idx] = _PagedSlot(f, p, seq, cached, seed)
            admitted += 1
            if self._slot_state:
                f._state_bytes = self.slot_state_bytes()
        if self._slot_state and admitted:
            # the slots' leaves are zeroed on the device by each
            # sequence's first chunk (it starts at position 0)
            now = now_ns()
            record_span("state_reset", now, now, nest=True, slots=admitted)

    def _sampling_rows(self, n):
        return (np.zeros((n,), np.float32), np.zeros((n,), np.int32),
                np.ones((n,), np.float32), np.zeros((n,), np.int32))

    def _slot_rows(self, n):
        """The steps' ``slots`` argument for ``n`` rows, every row on the
        trash slot; nothing for a model without per-slot state."""
        return (np.full((n,), self.slots, np.int32),) \
            if self._slot_state else ()

    def _counters(self):
        """The pool's counter leaves as arrays of their own, or None: what
        the call just launched counted, taken out before the next call
        donates the pool."""
        counted = self._leaves_of(COUNTER)
        return _copy_leaves(counted) if counted else None

    def _fetch(self, tokens, counted):
        """A launched call's host sync: its tokens and, with them, what it
        counted -- one fetch, no sync of its own."""
        if counted is None:
            return np.asarray(tokens), None
        tokens, counted = jax.device_get((tokens, counted))
        return np.asarray(tokens), counted

    def _record_counts(self, counted):
        """What the step counted, as spans under the open ``tick``: one a
        counter leaf, named as the model names its counters
        (``tick_counters``: span name -> attribute names)."""
        if counted is None:
            return
        now = now_ns()
        for (name, attrs), leaf in zip(self.model.tick_counters.items(),
                                       counted):
            record_span(name, now, now, nest=True,
                        **{k: int(v) for k, v in zip(attrs, leaf)})

    @staticmethod
    def _fill_sampling(arrs, r, slot):
        sp = slot.fut.sampling
        if sp is None or sp.greedy:
            return
        temp, top_k, top_p, seed = arrs
        temp[r] = sp.temperature
        top_k[r] = sp.top_k
        top_p[r] = sp.top_p
        seed[r] = slot.seed

    def _occupancy(self, claimed):
        live = [s for _i, s in self._active() if not s.ended]
        prefilling = sum(s.prefilling for s in live)
        return {"slots_decoding": len(live) - prefilling,
                "slots_prefilling": prefilling,
                "blocks_free": self._alloc.stats()["blocks_free"]}

    def _run_device(self, claimed, placed, qdepth):
        """One dispatcher iteration of device work: at most ONE prefill
        chunk per currently-prefilling sequence, then one decode tick
        over every decoding slot -- the interleave that keeps chunked
        prefill from starving live streams.  Each is launched before
        the call ahead of it is fetched; with nothing to launch, what
        is in flight is fetched."""
        idle = True
        if any(s.prefilling and not s.ended for _i, s in self._active()):
            idle = False
            self._run_chunk_tick(qdepth)
        # asked again: a chunk just launched may have ended a prompt
        if any(s.decoding for _i, s in self._active()):
            idle = False
            self._run_decode_tick(qdepth)
        if idle:
            self._land(None)

    def _run_chunk_tick(self, qdepth):
        self._land(self._launch_chunk(qdepth))

    def _run_decode_tick(self, qdepth):
        self._land(self._launch_decode(qdepth))

    def _land(self, launched):
        """``launched`` (a call just launched, or None) becomes the call
        in flight, and the one that was is fetched, delivered and
        recorded while the device runs the new one."""
        ahead, self._inflight = self._inflight, launched
        if ahead is not None:
            self._complete(ahead)

    def _launch_chunk(self, qdepth):
        """Prepare and launch one chunk of every prefilling row; None
        where the launch failed (``_tick_failed`` has run)."""
        execs_before = self._compiles()
        ahead = self._inflight
        rows = [(i, s) for i, s in self._active()
                if s.prefilling and not s.ended]
        n = len(rows)
        with span("prefill_prep", rows=n, slots_total=self.slots,
                  ahead=int(ahead is not None)) as prep:
            bucket = self.batch_ladder.bucket_for(n) \
                or self.batch_ladder.add(n)
            tc = self.prefill_chunk
            mb = self.max_blocks_per_seq
            tokens = np.zeros((bucket, tc), np.int32)
            start = np.zeros((bucket,), np.int32)
            lens = np.zeros((bucket,), np.int32)
            tables = np.full((bucket, mb), self._alloc.trash, np.int32)
            knobs = self._sampling_rows(bucket)
            slot_ids = self._slot_rows(bucket)
            for r, (i, s) in enumerate(rows):
                chunk = s.prompt[s.consumed:s.consumed + tc]
                tokens[r, :chunk.size] = chunk
                start[r] = s.consumed
                lens[r] = chunk.size
                if slot_ids:
                    slot_ids[0][r] = i
                self._cow_guard(s, s.consumed, s.consumed + chunk.size - 1)
                tables[r] = self._alloc.table_row(s.seq, mb)
                self._fill_sampling(knobs, r, s)
            # context_tokens: what the rows already hold, which the chunk's
            # attention reads beside its own tokens; rows_sampling: the rows
            # with a temperature (one is enough for the sampler to sort)
            # tokens_computed: what the program runs over, padding rows
            # and padding tokens included (a chunked scan's products are
            # over whole chunks of it; rows and prompt_tokens are the live
            # part)
            prep.set(bucket=int(bucket), prompt_tokens=int(lens.sum()),
                     tokens_computed=int(bucket) * int(tc),
                     context_tokens=int(start.sum()),
                     rows_sampling=int((knobs[0] > 0).sum()))
        tick = self._tick + (ahead is not None)
        try:
            with span("generate_prefill", tick=tick, records=n):
                with span("launch"):
                    first, self._cache = self._chunk_fn(
                        self._params(), self._cache, tokens, start, lens,
                        tables, *knobs, *slot_ids)
                    counted = self._counters()
                self._mirror_chunk(tokens, start, lens, tables, knobs)
        except Exception as e:
            log.exception("chunk prefill tick failed (%d prompts)", n)
            self._tick_failed(e, [], [])
            return None
        riding = []
        for r, (i, s) in enumerate(rows):
            s.consumed += int(lens[r])
            s.riding += 1
            # full prompt blocks hold real K/V before any later program
            # reads them (launch order is program order): register their
            # hashes so later admissions can share them
            self._alloc.commit_full_blocks(s.seq, s.consumed)
            if s.prefilling:
                riding.append((i, s, None))
            else:                                    # prompt complete
                s.pos = int(s.prompt.size)
                s.launched = 1
                riding.append((i, s, r))
        self._launched_ahead += ahead is not None
        return _Launched("prefill", tick, riding, first, counted,
                         prep.start_ns, qdepth, execs_before,
                         dict(records=n, bucket=int(bucket),
                              prompt_bucket=tc))

    def _launch_decode(self, qdepth):
        """Prepare and launch one token of every decoding row; None where
        the launch failed.  A row whose newest token is in the unfetched
        call's output is fed it on the device."""
        execs_before = self._compiles()
        s_n = self.slots
        ahead = self._inflight
        unfetched = {} if ahead is None else \
            {i: at for i, _s, at in ahead.rows if at is not None}
        active = [(i, s) for i, s in self._active() if s.decoding]
        with span("decode_prep", rows=len(active), slots_total=s_n,
                  ahead=int(ahead is not None)) as prep:
            mb = self.max_blocks_per_seq
            tokens = np.zeros((s_n,), np.int32)
            src = np.full((s_n,), -1, np.int32)
            pos = np.zeros((s_n,), np.int32)
            tables = np.full((s_n, mb), self._alloc.trash, np.int32)
            knobs = self._sampling_rows(s_n)
            slot_ids = self._slot_rows(s_n)
            for i, s in active:
                self._cow_guard(s, s.pos, s.pos)
                if i in unfetched:
                    src[i] = unfetched[i]
                else:
                    tokens[i] = s.last
                pos[i] = s.pos
                if slot_ids:
                    slot_ids[0][i] = i
                tables[i] = self._alloc.table_row(s.seq, mb)
                self._fill_sampling(knobs, i, s)
            rows_ahead = int((src >= 0).sum())
            # the cache rows this tick's attention reads: every live
            # slot's positions up to and with the one it writes
            prep.set(context_tokens=int(pos.sum()) + len(active),
                     rows_sampling=int((knobs[0] > 0).sum()),
                     rows_ahead=rows_ahead)
        tick = self._tick + (ahead is not None)
        try:
            with span("generate_decode", tick=tick, records=len(active)):
                with span("launch"):
                    if rows_ahead:
                        tokens = _feed_tokens(tokens, src, ahead.out)
                    nxt, self._cache = self._decode_fn(
                        self._params(), self._cache, tokens, pos, tables,
                        *knobs, *slot_ids)
                    counted = self._counters()
        except Exception as e:
            log.exception("decode tick failed (%d slots)", len(active))
            self._tick_failed(e, [], [])
            return None
        for _i, s in active:
            s.pos += 1
            s.launched += 1
            s.riding += 1
        self._launched_ahead += ahead is not None
        return _Launched("decode", tick, [(i, s, i) for i, s in active],
                         nxt, counted, prep.start_ns, qdepth, execs_before,
                         dict(records=0, slots_before=len(active)))

    def _complete(self, call):
        """Fetch a launched call's tokens, deliver them and record the
        tick.  A failure surfaces here, one call late, when the next call
        is already launched on a pool that is lost with this one."""
        try:
            with span("generate_" + call.kind, tick=call.tick,
                      records=len(call.rows)):
                with span("fetch"):
                    out, counted = self._fetch(call.out, call.counted)
        except Exception as e:
            log.exception("%s tick failed (%d rows)", call.kind,
                          len(call.rows))
            self._tick_failed(e, [], [])
            return
        self._record_counts(counted)
        done_lat = []
        emitted = wasted = 0
        with span("deliver") as dlv:
            for i, s, at in call.rows:
                s.riding -= 1
                if s.ended:
                    # its request ended while this row was on the device
                    wasted += 1
                    self._release_slot(i, s)
                    continue
                if call.kind == "prefill":
                    s.fut._chunks += 1
                if at is not None:
                    s.last = int(out[at])
                    s.tokens.append(s.last)
                    emitted += 1
                    self._deliver(i, s, done_lat)
            dlv.set(tokens=emitted, finished=len(done_lat),
                    rows_wasted=wasted)
        self._tick += 1
        self._rows_wasted += wasted
        self._record_tick(call.kind, call.start_ns, dlv.end_ns,
                          tokens=emitted, qdepth=call.qdepth,
                          execs_before=call.execs_before,
                          latencies=done_lat,
                          riders=[s.fut for _i, s, _at in call.rows],
                          extra=self._kv_extra(), **call.event)

    def _mirror_chunk(self, tokens, start, lens, tables, knobs):
        """Hook for a twin cache that must see every prompt chunk:
        no-op here; the speculative subclass replays the chunk through
        its drafter pool so draft decoding starts from a prefilled
        drafter context."""

    def _cow_guard(self, slot, first_pos, last_pos):
        """Copy-on-write check over the blocks a write will touch.  By
        construction writes only land in private blocks (prefix
        matching is capped below the last prompt token), so this
        normally just unregisters a block that was about to be shared;
        if a shared block IS about to be written, the sequence detaches
        onto a fresh copy first -- a refcount bug corrupts nobody."""
        bs = self.block_size
        for b in range(int(first_pos) // bs, int(last_pos) // bs + 1):
            cow = self._alloc.ensure_writable(slot.seq, b * bs)
            if cow is not None:
                src, dst = cow
                self._copy_cow_block(src, dst)

    def _copy_cow_block(self, src, dst):
        """Duplicate physical block ``src`` into ``dst`` (the
        speculative subclass also copies the drafter pool: the shared
        allocator's table move covers BOTH pools, so both must carry
        the content across)."""
        self._cache = self._copy_fn(self._cache, np.int32(src),
                                    np.int32(dst))


def speculative_verify_step(model, cache_dtype, k: int):
    """The jitted VERIFY step for speculative decoding, compiled once
    per (model, cache dtype, k) and cached on the instance.

    ``verify(params, pool, last (S,), drafts (k arrays of (S,)), pos
    (S,), tables (S, MB), temperature, top_k, top_p, seed (each (S,)))
    -> (sampled (S, k+1), new_pool)``: row ``i`` feeds ``[last,
    d_1 .. d_k]`` -- the newest
    committed token plus the drafter's k guesses -- at positions
    ``pos .. pos+k`` through the chunk-prefill path (every position's
    K/V scattered, every position's logits returned), then samples a
    token at EVERY position ``pos+1 .. pos+k+1`` with the same
    ``(seed, position)``-pure sampler plain decode uses.  Column ``j``
    of the result is therefore EXACTLY the token one fp32 decode tick
    would have drawn at position ``pos+j+1`` given the fed prefix --
    the property that makes greedy (and seeded-sampling) speculative
    output bit-identical to verifier-only decoding.  Donates the pool.
    """
    from bigdl_tpu.serving.sampling import sample_tokens

    cache = model.__dict__.setdefault("_compiled_spec_steps", {})
    key = (np.dtype(cache_dtype).name, int(k))
    fn = cache.get(key)
    if fn is not None:
        return fn

    def verify(params, pool, last, drafts, pos, tables, temperature,
               top_k, top_p, seed):
        # assemble [last, d_1 .. d_k] IN-JIT: the tick then issues no
        # bare jnp glue ops, so the executable set after precompile()
        # is exactly the warmed one (the zero-recompile contract)
        tokens = jnp.concatenate(
            [last[:, None]] + [d[:, None] for d in drafts], axis=1)
        k1 = tokens.shape[1]
        logits, new = model.apply_paged(
            params, tokens, pool, tables, pos=pos,
            lengths=jnp.full_like(pos, k1))

        def rep(a):
            return jnp.repeat(a, k1)

        with jax.named_scope("sampler"):
            flat = logits.reshape((-1, logits.shape[-1]))
            positions = (pos[:, None] + 1
                         + jnp.arange(k1, dtype=jnp.int32)[None, :])
            sampled = sample_tokens(flat, rep(temperature), rep(top_k),
                                    rep(top_p), rep(seed),
                                    positions.reshape(-1))
        return sampled.reshape(tokens.shape), new

    fn = jax.jit(verify, donate_argnums=(1,))
    cache[key] = fn
    return fn


class SpeculativeScheduler(PagedGenerateScheduler):
    """Draft/verify decoding over the paged pool: per round, the int8
    TWIN (``quantize_model``'s structural copy, PR 10 -- gated into
    serving by the same ``AccuracyDeltaGate`` evidence) drafts
    ``spec_k`` tokens with cheap sequential decode steps, and the fp32
    verifier scores ALL of them in ONE chunk-shaped forward.  The
    longest prefix of drafts that matches what the verifier itself
    would have sampled is accepted, plus the verifier's own next token
    (the correction on a miss, the bonus on a clean sweep) -- so one
    fp32 forward emits between 1 and ``spec_k + 1`` tokens, and the
    stream is EXACTLY the verifier-only stream (greedy bit-identical;
    seeded sampling replay-stable, because acceptance compares against
    the ``(seed, position)``-pure draw the verifier would have made).

    Cache story: the drafter runs against its OWN device pool, but the
    two pools share ONE ``BlockAllocator`` -- same geometry, same
    block tables, so prefix hits, COW detaches and LRU evictions stay
    single-sourced (a COW copies the block in BOTH pools; every prompt
    chunk is mirrored into the drafter pool via ``_mirror_chunk``).
    Rejection needs no explicit rollback: a rejected draft's K/V sits
    BEYOND the committed frontier, causally masked until the next
    round's scatter overwrites it (writes precede reads in the
    compiled steps), and ``_cow_guard`` runs over the whole
    ``pos .. pos+k`` write span first so shared blocks detach before
    any speculative write lands.  Block tables carry
    ``ceil((spec_k+1)/block_size)`` extra trash-padded entries so a
    round straddling a sequence's reserved range routes its overshoot
    writes to the trash block instead of clamping into a live one.

    The executable set stays closed: the drafter's decode + chunk
    rungs + copy, the one ``spec_verify`` shape, and the inherited
    verifier set -- zero steady-state recompiles (pinned in
    tests/test_speculative.py).
    """

    def __init__(self, model, draft_model, spec_k: int = 4,
                 draft_params_fn=None, **kw):
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        if not hasattr(draft_model, "init_paged_cache"):
            raise TypeError(
                f"{type(draft_model).__name__} has no init_paged_cache():"
                f" the drafter must run the same paged decode mode as "
                f"the verifier")
        for m in (model, draft_model):
            if _has_slot_leaves(
                    _state_kinds(m, kw.get("cache_dtype", jnp.float32))):
                raise TypeError(
                    f"{type(m).__name__} keeps per-slot generation state "
                    f"(a recurrent state or a convolution's tail): a "
                    f"rejected draft cannot be rolled back out of it, so "
                    f"speculative decoding is not offered for it; serve it "
                    f"with speculative=0")
        self.spec_k = int(spec_k)
        self.draft_model = draft_model
        self._dparams = draft_params_fn or \
            (lambda: draft_model.weights())
        self._spec_rounds = 0
        self._spec_drafted = 0
        self._spec_accepted = 0
        super().__init__(model, **kw)
        # widen every table row so verify's pos..pos+k write span can
        # overshoot a finishing sequence's reserved blocks: the extra
        # entries are trash-padded, turning overshoot into trash-block
        # writes rather than an index clamp into a neighbour's block
        self.max_blocks_per_seq += -(-(self.spec_k + 1) // self.block_size)

    def _setup_steps(self):
        super()._setup_steps()
        self._dchunk_fn, self._ddecode_fn, self._dcopy_fn = \
            paged_generate_steps(self.draft_model, self._cache_dtype)
        self._verify_fn = speculative_verify_step(
            self.model, self._cache_dtype, self.spec_k)
        self._build_drafter_pool()

    def _build_drafter_pool(self):
        self._dcache = self.draft_model.init_paged_cache(
            self.num_blocks, self.block_size, self._cache_dtype)
        # one addressable block is backed by BOTH pools' leaves; the
        # allocator's byte report must say so or the ledger understates
        # the speculative price by half
        dbytes = sum(leaf.size * leaf.dtype.itemsize
                     for leaf in jax.tree.leaves(self._dcache))
        self._alloc.bytes_per_block += int(dbytes) // (self.num_blocks + 1)

    def _reset_pool(self):
        super()._reset_pool()
        self._build_drafter_pool()

    def _release_cache(self):
        super()._release_cache()
        self._dcache = None

    def cache_bytes(self) -> int:
        """Verifier pool + drafter pool -- the speculative price is
        BOTH pools resident, and hiding the drafter's share would
        falsify a comparison of peak bytes."""
        return super().cache_bytes() + int(sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(self._dcache)))

    def stats(self):
        st = super().stats()
        drafted = self._spec_drafted
        st["speculative"] = {
            "k": self.spec_k, "rounds": self._spec_rounds,
            "drafted": drafted, "accepted": self._spec_accepted,
            "acceptance_rate": (self._spec_accepted / drafted)
            if drafted else None}
        return st

    def _mirror_chunk(self, tokens, start, lens, tables, knobs):
        """Replay the verifier's prompt chunk through the drafter pool
        (same tables -- the allocator is shared), so by the time a
        sequence flips to decoding, the drafter has its own K/V for
        every prompt position.  Runs inside the chunk tick's try:
        a drafter failure is a pool loss like any other donating-step
        failure, and ``_reset_pool`` rebuilds both pools."""
        first, self._dcache = self._dchunk_fn(
            self._dparams(), self._dcache, tokens, start, lens, tables,
            *knobs)
        jax.block_until_ready(first)      # surface errors in-tick

    def _copy_cow_block(self, src, dst):
        super()._copy_cow_block(src, dst)
        self._dcache = self._dcopy_fn(self._dcache, np.int32(src),
                                      np.int32(dst))

    # ----- warmup ----------------------------------------------------------- #
    def precompile(self) -> int:
        """Warm the inherited verifier set plus the speculative
        additions: drafter decode/chunk-rungs/copy and the one verify
        shape.  Dummy pools only, as in the base."""
        from bigdl_tpu.observability.watchdogs import backend_compile_count

        before = backend_compile_count()
        super().precompile()
        dparams = self._dparams()
        s = self.slots
        mb = self.max_blocks_per_seq
        trash = np.int32(self._alloc.trash)
        tabs = np.full((s, mb), trash, np.int32)
        knobs = self._sampling_rows(s)
        ddummy = jax.tree.map(jnp.zeros_like, self._dcache)
        nxt, ddummy = self._ddecode_fn(
            dparams, ddummy, np.zeros((s,), np.int32),
            np.zeros((s,), np.int32), tabs, *knobs)
        jax.block_until_ready(nxt)
        tc = self.prefill_chunk
        for b in self.batch_ladder:
            b = int(b)
            first, ddummy = self._dchunk_fn(
                dparams, ddummy, np.zeros((b, tc), np.int32),
                np.zeros((b,), np.int32), np.ones((b,), np.int32),
                np.full((b, mb), trash, np.int32),
                *self._sampling_rows(b))
            jax.block_until_ready(first)
        ddummy = self._dcopy_fn(ddummy, np.int32(0), np.int32(0))
        jax.block_until_ready(jax.tree.leaves(ddummy)[0])
        vdummy = jax.tree.map(jnp.zeros_like, self._cache)
        vt, vdummy = self._verify_fn(
            self._params(), vdummy, np.zeros((s,), np.int32),
            tuple(np.zeros((s,), np.int32)
                  for _ in range(self.spec_k)),
            np.zeros((s,), np.int32), tabs, *knobs)
        jax.block_until_ready(vt)
        return backend_compile_count() - before

    # ----- the speculative round --------------------------------------------- #
    def _run_decode_tick(self, qdepth):
        """One draft/verify round over every decoding slot:

        1. ``spec_k + 1`` drafter decode steps -- the first ``spec_k``
           produce the draft tokens ``d_1 .. d_k`` (each fed back in),
           the final one only WRITES ``d_k``'s K/V so the drafter pool
           covers the same ``pos .. pos+k`` span the verifier writes
           (without it, a clean-sweep round would leave the last
           accepted draft's position forever unwritten in the drafter
           pool, and later drafter reads would attend to garbage).
        2. One fp32 verify over ``[last, d_1 .. d_k]`` sampling every
           position.
        3. Accept the longest matching draft prefix + the verifier's
           next token; stream them through the normal ``_deliver``
           path (EOS / token budget truncate the run mid-emission).

        The next round needs this one's accepted counts on the host, so
        nothing is launched ahead of a round: the chunk call that may be
        in flight is fetched first.
        """
        self._land(None)
        t0_ns = now_ns()
        execs_before = self._compiles()
        s_n = self.slots
        k = self.spec_k
        mb = self.max_blocks_per_seq
        tokens = np.zeros((s_n,), np.int32)
        pos = np.zeros((s_n,), np.int32)
        tables = np.full((s_n, mb), self._alloc.trash, np.int32)
        knobs = self._sampling_rows(s_n)
        active = [(i, s) for i, s in self._active() if not s.prefilling]
        if not active:         # the chunk just fetched ended them all
            return
        for i, s in active:
            # COW the WHOLE write span up front, clamped to the
            # sequence's reserved range (overshoot writes go to trash
            # via the widened table padding, no block to detach there)
            hi = min(s.pos + k,
                     int(s.prompt.size) + s.fut.max_new_tokens - 1)
            self._cow_guard(s, s.pos, max(s.pos, hi))
            tokens[i] = s.last
            pos[i] = s.pos
            tables[i] = self._alloc.table_row(s.seq, mb)
            self._fill_sampling(knobs, i, s)
        try:
            with span("generate_decode", tick=self._tick,
                      records=len(active)):
                drafts = []
                cur = tokens
                for j in range(k + 1):
                    cur, self._dcache = self._ddecode_fn(
                        self._dparams(), self._dcache, cur, pos + j,
                        tables, *knobs)
                    if j < k:
                        drafts.append(cur)
                vtoks, self._cache = self._verify_fn(
                    self._params(), self._cache, tokens, tuple(drafts),
                    pos, tables, *knobs)
                dtoks = np.stack([np.asarray(d) for d in drafts],
                                 axis=1)                    # host sync
                vtoks = np.asarray(vtoks)
        except Exception as e:
            log.exception("speculative tick failed (%d slots)",
                          len(active))
            self._tick_failed(e, [], [])
            return
        done_lat = []
        emitted = 0
        drafted = accepted = 0
        for i, s in active:
            drafted += k
            a = 0
            while a < k and int(dtoks[i, a]) == int(vtoks[i, a]):
                a += 1
            accepted += a
            # vtoks[i, :a] == the accepted drafts; vtoks[i, a] is the
            # verifier's own next token (correction or bonus)
            for j in range(a + 1):
                s.pos += 1
                s.last = int(vtoks[i, j])
                s.tokens.append(s.last)
                emitted += 1
                self._deliver(i, s, done_lat)
                if s.fut.done():            # EOS / budget mid-run
                    break
        self._spec_rounds += 1
        self._spec_drafted += drafted
        self._spec_accepted += accepted
        extra = self._kv_extra()
        extra["spec_k"] = k
        extra["spec_drafted"] = drafted
        extra["spec_accepted"] = accepted
        self._tick += 1
        self._record_tick("decode", t0_ns, now_ns(), records=0,
                          tokens=emitted, qdepth=qdepth,
                          execs_before=execs_before,
                          latencies=done_lat, slots_before=len(active),
                          riders=[s.fut for _i, s in active],
                          extra=extra)
