"""The paged dispatcher launches tick k+1 before it fetches tick k
(``PagedGenerateScheduler``, one call deep).  What has to hold:

- the tokens are those of the same requests served one at a time (rows
  are independent, launch order is program order), on a transformer and
  on a model with per-slot state and counters, whose ``tick_counters``
  spans count the same rows either way;
- it engages: in steady state call k+1 is made before fetch k;
- a request that ends (EOS, abandoned) while a launched call carries its
  row keeps its blocks until that call has been fetched, and the row is
  counted as wasted; a ``cancel()`` of a running request changes nothing;
- a failure surfaces one call late and fails every rider once;
- ``drain()`` and ``close()`` wait for the call in flight.
"""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import resolve  # noqa: E402

from bigdl_tpu.nn.attention import TransformerLM  # noqa: E402
from bigdl_tpu.observability.spans import recorder  # noqa: E402
from bigdl_tpu.serving import ServingEngine  # noqa: E402

VOCAB = 50
LING = "ling-3.0-flash-vl.serve.long-decode"


def _lm(scan=False):
    m = TransformerLM(vocab_size=VOCAB, hidden_size=32, num_heads=4,
                      num_layers=2, max_len=64, scan_layers=scan)
    m.build(jax.ShapeDtypeStruct((2, 16), jnp.int32),
            rng=jax.random.PRNGKey(0))
    return m


def _ling():
    cell = resolve.Cell(LING)
    cfg, _ = cell.sized(True, ({"program": {
        "class": "bigdl_tpu.models.ling.Ling", "dtype": "float32"}}, {}))
    params = cell.model.make_params(cfg, 7)
    spec = jax.ShapeDtypeStruct((1, cfg["n_positions"]), jnp.int32)
    return cell.model.program_model(cfg, params, spec), cfg["vocab_size"]


@pytest.fixture(scope="module")
def models():
    made = {}

    def get(name):
        if name not in made:
            made[name] = _ling() if name == "ling" else \
                (_lm(scan=name == "lm_scan"), VOCAB)
        return made[name]
    return get


def _engine(model, slots, **kw):
    kw = {"kv_block_size": 4, "prefill_chunk": 8, **kw}
    return ServingEngine(model, decode_slots=slots, decode_max_len=64,
                         kv_cache="paged", **kw)


def _requests(vocab, eos):
    """Prompts shorter than a chunk, of exactly one and of several; a row
    that ends at once, rows that end by length, greedy beside seeded
    sampling.  With ``eos`` two of them are given the ``eos_id`` that a
    first pass found in the middle of their output."""
    rng = np.random.default_rng(11)
    shapes = [(3, 6, {}), (8, 1, {}), (21, 9, {}), (13, 12, {"eos": 4}),
              (5, 7, {"temperature": 0.9, "top_p": 0.8, "seed": 5}),
              (17, 10, {"temperature": 1.3, "top_k": 9, "seed": 6,
                        "eos": 3}),
              (9, 5, {})]
    out = []
    for n, new, kw in shapes:
        kw = dict(kw)
        stop = kw.pop("eos", None)
        out.append((rng.integers(0, vocab, n).astype(np.int32), new, kw,
                    stop if eos else None))
    return out


def _loads(recs):
    loads = [r.attrs for r in recs if r.name == "moe_load"]
    return {k: sum(a[k] for a in loads)
            for k in ("rows_routed", "rows_here")}


@pytest.mark.parametrize("name,eos", [("lm", True), ("lm_scan", True),
                                      ("ling", False), ("ling", True)])
def test_a_mixed_batch_equals_one_request_at_a_time(models, name, eos):
    model, vocab = models(name)
    reqs = _requests(vocab, eos)
    # one at a time, and from that pass the token each stopping row stops on
    before = len(recorder().snapshot())
    alone = []
    with _engine(model, 1) as eng:
        for i, (prompt, new, kw, stop) in enumerate(reqs):
            out = eng.generate(prompt, max_new_tokens=new, **kw).result(600)
            if stop is not None:
                eos_id = out[stop]
                out = out[:out.index(eos_id) + 1]
                reqs[i] = (prompt, new, dict(kw, eos_id=eos_id), stop)
            alone.append(out)
        alone_stats = eng._generation().stats()
    loads_alone = _loads(recorder().snapshot()[before:])
    assert alone_stats["rows_wasted"] == 0
    # together: more requests than slots, so rows join and leave mid-flight
    before = len(recorder().snapshot())
    with _engine(model, 3) as eng:
        futs = [eng.generate(prompt, max_new_tokens=new, **kw)
                for prompt, new, kw, _stop in reqs]
        together = [f.result(600) for f in futs]
        assert eng._generation().drain(60)
        stats = eng._generation().stats()
    recs = recorder().snapshot()[before:]
    assert together == alone
    assert [f.finish_reason for f in futs] == [
        "eos" if stop is not None else "length" for *_r, stop in reqs]
    assert stats["launched_ahead"] > 0
    assert stats["kv"]["blocks_used"] == 0 and stats["kv"]["sequences"] == 0
    wasted = sum(r.attrs["rows_wasted"] for r in recs if r.name == "deliver")
    assert wasted == stats["rows_wasted"]
    if not eos:
        assert wasted == 0
    if name == "ling" and not eos:
        # the counters come back with each tick's tokens, whichever call
        # has the pool by then: the same rows were routed, to the same
        # experts, as when nothing was launched ahead of anything
        assert loads_alone["rows_routed"] > 0
        assert _loads(recs) == loads_alone
    # some decode tick took a token on the device
    assert any(r.attrs["rows_ahead"] for r in recs if r.name == "decode_prep")


def test_the_next_call_is_made_before_the_last_is_fetched(models):
    model, _vocab = models("lm")
    with _engine(model, 2) as eng:
        sched = eng._generation()
        log = []

        def logged(name, fn):
            def wrapper(*a, **k):
                log.append(name)
                return fn(*a, **k)
            return wrapper

        sched._chunk_fn = logged("call", sched._chunk_fn)
        sched._decode_fn = logged("call", sched._decode_fn)
        sched._fetch = logged("fetch", sched._fetch)
        out = eng.generate(np.arange(1, 20, dtype=np.int32),
                           max_new_tokens=12).result(120)
        assert sched.drain(30)
        stats = sched.stats()
    assert len(out) == 12
    # 3 chunks and 11 decode ticks, each fetched once
    assert log.count("call") == log.count("fetch") == 14
    # steady state: two calls made before the first fetch, then one call a
    # fetch, until there is nothing left to launch
    at_fetch = [log[:i].count("call") - log[:i].count("fetch")
                for i, what in enumerate(log) if what == "fetch"]
    assert at_fetch[:-1] == [2] * 13 and at_fetch[-1] == 1
    assert stats["launched_ahead"] == 13
    assert stats["ticks"] == 14 and stats["rows_wasted"] == 0


@pytest.mark.parametrize("how", ["eos", "abandon", "cancel"])
def test_a_row_in_flight_keeps_its_blocks(models, how):
    """Nothing a launched call still writes goes back to the allocator."""
    model, _vocab = models("lm")
    prompt = np.arange(2, 12, dtype=np.int32)
    with _engine(model, 2) as eng:
        whole = eng.generate(prompt, max_new_tokens=16).result(120)
    with _engine(model, 2) as eng:
        sched = eng._generation()
        freed_while_riding = []
        free = sched._alloc.free_sequence

        def checked(seq):
            call = sched._inflight
            if call is not None and any(s.seq == seq
                                        for _i, s, _at in call.rows):
                freed_while_riding.append(seq)
            return free(seq)

        sched._alloc.free_sequence = checked
        real = sched._decode_fn

        def slow(*a, **k):
            time.sleep(0.02)
            return real(*a, **k)

        sched._decode_fn = slow
        # a neighbour rides along and must come out whole
        other = eng.generate(prompt[::-1].copy(), max_new_tokens=16)
        if how == "eos":
            # a token that is not the row's last: a call is launched ahead
            eos_id = next(t for t in whole[2:] if t not in whole[:2])
            fut = eng.generate(prompt, max_new_tokens=16, eos_id=eos_id)
            want = whole[:whole.index(eos_id) + 1]
        else:
            fut = eng.generate(prompt, max_new_tokens=16)
            stream = fut.stream(60)
            first = [next(stream), next(stream)]      # mid-flight for sure
            if how == "abandon":
                eng._abandon(fut)
            else:
                # a running future cannot be cancelled: the row rides on
                assert fut.cancel() is False
        got = fut.result(120)
        if how == "eos":
            assert got == want and fut.finish_reason == "eos"
        elif how == "abandon":
            assert fut.finish_reason == "abandoned"
            assert got == whole[:len(got)] and 2 <= len(got) < 16
            assert first + list(stream) == got
        else:
            assert got == whole and first + list(stream) == got
        assert len(other.result(120)) == 16
        assert sched.drain(30)
        stats = sched.stats()
        # the slot and the blocks came back, and serve the next request
        assert eng.generate(prompt, max_new_tokens=16).result(120) == whole
    assert freed_while_riding == []
    assert stats["rows_wasted"] == (0 if how == "cancel" else 1)
    assert stats["kv"]["blocks_used"] == 0 and stats["slots_active"] == 0


@pytest.mark.parametrize("where", ["launch", "fetch"])
def test_a_failure_one_call_late_fails_every_rider_once(models, where):
    model, _vocab = models("lm")
    prompts = [np.arange(1, 10, dtype=np.int32),
               np.arange(3, 20, dtype=np.int32)]
    with _engine(model, 2) as eng:
        ref = eng.generate(prompts[0], max_new_tokens=6).result(120)
        sched = eng._generation()
        pool_before = sched._cache
        attr = "_decode_fn" if where == "launch" else "_fetch"
        good = getattr(sched, attr)
        calls = []

        def boom(*a, **k):
            calls.append(sched._inflight is not None)
            if len(calls) == 3:
                raise RuntimeError("injected, a call late")
            return good(*a, **k)

        setattr(sched, attr, boom)
        futs = [eng.generate(p, max_new_tokens=20) for p in prompts]
        seen = []
        for f in futs:
            with pytest.raises(RuntimeError, match="injected"):
                f.result(60)
            # the exception once, then the end of the stream
            seen.append([x for x in f._stream.queue
                         if isinstance(x, BaseException)])
        assert all(len(s) == 1 for s in seen)
        # the failing call was made with another one launched and unfetched
        assert calls[2] is True
        setattr(sched, attr, good)
        assert sched.drain(30)
        assert sched._inflight is None
        assert sched._cache is not pool_before
        stats = sched.stats()
        assert stats["kv"]["blocks_used"] == 0 and stats["slots_active"] == 0
        assert eng.generate(prompts[0], max_new_tokens=6).result(120) == ref


@pytest.mark.parametrize("how", ["drain", "close"])
def test_drain_and_close_wait_for_the_call_in_flight(models, how):
    model, _vocab = models("lm")
    before = len(recorder().snapshot())
    eng = _engine(model, 2)
    try:
        sched = eng._generation()
        real = sched._fetch

        def slow(*a, **k):
            time.sleep(0.03)
            return real(*a, **k)

        sched._fetch = slow
        fut = eng.generate(np.arange(1, 8, dtype=np.int32),
                           max_new_tokens=10)
        next(fut.stream(60))                 # calls are being launched
        if how == "drain":
            assert sched.drain(60)
        else:
            eng.close()
            assert not sched._dispatcher.is_alive()
            assert sched._cache is None
        assert sched._inflight is None
        assert len(fut.result(0)) == 10
    finally:
        eng.close()
    # the dispatcher never went idle between a launch and its fetch
    recs = recorder().snapshot()[before:]
    launches = sorted(r.start_ns for r in recs if r.name == "launch")
    fetches = sorted(r.end_ns for r in recs if r.name == "fetch")
    assert len(launches) == len(fetches) > 0
    for idle in (r for r in recs if r.name == "dispatcher_idle"):
        assert sum(t < idle.start_ns for t in launches) \
            == sum(t <= idle.start_ns for t in fetches)
