"""The span recorder (``observability/spans.py``): one process-wide ring on
the device trace's clock, and the spans the serving dispatcher and the
trainer loop record into it with no telemetry object attached."""

import collections
import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import nn, optim
from bigdl_tpu.dataset import SampleToMiniBatch, array_dataset
from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.observability import spans
from bigdl_tpu.observability.spans import (SpanTracer, instant, now_ns,
                                           record_span, recorder, span, to_ns)
from bigdl_tpu.serving import ServingEngine
from bigdl_tpu.utils.random_generator import RNG

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mine(recs=None):
    """The calling thread's records (other tests' threads may still run)."""
    me = threading.get_ident()
    return [r for r in (recorder().snapshot() if recs is None else recs)
            if r.thread == me]


@pytest.fixture(autouse=True)
def fresh_ring():
    recorder().clear()
    recorder().enabled = True
    yield
    recorder().enabled = True


class TestRecorder:
    def test_nesting_gives_parent_id(self):
        with span("outer", a=1) as o:
            with span("inner"):
                with span("leaf"):
                    pass
            with span("sibling"):
                pass
        by = {r.name: r for r in mine()}
        assert by["outer"].parent_id is None
        assert by["inner"].parent_id == by["outer"].span_id
        assert by["leaf"].parent_id == by["inner"].span_id
        assert by["sibling"].parent_id == by["outer"].span_id
        assert by["outer"].attrs == {"a": 1} and by["leaf"].attrs is None
        # children close first; a parent's interval holds its children's
        assert [r.name for r in mine()] == ["leaf", "inner", "sibling",
                                            "outer"]
        assert by["outer"].start_ns <= by["inner"].start_ns \
            <= by["inner"].end_ns <= by["outer"].end_ns
        assert o.end_ns == by["outer"].end_ns >= o.start_ns

    def test_two_threads_do_not_interleave_parents(self):
        go = threading.Barrier(2)

        def work(tag):
            go.wait(timeout=10)
            for i in range(200):
                with span("root", tag=tag):
                    with span("child", tag=tag):
                        pass

        ts = [threading.Thread(target=work, args=(t,)) for t in "ab"]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
            assert not t.is_alive()
        recs = [r for r in recorder().snapshot()
                if r.name in ("root", "child")
                and r.attrs["tag"] in ("a", "b")]
        assert len(recs) == 800
        by = {r.span_id: r for r in recs}
        assert len(by) == 800                        # ids never collide
        for r in recs:
            if r.name == "child":
                parent = by[r.parent_id]
                assert parent.name == "root"
                assert parent.thread == r.thread
                assert parent.attrs["tag"] == r.attrs["tag"]
            else:
                assert r.parent_id is None

    def test_ring_is_bounded(self, monkeypatch):
        assert recorder()._ring.maxlen == spans.RING_RECORDS == 65536
        monkeypatch.setattr(recorder(), "_ring",
                            collections.deque(maxlen=8))
        for i in range(50):
            with span("s", i=i):
                pass
        recs = recorder().snapshot()
        assert len(recs) == 8
        assert [r.attrs["i"] for r in recs] == list(range(42, 50))

    def test_disabled_records_nothing_but_still_stamps(self):
        recorder().enabled = False
        with span("off") as s:
            with span("off_child"):
                pass
        instant("off_instant")
        record_span("off_given", now_ns() - 10, now_ns())
        assert recorder().snapshot() == []
        assert s.end_ns >= s.start_ns > 0            # the caller's times
        recorder().enabled = True
        with span("on"):
            pass
        assert [r.name for r in mine()] == ["on"]
        assert mine()[0].parent_id is None           # no stale stack

    def test_snapshot_since_and_clear(self):
        with span("early"):
            pass
        cut = now_ns()
        with span("late"):
            pass
        assert [r.name for r in mine(recorder().snapshot(cut))] == ["late"]
        snap = recorder().snapshot()
        recorder().clear()
        assert recorder().snapshot() == [] and len(snap) >= 2

    def test_clock_is_epoch_nanoseconds_on_a_monotonic_base(self):
        a, wall, b = now_ns(), time.time_ns(), now_ns()
        assert a <= b
        # the anchor was taken at import: the two clocks may have drifted
        # apart since, but not by a second
        assert abs(wall - a) < 1e9
        p = time.perf_counter()
        assert abs(to_ns(p) - now_ns()) < 50e6
        with span("x") as s:
            pass
        assert a < s.start_ns <= s.end_ns

    def test_instant_and_given_stamps(self):
        with span("holder") as h:
            instant("sample", depth=3)
            record_span("nested", h.start_ns, h.start_ns + 5, nest=True)
        record_span("request", 1000, 2000, request_id=7, tokens=4)
        by = {r.name: r for r in mine()}
        assert by["sample"].start_ns == by["sample"].end_ns
        assert by["sample"].parent_id == by["holder"].span_id
        assert by["sample"].attrs == {"depth": 3}
        assert by["nested"].parent_id == by["holder"].span_id
        req = by["request"]
        assert (req.start_ns, req.end_ns, req.parent_id, req.request_id) \
            == (1000, 2000, None, 7)

    def test_set_adds_attributes_before_the_span_closes(self):
        with span("deliver", tokens=2) as s:
            s.set(finished=1)
        with span("bare") as b:
            b.set(k="v")
        by = {r.name: r for r in mine()}
        assert by["deliver"].attrs == {"tokens": 2, "finished": 1}
        assert by["bare"].attrs == {"k": "v"}

    def test_a_backend_compile_is_a_compile_span(self):
        with span("outer"):
            # a constant no cache has seen: the program must compile
            k = float(time.time_ns() % 1000003)
            jax.jit(lambda x: x * 3 + k)(jnp.ones((3, 5)))
        recs = mine()
        comp = [r for r in recs if r.name == "compile"]
        assert comp, [r.name for r in recs]
        outer = [r for r in recs if r.name == "outer"][0]
        assert all(c.parent_id == outer.span_id for c in comp)
        assert all(c.end_ns > c.start_ns for c in comp)

    def test_spans_module_needs_the_standard_library_only(self):
        code = ("import importlib.util, sys\n"
                "spec = importlib.util.spec_from_file_location('s', %r)\n"
                "m = importlib.util.module_from_spec(spec)\n"
                "spec.loader.exec_module(m)\n"
                "with m.span('a'):\n    pass\n"
                "assert [r.name for r in m.recorder().snapshot()] == ['a']\n"
                "assert 'jax' not in sys.modules\n"
                "assert 'numpy' not in sys.modules\n"
                % os.path.join(REPO, "bigdl_tpu", "observability",
                               "spans.py"))
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stderr


class TestSpanTracerIsASink:
    def test_active_tracer_writes_the_same_spans_in_the_same_order(
            self, tmp_path):
        path = str(tmp_path / "t.json")
        with span("before_the_tracer"):
            pass
        with SpanTracer(path):
            with span("a", n=1):
                with span("b"):
                    pass
            instant("mark", k=2)
            with span("c"):
                pass
        with span("after_the_tracer"):
            pass
        ring = [r for r in mine() if r.name in ("a", "b", "mark", "c")]
        events = [e for e in json.load(open(path))
                  if e.get("ph") in ("X", "i")
                  and e["name"] != "wall_time_origin"]
        assert [e["name"] for e in events] == [r.name for r in ring] \
            == ["b", "a", "mark", "c"]
        origin = [e for e in json.load(open(path))
                  if e["name"] == "wall_time_origin"][0]
        o_ns = origin["args"]["wall_time_origin"] * 1e9
        for e, r in zip(events, ring):
            # one clock: the file's microseconds are the ring's stamps
            assert abs(o_ns + e["ts"] * 1e3 - r.start_ns) < 2e3
            if e["ph"] == "X":
                assert abs(e["dur"] * 1e3 - (r.end_ns - r.start_ns)) < 2
        assert events[1]["args"] == {"n": 1}
        assert events[2]["ph"] == "i" and events[2]["args"] == {"k": 2}

    def test_two_tracers_and_a_dead_one(self, tmp_path):
        """Sinks are held weakly and each sees every record; a record that
        began before a tracer did is not written to it."""
        p1, p2 = str(tmp_path / "1.json"), str(tmp_path / "2.json")
        t1 = SpanTracer(p1).activate()
        with span("straddles"):
            t2 = SpanTracer(p2).activate()
            with span("both"):
                pass
        dead = SpanTracer(str(tmp_path / "3.json")).activate()
        del dead
        with span("after_dead"):
            pass
        t1.close()
        t2.close()
        names = lambda p: [e["name"] for e in json.load(open(p))
                           if e.get("ph") == "X"]
        assert names(p1) == ["both", "straddles", "after_dead"]
        assert names(p2) == ["both", "after_dead"]
        assert recorder()._sinks == () or all(
            r() is None for r in recorder()._sinks)


def _lm():
    m = TransformerLM(vocab_size=32, hidden_size=16, num_heads=4,
                      num_layers=1, max_len=32)
    m.build(jax.ShapeDtypeStruct((2, 8), jnp.int32),
            rng=jax.random.PRNGKey(0))
    return m


def _children(recs):
    kids = collections.defaultdict(list)
    for r in recs:
        kids[r.parent_id].append(r)
    for v in kids.values():
        v.sort(key=lambda r: r.start_ns)
    return kids


class TestServingSpans:
    def test_three_requests_leave_every_span_of_the_table(self):
        """No StepTelemetry, no SpanTracer: the ring alone."""
        with ServingEngine(_lm(), decode_slots=2, decode_max_len=32,
                           kv_block_size=4, prefill_chunk=4) as eng:
            futs = [eng.generate([1 + i, 2, 3, 4, 5, 6], max_new_tokens=3)
                    for i in range(3)]
            outs = [f.result(120) for f in futs]
        assert all(len(o) == 3 for o in outs)
        recs = recorder().snapshot()
        ticks = [r for r in recs if r.name == "tick"]
        thread = ticks[0].thread
        assert all(t.thread == thread and t.parent_id is None
                   for t in ticks)
        kids = _children([r for r in recs if r.thread == thread])
        prefills = decodes = 0
        launched, fetched = {}, {}
        for t in ticks:
            names = [c.name for c in kids[t.span_id]]
            assert names[0] == "admit"
            assert {"tick", "queue_depth", "slots_decoding",
                    "slots_prefilling", "slots_total",
                    "blocks_free"} <= set(t.attrs)
            assert t.attrs["slots_total"] == 2
            rest = kids[t.span_id][1:]
            # the dispatcher launches a call before it fetches the one
            # ahead of it: pairs of (prep, generate_* > launch) and of
            # (generate_* > fetch, deliver), in any order
            assert len(rest) % 2 == 0
            for a, b in zip(*[iter(rest)] * 2):
                gen = b if a.name.endswith("_prep") else a
                kind = gen.name.split("_")[1]
                assert gen.name in ("generate_prefill", "generate_decode")
                assert {"tick", "records"} <= set(gen.attrs)
                did = [c.name for c in kids[gen.span_id]
                       if c.name != "compile"]
                key = (kind, gen.attrs["tick"])
                if gen is b:
                    assert a.name == kind + "_prep" and did == ["launch"]
                    assert {"rows", "slots_total", "ahead"} <= set(a.attrs)
                    assert key not in launched
                    launched[key] = a
                else:
                    assert b.name == "deliver" and did == ["fetch"]
                    assert {"tokens", "finished", "rows_wasted"} \
                        <= set(b.attrs)
                    assert key in launched and key not in fetched
                    fetched[key] = b
                assert t.start_ns <= a.start_ns <= a.end_ns \
                    <= b.start_ns <= b.end_ns <= t.end_ns
        # every call launched was fetched, one call later at most, and
        # delivered a token for each of its rows that was still wanted
        assert sorted(launched) == sorted(fetched)
        ticks_of = sorted(n for _kind, n in launched)
        assert ticks_of == list(range(ticks_of[0], ticks_of[-1] + 1))
        for (kind, n), prep in launched.items():
            dlv = fetched[kind, n]
            if kind == "prefill":
                prefills += 1
                assert {"bucket", "prompt_tokens"} <= set(prep.attrs)
            else:
                decodes += 1
                assert {"rows_ahead"} <= set(prep.attrs)
                assert prep.attrs["rows"] == dlv.attrs["tokens"] \
                    + dlv.attrs["rows_wasted"]
        assert any(p.attrs["ahead"] for p in launched.values())
        assert prefills >= 2 and decodes >= 4
        assert sum(kids[t.span_id][0].attrs["requests"]
                   for t in ticks) == 3
        assert any(r.name == "dispatcher_idle" for r in recs)
        assert any(r.name == "compile" for r in recs)
        # one request record per future, and its three parts add up
        reqs = {r.request_id: r for r in recs if r.name == "request"}
        assert sorted(reqs) == sorted(f.request_id for f in futs)
        for f in futs:
            r = reqs[f.request_id]
            a = r.attrs
            assert a["prompt_tokens"] == 6 and a["new_tokens"] == 3
            assert a["finish_reason"] == "length"
            assert a["chunks"] == 2 - (a["prefix_hit_tokens"] > 0)
            parts = a["queue_wait_ns"] + a["prefill_ns"] + a["decode_ns"]
            assert abs(parts * 1e-9 - f.latency_s) < 1e-3
            assert abs((r.end_ns - r.start_ns) * 1e-9 - f.latency_s) < 1e-3
            assert abs(f.queue_wait_s + f.decode_s - f.latency_s) < 1e-3
            assert a["prefill_ns"] > 0 and a["decode_ns"] > 0
        # two slots, three requests: the third waited for a slot
        assert max(r.attrs["queue_wait_ns"] for r in reqs.values()) \
            > min(r.attrs["prefill_ns"] for r in reqs.values())

    def test_a_decode_tick_beside_a_chunk_counts_both_calls_ahead(self):
        """A prompt prefills while another row decodes: each of those
        iterations launches its decode tick with the decode tick before it
        and its chunk unfetched (``ahead`` 2), and the decoding row's token
        is taken on the device from the first (``rows_ahead``)."""
        with ServingEngine(_lm(), decode_slots=2, decode_max_len=32,
                           kv_block_size=4, prefill_chunk=4) as eng:
            first = eng.generate([1, 2, 3], max_new_tokens=16)
            next(first.stream(120))                  # it decodes
            second = eng.generate(list(range(4, 16)), max_new_tokens=2)
            assert len(second.result(120)) == 2
            assert len(first.result(120)) == 16
        recs = recorder().snapshot()
        ahead = {name: {r.attrs["ahead"] for r in recs if r.name == name}
                 for name in ("prefill_prep", "decode_prep")}
        assert ahead["prefill_prep"] <= {0, 1}
        assert ahead["decode_prep"] <= {0, 1, 2}
        two = [r.attrs for r in recs
               if r.name == "decode_prep" and r.attrs["ahead"] == 2]
        # one a chunk of the second prompt; the last chunk's decode tick
        # also takes the second row's first token from that chunk
        assert [(a["rows"], a["rows_ahead"]) for a in two] \
            == [(1, 1), (1, 1), (2, 2)]

    def test_a_failed_request_is_recorded_once_with_its_reason(self):
        with ServingEngine(_lm(), decode_slots=2, decode_max_len=32,
                           kv_block_size=4, kv_blocks=2) as eng:
            fut = eng.generate([1, 2, 3, 4, 5, 6, 7, 8, 9], max_new_tokens=8)
            with pytest.raises(Exception):
                fut.result(120)
        reqs = [r for r in recorder().snapshot() if r.name == "request"
                and r.request_id == fut.request_id]
        assert len(reqs) == 1
        assert reqs[0].attrs["finish_reason"] == "error:BlockPoolExhausted"
        assert reqs[0].attrs["new_tokens"] == 0


class TestTrainerSpans:
    def test_three_steps_without_telemetry(self):
        RNG.set_seed(0)
        rng = np.random.default_rng(0)
        x = rng.standard_normal((96, 8)).astype("float32")
        y = rng.integers(0, 4, 96).astype("int32")
        model = (nn.Sequential().add(nn.Linear(8, 16)).add(nn.ReLU())
                 .add(nn.Linear(16, 4)))
        opt = optim.Optimizer(
            model=model, dataset=array_dataset(x, y) >> SampleToMiniBatch(32),
            criterion=nn.CrossEntropyCriterion(),
            optim_method=optim.SGD(learning_rate=0.1))
        opt.set_end_when(optim.Trigger.max_iteration(3))
        assert opt.telemetry is None
        opt.optimize()
        recs = mine()
        steps = [r for r in recs if r.name == "step"]
        assert [s.attrs["step"] for s in steps] == [1, 2, 3]
        kids = _children(recs)
        for s in steps:
            assert s.parent_id is None
            names = [c.name for c in kids[s.span_id]]
            assert names.count("dispatch") == 1
            assert names.count("loss_sync") == 1
            assert names.count("stage_next_batch") == 1
            assert names.index("dispatch") < names.index("stage_next_batch") \
                < names.index("loss_sync")
            assert all(c.attrs["step"] in (s.attrs["step"],
                                           s.attrs["step"] + 1)
                       for c in kids[s.span_id] if c.name != "compile")
        # the first batch is staged inside step 1, later ones a step ahead
        assert "device_stage" in [c.name for c in kids[steps[0].span_id]]
        # the step compiled inside the first dispatch, and says so
        first = [c for c in kids[steps[0].span_id]
                 if c.name == "dispatch"][0]
        assert any(c.name == "compile" for c in kids[first.span_id])
