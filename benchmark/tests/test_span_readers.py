"""The two readers of the program's spans (``span_stat``, ``span_idle``)
and the clock that joins them to a device trace (``harness/spans.py``),
on inputs built by hand.

The device (ns from the trace's start): ``jit_decode`` runs at [0, 1000],
[2000, 3000] and [4000, 5000], so it idles in [1000, 2000] and
[3000, 4000].  The host's clock is the device's plus ``OFF``.  On the
dispatcher thread, relative to ``OFF``::

    tick A [-500, 3500]
      decode_prep     [-400, -100]   rows 3 of 4 slots
      generate_decode [-100, 1000]   launch [-100, -50], fetch [-50, 1000]
      decode_prep     [1000, 2050]   rows 4 of 4: covers the first gap whole
      generate_decode [2050, 3100]   launch [2050, 2100], fetch [2100, 3100]
      deliver         [3100, 3500]
    (nothing)         [3500, 4000]   half of the second gap is uncovered
    tick B [4000, 5200]
      generate_decode [4000, 5050]   launch [4000, 4100], fetch [4100, 5050]

Idle by span: decode_prep 1000, fetch 100, deliver 400, uncovered 500 of
2000 ns.  Fence residuals (fetch end - decode end): 0, 100, 50.
"""

import collections
import os

import pytest

from harness import resolve, spans, trace

Record = collections.namedtuple(
    "Record", "name start_ns end_ns thread span_id parent_id request_id "
              "attrs")
OFF = 1_700_000_000_000_000_000
FENCE = {"span": "fetch", "under": "generate_decode", "module": "^jit_decode",
         "slack_ns": 200}      # the toy's programs are 2000 ns apart
IDLE_ARGS = {"roots": ["tick", "dispatcher_idle"], "not_counted": ["tick"],
             "fence": FENCE}


def _reader(name):
    return resolve.load_module(os.path.join(
        resolve.BENCH_DIR, "metrics", "readers", name + ".py"), "r_" + name)


def plane():
    runs = [(0, 1000), (2000, 1000), (4000, 1000)]
    return trace.DeviceTrace(
        "/device:TPU:0",
        ["%fusion.1 = f32[8] fusion(...)"] * 3, [s for s, _ in runs],
        [d for _, d in runs],
        ["jit_decode(123)"] * 3, [s for s, _ in runs], [d for _, d in runs])


def records(deliver=True, shift=0):
    ids = iter(range(1, 100))
    out = []

    def rec(name, a, b, parent=None, thread=7, request_id=None, **attrs):
        r = Record(name, OFF + shift + a, OFF + shift + b, thread,
                   next(ids), parent.span_id if parent else None,
                   request_id, attrs or None)
        out.append(r)
        return r

    a = rec("tick", -500, 3500, tick=0)
    rec("decode_prep", -400, -100, a, rows=3, slots_total=4)
    g = rec("generate_decode", -100, 1000, a)
    rec("launch", -100, -50, g)
    rec("fetch", -50, 1000, g)
    rec("decode_prep", 1000, 2050, a, rows=4, slots_total=4)
    g = rec("generate_decode", 2050, 3100, a)
    rec("launch", 2050, 2100, g)
    rec("fetch", 2100, 3100, g)
    if deliver:
        rec("deliver", 3100, 3500, a, tokens=4, finished=1)
    b = rec("tick", 4000, 5200, tick=2)
    g = rec("generate_decode", 4000, 5050, b)
    rec("launch", 4000, 4100, g)
    rec("fetch", 4100, 5050, g)
    # what must be left out: a request record (no parent, not a root), a
    # span of another thread over the uncovered stretch, an instant
    rec("request", -5000, 4500, request_id=1, queue_wait_ns=1000,
        prefill_ns=3000, decode_ns=5500)
    rec("request", -9000, 9000, request_id=2, queue_wait_ns=3000,
        prefill_ns=17000, decode_ns=0)
    other = rec("tick", 3400, 4100, thread=9)
    rec("deliver", 3500, 4000, other, thread=9)
    rec("mark", 3600, 3600, a)
    return out


def test_idle_is_cut_by_the_innermost_span_of_the_feeding_thread():
    r = _reader("span_idle")
    assert r.idle_intervals(plane()) == [(1000.0, 2000.0), (3000.0, 4000.0)]
    table = r.idle_by_span(plane(), records(), OFF, IDLE_ARGS)
    assert {k: round(v * 1e9) for k, v in table.items()} == {
        "decode_prep": 1000, "fetch": 100, "deliver": 400,
        r.UNCOVERED: 500}
    assert r.share(table, {"tick"}) == pytest.approx(75.0)
    # without the deliver span that stretch is the bare tick's: not named
    table = r.idle_by_span(plane(), records(deliver=False), OFF, IDLE_ARGS)
    assert round(table["tick"] * 1e9) == 400
    assert r.share(table, {"tick"}) == pytest.approx(55.0)
    assert r.share(table, set()) == pytest.approx(75.0)


def test_segments_name_every_stretch_once():
    r = _reader("span_idle")
    segs = r.innermost_segments(records(), {"tick", "dispatcher_idle"})
    assert segs[0] == (OFF - 500, OFF - 400, "tick")
    assert all(a[1] == b[0] for a, b in zip(segs, segs[1:]))   # no hole
    assert (OFF + 3500, OFF + 4000, r.UNCOVERED) in segs
    assert [n for _a, _b, n in segs].count("launch") == 3
    assert r.innermost_segments(records(), {"step"}) == []


MS = 1_000_000


def ticking(residuals_ns, period=200 * MS, busy=150 * MS, lead=100_000):
    """A device program every ``period`` and, on the host, the launch that
    started ``lead`` before each and the fetch that waits for each, ending
    ``residuals_ns[i]`` after it."""
    n = len(residuals_ns)
    starts = [i * period for i in range(n)]
    dev = trace.DeviceTrace("/device:TPU:0", ["%fusion.1 = f32[8] f()"] * n,
                            starts, [busy] * n, ["jit_decode(1)"] * n,
                            starts, [busy] * n)
    recs = []
    for i, r in enumerate(residuals_ns):
        g = Record("generate_decode", OFF + starts[i] - lead,
                   OFF + starts[i] + busy + r, 7, 3 * i + 1, None, None, None)
        recs += [g, Record("launch", g.start_ns, g.start_ns + 2 * lead, 7,
                           3 * i + 2, g.span_id, None, None),
                 Record("fetch", g.start_ns + 2 * lead, g.end_ns, 7,
                        3 * i + 3, g.span_id, None, None)]
    return dev, recs


MS_FENCE = {k: v for k, v in FENCE.items() if k != "slack_ns"}
LAUNCH = {**MS_FENCE, "launch": {"span": "launch", "under": "generate_decode",
                                 "module": "^jit_decode"}}


def test_clock_holds_the_offset_to_the_fence():
    c = spans.clock(plane(), records(), OFF, FENCE)
    assert c["source"] == "profile_start_time" and c["fences"] == 3
    assert c["residual_ns"] == {"least": 0.0, "median": 50.0, "worst": 100.0}
    assert c["offset_ns"] == OFF
    dev, recs = ticking([40_000, 300_000, 90_000, 60_000, 70_000])
    c = spans.clock(dev, recs, OFF, MS_FENCE)
    assert c["source"] == "profile_start_time" and c["offset_ns"] == OFF
    assert c["residual_ns"] == {"least": 40_000.0, "median": 70_000.0,
                                "worst": 300_000.0}
    # a start stamp 3 ms early: every fence reads 3 ms late, over the
    # limit; the offset is moved to the least residual and says so
    c = spans.clock(dev, recs, OFF - 3 * MS, MS_FENCE)
    assert c["source"] == "fence" and c["offset_ns"] == OFF + 40_000
    assert c["residual_ns"] == {"least": 0.0, "median": 30_000.0,
                                "worst": 260_000.0}
    # 1 ms late: fences would end before the device did
    c = spans.clock(dev, recs, OFF + MS, MS_FENCE)
    assert c["source"] == "fence" and c["offset_ns"] == OFF + 40_000
    # half a millisecond early is within the limit: taken as it is
    c = spans.clock(dev, recs, OFF - MS // 2, MS_FENCE)
    assert c["source"] == "profile_start_time"
    assert c["residual_ns"]["median"] == pytest.approx(570_000.0)
    # the other side: a program cannot start before its launch.  Under the
    # true offset each starts 100 us after; a start stamp 0.5 ms EARLY puts
    # them 400 us before, which the fetch fence alone cannot see as long
    # as its median stays under the limit
    late, recs_late = ticking([700_000, 900_000, 800_000])
    c = spans.clock(late, recs_late, OFF, LAUNCH)
    assert c["source"] == "profile_start_time"
    assert c["launch_lead_ns"] == 100_000.0
    assert spans.clock(late, recs_late, OFF - MS // 2, MS_FENCE)["source"] \
        == "profile_start_time"
    c = spans.clock(late, recs_late, OFF - MS // 2, LAUNCH)
    assert c["source"] == "fence" and c["offset_ns"] == OFF + 700_000
    # the bracket: the true offset lies no higher than the one taken and
    # at most launch_lead_ns below it
    assert c["launch_lead_ns"] == 800_000.0
    assert spans.clock(dev, recs, OFF, MS_FENCE)["launch_lead_ns"] is None
    # a start stamp off by more than the slack is not repaired: the fences
    # of the next period would fit as well
    assert spans.clock(dev, recs, OFF - 20 * MS, MS_FENCE) is None
    # fences that no one offset fits: most end 3 ms after the device did
    dev, recs = ticking([0, 3 * MS, 3 * MS, 3 * MS, 0])
    assert spans.clock(dev, recs, OFF, MS_FENCE) is None
    assert spans.clock(plane(), records(), None, FENCE) is None
    assert spans.clock(plane(), [], OFF, FENCE) is None
    assert spans.clock(plane(), records(), OFF,
                       {"span": "loss_sync", "module": "^jit_train"}) is None


def test_span_stat_reductions():
    r = _reader("span_stat")
    recs = records()
    lo, hi = OFF - 1000, OFF + 6000
    # ticks of thread 7 and 9: durations 4000, 1200, 700
    assert r.reduce(recs, lo, hi, {"reduce": "median_ms", "span": "tick"}) \
        == pytest.approx(1200e-6)
    # less their generate_decode children: 4000-1100-1050, 1200-1050, 700
    assert r.reduce(recs, lo, hi, {
        "reduce": "median_ms", "span": "tick",
        "minus": ["generate_decode"]}) == pytest.approx(700e-6)
    assert r.reduce(recs, lo, hi, {
        "reduce": "share", "numerator": "fetch",
        "denominator": "generate_decode"}) \
        == pytest.approx(100.0 * 3000 / 3200)
    assert r.reduce(recs, lo, hi, {
        "reduce": "ratio", "span": "decode_prep", "numerator": ["rows"],
        "denominator": ["slots_total"]}) == pytest.approx(100.0 * 7 / 8)
    # a request counts by its first token: -5000+4000 is inside, -9000+20000
    # is not
    args = {"reduce": "ratio", "span": "request",
            "at": ["queue_wait_ns", "prefill_ns"],
            "numerator": ["queue_wait_ns"],
            "denominator": ["queue_wait_ns", "prefill_ns"]}
    assert r.reduce(recs, lo, hi, args) == pytest.approx(25.0)
    assert r.reduce(recs, lo, OFF + 20000, args) \
        == pytest.approx(100.0 * 4000 / 24000)
    assert r.reduce(recs, lo, hi, {"reduce": "median_ms",
                                   "span": "no_such"}) is None
    with pytest.raises(ValueError):
        r.reduce(recs, lo, hi, {"reduce": "mean"})


def test_span_stat_clips_to_the_window():
    r = _reader("span_stat")
    recs = records()
    lo, hi = OFF + 0, OFF + 3000
    # a median takes only the spans wholly inside: no tick is
    assert r.reduce(recs, lo, hi, {"reduce": "median_ms",
                                   "span": "tick"}) is None
    assert r.reduce(recs, lo, hi, {"reduce": "median_ms",
                                   "span": "decode_prep"}) \
        == pytest.approx(1050e-6)
    # a share cuts each span to the window: fetch 1000+900 of
    # generate_decode 1000+950
    assert r.reduce(recs, lo, hi, {
        "reduce": "share", "numerator": "fetch",
        "denominator": "generate_decode"}) \
        == pytest.approx(100.0 * 1900 / 1950)
    # a ratio counts the spans that start inside: the second decode_prep
    assert r.reduce(recs, lo, hi, {
        "reduce": "ratio", "span": "decode_prep", "numerator": ["rows"],
        "denominator": ["slots_total"]}) == pytest.approx(100.0)


class _Cell:
    name = "toy.cell"


def _env():
    return {"planes": [plane()], "cell": _Cell()}


def test_read_joins_ring_window_and_clock(monkeypatch, capsys):
    monkeypatch.setattr(spans, "records", records)
    monkeypatch.setattr(spans, "profile_start_ns", lambda cell: OFF)
    env = _env()
    assert _reader("span_idle").read(env, IDLE_ARGS) == pytest.approx(75.0)
    err = capsys.readouterr().err
    assert "idle seconds by span name: decode_prep 0.000001" in err
    assert "clock: offset from profile_start_time" in err
    assert "median 0.1 us" in err          # 50 ns
    assert "after its launch: not asked" in err
    # the traced window is [0, 5000] on the device: tick A starts before it
    stat = _reader("span_stat")
    assert stat.read(env, {"reduce": "median_ms", "span": "decode_prep",
                           "fence": FENCE}) == pytest.approx(1050e-6)
    assert stat.read(env, {"reduce": "share", "numerator": "tick",
                           "denominator": "tick", "fence": FENCE}) == 100.0
    # worked out once a run
    assert capsys.readouterr().err.count("clock:") == 0


def test_read_returns_none_when_there_is_nothing_to_read(monkeypatch):
    idle, stat = _reader("span_idle"), _reader("span_stat")
    args = {"reduce": "median_ms", "span": "tick", "fence": FENCE}
    monkeypatch.setattr(spans, "profile_start_ns", lambda cell: OFF)
    # an empty ring; a program without the recorder
    for nothing in ([], None):
        monkeypatch.setattr(spans, "records", lambda: nothing)
        assert idle.read(_env(), IDLE_ARGS) is None
        assert stat.read(_env(), args) is None
    # a ring, but no Task Environment plane in the trace
    monkeypatch.setattr(spans, "records", records)
    monkeypatch.setattr(spans, "profile_start_ns", lambda cell: None)
    assert idle.read(_env(), IDLE_ARGS) is None
    # a clock that no offset repairs
    dev, recs = ticking([0, 3 * MS, 3 * MS, 3 * MS, 0])
    monkeypatch.setattr(spans, "records", lambda: recs)
    assert idle.read({"planes": [dev], "cell": _Cell()}, IDLE_ARGS) is None
    # no root span on any thread
    monkeypatch.setattr(spans, "records", records)
    assert idle.read(_env(), {**IDLE_ARGS, "roots": ["step"]}) is None


def test_the_programs_recorder_is_what_records_reads():
    from bigdl_tpu.observability.spans import recorder, span

    recorder().clear()
    with span("tick", tick=1):
        pass
    got = spans.records()
    assert [r.name for r in got] == ["tick"]
    assert got[0]._fields == Record._fields


def test_profile_start_time_of_a_committed_trace(monkeypatch, tmp_path):
    r4 = os.path.join(resolve.ROOT, "docs", "traces", "r4_tpu_b128")
    if not os.path.isdir(r4):
        pytest.skip("trace not here")
    monkeypatch.setattr(resolve, "ROOT", str(tmp_path))
    assert spans.profile_start_ns("toy.cell") is None        # no file
    os.makedirs(tmp_path / ".bench_tmp")
    os.symlink(r4, tmp_path / ".bench_tmp" / "toy.cell")
    assert spans.profile_start_ns("toy.cell") == 1785459843454610149
    # the first train step starts 10.25 ms into the session: on the host's
    # clock that is the session's start plus its device offset
    (dev,) = trace.load(r4)
    assert dev.module_runs("^jit_train_step")[0][0] == 10250856.0
