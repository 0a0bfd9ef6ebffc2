"""``harness/trace_meta.py`` and the ``scope_share`` reader (PR 37) against
a trace the test writes itself, byte by byte: two programs that share a
short operation name, operations with and without a ``tf_op``, a ``while``
container, one path of each phase, a string kept by reference; the same
decode once against the recorded ResNet trace under ``docs/traces``; and
every metric file of the PR through ``resolve.Cell``."""

import os

import pytest

from harness import resolve, trace, trace_meta

ROOT = resolve.ROOT
RECORDED = os.path.join(ROOT, "docs", "traces", "r4_tpu_b128")
TRAIN_CELLS = ["gpt2-medium.train.seq1024", "lfm2-8b-a1b.train.seq4096"]
SERVE_CELL = "gpt2-medium.serve.batch-generate"
#: metric -> (its cells, layer, moves, better)
METRICS = {
    "scope_named_share.train": (TRAIN_CELLS, "step program",
                                "train_step_ms", "higher"),
    "backward_device_share.train": (TRAIN_CELLS, "step program",
                                    "train_step_ms", "lower"),
    "recompute_device_share.train": (TRAIN_CELLS, "step program",
                                     "train_step_ms", "lower"),
    "optimizer_device_share.train": (TRAIN_CELLS, "step program",
                                     "train_step_ms", "lower"),
    "head_loss_device_share.train": (TRAIN_CELLS, "step program",
                                     "train_step_ms", "lower"),
    "attention_device_share.train": (TRAIN_CELLS, "step program",
                                     "train_step_ms", "lower"),
    "moe_layer_device_share.train": (TRAIN_CELLS[1:], "step program",
                                     "train_step_ms", "lower"),
    "scope_named_share.serve": ([SERVE_CELL], "serving engine",
                                "serve_tokens_per_s", "higher"),
    "attention_device_share.serve": ([SERVE_CELL], "serving engine",
                                     "serve_tokens_per_s", "lower"),
    "head_sampler_device_share.serve": ([SERVE_CELL], "serving engine",
                                        "serve_tokens_per_s", "lower"),
    "chunk_prefill_device_share.serve": ([SERVE_CELL], "serving engine",
                                         "serve_ttft_p90_ms", "lower"),
}


# ----------------------------------------------- a trace, byte by byte -- #

def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    """A varint field for an int, a length-delimited one for bytes/str."""
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def entry(key, message):
    """One entry of a ``map<int64, Message>``."""
    return field(1, key) + field(2, message)


TF_OP, PROGRAM_ID, CATEGORY, FLOPS, DATA_FORMATTING = 1, 2, 3, 4, 9
TRAIN, DECODE = 111, 2707457242856997706     # a program id needs 64 bits

#: metadata id -> (HLO text, tf_op, program, hlo_category); a category
#: given as an int is kept by reference (``ref_value``)
OPERATIONS = {
    1: ("%fusion.7 = bf16[8,64]{1,0:T(8,128)(2,1)} fusion(%p), kind=kOutput",
        "jit(train_step)/jvp()/while/body/closed_call/checkpoint/attention/"
        "dot_general", TRAIN, "convolution fusion"),
    2: ("%fusion.8 = bf16[8,64]{1,0} fusion(%p), kind=kOutput",
        "jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
        "rematted_computation/attention/dot_general", TRAIN,
        "convolution fusion"),
    3: ("%fusion.9 = bf16[8,64]{1,0} fusion(%p), kind=kOutput",
        "jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
        "mlp/dot_general", TRAIN, "convolution fusion"),
    4: ("%fusion.10 = f32[64]{0} fusion(%p), kind=kLoop",
        "jit(train_step)/optimizer/sub", TRAIN, "loop fusion"),
    5: ("%copy.1 = f32[64]{0} copy(%p)", None, TRAIN, DATA_FORMATTING),
    6: ("%while.2 = (s32[], bf16[8,64]{1,0}) while(%t), condition=%c, "
        "body=%b", "jit(train_step)/jvp()/while", TRAIN, "while"),
    7: ("%fusion.3 = f32[8]{0} fusion(%p), kind=kLoop",
        "jit(train_step)/transpose(jvp(loss))/mul", TRAIN, "loop fusion"),
    # the same short name in another program
    8: ("%fusion.3 = f32[1257472]{0} fusion(%p), kind=kLoop",
        "jit(decode)/sampler/gather", DECODE, "loop fusion"),
    9: ("%fusion.20 = bf16[8,64]{1,0} fusion(%p), kind=kOutput",
        "jit(decode)/moe_weights/while/body/moe/moe_experts/dot_general",
        DECODE, "convolution fusion"),
    10: ("%convert.12 = bf16[1024,1024]{1,0} convert(%p)", None, DECODE,
         "convert"),
    100: ("jit_train_step(%d)" % TRAIN, None, None, None),
    101: ("jit_decode(%d)" % DECODE, None, None, None),
}
#: (metadata id, start ns, duration ns) on the line's clock
TRAIN_RUN = [(6, 0, 600), (1, 0, 100), (2, 100, 200), (3, 300, 300),
             (7, 600, 75), (4, 675, 50), (5, 725, 25)]
DECODE_RUN = [(8, 0, 400), (9, 400, 150), (10, 550, 50)]
OPS = TRAIN_RUN + [(m, 1000 + s, d) for m, s, d in DECODE_RUN] \
    + [(m, 2000 + s, d) for m, s, d in TRAIN_RUN]
MODULES = [(100, 0, 800), (101, 1000, 700), (100, 2000, 800)]
BUSY = 750 + 600 + 750


def stat(which, value):
    if isinstance(value, str):
        return field(1, which) + field(5, value)
    return field(1, which) + field(3, value)


def event_metadata(ident, text, tf_op, program, category):
    out = field(1, ident) + field(2, text)
    if tf_op is not None:
        out += field(5, stat(TF_OP, tf_op))
    if program is not None:
        out += field(5, stat(PROGRAM_ID, program))
        out += field(5, stat(FLOPS, 12345))          # a stat that is not kept
    if isinstance(category, int):
        out += field(5, field(1, CATEGORY) + field(7, category))
    elif category is not None:
        out += field(5, stat(CATEGORY, category))
    return out


def line(name, stamp_ns, events):
    out = field(2, name) + field(3, stamp_ns)
    for ident, start, dur in events:
        out += field(4, field(1, ident) + field(2, start * 1000)
                     + field(3, dur * 1000))
    return out


def device_plane(name, ops, operations):
    out = field(1, 7) + field(2, name)
    out += field(3, line("Steps", 5000, [(100, 0, 2800)]))
    out += field(3, line("XLA Modules", 5000, MODULES))
    out += field(3, line("XLA Ops", 5000, ops))
    for ident, args in operations.items():
        out += field(4, entry(ident, event_metadata(ident, *args)))
    for ident, text in ((TF_OP, "tf_op"), (PROGRAM_ID, "program_id"),
                        (CATEGORY, "hlo_category"), (FLOPS, "flops"),
                        (DATA_FORMATTING, "data formatting")):
        out += field(5, entry(ident, field(1, ident) + field(2, text)))
    return out


def write_trace(directory, operations=OPERATIONS):
    host = field(1, 1) + field(2, "/host:CPU") \
        + field(3, line("python", 1, [(1, 0, 5)]))
    space = field(1, host) \
        + field(1, device_plane("/device:TPU:1", OPS[:3], operations)) \
        + field(1, device_plane("/device:TPU:0", OPS, operations)) \
        + field(4, "a hostname")
    path = os.path.join(directory, "plugins", "profile", "t")
    os.makedirs(path)
    with open(os.path.join(path, "vm.xplane.pb"), "wb") as f:
        f.write(space)
    return directory


@pytest.fixture(scope="module")
def meta(tmp_path_factory):
    return trace_meta.load(write_trace(str(tmp_path_factory.mktemp("t"))))


def share(meta, **kw):
    return 100.0 * meta.dur[meta.select(**kw)].sum() / meta.busy_ns


# ------------------------------------------------------- trace_meta -- #

def test_the_first_device_plane_with_programs_and_leaves(meta):
    assert meta.name == "/device:TPU:0"
    assert meta.programs == {TRAIN: "jit_train_step", DECODE: "jit_decode"}
    assert meta.module_runs["jit_train_step"] == [(5000.0, 5800.0),
                                                  (7000.0, 7800.0)]
    assert meta.busy_ns == pytest.approx(BUSY)
    # the container is no operation of its own, and adds nothing to busy
    assert len(meta.op) == len(OPS) - 2
    assert not any(o.name.startswith("%while") for o in meta.op)
    assert meta.dur.sum() == pytest.approx(BUSY)
    assert meta.start[0] == pytest.approx(5000.0)
    assert meta.has_paths()


def test_stats_by_value_and_by_reference(meta):
    by_name = {(trace.short_name(o.name), o.program_id): o
               for o in meta.metadata.values()}
    copy = by_name["copy.1", TRAIN]
    assert (copy.tf_op, copy.hlo_category) == (None, "data formatting")
    first = by_name["fusion.7", TRAIN]
    assert first.hlo_category == "convolution fusion"
    assert first.tf_op.endswith("checkpoint/attention/dot_general")
    assert ("fusion.20", DECODE) in by_name       # 64 bits of program id


def test_a_shared_short_name_goes_to_its_own_program(meta):
    mine = [i for i, o in enumerate(meta.op)
            if trace.short_name(o.name) == "fusion.3"]
    by = {(meta.op[i].program, meta.op[i].scope, meta.op[i].phase)
          for i in mine}
    assert by == {("jit_train_step", "loss", "backward"),
                  ("jit_decode", "sampler", "forward")}
    assert meta.dur[meta.select(scopes=["sampler"])].sum() == 400.0
    assert meta.dur[meta.select(scopes=["sampler"],
                                module="^jit_train_step")].sum() == 0.0


def test_phases_add_up_to_the_busy_time(meta):
    by_phase = {p: meta.dur[meta.select(phases=[p])].sum()
                for p in trace_meta.PHASES}
    assert by_phase == {"forward": 750.0, "recompute": 400.0,
                        "backward": 750.0, "optimizer": 100.0,
                        "no-path": 100.0}
    assert sum(by_phase.values()) == pytest.approx(meta.busy_ns)


@pytest.mark.parametrize("kw,percent", [
    ({"named": True}, 100.0 * 2000 / BUSY),
    ({"scopes": ["attention"]}, 100.0 * 600 / BUSY),
    ({"scopes": ["attention"], "phases": ["recompute"]}, 100.0 * 400 / BUSY),
    ({"phases": ["backward"]}, 100.0 * 750 / BUSY),
    ({"scopes": ["optimizer"]}, 100.0 * 100 / BUSY),
    ({"scopes": ["head", "loss"]}, 100.0 * 150 / BUSY),
    ({"scopes": ["moe"]}, 100.0 * 150 / BUSY),          # moe_experts is moe
    ({"scopes": ["moe_experts"]}, 100.0 * 150 / BUSY),
    ({"module": "^jit_decode"}, 100.0 * 600 / BUSY),
    ({"module": "^jit_decode", "phases": ["no-path"]}, 100.0 * 50 / BUSY),
])
def test_shares_against_hand_counts(meta, kw, percent):
    assert share(meta, **kw) == pytest.approx(percent)


def test_the_recorded_trace(meta):
    """The one TPU trace in the tree, a ResNet step that opened no scope."""
    got = trace_meta.load(RECORDED)
    assert len(got.metadata) == 4917           # 4,899 of them operations'
    ops = [o for o in got.metadata.values() if o.hlo_category]
    assert len(ops) == 4899
    assert sum(1 for o in ops if o.tf_op) == 371
    assert got.programs == {2707457242856997706: "jit_train_step"}
    assert {op.program for op in got.op} == {"jit_train_step"}
    assert len(got.module_runs["jit_train_step"]) == 16
    with_path = sum(d for d, o in zip(got.dur, got.op) if o.tf_op)
    assert with_path * 1e-6 == pytest.approx(696.6, abs=0.05)
    assert got.busy_ns * 1e-6 == pytest.approx(741.2, abs=0.05)
    assert got.select(named=True) == []
    # harness/trace.py reads the same events (it keeps whole nanoseconds)
    (plane,) = trace.load(RECORDED)
    assert got.busy_ns == pytest.approx(plane.busy_ns(), rel=1e-4)
    backward = got.dur[got.select(phases=["backward"])].sum()
    assert 0.5 < backward / got.busy_ns < 0.75


# ------------------------------------------------------- the reader -- #

@pytest.fixture()
def env(tmp_path, monkeypatch):
    """What ``run.py`` hands a reader, with the cell's trace where a traced
    run leaves it."""
    cell = resolve.Cell(SERVE_CELL)
    monkeypatch.setattr(resolve, "ROOT", str(tmp_path))
    write_trace(str(tmp_path / ".bench_tmp" / cell.name))
    reader = cell.reader("scope_share")
    return reader, {"cell": cell}


def test_the_reader_reads_the_cells_newest_trace(env):
    reader, e = env
    assert reader.read(e, {"scopes": ["attention"]}) \
        == pytest.approx(100.0 * 600 / BUSY)
    assert reader.read(e, {"named": True}) == pytest.approx(100.0 * 2000 / BUSY)
    assert reader.read(e, {"module": "^jit_decode", "scopes": None,
                           "phases": None}) == pytest.approx(100.0 * 600 / BUSY)
    # nothing matched: no metric
    assert reader.read(e, {"scopes": ["state_mixer"]}) is None
    assert reader.read(e, {"module": "^jit_chunk_prefill"}) is None


def test_no_trace_or_no_path_reads_nothing(tmp_path, monkeypatch):
    cell = resolve.Cell(TRAIN_CELLS[0])
    monkeypatch.setattr(resolve, "ROOT", str(tmp_path))
    reader = cell.reader("scope_share")
    assert reader.read({"cell": cell}, {"named": True}) is None
    # a trace whose operations carry no tf_op at all (an old profiler)
    bare = {k: (v[0], None) + v[2:] for k, v in OPERATIONS.items()}
    other = resolve.Cell(TRAIN_CELLS[1])
    write_trace(str(tmp_path / ".bench_tmp" / other.name), bare)
    assert reader.read({"cell": other}, {"phases": ["no-path"]}) is None


@pytest.mark.parametrize("name", list(METRICS))
def test_every_metric_file_resolves(name):
    cells, layer, moves, better = METRICS[name]
    bench = resolve.benchmark_json()
    (entry_,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry_ == {"name": name, "unit": "%", "better": better,
                      "source": "device_trace", "layer": layer,
                      "moves": moves, "workloads": cells}
    for cell_name in cells:
        cell = resolve.Cell(cell_name)
        assert name in [m["name"] for m in cell.metrics("per_layer")]
        assert moves in [m["name"] for m in cell.metrics("end_to_end")]
        spec = cell.metric_file(name)
        assert spec["layer"] == layer and spec["reader"] == "scope_share"
        assert callable(cell.reader(spec["reader"]).read)
        assert set(spec["args"]) <= {"scopes", "phases", "module", "named"}
        for scope in spec["args"].get("scopes") or []:
            assert scope in trace_meta.VOCABULARY
        for phase in spec["args"].get("phases") or []:
            assert phase in trace_meta.PHASES
    # the Ling and kanana cells' metric lists are pinned by their own tests
    for other in ("ling-3.0-flash-vl.serve.long-decode",
                  "kanana-2-30b-a3b.serve.long-prompt"):
        assert other not in cells
