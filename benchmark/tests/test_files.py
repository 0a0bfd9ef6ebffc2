"""BENCHMARK.json against the files it names, the contract's limits on
names and units, and a throwaway configuration, mix, cell and per-layer
metric added as new files and entries only."""

import json
import os
import re
import shutil

import pytest

from harness import resolve

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return resolve.benchmark_json()


def test_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_names_and_units(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
            for key in ("why", "layer", "source"):
                if key in e:
                    assert 1 <= len(e[key]) <= 200 and "\n" not in e[key]
    assert len(names) == len(set(names))
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_cell_resolves(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    used = set()
    for w in bench["workloads"]:
        cell = resolve.Cell(w["name"], bench)
        used.add(w["config"])
        assert cell.config["name"] == w["config"]
        assert os.path.isfile(os.path.join(
            resolve.BENCH_DIR, "drivers", cell.traffic["driver"] + ".py"))
        assert hasattr(cell.model, "make_params")
        assert hasattr(cell.driver, "run")
        names = {m["name"] for m in cell.metrics("end_to_end")}
        assert "setup_s" in names and len(names) >= 2
        layer = cell.metrics("per_layer")
        assert layer
        for m in layer:
            spec = cell.metric_file(m["name"])
            assert spec["layer"] == m["layer"]
            assert hasattr(cell.reader(spec["reader"]), "read")
            assert m["moves"] in names, (m["name"], "moves", m["moves"])
    assert used == {c["name"] for c in bench["configs"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", [])) <= cells
    for m in bench["per_layer"]:
        assert m["moves"] in e2e


def test_files_are_named_from_allowed_characters():
    for base, _dirs, files in os.walk(resolve.BENCH_DIR):
        if "__pycache__" in base:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(base, f)


def test_configs_state_source_and_reduced(bench):
    for c in bench["configs"]:
        held = resolve.load_json(os.path.join(resolve.ROOT, c["file"]))
        assert held["source"] == c["source"]
        assert held["reduced"] == c["reduced"]
        assert "assumed" in held and "dtypes" in held


TOY_MODEL = '''
def make_params(cfg, seed):
    return {"w": [float(seed)] * cfg["width"]}

def kernel_work(cfg, mix, name):
    return {"flops": 2.0 * cfg["width"], "bytes": 8.0 * cfg["width"]}
'''

TOY_READER = '''
def read(env, args):
    idx = env["planes"][0].matching(args["events"])
    if not idx:
        return None
    return 1e-3 * float(env["planes"][0].op_dur[idx].sum())
'''


def test_a_throwaway_cell_is_files_and_entries_only(bench, tmp_path):
    """A later PR adds a configuration, a mix, a cell and a per-layer
    metric without editing a file that is there."""
    from harness import trace

    root = tmp_path / "checkout"
    shutil.copytree(resolve.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    b = root / "benchmark"
    (b / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "source": "none", "reduced": [], "width": 4}))
    (b / "models" / "toy.py").write_text(TOY_MODEL)
    (b / "traffic" / "toy.mix.json").write_text(json.dumps(
        {"driver": "train_loop", "batch": 2}))
    (b / "metrics" / "toy_us.json").write_text(json.dumps(
        {"layer": "kernels", "reader": "toy_reader",
         "args": {"events": ["convolution"]}}))
    (b / "metrics" / "readers" / "toy_reader.py").write_text(TOY_READER)
    grown = json.loads(json.dumps(bench))
    grown["configs"].append({"name": "toy", "source": "none",
                             "file": "benchmark/configs/toy.json",
                             "reduced": [], "why": "a toy"})
    grown["workloads"].append({"name": "toy.toy.mix", "config": "toy",
                               "traffic": "toy.mix", "chips": 1,
                               "why": "a toy"})
    grown["per_layer"].append(
        {"name": "toy_us", "unit": "us", "better": "lower",
         "source": "device_trace", "layer": "kernels",
         "moves": "train_step_ms", "workloads": ["toy.toy.mix"]})
    cell = resolve.Cell("toy.toy.mix", grown, bench_dir=str(b))
    assert cell.config["width"] == 4
    assert cell.model.make_params(cell.config, 3) == {"w": [3.0] * 4}
    assert cell.driver.__name__.endswith("train_loop")
    assert [m["name"] for m in cell.metrics("per_layer")] == ["toy_us"]
    fixture = os.path.join(resolve.ROOT, "tests", "fixtures",
                           "synthetic.xplane.pb")
    if os.path.exists(fixture):
        spec = cell.metric_file("toy_us")
        env = {"planes": trace.load(fixture)}
        assert cell.reader(spec["reader"]).read(env, spec["args"]) \
            == pytest.approx(3.0)
    # and the old cells are what they were
    for p, data in before.items():
        assert p.read_bytes() == data
    assert resolve.Cell(bench["workloads"][0]["name"], grown,
                        bench_dir=str(b)).config["name"] \
        == bench["workloads"][0]["config"]
