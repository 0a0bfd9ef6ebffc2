"""From a cell's name in ``BENCHMARK.json`` to the files that define it.

Nothing here knows a configuration, a mix or a metric by name: a later PR
adds ``configs/<config>.json`` + ``models/<config>.py``,
``traffic/<mix>.json``, ``metrics/<metric>.json`` (+ a reader) and the
entries in ``BENCHMARK.json``, and edits no file that is there."""

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    """A python file found by path (its name may hold ``-`` and ``.``)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def benchmark_json(root=ROOT):
    return load_json(os.path.join(root, "BENCHMARK.json"))


class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    def __init__(self, name, bench=None, bench_dir=BENCH_DIR):
        self.bench = bench if bench is not None else benchmark_json()
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(it has {sorted(cells)})")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.bench_dir = bench_dir
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config_entry = configs[self.entry["config"]]
        # the configuration's file is wherever BENCHMARK.json says
        self.config = load_json(os.path.join(
            os.path.dirname(bench_dir), self.config_entry["file"]))
        self.traffic = load_json(os.path.join(
            bench_dir, "traffic", self.entry["traffic"] + ".json"))
        self._model = None
        self._driver = None

    @property
    def model(self):
        """``models/<config>.py``: builder of the program's model, weights
        from a seed, required work from shapes, and the plain reference."""
        if self._model is None:
            self._model = load_module(
                os.path.join(self.bench_dir, "models",
                             self.entry["config"] + ".py"),
                "benchmark_model_" + _ident(self.entry["config"]))
        return self._model

    @property
    def driver(self):
        """``drivers/<traffic.driver>.py``: the one general generator and
        loop that this mix's parameters feed."""
        if self._driver is None:
            self._driver = load_module(
                os.path.join(self.bench_dir, "drivers",
                             self.traffic["driver"] + ".py"),
                "benchmark_driver_" + _ident(self.traffic["driver"]))
        return self._driver

    def sized(self, rehearse=False, sizes=None):
        """(configuration, mix) as a run uses them: the files' own sizes,
        or with ``rehearse`` the toy sizes of their ``rehearsal`` blocks;
        ``sizes`` (tests only) lays a pair of overrides on top."""
        cfg, mix = dict(self.config), dict(self.traffic)
        if rehearse:
            cfg.update(cfg.get("rehearsal", {}))
            mix.update(mix.get("rehearsal", {}))
        for into, over in zip((cfg, mix), sizes or ({}, {})):
            into.update(over)
        return cfg, mix

    def metrics(self, group):
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports:
        those without a ``workloads`` key, and those that list it."""
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def metric_file(self, metric_name):
        return load_json(os.path.join(self.bench_dir, "metrics",
                                      metric_name + ".json"))

    def reader(self, reader_name):
        return load_module(
            os.path.join(self.bench_dir, "metrics", "readers",
                         reader_name + ".py"),
            "benchmark_reader_" + _ident(reader_name))


def _ident(name):
    return "".join(c if c.isalnum() else "_" for c in name)
