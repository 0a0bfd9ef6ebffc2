"""Tier-1 runs the benchmark's own tests: each ``test_bench_*.py`` beside
this file takes the public names of one ``benchmark/tests/test_*.py``
(tests and fixtures alike) into its own globals, so ``pytest tests/``
collects and counts every one of them, and ``pytest benchmark/tests``
still works by itself.  What ``benchmark/tests/conftest.py`` does for
that run (the root and ``benchmark/`` on ``sys.path``) is done here."""

import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


def public_names(basename):
    """The public names of ``benchmark/tests/<basename>``, loaded by path."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_tests_" + basename[:-3],
        os.path.join(BENCH, "tests", basename))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return {k: v for k, v in vars(module).items() if not k.startswith("_")}
