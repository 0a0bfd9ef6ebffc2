import glob
import os

from benchmark_tests import BENCH


def test_every_benchmark_test_file_has_its_wrapper():
    """A ``benchmark/tests/test_x.py`` without a ``test_bench_x.py`` here
    would be run by no gate."""
    here = os.path.dirname(os.path.abspath(__file__))
    theirs = {os.path.basename(p)[len("test_"):] for p in
              glob.glob(os.path.join(BENCH, "tests", "test_*.py"))}
    ours = {os.path.basename(p)[len("test_bench_"):] for p in
            glob.glob(os.path.join(here, "test_bench_*.py"))}
    assert theirs == ours
