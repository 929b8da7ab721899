"""The benchmark's generator self-check, in tier-1.

`benchmarks/selfcheck/test_generators.py` holds the loader to its
configurations: every listed configuration's generator makes its listed
columns, the arrays the loader hands the program are pinned for the
recorded loads, a star from a generator of the file's own is served as its
reference, and a column or a table a generator does not make stops the
load with an error that names it.  Its cases are collected here, as they
are, so that the repository's own test run guards the generators too
(the SSB generator among them); the benchmark's directory is on `sys.path`
while each runs, as `benchmarks/run.py` puts it.
"""

import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")


def _selfcheck():
    spec = importlib.util.spec_from_file_location(
        "benchmark_selfcheck_generators",
        os.path.join(BENCH, "selfcheck", "test_generators.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_SELF = _selfcheck()
star_generator = _SELF.star_generator
for _name in dir(_SELF):
    if _name.startswith("test_"):
        globals()[_name] = getattr(_SELF, _name)


@pytest.fixture(autouse=True)
def _bench_path(monkeypatch):
    for p in (ROOT, BENCH):
        monkeypatch.syspath_prepend(p)
