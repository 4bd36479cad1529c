"""The benchmark's tests import it as ``bench`` from the checkout's root."""

import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
for p in (ROOT, os.path.dirname(os.path.abspath(__file__))):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

from bench_smoke import WAITING, cell_from_files, make_smoke  # noqa: E402


@pytest.fixture
def smoke_cell():
    """``smoke_cell(name, **cut)``: the named cell of BENCHMARK.json at
    smoke size."""
    from bench import harness

    spec = harness.load_spec()

    def make(name, **cut):
        if name in WAITING:
            like, config, traffic = WAITING[name]
            return make_smoke(cell_from_files(harness.find_cell(spec, like),
                                              config, traffic), **cut)
        return make_smoke(harness.find_cell(spec, name), **cut)
    return make
