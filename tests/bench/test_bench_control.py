"""The lower-precision control, put in the program's place and judged by
the harness's own comparison, reads worse than the program, at a size a
test run holds.  On the chip, at each cell's own size, the same code
sets the upper reading of every limit and comes out not correct
(``bench/control.py``).  The control is read for the cells that
``BENCHMARK.json`` runs; a waiting cell's control is read with its
limits, once the program can build it."""

import time

import pytest

from bench import harness

PEAK = harness.load_peaks()["TPU v5 lite"]


@pytest.mark.parametrize("name,number,rows", [
    ("olmo-1b.batch_map", "logit_gap", 12),
    ("olmo-1b.rag_scan", "embed_dist", 3),
])
@pytest.mark.parametrize("seed", [2**31 + 29, 5])
def test_control_reads_worse_than_the_program(smoke_cell, name, number,
                                              rows, seed):
    cell = smoke_cell(name, rows=rows)
    res = harness.run(cell, seed, 0.05, False, time.perf_counter(), PEAK,
                      control=True)
    assert res["correct"], res["checks"]
    low = res["control"]["checks"]
    assert set(low) == set(res["checks"])
    assert low[number]["value"] > res["checks"][number]["value"]
