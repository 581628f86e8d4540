"""The control of every cell's comparison, at a size a test can hold: the
reference with one stated guarantee broken, put in the program's place, has to
fail at least one of the cell's numbers. (At the cells' own size it was
run on the chip's machine on three seeds: PERF.md.)"""

import json
import os

import pytest

from perfbench import control

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [7, 2 ** 31 + 5])
def test_control_fails(cell, seed):
    readings = control.control_readings(cell, seed, 8, rehearse=True)
    failing = [name for name, pairs in readings.items()
               if max(v for v, _ in pairs) > pairs[0][1]]
    assert failing, readings
