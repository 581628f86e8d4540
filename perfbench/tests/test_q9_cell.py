"""The cell `q9_scalar.s4`'s own pieces: the template's arithmetic by
hand, its three readers on a recorded POLL and a recorded trace
reduction, and one whole run of the cell on the CPU at the
configuration's rehearsal size (serving child, warm-up of the fifteen
shapes, window, every answer compared)."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from perfbench import run as bench_run
from perfbench.layer_metrics import (
    agg_carry_batches, agg_fetch_ms, scan_agg_roofline,
)
from perfbench.templates import q9_scalar

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "q9_scalar.s4"


@pytest.fixture(scope="module")
def cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return bench_run.Cell(json.load(f), CELL, rehearse=False)


# ---- the template -------------------------------------------------------

def frame(quantity, q_ok, cents, c_ok):
    return {"rows": len(quantity),
            "values": {"ss_quantity": np.array(quantity, np.int32),
                       "ss_net_paid": np.array(cents, np.int64)},
            "valid": {"ss_quantity": np.array(q_ok, bool),
                      "ss_net_paid": np.array(c_ok, bool)}}


# quantities 5, NULL (stored 7), 20, 21; amounts 1.00, 2.00, NULL, 9.99
FRAME = frame([5, 7, 20, 21], [1, 0, 1, 1], [100, 200, 300, 999],
              [1, 1, 0, 1])
COUNT = {"lo": 1, "hi": 20, "agg": "count", "column": None}
AVG = {"lo": 1, "hi": 20, "agg": "avg", "column": "ss_net_paid"}


def test_reference_and_control_by_hand():
    # rows 0 and 2 pass; the NULL quantity's stored 7 does not
    assert q9_scalar.reference(FRAME, COUNT) == {"kind": "count",
                                                 "value": 2}
    assert q9_scalar.control(FRAME, COUNT)["value"] == 3
    # one non-NULL amount among them: 1.00 / 1 at scale 6
    assert q9_scalar.reference(FRAME, AVG) == {"kind": "avg",
                                               "value": 1_000_000}
    # the control divides by the bucket's two rows
    assert q9_scalar.control(FRAME, AVG)["value"] == 500_000
    none = dict(AVG, lo=30, hi=40)
    assert q9_scalar.reference(FRAME, none) == {"kind": "avg",
                                                "value": None}


@pytest.mark.parametrize("cents, n, want", [
    (200, 3, 666_667),        # 0.666666.6 rounds up
    (100, 3, 333_333),
    (1, 20_000, 1),           # 0.0000005 is a half: away from zero
    (-1, 20_000, -1),
    (-200, 3, -666_667),
])
def test_half_up(cents, n, want):
    assert q9_scalar._half_up(cents * 10 ** 4, n) == want


def test_answer_and_compare():
    import decimal

    import pyarrow as pa

    def batches(array):
        return pa.table({"x": array}).to_batches()

    got = q9_scalar.answer(batches(pa.array([7], pa.int64())), {})
    assert got == {"kind": "count", "value": 7}
    dec = decimal.Decimal("-12.345678")
    for t in (pa.decimal128(11, 6), pa.decimal128(38, 6)):
        got = q9_scalar.answer(batches(pa.array([dec], t)), {})
        assert got == {"kind": "avg", "value": -12_345_678}
    null = q9_scalar.answer(
        batches(pa.array([None], pa.decimal128(38, 6))), {})
    assert null == {"kind": "avg", "value": None}
    want = {"kind": "avg", "value": -12_345_678}
    assert q9_scalar.compare(want, got) == {"values_wrong": 0,
                                            "answer_shape_wrong": 0}
    assert q9_scalar.compare(want, null)["values_wrong"] == 1
    # another scale, another width, two rows, a value too wide for
    # decimal(11,6): no answer at all
    for wrong in (pa.array([dec], pa.decimal128(38, 8)),
                  pa.array([7], pa.int32()),
                  pa.array([7, 8], pa.int64()),
                  pa.array([decimal.Decimal("123456.000000")],
                           pa.decimal128(38, 6))):
        assert q9_scalar.answer(batches(wrong), {}) is None
    assert q9_scalar.answer([], {}) is None
    assert q9_scalar.compare(want, None) == {"values_wrong": 1,
                                             "answer_shape_wrong": 1}


def test_least_bytes(cell):
    # an int32 and a validity bit a row, a 16-byte row out
    assert q9_scalar.least_bytes(16384, 1, cell.types) \
        == 16384 * 4.125 + 16
    assert q9_scalar.least_bytes(
        16384, 0, cell.types, q9_scalar.columns_read(AVG)) \
        == 16384 * 8.25


# ---- the readers --------------------------------------------------------

def record(poll, params=COUNT, ok=True, device_run=True):
    return {"ok": ok, "device_run": device_run, "poll": poll,
            "template": "q9_scalar", "params": params, "rows_out": 1}


def task(batches, fetch_s):
    return {"task_dispatches": batches, "agg_carry_batches": batches,
            "stages": {"agg_fetch": {"wall_s": fetch_s, "cpu_s": 0.0,
                                     "n": 1},
                       "d2h": {"wall_s": 0.001, "cpu_s": 0.0, "n": 1}}}


RUN = {"records": [
    record(task(170, 0.002)), record(task(170, 0.004), AVG),
    record(task(170, 0.009), AVG),
    # a failed task and one a cache answered are no device runs
    record(task(0, 1.0), ok=False), record(task(0, 1.0), device_run=False),
]}
# what the parent of this PR answers: a stage table without the stage,
# no counter
PARENT_RUN = {"records": [record({"task_dispatches": 170, "stages": {
    "d2h": {"wall_s": 0.001, "cpu_s": 0.0, "n": 1}}})]}


def test_readers_on_a_recorded_poll():
    assert agg_carry_batches.read(RUN) == 170.0
    assert agg_fetch_ms.read(RUN) == pytest.approx(4.0)


@pytest.mark.parametrize("reader", [agg_carry_batches, agg_fetch_ms,
                                    scan_agg_roofline])
@pytest.mark.parametrize("run", [PARENT_RUN, {"records": []}],
                         ids=["parent", "empty"])
def test_reader_finds_nothing_and_does_not_raise(reader, run):
    assert reader.read(dict(run, trace=None)) is None


def test_scan_agg_roofline_on_a_recorded_reduction(cell):
    """340 launches of the per-batch program in a slice whose device was
    busy 10 ms: two tasks' worth of rows, a third of the tasks reading
    one column and two thirds two."""
    trace = {"devices": 1, "busy_s": 0.010, "window_s": 8.0,
             "launches": {"jit_kernel": 340, "jit_other": 5}}
    run = dict(RUN, cell=cell, trace=trace,
               peaks={"hbm_bytes_per_s": 819e9})
    rows = 340 * 16384
    assert scan_agg_roofline.traced_rows(run) == rows
    least = rows * 4.125 * (1 + 2 + 2) / 3 \
        + 16 * 3 * (rows / 3) / 2785280
    assert scan_agg_roofline.least_bytes(run, rows) \
        == pytest.approx(least)
    assert scan_agg_roofline.read(run) == pytest.approx(
        100.0 * least / 819e9 / 0.010)
    # a trace without the program reads nothing
    trace["launches"] = {"jit_other": 5}
    assert scan_agg_roofline.read(run) is None


# ---- one whole run ------------------------------------------------------

def test_rehearsal_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {ROOT!r})
        from perfbench import run
        run.chip_found = lambda device, cell: True
        sys.exit(run.main(["--workload", {CELL!r}, "--seed", "2147483659",
                           "--seconds", "3", "--trace", "1", "--rehearse"]))
    """)
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and done.returncode == 0, \
        done.stderr[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["compared"]["answers_compared"] == result["attempted"]
    m = {k: v["value"] for k, v in result["metrics"].items()}
    # two batches a split at the rehearsal size, both into the carry
    assert m["rehearsal.agg_carry_batches"] == 2.0
    assert m["rehearsal.task_dispatches"] == 2.0
    assert m["rehearsal.agg_fetch_ms"] > 0
    assert m["rehearsal.xla_compiles_in_window"] == 0
    # no device plane on the CPU: nothing to read, and not this cell's
    assert "rehearsal.scan_agg_roofline" not in m
    assert "rehearsal.query_roofline" not in m
