"""A result sink reads a filtered batch back whole and trims it on the
host (`ops/util.py: sink_arrow`): the same rows, in the same order and
types, as the device compaction it replaced."""

import datetime
import decimal

import numpy as np
import pyarrow as pa
import pytest

from blaze_tpu import ColumnBatch
from blaze_tpu.exprs import AggExpr, AggFn, Col
from blaze_tpu.io.ipc import decode_ipc_parts
from blaze_tpu.ops import (
    AggMode,
    ExecContext,
    FilterExec,
    HashAggregateExec,
    MemoryScanExec,
    ProjectExec,
)
from blaze_tpu.ops.fused import FusedPipelineExec, fuse_pipelines
from blaze_tpu.ops.ipc_writer import collect_ipc
from blaze_tpu.ops.util import compact
from blaze_tpu.runtime.executor import execute_partition

COLUMNS = ("k", "amt", "wide", "s", "d", "q")
ROWS = (1024, 1024, 700)  # the last batch leaves its capacity unfilled


def _batch(part: int, n: int) -> ColumnBatch:
    rng = np.random.default_rng(28 + part)

    def nulls():
        return rng.random(n) < 0.1

    cents = rng.integers(-99999, 99999, n)
    wide = rng.integers(-10**15, 10**15, n)
    days = rng.integers(10000, 12000, n)
    rb = pa.RecordBatch.from_arrays([
        pa.array(rng.integers(0, 1 << 30, n).astype(np.int32),
                 mask=nulls()),
        pa.array([decimal.Decimal(int(c)).scaleb(-2) for c in cents],
                 type=pa.decimal128(7, 2), mask=nulls()),
        pa.array([decimal.Decimal(int(w) * 10**9).scaleb(-4)
                  for w in wide],
                 type=pa.decimal128(30, 4), mask=nulls()),
        pa.array([f"store-{v}" for v in rng.integers(0, 40, n)],
                 mask=nulls()),
        pa.array([datetime.date.fromordinal(720000 + int(v))
                  for v in days], type=pa.date32(), mask=nulls()),
        pa.array(rng.integers(0, 100, n).astype(np.int32)),
        pa.array(np.full(n, part, dtype=np.int32)),
    ], names=list(COLUMNS) + ["part"])
    return ColumnBatch.from_arrow(rb, capacity=1024)


@pytest.fixture(scope="module")
def scan():
    batches = [_batch(i, n) for i, n in enumerate(ROWS)]
    return MemoryScanExec([batches], batches[0].schema)


# predicate and the share of the rows it keeps
KEEPS = {
    "none": (Col("q") < 0, 0.0),
    "1pct": (Col("q") == 1, 0.01),
    "93pct": (Col("q") >= 7, 0.93),
    "all": (Col("q") >= 0, 1.0),
    "middle_batch_gone": (Col("part") != 1, 1724 / 2748),
}


def filtered(scan, keep: str) -> FusedPipelineExec:
    op = fuse_pipelines(ProjectExec(
        FilterExec(scan, KEEPS[keep][0]), [(Col(c), c) for c in COLUMNS]))
    assert isinstance(op, FusedPipelineExec)
    return op


def device_compacted(op) -> list:
    """What the sinks returned before: every batch packed on the device,
    then read back."""
    out = []
    for cb in op.execute(0, ExecContext()):
        assert cb.selection is not None
        cb = compact(cb)
        if cb.num_rows:
            out.append(cb.to_arrow())
    return out


def through_executor(op, ctx) -> list:
    return list(execute_partition(op, 0, ctx))


def through_ipc_writer(op, ctx) -> list:
    return [rb for part in collect_ipc(op, ctx)
            for rb in decode_ipc_parts(part)]


SINKS = {"executor": through_executor, "ipc_writer": through_ipc_writer}


@pytest.mark.parametrize("keep", list(KEEPS))
@pytest.mark.parametrize("sink", list(SINKS))
def test_host_trim_equals_device_compaction(sink, keep, scan):
    op = filtered(scan, keep)
    want = device_compacted(op)
    ctx = ExecContext()
    got = SINKS[sink](op, ctx)
    # frame for frame: same rows in batch order, same Arrow types, NULLs
    # as NULLs; a batch from which no row survives yields no frame
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.schema == w.schema
        assert g.equals(w)
    rows = sum(rb.num_rows for rb in got)
    assert rows == pytest.approx(KEEPS[keep][1] * sum(ROWS), abs=30)
    assert len(got) == {"none": 0, "middle_batch_gone": 2}.get(keep, 3)
    m = ctx.metrics.counters
    # every batch came with a selection, the ones trimmed to nothing too
    assert m["sink_trim_batches"] == len(ROWS)
    if sink == "executor":
        assert m.get("output_rows", 0) == rows
        assert m.get("output_batches", 0) == len(got)


def keyless_count(scan):
    return HashAggregateExec(
        FilterExec(scan, Col("q") >= 7), keys=[],
        aggs=[(AggExpr(AggFn.COUNT, Col("k")), "n")], mode=AggMode.COMPLETE)


@pytest.mark.parametrize("plan, rows", [
    (lambda scan: scan, sum(ROWS)),
    (lambda scan: fuse_pipelines(keyless_count(scan)), 1),
], ids=["unfiltered_scan", "aggregate"])
@pytest.mark.parametrize("sink", list(SINKS))
def test_no_trim_where_the_sink_sees_no_selection(sink, plan, rows, scan):
    ctx = ExecContext()
    got = SINKS[sink](plan(scan), ctx)
    assert sum(rb.num_rows for rb in got) == rows
    assert "sink_trim_batches" not in ctx.metrics.counters
