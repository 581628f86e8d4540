"""What every template's blob builder shares: the scan of one parquet
file and the encoding of a one-partition task. This is the client's side
of the system under test (how a driver hands the engine a plan), so it
imports the program; a template's reference never does."""

from __future__ import annotations


def scan(path: str, columns):
    from blaze_tpu.ops.parquet_scan import FileRange, ParquetScanExec

    return ParquetScanExec([[FileRange(path)]], projection=columns)


def blob(op) -> bytes:
    from blaze_tpu.plan.serde import task_to_proto

    return task_to_proto(op, 0)


def as_side(batches_or_table, types: dict) -> dict:
    """Fetched frames (or a table read back) as the numpy side the
    comparisons take; a column of another type than `types` names is left
    out, and the template's `answer` turns that into a wrong shape."""
    import pyarrow as pa

    from perfbench import datagen

    table = batches_or_table
    if not isinstance(table, pa.Table):
        table = pa.Table.from_batches(table)
    return datagen.from_arrow(table, types)
