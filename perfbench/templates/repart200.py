"""The 200-way shuffle repartition, `BASELINE.json` config 4
(spark.sql.shuffle.partitions=200, Spark's default): every row of the
split goes, whole, to partition `pmod(hash(ss_customer_sk), 200)` of a
segmented Arrow-IPC data file with its index file. The key is Spark's
`int`, so the hash is `Murmur3_x86_32.hashInt` with seed 42, and a NULL
key leaves the seed as the hash (partition 42).

Exact throughout: the answer is the files the task wrote, read back by the
benchmark's own reader and compared with its own Spark murmur3.
"""

from __future__ import annotations

import functools

import numpy as np

from . import _plan, _rows
from .. import murmur3, segipc

PARTITIONS = 200
KEY = "ss_customer_sk"

# rows_misplaced: rows found in a partition other than their key's.
# rows_differ: rows lost, added or altered (whole rows, every column,
#   NULLs as NULLs).
# partitions_wrong: 1 when the files are not a 200-way shuffle of this
#   table's columns, or frames were fetched where a shuffle writer
#   returns none.
LIMITS = {"rows_misplaced": 0, "rows_differ": 0, "partitions_wrong": 0}


def build(scan_path: str, params: dict, out: dict) -> bytes:
    from blaze_tpu.exprs import Col
    from blaze_tpu.ops import ShuffleWriterExec

    op = ShuffleWriterExec(
        _plan.scan(scan_path, None), [Col(KEY)], PARTITIONS,
        out["data"], out["index"],
    )
    return _plan.blob(op)


def answer(batches, out: dict):
    """The files are read back when the window has closed: `judge` calls
    what this returns with the table's column types. None when the task
    returned frames, which a shuffle writer never does."""
    if batches:
        return None
    return functools.partial(_read_answer, dict(out))


def _read_answer(out: dict, types: dict):
    """The rows as found, with `partition`: the segment each row lay in.
    None when the files are not a 200-way shuffle of these columns."""
    import pyarrow as pa

    try:
        parts = segipc.read_partitions(out["data"], out["index"])
    except (ValueError, OSError):
        return None
    if len(parts) != PARTITIONS:
        return None
    found = [(p, t) for p, t in enumerate(parts) if t is not None]
    if not found or any(tuple(t.column_names) != tuple(types)
                        for _, t in found):
        return None
    side = _plan.as_side(pa.concat_tables([t for _, t in found]), types)
    if tuple(side["values"]) != tuple(types):
        return None
    side["partition"] = np.concatenate(
        [np.full(t.num_rows, p, np.int32) for p, t in found])
    return side


def _partition_of(side: dict, null_hashes_to_seed: bool) -> np.ndarray:
    ok = _rows.is_valid(side, KEY)
    h = murmur3.hash_int(np.where(ok, side["values"][KEY], 0))
    if null_hashes_to_seed:
        h = np.where(ok, h, np.int32(murmur3.SPARK_SEED))
    return murmur3.pmod(h, PARTITIONS)


def reference(frame: dict, params: dict) -> dict:
    return dict(frame, partition=_partition_of(frame, True))


def control(frame: dict, params: dict) -> dict:
    """A NULL key hashed as the integer 0: what comes of hashing the
    values without their validity, the step that would let the Pallas
    kernel take a nullable key. Spark leaves the seed where the key is
    NULL."""
    return dict(frame, partition=_partition_of(frame, False))


def compare(want: dict, got) -> dict:
    n = want["rows"]
    if got is None:
        return {"rows_misplaced": n, "rows_differ": n,
                "partitions_wrong": 1}
    columns = list(want["types"])
    own = _partition_of(got, True)
    # ordered with the partition the reference gives each row in the
    # lead, so a row the program moved elsewhere shows as misplaced and
    # not as altered
    return {
        "rows_misplaced": int(np.count_nonzero(own != got["partition"])),
        "rows_differ": _rows.rows_differ(
            want, got, columns, _partition_of(want, True), own),
        "partitions_wrong": 0,
    }


def least_bytes(rows_in: int, rows_out: int, types: dict) -> int:
    """Every row read and every row written, at its narrowest width."""
    row = sum(_rows.width(t) for t in types.values())
    return 2 * row * rows_in
