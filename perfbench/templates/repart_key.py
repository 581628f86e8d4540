"""A hash shuffle write on one `int` key column that the request names:
every row of the split goes, whole, to partition
`pmod(hash(<key>), <partitions>)` of a segmented Arrow-IPC data file with
its index file. `params` is `{"key": column, "partitions": n}`, both
fixed in the cell's traffic file: `inv_item_sk` into 200 for TPC-DS query
72's map stage over `inventory`, `ss_item_sk` into 200 for `store_sales`
keyed on its item. The key is Spark's `int`, so the hash is
`Murmur3_x86_32.hashInt` with seed 42, and a NULL key, where the column
has any, leaves the seed as the hash.

Exact throughout: the answer is the files the task wrote, read back by the
benchmark's own reader and compared with its own Spark murmur3.
"""

from __future__ import annotations

import functools

import numpy as np

from . import _plan, _rows
from .. import murmur3, segipc

# rows_misplaced: rows found in a partition other than their key's.
# rows_differ: rows lost, added or altered (whole rows, every column,
#   NULLs as NULLs).
# partitions_wrong: 1 when the files are not a shuffle of this table's
#   columns into as many partitions as the request asked for, or frames
#   were fetched where a shuffle writer returns none.
LIMITS = {"rows_misplaced": 0, "rows_differ": 0, "partitions_wrong": 0}


def build(scan_path: str, params: dict, out: dict) -> bytes:
    from blaze_tpu.exprs import Col
    from blaze_tpu.ops import ShuffleWriterExec

    op = ShuffleWriterExec(
        _plan.scan(scan_path, None), [Col(params["key"])],
        int(params["partitions"]), out["data"], out["index"],
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
    """The rows as found, with `partition`, the segment each row lay in,
    and `partitions`, the segments the index describes. None when the
    files are not a shuffle of these columns."""
    import pyarrow as pa

    try:
        parts = segipc.read_partitions(out["data"], out["index"])
    except (ValueError, OSError):
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
    side["partitions"] = len(parts)
    return side


def _partition_of(side: dict, params: dict, hash_fn=murmur3.hash_int
                  ) -> np.ndarray:
    key = params["key"]
    ok = _rows.is_valid(side, key)
    h = np.where(ok, hash_fn(np.where(ok, side["values"][key], 0)),
                 np.int32(murmur3.SPARK_SEED))
    return murmur3.pmod(h, int(params["partitions"]))


def reference(frame: dict, params: dict) -> dict:
    return dict(frame, params=params,
                partition=_partition_of(frame, params))


def control(frame: dict, params: dict) -> dict:
    """The key widened to `bigint` and hashed with `hashLong`: what a
    plan that casts the key before the exchange would do. Spark hashes an
    `int` key with `hashInt`, and the two agree on one row in
    `partitions`."""
    return dict(frame, partitions=int(params["partitions"]),
                partition=_partition_of(frame, params, murmur3.hash_long))


def compare(want: dict, got) -> dict:
    n = want["rows"]
    if got is None \
            or got["partitions"] != int(want["params"]["partitions"]):
        return {"rows_misplaced": n, "rows_differ": n,
                "partitions_wrong": 1}
    columns = list(want["types"])
    own = _partition_of(got, want["params"])
    # ordered with the partition the reference gives each row in the
    # lead, so a row the program moved elsewhere shows as misplaced and
    # not as altered
    return {
        "rows_misplaced": int(np.count_nonzero(own != got["partition"])),
        "rows_differ": _rows.rows_differ(
            want, got, columns, want["partition"], own),
        "partitions_wrong": 0,
    }


def least_bytes(rows_in: int, rows_out: int, types: dict) -> int:
    """Every row read and every row written, at its narrowest width."""
    row = sum(_rows.width(t) for t in types.values())
    return 2 * row * rows_in


def hash_bytes(rows: int, key_type: str) -> int:
    """What the partition-id program must move for `rows` keys: the key
    in at its width and an `int` id out."""
    return (_rows.width(key_type) + 4) * rows
