"""Query 1's hash-aggregate over store_returns, `BASELINE.json` config 2:
the CTE `customer_total_return` of TPC-DS query 1, as one task over one
input split (sent in COMPLETE mode; Spark sends the same plan as a partial
aggregate and merges the tasks' answers after a shuffle).

    SELECT sr_customer_sk, sr_store_sk, SUM([AGG_FIELD])
    FROM store_returns
    WHERE sr_returned_date_sk BETWEEN :first AND :last   -- d_year = [YEAR]
    GROUP BY sr_customer_sk, sr_store_sk

`query1.tpl` draws YEAR from 1998-2002 and AGG_FIELD from seven money
columns; the traffic file fixes both at the qualification run's values
(2000, SR_RETURN_AMT). date_dim's year reaches the scan as a range of
d_date_sk (Julian day numbers). Exact: a NULL key is a group of its own,
a NULL amount adds nothing (a group of NULL amounts alone sums to NULL), a
NULL date passes no comparison, and a sum of decimal(7,2) is a decimal.
"""

from __future__ import annotations

import datetime

import numpy as np

from . import _plan, _rows

KEYS = ("sr_customer_sk", "sr_store_sk")
OUT = ("ctr_customer_sk", "ctr_store_sk", "ctr_total_return")
JULIAN = 1721425

# groups_wrong: groups missing, extra, with another key or with another
#   sum (keys and unscaled cents compared exactly).
# answer_shape_wrong: 1 when the frames are not two int32 keys and a
#   decimal of scale 2.
LIMITS = {"groups_wrong": 0, "answer_shape_wrong": 0}


def year_keys(params: dict) -> tuple:
    y = int(params["year"])
    return (datetime.date(y, 1, 1).toordinal() + JULIAN,
            datetime.date(y, 12, 31).toordinal() + JULIAN)


def build(scan_path: str, params: dict, out: dict) -> bytes:
    from blaze_tpu.exprs import AggExpr, AggFn, Col
    from blaze_tpu.ops import AggMode, FilterExec, HashAggregateExec

    first, last = year_keys(params)
    amount = params["agg_field"]
    op = HashAggregateExec(
        FilterExec(
            _plan.scan(scan_path, ["sr_returned_date_sk", *KEYS, amount]),
            (Col("sr_returned_date_sk") >= first)
            & (Col("sr_returned_date_sk") <= last),
        ),
        keys=[(Col(k), o) for k, o in zip(KEYS, OUT)],
        aggs=[(AggExpr(AggFn.SUM, Col(amount)), OUT[2])],
        mode=AggMode.COMPLETE,
    )
    return _plan.blob(op)


def answer(batches, out: dict):
    import pyarrow as pa

    if not batches:
        return None
    table = pa.Table.from_batches(batches)
    total = table.schema.field(OUT[2]).type if OUT[2] in \
        table.column_names else None
    if tuple(table.column_names) != OUT or not pa.types.is_decimal(total) \
            or total.scale != 2:
        return None
    got = _plan.as_side(table, {OUT[0]: "int32", OUT[1]: "int32",
                                OUT[2]: f"decimal({total.precision},2)"})
    return got if tuple(got["values"]) == OUT else None


def _grouped(frame: dict, params: dict) -> dict:
    first, last = year_keys(params)
    day = frame["values"]["sr_returned_date_sk"]
    m = _rows.is_valid(frame, "sr_returned_date_sk") \
        & (day >= first) & (day <= last)
    cols = _rows.settled(frame, [*KEYS, params["agg_field"]])
    (c, c_ok), (s, s_ok), (amt, amt_ok) = (
        (v[m], ok[m]) for v, ok in cols.values())
    # one 64-bit key a group: two 31-bit keys and their two NULL flags
    packed = ((c.astype(np.int64) * 2 + c_ok) << 32) \
        | (s.astype(np.int64) * 2 + s_ok)
    uniq, inv = np.unique(packed, return_inverse=True)
    sums = np.zeros(len(uniq), np.int64)
    np.add.at(sums, inv, amt)  # cents, exactly
    some = np.bincount(inv, weights=amt_ok, minlength=len(uniq)) > 0
    hi, lo = uniq >> 32, uniq & 0xFFFFFFFF
    return {
        "values": {OUT[0]: (hi >> 1).astype(np.int32),
                   OUT[1]: (lo >> 1).astype(np.int32),
                   OUT[2]: np.where(some, sums, 0)},
        "valid": {OUT[0]: (hi & 1).astype(bool),
                  OUT[1]: (lo & 1).astype(bool), OUT[2]: some},
    }


def reference(frame: dict, params: dict) -> dict:
    return _grouped(frame, params)


def control(frame: dict, params: dict) -> dict:
    """A group whose amounts are all NULL answered with 0 where SQL's SUM
    gives NULL: what comes of summing the values without their validity,
    the step that saves the aggregate a lane."""
    out = _grouped(frame, params)
    out["valid"] = dict(out["valid"], **{OUT[2]: None})
    return out


def compare(want: dict, got) -> dict:
    n_want = len(want["values"][OUT[0]])
    if got is None:
        return {"groups_wrong": max(n_want, 1), "answer_shape_wrong": 1}
    return {"groups_wrong": _rows.rows_differ(want, got, OUT),
            "answer_shape_wrong": 0}


def least_bytes(rows_in: int, rows_out: int, types: dict) -> int:
    """The date, the two keys and the amount of every row in; two keys
    and an 8-byte sum for every group out."""
    read = sum(_rows.width(types[c]) for c in
               ("sr_returned_date_sk", *KEYS, "sr_return_amt"))
    return read * rows_in + (4 + 4 + 8) * rows_out
