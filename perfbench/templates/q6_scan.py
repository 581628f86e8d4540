"""Query 6's scan of store_sales, `BASELINE.json` config 1 ("q6,
scan+filter+project only"): the leaf of TPC-DS query 6 that reads the fact
table, as one task over one input split.

    SELECT ss_item_sk, ss_customer_sk FROM store_sales
    WHERE ss_sold_date_sk BETWEEN :first AND :last   -- d_month_seq of
                                                     -- [YEAR], [MONTH]
      AND ss_customer_sk IS NOT NULL

Query 6 joins store_sales to date_dim on the month [MONTH] of [YEAR]
(`query6.tpl`: YEAR uniform in 1998-2002, MONTH uniform in 1-7) and to
customer and item by key; a month is a run of consecutive d_date_sk
(Julian day numbers), so the join's filter reaches the scan as a range of
keys, as Spark's dynamic partition pruning hands it over, and the inner
join on ss_customer_sk as IS NOT NULL. Every answer is exact: each
passing row once, whole, in any order.
"""

from __future__ import annotations

import calendar
import datetime

import numpy as np

from . import _plan, _rows

COLUMNS = ("ss_item_sk", "ss_customer_sk")
JULIAN = 1721425  # d_date_sk = proleptic ordinal + JULIAN

# rows_differ: rows lost, added or altered, as a multiset of whole rows.
# answer_shape_wrong: 1 when the frames are not these two int32 columns
#   without a NULL.
LIMITS = {"rows_differ": 0, "answer_shape_wrong": 0}


def month_keys(params: dict) -> tuple:
    y, m = int(params["year"]), int(params["month"])
    first = datetime.date(y, m, 1).toordinal() + JULIAN
    return first, first + calendar.monthrange(y, m)[1] - 1


def build(scan_path: str, params: dict, out: dict) -> bytes:
    from blaze_tpu.exprs import Col, IsNotNull
    from blaze_tpu.ops import FilterExec, ProjectExec

    first, last = month_keys(params)
    op = ProjectExec(
        FilterExec(
            _plan.scan(scan_path, ["ss_sold_date_sk", "ss_item_sk",
                                   "ss_customer_sk"]),
            (Col("ss_sold_date_sk") >= first)
            & (Col("ss_sold_date_sk") <= last)
            & IsNotNull(Col("ss_customer_sk")),
        ),
        [(Col(c), c) for c in COLUMNS],
    )
    return _plan.blob(op)


def answer(batches, out: dict):
    if not batches:
        return None
    got = _plan.as_side(batches, {c: "int32" for c in COLUMNS})
    if tuple(got["values"]) != COLUMNS \
            or any(v is not None for v in got["valid"].values()):
        return None
    return got


def _answer_of(frame: dict, params: dict, date_valid) -> dict:
    first, last = month_keys(params)
    day = frame["values"]["ss_sold_date_sk"]
    m = date_valid & (day >= first) & (day <= last) \
        & _rows.is_valid(frame, "ss_customer_sk")
    return {"values": {c: frame["values"][c][m] for c in COLUMNS},
            "valid": {c: None for c in COLUMNS}}


def reference(frame: dict, params: dict) -> dict:
    return _answer_of(frame, params,
                      _rows.is_valid(frame, "ss_sold_date_sk"))


def control(frame: dict, params: dict) -> dict:
    """The date compared without its validity: a NULL's stored value
    passes the range where SQL lets no NULL pass. The step a later PR is
    tempted to: dropping the validity lane from the filter's kernel."""
    return _answer_of(frame, params, np.ones(frame["rows"], bool))


def compare(want: dict, got) -> dict:
    if got is None:
        return {"rows_differ": max(len(want["values"][COLUMNS[0]]), 1),
                "answer_shape_wrong": 1}
    return {"rows_differ": _rows.rows_differ(want, got, COLUMNS),
            "answer_shape_wrong": 0}


def least_bytes(rows_in: int, rows_out: int, types: dict) -> int:
    """The least the device must move: the three scanned columns of every
    row in, the two projected columns of every passing row out."""
    read = sum(_rows.width(types[c]) for c in
               ("ss_sold_date_sk", "ss_item_sk", "ss_customer_sk"))
    wrote = sum(_rows.width(types[c]) for c in COLUMNS)
    return read * rows_in + wrote * rows_out
