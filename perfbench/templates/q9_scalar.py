"""One of query 9's fifteen scalar subqueries over store_sales, as one
task over one input split (sent in COMPLETE mode; Spark sends the same
plan as a partial aggregate and merges the tasks' one-row answers after an
exchange to one partition).

    SELECT count(*)          FROM store_sales WHERE ss_quantity BETWEEN :lo AND :hi
    SELECT avg([AGGCTHEN])   FROM store_sales WHERE ss_quantity BETWEEN :lo AND :hi
    SELECT avg([AGGCELSE])   FROM store_sales WHERE ss_quantity BETWEEN :lo AND :hi

`query9.tpl` fixes the five ranges (1-20 .. 81-100) and draws AGGCTHEN and
AGGCELSE from lists of money columns; the traffic file fixes them at the
values Spark's `q9.sql` carries. `params` are `lo`, `hi`, `agg` (`count`,
`avg`) and `column` (null for `count`). Exact: a NULL quantity passes no
comparison; `count(*)` is a bigint; `avg` is over the column's non-NULL
values among the passing rows, the quotient of the cents' sum and their
count at scale 6, rounded HALF_UP (Spark's Average over decimal(7,2):
decimal(11,6)), NULL where there is no such value.
"""

from __future__ import annotations

import numpy as np

from . import _plan, _rows

QUANTITY = "ss_quantity"
AVG_SCALE = 6        # the column's scale 2, and Average's 4 more
AVG_DIGITS = 11      # decimal(7,2) -> decimal(11,6)

# values_wrong: 1 when the one value is another, or NULL where the
#   reference has a value, or a value where it has NULL.
# answer_shape_wrong: 1 when the frames are not one row of one column, a
#   bigint for `count`, for `avg` a decimal of scale 6 whose value fits
#   decimal(11,6).
LIMITS = {"values_wrong": 0, "answer_shape_wrong": 0}


def columns_read(params: dict) -> tuple:
    return (QUANTITY,) if params["agg"] == "count" \
        else (QUANTITY, params["column"])


def build(scan_path: str, params: dict, out: dict) -> bytes:
    from blaze_tpu.exprs import AggExpr, AggFn, Col
    from blaze_tpu.ops import AggMode, FilterExec, HashAggregateExec

    if params["agg"] == "count":
        agg = (AggExpr(AggFn.COUNT_STAR, None), "cnt")
    else:
        agg = (AggExpr(AggFn.AVG, Col(params["column"])), "avg")
    op = HashAggregateExec(
        FilterExec(
            _plan.scan(scan_path, list(columns_read(params))),
            (Col(QUANTITY) >= int(params["lo"]))
            & (Col(QUANTITY) <= int(params["hi"])),
        ),
        keys=[],
        aggs=[agg],
        mode=AggMode.COMPLETE,
    )
    return _plan.blob(op)


def answer(batches, out: dict):
    """{"kind": "count" | "avg", "value": int or None}: a count as it
    is, an average as its unscaled integer at scale 6. None where the
    frames are not one row of one column of one of the two types."""
    import pyarrow as pa

    if not batches:
        return None
    table = pa.Table.from_batches(batches)
    if table.num_rows != 1 or table.num_columns != 1:
        return None
    t = table.schema.field(0).type
    v = table.column(0)[0].as_py()
    if pa.types.is_int64(t):
        return None if v is None else {"kind": "count", "value": int(v)}
    if pa.types.is_decimal(t) and t.scale == AVG_SCALE \
            and t.precision >= AVG_DIGITS:
        if v is None:
            return {"kind": "avg", "value": None}
        unscaled = int(v.scaleb(AVG_SCALE))
        if abs(unscaled) >= 10 ** AVG_DIGITS:
            return None
        return {"kind": "avg", "value": unscaled}
    return None


def _bucket(frame: dict, params: dict, quantity_valid) -> np.ndarray:
    q = frame["values"][QUANTITY]
    return quantity_valid & (q >= int(params["lo"])) \
        & (q <= int(params["hi"]))


def _half_up(num: int, den: int) -> int:
    """num / den rounded to the nearest integer, a half away from 0."""
    q, r = divmod(abs(num), den)
    q += 2 * r >= den
    return q if num >= 0 else -q


def _answer_of(frame: dict, params: dict, quantity_valid,
               divide_by_rows: bool) -> dict:
    m = _bucket(frame, params, quantity_valid)
    if params["agg"] == "count":
        return {"kind": "count", "value": int(np.count_nonzero(m))}
    col = params["column"]
    some = m & _rows.is_valid(frame, col)
    n = int(np.count_nonzero(some))
    if not n:
        return {"kind": "avg", "value": None}
    if divide_by_rows:
        n = int(np.count_nonzero(m))
    total = int(frame["values"][col][some].sum(dtype=np.int64))  # cents
    return {"kind": "avg",
            "value": _half_up(total * 10 ** (AVG_SCALE - 2), n)}


def reference(frame: dict, params: dict) -> dict:
    return _answer_of(frame, params, _rows.is_valid(frame, QUANTITY), False)


def control(frame: dict, params: dict) -> dict:
    """One stated guarantee broken a shape. `count`: the quantity
    compared without its validity, so a NULL's stored value passes the
    range (the validity lane dropped from the filter's kernel). `avg`:
    the sum divided by the bucket's rows and not by the column's non-NULL
    values among them (the count carried once for the task, not once a
    column)."""
    if params["agg"] == "count":
        return _answer_of(frame, params, np.ones(frame["rows"], bool), False)
    return _answer_of(frame, params, _rows.is_valid(frame, QUANTITY), True)


def compare(want: dict, got) -> dict:
    if got is None or got["kind"] != want["kind"]:
        return {"values_wrong": 1, "answer_shape_wrong": 1}
    return {"values_wrong": int(got["value"] != want["value"]),
            "answer_shape_wrong": 0}


def least_bytes(rows_in: int, rows_out: int, types: dict,
                columns=(QUANTITY,)) -> float:
    """The least the device must move: of every row in, each column read
    at its narrowest width and a validity bit beside it (every column of
    the table but two is nullable); of the row out, a bigint or the 16
    bytes of a decimal. `columns` is what one request reads
    (`columns_read`); left out, the quantity alone, the least of any."""
    read = sum(_rows.width(types[c]) + 1 / 8 for c in columns)
    return read * rows_in + 16 * rows_out
