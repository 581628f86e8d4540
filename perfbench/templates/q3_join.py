"""TPC-DS query 3's first stage, `BASELINE.json` config 3: a store_sales
split probed against the broadcast date_dim and item relations and summed
by brand, as one task (sent in COMPLETE mode; Spark sends the same plan as
a partial aggregate and merges the tasks' answers after a shuffle).
Queries 42, 52 and 55 share the plan.

    SELECT dt.d_year, item.i_brand_id brand_id, item.i_brand brand,
           SUM(ss_ext_sales_price) sum_agg
    FROM date_dim dt, store_sales, item
    WHERE dt.d_date_sk = store_sales.ss_sold_date_sk
      AND store_sales.ss_item_sk = item.i_item_sk
      AND item.i_manufact_id = [MANUFACT] AND dt.d_moy = [MONTH]
    GROUP BY dt.d_year, item.i_brand, item.i_brand_id

The traffic file fixes MONTH and MANUFACT at the qualification run's
values (11, 128) and names the configuration, whose `broadcast` section
the dimensions are made from (`generators/tpcds_dims.py`). Spark plans two
BroadcastHashJoins: the scan with the inferred `isnotnull` of both keys,
the join on the date, a project, the join on the item, a project, then the
aggregate. The two broadcast relations, filtered and projected as Spark's
broadcast jobs leave them, are written once a run as segmented Arrow-IPC
files beside the task links, and every task reads them through an
`IpcReaderExec` over file segments shipped in its blob, as Blaze's
NativeBroadcastExchange sends a broadcast. Exact: a NULL date or item, or
one no broadcast row matches, drops the sale; a NULL amount adds nothing
(a group of NULL amounts alone sums to NULL); the brand comes back byte
for byte.
"""

from __future__ import annotations

import json
import os
import threading

import numpy as np

from . import _plan, _rows

SOLD, ITEM, AMOUNT = "ss_sold_date_sk", "ss_item_sk", "ss_ext_sales_price"
OUT = ("d_year", "brand", "brand_id", "sum_agg")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# what a brand takes out, for `least_bytes`: its bytes (the generator's
# names run 9 to 30) and a 4-byte offset
BRAND_BYTES = 20

# groups_wrong: groups missing, extra, with another key, another brand
#   string or another sum (keys, strings and unscaled cents compared
#   exactly).
# answer_shape_wrong: 1 when the frames are not an int32 year, a utf8
#   brand (or a dictionary of utf8), an int32 brand id and a decimal of
#   scale 2, in that order.
LIMITS = {"groups_wrong": 0, "answer_shape_wrong": 0}

_LOCK = threading.Lock()
_RELATIONS = {}  # (config, month, manufact) -> the broadcast relations
_WRITTEN = {}    # (directory, config, month, manufact) -> segment files


def relations(params: dict) -> dict:
    """The two broadcast relations of the traffic's configuration, as
    `tpcds_dims.broadcast` gives them; made once a process."""
    from perfbench.generators import tpcds_dims

    key = (params["config"], int(params["month"]), int(params["manufact"]))
    with _LOCK:
        if key not in _RELATIONS:
            with open(os.path.join(HERE, "configs",
                                   params["config"] + ".json")) as f:
                bcfg = json.load(f)["broadcast"]
            _RELATIONS[key] = tpcds_dims.broadcast(
                tpcds_dims.generate(bcfg), key[1], key[2])
        return _RELATIONS[key]


def _arrow(rel: dict):
    import pyarrow as pa

    return pa.RecordBatch.from_arrays(
        [pa.array(v.tolist() if v.dtype == object else v,
                  type=pa.string() if v.dtype == object else pa.int32())
         for v in rel.values()], names=list(rel))


def broadcast_files(directory: str, params: dict) -> dict:
    """{relation: (path, bytes, Arrow schema)}: each broadcast relation as
    one segmented zstd Arrow-IPC file in `directory`, written by the
    first task of a run that asks and read by every task."""
    from blaze_tpu.io.ipc import encode_ipc_segment

    key = (directory, params["config"], int(params["month"]),
           int(params["manufact"]))
    rels = relations(params)
    with _LOCK:
        got = _WRITTEN.get(key)
        if got is None or not all(os.path.exists(p) for p, _, _ in
                                  got.values()):
            got = {}
            for name, rel in rels.items():
                rb = _arrow(rel)
                blob = encode_ipc_segment(rb)
                path = os.path.join(directory, f"broadcast_{name}.seg")
                with open(path, "wb") as f:
                    f.write(blob)
                got[name] = (path, len(blob), rb.schema)
            _WRITTEN[key] = got
        return got


def build(scan_path: str, params: dict, out: dict) -> bytes:
    from blaze_tpu.exprs import AggExpr, AggFn, Col
    from blaze_tpu.ops import (
        AggMode,
        FileSegment,
        FilterExec,
        HashAggregateExec,
        HashJoinExec,
        IpcReaderExec,
        IpcReadMode,
        JoinType,
        ProjectExec,
    )
    from blaze_tpu.plan.serde import task_to_proto
    from blaze_tpu.types import from_arrow_schema

    files = broadcast_files(os.path.dirname(scan_path), params)
    leaf = {name: IpcReaderExec(
        f"broadcast.{name}", from_arrow_schema(schema), 1,
        IpcReadMode.CHANNEL_AND_FILE_SEGMENT)
        for name, (_, _, schema) in files.items()}
    probe = FilterExec(
        _plan.scan(scan_path, [SOLD, ITEM, AMOUNT]),
        Col(SOLD).is_not_null() & Col(ITEM).is_not_null(),
    )
    by_date = ProjectExec(
        HashJoinExec(leaf["date_dim"], probe, ["d_date_sk"], [SOLD],
                     JoinType.INNER),
        [(Col("d_year"), "d_year"), (Col(ITEM), ITEM),
         (Col(AMOUNT), AMOUNT)],
    )
    by_item = ProjectExec(
        HashJoinExec(leaf["item"], by_date, ["i_item_sk"], [ITEM],
                     JoinType.INNER),
        [(Col("d_year"), "d_year"), (Col(AMOUNT), AMOUNT),
         (Col("i_brand_id"), "i_brand_id"), (Col("i_brand"), "i_brand")],
    )
    op = HashAggregateExec(
        by_item,
        keys=[(Col("d_year"), OUT[0]), (Col("i_brand"), OUT[1]),
              (Col("i_brand_id"), OUT[2])],
        aggs=[(AggExpr(AggFn.SUM, Col(AMOUNT)), OUT[3])],
        mode=AggMode.COMPLETE,
    )
    return task_to_proto(op, 0, file_resources={
        f"broadcast.{name}": [FileSegment(path, 0, length)]
        for name, (path, length, _) in files.items()})


def answer(batches, out: dict):
    """The frames as a side: the year, brand id and cents through
    `datagen.from_arrow`, the brand as str objects (a dictionary
    decoded)."""
    import pyarrow as pa

    if not batches:
        return None
    table = pa.Table.from_batches(batches)
    if tuple(table.column_names) != OUT:
        return None
    brand = table.column(OUT[1]).combine_chunks()
    if pa.types.is_dictionary(brand.type):
        brand = brand.cast(brand.type.value_type)
    total = table.schema.field(OUT[3]).type
    if brand.type != pa.string() or not pa.types.is_decimal(total) \
            or total.scale != 2:
        return None
    got = _plan.as_side(table, {OUT[0]: "int32", OUT[2]: "int32",
                                OUT[3]: f"decimal({total.precision},2)"})
    if set(got["values"]) != {OUT[0], OUT[2], OUT[3]}:
        return None
    ok = np.asarray(brand.is_valid()) if brand.null_count else None
    got["values"][OUT[1]] = np.array(
        ["" if s is None else s for s in brand.to_pylist()], dtype=object)
    got["valid"][OUT[1]] = ok
    return got


def _grouped(frame: dict, params: dict, date_validity: bool = True) -> dict:
    rel = relations(params)
    d, it = rel["date_dim"], rel["item"]
    # the broadcasts as arrays indexed by key: -1 where no row has it
    d0 = int(d["d_date_sk"].min())
    year_of = np.full(int(d["d_date_sk"].max()) - d0 + 2, -1, np.int64)
    year_of[d["d_date_sk"] - d0] = d["d_year"]
    names, name_code = np.unique(it["i_brand"], return_inverse=True)
    row_of = np.full(int(it["i_item_sk"].max()) + 2, -1, np.int64)
    row_of[it["i_item_sk"]] = np.arange(len(it["i_item_sk"]))

    day = frame["values"][SOLD].astype(np.int64)
    item = frame["values"][ITEM].astype(np.int64)
    m = _rows.is_valid(frame, ITEM) & (item >= 0) & (item < len(row_of) - 1)
    if date_validity:
        m &= _rows.is_valid(frame, SOLD)
    m &= (day >= d0) & (day < d0 + len(year_of) - 1)
    year = np.where(m, year_of[np.clip(day - d0, 0, len(year_of) - 1)], -1)
    row = np.where(m, row_of[np.clip(item, 0, len(row_of) - 1)], -1)
    m &= (year >= 0) & (row >= 0)
    year, row = year[m], row[m]
    amt, amt_ok = (a[m] for a in _rows.settled(frame, [AMOUNT])[AMOUNT])
    brand_id = it["i_brand_id"][row].astype(np.int64)
    code = name_code.reshape(-1)[row].astype(np.int64)
    # one 64-bit key a group: year, the brand's name and its id
    packed = (year << 48) | (code << 24) | brand_id
    uniq, inv = np.unique(packed, return_inverse=True)
    inv = inv.reshape(-1)
    sums = np.zeros(len(uniq), np.int64)
    np.add.at(sums, inv, amt)  # cents, exactly
    some = np.bincount(inv, weights=amt_ok, minlength=len(uniq)) > 0
    return {
        "values": {OUT[0]: (uniq >> 48).astype(np.int32),
                   OUT[1]: names[(uniq >> 24) & 0xFFFFFF],
                   OUT[2]: (uniq & 0xFFFFFF).astype(np.int32),
                   OUT[3]: np.where(some, sums, 0)},
        "valid": {OUT[0]: None, OUT[1]: None, OUT[2]: None, OUT[3]: some},
    }


def reference(frame: dict, params: dict) -> dict:
    return _grouped(frame, params)


def control(frame: dict, params: dict) -> dict:
    """A sale whose date is NULL joined on the day stored under the NULL:
    what comes of comparing the join key without its validity, the step
    that saves the probe a lane. The split's NULL dates all lie in the
    month, so every group they fall in sums more."""
    return _grouped(frame, params, date_validity=False)


def _coded(want: dict, got: dict) -> tuple:
    """Both sides with the brand as an int64 code of one vocabulary, so
    whole rows compare as numbers; equal codes are equal strings."""
    w, g = want["values"][OUT[1]], got["values"][OUT[1]]
    _, codes = np.unique(np.concatenate((w, g)), return_inverse=True)
    codes = codes.reshape(-1).astype(np.int64)
    sides = []
    for side, c in ((want, codes[:len(w)]), (got, codes[len(w):])):
        sides.append({"values": dict(side["values"], **{OUT[1]: c}),
                      "valid": side["valid"]})
    return sides[0], sides[1]


def compare(want: dict, got) -> dict:
    n_want = len(want["values"][OUT[0]])
    if got is None:
        return {"groups_wrong": max(n_want, 1), "answer_shape_wrong": 1}
    w, g = _coded(want, got)
    return {"groups_wrong": _rows.rows_differ(w, g, OUT),
            "answer_shape_wrong": 0}


def least_bytes(rows_in: int, rows_out: int, types: dict) -> float:
    """The date, the item key and the amount of every row in, each at its
    narrowest width and a validity bit; a year, a brand id, an 8-byte sum
    and the brand's bytes for every group out."""
    read = sum(_rows.width(types[c]) + 1 / 8 for c in (SOLD, ITEM, AMOUNT))
    return read * rows_in + (4 + 4 + 8 + BRAND_BYTES) * rows_out
