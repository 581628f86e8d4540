"""TPC-DS's `inventory`, made the way dsdgen makes it: the first table
here outside the store channel, four `int` columns and no money.

What is kept from dsdgen (`w_inventory.c`, `scd.c`, `tdefs.h`; written
from memory of the kit's source, there is no network here, and each point
is listed under `assumed` in the configuration's file):

- the table is a weekly snapshot of every item id in every warehouse,
  and row `i` (from 0) is worked out from its number alone: the item id
  runs fastest (`i % item_ids + 1`), then the warehouse
  (`i // item_ids % warehouses + 1`), then the week
  (`i // (item_ids * warehouses)`), whose `inv_date_sk` is the first
  date of the data, a Thursday, plus seven days a week;
- `item` is a slowly changing dimension: three ids in a row have one, two
  and three revisions, six rows of `item` in all, so `item_ids` is half
  of `item`'s rows. `inv_item_sk` is the surrogate key of the revision
  valid on the snapshot's date (`matchSCDSK`): the id alone picks among
  `6 * (id // 3) + {1}`, `{2, 3}` or `{-2, -1, 0}`, and the date picks
  inside the set (the second revision after half of the data's range;
  the second and third after one and two thirds of it);
- `inv_quantity_on_hand` is uniform over `quantity_range`;
- a row in `null_row_share` of the rows draws a bitmap of NULLs
  (`nullSet`), which the three columns of the primary key never take:
  the store generator's rule, shared with it.

A frame is what `tpcds_store` makes: {"rows", "types", "values",
"valid"}.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .tpcds_store import _frame, _nulls, date_sk

KEY_COLUMNS = ("inv_date_sk", "inv_item_sk", "inv_warehouse_sk")


def item_sk(item_id: np.ndarray, day: np.ndarray, cfg: dict) -> np.ndarray:
    """`matchSCDSK`: the surrogate key of the revision of `item_id` that
    is valid on `day` (a `d_date_sk`)."""
    first, last = (date_sk(d) for d in cfg["data_date_range"])
    span = last - first
    half = first + span // 2
    third1 = first + span // 3
    third2 = third1 + span // 3
    base = item_id // 3 * 6
    kind = item_id % 3
    sk = np.where(
        kind == 1, base + 1,
        np.where(kind == 2, base + 2 + (day > half),
                 base - 2 + (day > third1) + (day > third2)))
    return np.minimum(sk, cfg["cardinalities"]["item"])


def inventory(cfg: dict, table_cfg: dict, rng, first_row: int,
              rows: int) -> dict:
    ids = cfg["cardinalities"]["item"] // 2
    warehouses = cfg["cardinalities"]["warehouse"]
    i = first_row + np.arange(rows, dtype=np.int64)
    day = date_sk(cfg["data_date_range"][0]) + 7 * (i // (ids * warehouses))
    lo, hi = table_cfg["quantity_range"]
    values = {
        "inv_date_sk": day,
        "inv_item_sk": item_sk(i % ids + 1, day, cfg),
        "inv_warehouse_sk": i // ids % warehouses + 1,
        "inv_quantity_on_hand": rng.integers(lo, hi + 1, rows),
    }
    valid = _nulls(rng, rows, values, KEY_COLUMNS,
                   table_cfg["null_row_share"])
    return _frame(table_cfg, values, valid)


TABLES = {"inventory": inventory}


def generate(cfg: dict, seed: int) -> dict:
    """{table: [frame per split]}: consecutive runs of the table's rows
    from a row drawn from the seed inside `first_row_range`, as
    `tpcds_store.generate` draws its own."""
    out = {}
    for k, (name, t) in enumerate(sorted(cfg["tables"].items())):
        rng = np.random.default_rng([seed, 0x1A7E, k])
        lo, hi = t["first_row_range"]
        first = int(rng.integers(lo, hi + 1))
        per = int(t["split_rows"])
        n = int(t["splits"])
        with ThreadPoolExecutor(n) as pool:
            out[name] = list(pool.map(
                lambda s: TABLES[name](
                    cfg, t, np.random.default_rng([seed, 0x1A7E, k, s]),
                    first + s * per, per),
                range(n)))
    return out
