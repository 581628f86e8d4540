"""TPC-DS's store channel, made the way dsdgen makes it: `store_sales` and
the `store_returns` it draws from those sales.

What is kept from dsdgen (`w_store_sales.c`, `w_store_returns.c`,
`pricing.c`, `tdefs.h`; written from memory of the kit's source, there is
no network here, and each point is listed under `assumed` in the
configuration's file):

- rows come in the order of the sale: by date, then by ticket. A ticket
  holds 8 to 16 rows that share date, time, customer, demographics,
  address, store and ticket number; its items are distinct (consecutive
  entries of one permutation of the item keys);
- every foreign key is uniform over its dimension's rows at the scale
  factor, which the configuration's `cardinalities` give;
- money is decimal(7,2), worked out in cents the way `set_pricing` does:
  wholesale cost, markup, discount, quantity, coupon, tax;
- a row in `null_row_share` of the rows gets a random bitmap of NULLs over
  every column but the primary key's;
- one sale row in ten is returned, up to 90 days later, by the buyer in
  four cases of five.

The configuration's `columns` give each column's name and type, in the
table's order; a column this module has no rule for is an error. A frame
is {"rows": n, "types": {col: type}, "values": {col: ndarray},
"valid": {col: bool ndarray, or None where no row is NULL}}: decimals are
held as int64 cents.
"""

from __future__ import annotations

import datetime
from concurrent.futures import ThreadPoolExecutor

import numpy as np

JULIAN = 1721425  # d_date_sk is the Julian day number: ordinal + JULIAN


def date_sk(iso: str) -> int:
    return datetime.date.fromisoformat(iso).toordinal() + JULIAN


def _tickets(rng, rows: int, lo: int, hi: int):
    """(ticket of each row, place of each row in its ticket, tickets)."""
    n = rows // lo + 1
    sizes = rng.integers(lo, hi + 1, n)
    ticket = np.repeat(np.arange(n), sizes)[:rows]
    starts = np.concatenate(([0], np.cumsum(sizes)))[:-1]
    return ticket, np.arange(rows) - starts[ticket], int(ticket[-1]) + 1


def _pricing(rng, n: int) -> dict:
    """`set_pricing` for a sale, in cents."""
    qty = rng.integers(1, 101, n)
    wholesale = rng.integers(100, 10001, n)
    list_price = wholesale * (100 + rng.integers(0, 201, n)) // 100
    sales_price = list_price * (100 - rng.integers(0, 101, n)) // 100
    ext_sales = sales_price * qty
    ext_list = list_price * qty
    coupon = np.where(rng.integers(1, 101, n) <= 20,
                      ext_sales * rng.integers(0, 101, n) // 100, 0)
    net_paid = ext_sales - coupon
    tax = net_paid * rng.integers(0, 10, n) // 100
    ext_wholesale = wholesale * qty
    return {
        "quantity": qty, "wholesale_cost": wholesale,
        "list_price": list_price, "sales_price": sales_price,
        "ext_discount_amt": ext_list - ext_sales,
        "ext_sales_price": ext_sales, "ext_wholesale_cost": ext_wholesale,
        "ext_list_price": ext_list, "ext_tax": tax, "coupon_amt": coupon,
        "net_paid": net_paid, "net_paid_inc_tax": net_paid + tax,
        "net_profit": net_paid - ext_wholesale,
    }


def _nulls(rng, n: int, names, never, share: float) -> dict:
    """dsdgen's `nullSet`: a row in `share` of the rows draws a bitmap,
    and a column whose bit is set is NULL there."""
    hit = np.flatnonzero(rng.random(n) < share)
    valid = {}
    for name in names:
        if name in never or not len(hit):
            valid[name] = None
            continue
        v = np.ones(n, bool)
        v[hit[rng.integers(0, 2, len(hit)).astype(bool)]] = False
        valid[name] = v
    return valid


def _frame(table_cfg: dict, values: dict, valid: dict) -> dict:
    types = {c["name"]: c["type"] for c in table_cfg["columns"]}
    missing = [c for c in types if c not in values]
    if missing:
        raise KeyError(f"no rule for column(s) {missing}")
    out = {}
    for name, t in types.items():
        v = values[name]
        out[name] = v.astype(np.int32 if t == "int32" else np.int64)
    return {"rows": len(next(iter(out.values()))), "types": types,
            "values": out, "valid": {c: valid[c] for c in types}}


def _sale_side(cfg: dict, rng, first_row: int, rows: int, keep=None):
    """What the rows of a run of sales share by ticket: (ticket of each
    kept row, its item, its day, a draw of one key a ticket). `keep`
    picks rows of the run (a return keeps one in ten); everything is
    worked out for those rows only."""
    card = cfg["cardinalities"]
    lo, hi = cfg["ticket_rows"]
    ticket, place, n_t = _tickets(rng, rows, lo, hi)
    first_of = np.searchsorted(ticket, np.arange(n_t))
    if keep is not None:
        ticket, place = ticket[keep], place[keep]
    perm = np.random.default_rng(cfg["item_permutation_seed"]).permutation(
        card["item"]) + 1
    item = perm[(rng.integers(0, card["item"], n_t)[ticket] + place)
                % card["item"]]
    # a ticket's rows share the day of its first row
    day = date_sk(cfg["first_sale_date"]) + (
        (first_row + first_of[ticket]) // cfg["rows_per_day"])

    def per_ticket(dimension):
        return rng.integers(1, card[dimension] + 1, n_t)[ticket]

    return ticket, item, day, per_ticket


def store_sales(cfg: dict, table_cfg: dict, rng, first_row: int,
                rows: int) -> dict:
    ticket, item, day, per_ticket = _sale_side(cfg, rng, first_row, rows)
    p = _pricing(rng, rows)
    values = {
        "ss_sold_date_sk": day,
        "ss_sold_time_sk": per_ticket("time_dim") - 1,
        "ss_item_sk": item,
        "ss_customer_sk": per_ticket("customer"),
        "ss_cdemo_sk": per_ticket("customer_demographics"),
        "ss_hdemo_sk": per_ticket("household_demographics"),
        "ss_addr_sk": per_ticket("customer_address"),
        "ss_store_sk": per_ticket("store"),
        "ss_promo_sk": rng.integers(1, cfg["cardinalities"]["promotion"] + 1,
                                    rows),
        "ss_ticket_number": first_row // cfg["ticket_rows"][0] + 1 + ticket,
    }
    values.update({"ss_" + k: v for k, v in p.items()})
    valid = _nulls(rng, rows, values, ("ss_item_sk", "ss_ticket_number"),
                   table_cfg["null_row_share"])
    return _frame(table_cfg, values, valid)


def store_returns(cfg: dict, table_cfg: dict, rng, first_row: int,
                  rows: int) -> dict:
    """The returns of a run of sales: each sale row is returned with
    probability `return_share`, so the run is about rows / return_share
    sale rows long and a ticket brings 0, 1, 2.. returns."""
    share = table_cfg["return_share"]
    sale_rows = int(rows / share * 1.02) + 4096
    back = np.flatnonzero(rng.random(sale_rows) < share)[:rows]
    if len(back) < rows:
        raise RuntimeError("the run of sales was too short for its returns")
    ticket, item, day, per_ticket = _sale_side(
        cfg, rng, int(first_row / share), sale_rows, back)
    customer = per_ticket("customer")
    store = per_ticket("store")
    card = cfg["cardinalities"]
    same = rng.integers(1, 101, rows) <= table_cfg["same_customer_pct"]
    sale = _pricing(rng, rows)
    qty = rng.integers(1, sale["quantity"] + 1)
    amt = sale["sales_price"] * qty
    tax = amt * rng.integers(0, 10, rows) // 100
    fee = rng.integers(50, 10001, rows)
    ship = sale["list_price"] * rng.integers(0, 101, rows) // 100 * qty
    cash = amt * rng.integers(0, 101, rows) // 100
    charge = (amt - cash) * rng.integers(0, 101, rows) // 100
    values = {
        "sr_returned_date_sk": day + rng.integers(
            1, table_cfg["max_return_delay_days"] + 1, rows),
        "sr_return_time_sk": rng.integers(8 * 3600 - 1, 17 * 3600, rows),
        "sr_item_sk": item,
        "sr_customer_sk": np.where(
            same, customer,
            rng.integers(1, card["customer"] + 1, rows)),
        "sr_cdemo_sk": rng.integers(
            1, card["customer_demographics"] + 1, rows),
        "sr_hdemo_sk": rng.integers(
            1, card["household_demographics"] + 1, rows),
        "sr_addr_sk": rng.integers(1, card["customer_address"] + 1, rows),
        "sr_store_sk": store,
        "sr_reason_sk": rng.integers(1, card["reason"] + 1, rows),
        "sr_ticket_number": int(first_row / share)
        // cfg["ticket_rows"][0] + 1 + ticket,
        "sr_return_quantity": qty, "sr_return_amt": amt,
        "sr_return_tax": tax, "sr_return_amt_inc_tax": amt + tax,
        "sr_fee": fee, "sr_return_ship_cost": ship,
        "sr_refunded_cash": cash, "sr_reversed_charge": charge,
        "sr_store_credit": amt - cash - charge,
        "sr_net_loss": amt + tax + fee + ship - cash,
    }
    valid = _nulls(rng, rows, values, ("sr_item_sk", "sr_ticket_number"),
                   table_cfg["null_row_share"])
    return _frame(table_cfg, values, valid)


TABLES = {"store_sales": store_sales, "store_returns": store_returns}


def generate(cfg: dict, seed: int) -> dict:
    """{table: [frame per split]}: the splits of a table are consecutive
    runs of its rows, starting at a row drawn from the seed inside
    `first_row_range`, so every seed reads another stretch of the same
    days."""
    out = {}
    for k, (name, t) in enumerate(sorted(cfg["tables"].items())):
        rng = np.random.default_rng([seed, 0x7DC5, k])
        lo, hi = t["first_row_range"]
        first = int(rng.integers(lo, hi + 1))
        per = int(t["split_rows"])
        n = int(t["splits"])
        # a split a thread: numpy draws and sorts without the GIL
        with ThreadPoolExecutor(n) as pool:
            out[name] = list(pool.map(
                lambda s: TABLES[name](
                    cfg, t, np.random.default_rng([seed, 0x7DC5, k, s]),
                    first + s * per, per),
                range(n)))
    return out
