"""ALL 99 TPC-DS queries as engine plan builders over synthetic tables.

The reference's correctness backbone is whole-query differential testing:
99 TPC-DS queries x {broadcast-join, forced-SMJ} validated against
vanilla Spark (.github/workflows/tpcds.yml:105-147, dev/run-tpcds-test:
38-57). This module is that harness engine-side, at full 99-query
coverage: each query is a full multi-stage plan (CTE-depth joins,
agg-over-join-over-agg, unions, semi/anti joins, decorrelated
subqueries - the same rewrites Spark's optimizer performs) built twice,
once with broadcast hash joins and once with forced sort-merge joins.
Oracles live in test_tpcds_queries.py as independent pandas
implementations.

Scale is configurable (BLAZE_TPCDS_ROWS, default 200k store_sales
rows - raise to 1M+ for scale runs);
all generated data is deterministic (seeded) and includes NULL keys.
"""

from __future__ import annotations

import os

import numpy as np
import pandas as pd
import pyarrow as pa

from blaze_tpu import ColumnBatch
from blaze_tpu.exprs import (
    AggExpr,
    AggFn,
    CaseWhen,
    Coalesce,
    Col,
    If,
    InList,
    IsNotNull,
    Literal,
    ScalarFn,
)
from blaze_tpu.ops import (
    AggMode,
    CoalescePartitionsExec,
    FilterExec,
    HashAggregateExec,
    HashJoinExec,
    JoinType,
    LimitExec,
    MemoryScanExec,
    ProjectExec,
    RenameColumnsExec,
    SortExec,
    SortKey,
    SortMergeJoinExec,
    UnionExec,
)
from blaze_tpu.types import DataType

N_SALES = int(os.environ.get("BLAZE_TPCDS_ROWS", 200_000))
N_DATES = 1461  # 4 years
N_ITEMS = 2_000
N_CUSTOMERS = 20_000
N_STORES = 12
N_ADDRESSES = 10_000
N_CDEMO = 500
N_PROMOS = 30
N_HDEMO = 120

_STATES = ["TN", "GA", "CA", "TX", "OH", "NY", None]
_CATEGORIES = ["Books", "Music", "Home", "Sports", "Shoes"]
_GENDERS = ["M", "F"]
_MARITAL = ["S", "M", "D", "W"]
_EDU = ["College", "Primary", "2 yr Degree", "4 yr Degree"]
_YN = ["Y", "N"]


def gen_tables(seed: int = 20260729):
    rng = np.random.default_rng(seed)
    n = N_SALES

    def pick(values, size, null_frac=0.0):
        idx = rng.integers(0, len(values), size)
        out = np.array([values[i] for i in idx], dtype=object)
        if null_frac:
            out[rng.random(size) < null_frac] = None
        return out

    date_dim = pd.DataFrame(
        {
            "d_date_sk": np.arange(N_DATES, dtype=np.int32),
            "d_year": (1998 + np.arange(N_DATES) // 365).astype(np.int32),
            "d_moy": ((np.arange(N_DATES) % 365) // 31 % 12 + 1).astype(
                np.int32),
            "d_month_seq": (
                (1998 - 1900) * 12
                + (np.arange(N_DATES) // 365) * 12
                + ((np.arange(N_DATES) % 365) // 31 % 12)
            ).astype(np.int32),
            "d_week_seq": (np.arange(N_DATES) // 7).astype(np.int32),
            "d_day_name": np.array(
                ["Sunday", "Monday", "Tuesday", "Wednesday", "Thursday",
                 "Friday", "Saturday"], dtype=object,
            )[np.arange(N_DATES) % 7],
            "d_dom": ((np.arange(N_DATES) % 31) + 1).astype(np.int32),
        }
    )

    def sales_frame(prefix, size, date_null=0.01, cust_null=0.01):
        dsk = rng.integers(0, N_DATES, size).astype(np.float64)
        dsk[rng.random(size) < date_null] = np.nan
        csk = rng.integers(0, N_CUSTOMERS, size).astype(np.float64)
        csk[rng.random(size) < cust_null] = np.nan
        return {
            f"{prefix}_sold_date_sk": pd.array(
                dsk, dtype=pd.Int32Dtype()
            ),
            f"{prefix}_item_sk": rng.integers(0, N_ITEMS, size).astype(
                np.int32),
            f"{prefix}_ext_sales_price": np.round(
                rng.random(size) * 2000, 2),
            f"{prefix}_ext_list_price": np.round(
                rng.random(size) * 2500, 2),
            f"{prefix}_ext_wholesale_cost": np.round(
                rng.random(size) * 1500, 2),
            f"{prefix}_ext_discount_amt": np.round(
                rng.random(size) * 100, 2),
            f"{prefix}_customer_sk": pd.array(
                csk, dtype=pd.Int32Dtype()
            ),
        }

    store_sales = pd.DataFrame(sales_frame("ss", n))
    store_sales["ss_store_sk"] = rng.integers(0, N_STORES, n).astype(
        np.int32)
    store_sales["ss_cdemo_sk"] = rng.integers(0, N_CDEMO, n).astype(
        np.int32)
    store_sales["ss_promo_sk"] = rng.integers(0, N_PROMOS, n).astype(
        np.int32)
    store_sales["ss_quantity"] = rng.integers(1, 101, n).astype(np.int32)
    store_sales["ss_sales_price"] = np.round(rng.random(n) * 200, 2)
    store_sales["ss_list_price"] = np.round(rng.random(n) * 250, 2)
    store_sales["ss_coupon_amt"] = np.round(rng.random(n) * 50, 2)
    store_sales["ss_net_profit"] = np.round(rng.random(n) * 300 - 50, 2)

    n_sr = max(n // 10, 1000)
    store_returns = pd.DataFrame(
        {
            "sr_returned_date_sk": rng.integers(
                0, N_DATES, n_sr).astype(np.int32),
            "sr_customer_sk": pd.array(
                np.where(
                    rng.random(n_sr) < 0.02, np.nan,
                    rng.integers(0, N_CUSTOMERS, n_sr).astype(np.float64),
                ),
                dtype=pd.Int32Dtype(),
            ),
            "sr_store_sk": rng.integers(0, N_STORES, n_sr).astype(
                np.int32),
            "sr_item_sk": rng.integers(0, N_ITEMS, n_sr).astype(np.int32),
            "sr_return_amt": np.round(rng.random(n_sr) * 500, 2),
            "sr_net_loss": np.round(rng.random(n_sr) * 100, 2),
        }
    )

    n_ws = max(n // 4, 1000)
    web_sales = pd.DataFrame(sales_frame("ws", n_ws))
    web_sales = web_sales.rename(
        columns={"ws_customer_sk": "ws_bill_customer_sk"}
    )
    n_cs = max(n // 3, 1000)
    catalog_sales = pd.DataFrame(sales_frame("cs", n_cs))
    catalog_sales = catalog_sales.rename(
        columns={"cs_customer_sk": "cs_bill_customer_sk"}
    )
    n_wr = max(n_ws // 10, 200)
    web_returns = pd.DataFrame(
        {
            "wr_returned_date_sk": rng.integers(0, N_DATES, n_wr).astype(
                np.int32),
            "wr_item_sk": rng.integers(0, N_ITEMS, n_wr).astype(np.int32),
            "wr_return_amt": np.round(rng.random(n_wr) * 400, 2),
            "wr_net_loss": np.round(rng.random(n_wr) * 80, 2),
        }
    )
    n_cr = max(n_cs // 10, 200)
    catalog_returns = pd.DataFrame(
        {
            "cr_returned_date_sk": rng.integers(0, N_DATES, n_cr).astype(
                np.int32),
            "cr_item_sk": rng.integers(0, N_ITEMS, n_cr).astype(np.int32),
            "cr_return_amount": np.round(rng.random(n_cr) * 450, 2),
            "cr_net_loss": np.round(rng.random(n_cr) * 90, 2),
        }
    )

    store = pd.DataFrame(
        {
            "s_store_sk": np.arange(N_STORES, dtype=np.int32),
            "s_store_name": [f"store_{i%7}" for i in range(N_STORES)],
            "s_state": pick(_STATES[:-1], N_STORES),
            "s_zip": [f"{35000 + i * 97 % 60000:05d}" for i in
                      range(N_STORES)],
        }
    )
    customer = pd.DataFrame(
        {
            "c_customer_sk": np.arange(N_CUSTOMERS, dtype=np.int32),
            "c_customer_id": [
                f"AAAAAAAA{i:08d}" for i in range(N_CUSTOMERS)
            ],
            "c_current_addr_sk": rng.integers(
                0, N_ADDRESSES, N_CUSTOMERS).astype(np.int32),
            "c_current_cdemo_sk": pd.array(
                np.where(
                    rng.random(N_CUSTOMERS) < 0.05, np.nan,
                    rng.integers(0, N_CDEMO, N_CUSTOMERS).astype(
                        np.float64),
                ),
                dtype=pd.Int32Dtype(),
            ),
            "c_preferred_cust_flag": pick(_YN, N_CUSTOMERS, 0.02),
            "c_first_name": pick(
                ["John", "Jane", "Alex", "Sam", "Pat"], N_CUSTOMERS),
            "c_last_name": pick(
                ["Smith", "Jones", "Lee", "Patel", "Kim"], N_CUSTOMERS),
            "c_birth_year": pd.array(
                np.where(
                    rng.random(N_CUSTOMERS) < 0.03, np.nan,
                    rng.integers(1924, 1993, N_CUSTOMERS).astype(
                        np.float64),
                ),
                dtype=pd.Int32Dtype(),
            ),
        }
    )
    customer_address = pd.DataFrame(
        {
            "ca_address_sk": np.arange(N_ADDRESSES, dtype=np.int32),
            "ca_state": pick(_STATES, N_ADDRESSES, 0.02),
            # ~500 distinct zips -> ~20 addresses per zip, so q8's
            # ">10 preferred customers per zip" predicate selects a
            # non-trivial subset
            "ca_zip": [
                f"{(24000 + (i % 500) * 131) % 90000:05d}" for i in
                range(N_ADDRESSES)
            ],
            "ca_county": pick(
                ["Rich County", "Ziebach County", "Walker County"],
                N_ADDRESSES,
            ),
        }
    )
    customer_demographics = pd.DataFrame(
        {
            "cd_demo_sk": np.arange(N_CDEMO, dtype=np.int32),
            "cd_gender": pick(_GENDERS, N_CDEMO),
            "cd_marital_status": pick(_MARITAL, N_CDEMO),
            "cd_education_status": pick(_EDU, N_CDEMO),
            "cd_purchase_estimate": rng.integers(
                500, 10000, N_CDEMO).astype(np.int32),
            "cd_credit_rating": pick(
                ["Low Risk", "Good", "High Risk"], N_CDEMO),
            "cd_dep_count": rng.integers(0, 7, N_CDEMO).astype(np.int32),
            "cd_dep_employed_count": rng.integers(0, 7, N_CDEMO).astype(
                np.int32),
            "cd_dep_college_count": rng.integers(0, 7, N_CDEMO).astype(
                np.int32),
        }
    )
    item = pd.DataFrame(
        {
            "i_item_sk": np.arange(N_ITEMS, dtype=np.int32),
            "i_item_id": [f"ITEM{i:08d}" for i in range(N_ITEMS)],
            "i_item_desc": pick(
                ["desc one", "desc two", "desc three"], N_ITEMS),
            "i_current_price": np.round(
                rng.random(N_ITEMS) * 100 + 0.5, 2),
            "i_category": pick(_CATEGORIES, N_ITEMS, 0.01),
            "i_brand": pick(
                [f"brand_{j}" for j in range(20)], N_ITEMS),
            "i_brand_id": rng.integers(1, 21, N_ITEMS).astype(np.int32),
            "i_manufact_id": rng.integers(1, 200, N_ITEMS).astype(
                np.int32),
            "i_manager_id": rng.integers(1, 100, N_ITEMS).astype(
                np.int32),
        }
    )
    promotion = pd.DataFrame(
        {
            "p_promo_sk": np.arange(N_PROMOS, dtype=np.int32),
            "p_channel_email": pick(_YN, N_PROMOS),
            "p_channel_event": pick(_YN, N_PROMOS),
        }
    )
    reason = pd.DataFrame(
        {
            "r_reason_sk": np.arange(1, 10, dtype=np.int32),
            "r_reason_desc": [f"reason {i}" for i in range(1, 10)],
        }
    )
    return {
        "date_dim": date_dim,
        "store_sales": store_sales,
        "store_returns": store_returns,
        "web_sales": web_sales,
        "catalog_sales": catalog_sales,
        "web_returns": web_returns,
        "catalog_returns": catalog_returns,
        "store": store,
        "customer": customer,
        "customer_address": customer_address,
        "customer_demographics": customer_demographics,
        "item": item,
        "promotion": promotion,
        "reason": reason,
    }


def scans_of(tables: dict) -> dict:
    """MemoryScanExec per table (device-staged once per session)."""
    out = {}
    for name, df in tables.items():
        rb = pa.RecordBatch.from_pandas(df, preserve_index=False)
        cb = ColumnBatch.from_arrow(rb)
        out[name] = lambda cb=cb: MemoryScanExec([[cb]], cb.schema)
    return out


# ---------------------------------------------------------------------------
# plan-building helpers
# ---------------------------------------------------------------------------

def _union(children):
    """UNION ALL coalesced to one partition (the exchange Spark's
    planner would insert below a single-partition consumer)."""
    return CoalescePartitionsExec(UnionExec(children))


def _join(flavor, left, right, lk, rk, jt=JoinType.INNER):
    """BHJ (left = build/broadcast side) or forced SMJ - the two CI
    flavors of the reference (tpcds.yml:139-147)."""
    if flavor == "bhj":
        return HashJoinExec(left, right, lk, rk, jt)
    return SortMergeJoinExec(left, right, lk, rk, jt)


def _semi(flavor, left, right, lk, rk):
    """left SEMI right regardless of flavor's build-side convention."""
    if flavor == "bhj":
        # HashJoinExec LEFT_SEMI emits the build (left) side
        return HashJoinExec(left, right, lk, rk, JoinType.LEFT_SEMI)
    return SortMergeJoinExec(left, right, lk, rk, JoinType.LEFT_SEMI)


def _agg(child, keys, aggs, mode=AggMode.COMPLETE):
    return HashAggregateExec(child, keys=keys, aggs=aggs, mode=mode)


def _project_names(child, names):
    return ProjectExec(child, [(Col(n), n) for n in names])


def _sorted_limit(child, sort_keys, limit):
    return LimitExec(SortExec(child, sort_keys), limit)


# ---------------------------------------------------------------------------
# queries
# ---------------------------------------------------------------------------

def q1(s, flavor):
    """TPC-DS q1: customers returning >1.2x the store-average return.
    CTE customer_total_return; correlated subquery decorrelated into a
    per-store AVG join (Spark plans it the same way)."""
    ctr = _agg(
        _join(
            flavor,
            FilterExec(s["date_dim"](), Col("d_year") == 2000),
            s["store_returns"](),
            ["d_date_sk"], ["sr_returned_date_sk"],
        ),
        keys=[(Col("sr_customer_sk"), "ctr_customer_sk"),
              (Col("sr_store_sk"), "ctr_store_sk")],
        aggs=[(AggExpr(AggFn.SUM, Col("sr_return_amt")),
               "ctr_total_return")],
    )
    avg_ctr = ProjectExec(
        _agg(
            ctr,
            keys=[(Col("ctr_store_sk"), "avg_store_sk")],
            aggs=[(AggExpr(AggFn.AVG, Col("ctr_total_return")), "avg_r")],
        ),
        [(Col("avg_store_sk"), "avg_store_sk"),
         (Col("avg_r") * 1.2, "threshold")],
    )
    ctr2 = _agg(
        _join(
            flavor,
            FilterExec(s["date_dim"](), Col("d_year") == 2000),
            s["store_returns"](),
            ["d_date_sk"], ["sr_returned_date_sk"],
        ),
        keys=[(Col("sr_customer_sk"), "ctr_customer_sk"),
              (Col("sr_store_sk"), "ctr_store_sk")],
        aggs=[(AggExpr(AggFn.SUM, Col("sr_return_amt")),
               "ctr_total_return")],
    )
    over = FilterExec(
        _join(flavor, avg_ctr, ctr2, ["avg_store_sk"], ["ctr_store_sk"]),
        Col("ctr_total_return") > Col("threshold"),
    )
    with_store = _join(
        flavor,
        FilterExec(s["store"](), Col("s_state") == "TN"),
        over,
        ["s_store_sk"], ["ctr_store_sk"],
    )
    with_cust = _join(
        flavor, with_store, s["customer"](),
        ["ctr_customer_sk"], ["c_customer_sk"],
    )
    return _sorted_limit(
        _project_names(with_cust, ["c_customer_id"]),
        [SortKey(Col("c_customer_id"), True, True)],
        100,
    )


def q2(s, flavor):
    """TPC-DS q2: weekly web+catalog sales pivoted by day name, year vs
    year+1 ratio on aligned week_seq (self-join at +53 weeks)."""
    def wscs(prefix, table):
        return ProjectExec(
            s[table](),
            [(Col(f"{prefix}_sold_date_sk"), "sold_date_sk"),
             (Col(f"{prefix}_ext_sales_price"), "sales_price")],
        )

    both = _union([wscs("ws", "web_sales"), wscs("cs", "catalog_sales")])
    joined = _join(
        flavor, s["date_dim"](), both, ["d_date_sk"], ["sold_date_sk"]
    )

    def day_sum(day):
        return AggExpr(
            AggFn.SUM,
            If(Col("d_day_name") == day, Col("sales_price"),
               Literal(None, DataType.float64())),
        )

    days = ["Sunday", "Monday", "Tuesday", "Wednesday", "Thursday",
            "Friday", "Saturday"]
    wswscs = _agg(
        joined,
        keys=[(Col("d_week_seq"), "d_week_seq")],
        aggs=[(day_sum(d), f"{d.lower()[:3]}_sales") for d in days],
    )
    cols = [f"{d.lower()[:3]}_sales" for d in days]
    # year 1998 weeks vs 1999 weeks, aligned by week_seq + 53
    wk_year = _agg(
        _join(flavor, s["date_dim"](), wswscs,
              ["d_week_seq"], ["d_week_seq"]),
        keys=[(Col("d_week_seq"), "week_seq"), (Col("d_year"), "year")],
        aggs=[(AggExpr(AggFn.MAX, Col(c)), c) for c in cols],
    )
    y1 = RenameColumnsExec(
        FilterExec(wk_year, Col("year") == 1998),
        ["week_seq1", "year1"] + [c + "1" for c in cols],
    )
    y2 = ProjectExec(
        FilterExec(wk_year, Col("year") == 1999),
        [(Col("week_seq") - 53, "week_seq2")]
        + [(Col(c), c + "2") for c in cols],
    )
    paired = _join(flavor, y1, y2, ["week_seq1"], ["week_seq2"])
    ratios = ProjectExec(
        paired,
        [(Col("week_seq1"), "d_week_seq1")]
        + [
            (ScalarFn("round", (Col(c + "1") / Col(c + "2"),
                                Literal(2, DataType.int32()))), c + "_r")
            for c in cols
        ],
    )
    return SortExec(ratios, [SortKey(Col("d_week_seq1"), True, True)])


def q3(s, flavor):
    """TPC-DS q3: brand revenue for one manufacturer in November."""
    j = _join(
        flavor,
        FilterExec(s["date_dim"](), Col("d_moy") == 11),
        s["store_sales"](),
        ["d_date_sk"], ["ss_sold_date_sk"],
    )
    j2 = _join(
        flavor,
        FilterExec(s["item"](), Col("i_manufact_id") == 128),
        j,
        ["i_item_sk"], ["ss_item_sk"],
    )
    agg = _agg(
        j2,
        keys=[(Col("d_year"), "d_year"),
              (Col("i_brand_id"), "brand_id"),
              (Col("i_brand"), "brand")],
        aggs=[(AggExpr(AggFn.SUM, Col("ss_ext_sales_price")), "sum_agg")],
    )
    return _sorted_limit(
        agg,
        [SortKey(Col("d_year"), True, True),
         SortKey(Col("sum_agg"), False, False),
         SortKey(Col("brand_id"), True, True)],
        100,
    )


def _year_total(s, flavor, prefix, table, cust_col):
    """q4/q11 CTE: per customer per year net revenue for one channel."""
    j = _join(
        flavor,
        s["date_dim"](),
        s[table](),
        ["d_date_sk"], [f"{prefix}_sold_date_sk"],
    )
    j2 = _join(
        flavor, s["customer"](), j,
        ["c_customer_sk"], [cust_col],
    )
    return _agg(
        j2,
        keys=[(Col("c_customer_sk"), "customer_sk"),
              (Col("c_customer_id"), "customer_id"),
              (Col("d_year"), "dyear")],
        aggs=[
            (
                AggExpr(
                    AggFn.SUM,
                    (Col(f"{prefix}_ext_list_price")
                     - Col(f"{prefix}_ext_discount_amt")) / 2.0,
                ),
                "year_total",
            )
        ],
    )


def q4(s, flavor):
    """TPC-DS q4 (2-channel variant = q11 shape): customers whose
    catalog-channel growth outpaces store-channel growth across two
    years. 4-way self-join of the year_total CTE."""
    def yt(prefix, table, cust_col, year, names):
        base = _year_total(s, flavor, prefix, table, cust_col)
        return RenameColumnsExec(
            FilterExec(base, Col("dyear") == year), names
        )

    ts1 = yt("ss", "store_sales", "ss_customer_sk", 1998,
             ["s1_sk", "s1_id", "s1_year", "s1_total"])
    ts2 = yt("ss", "store_sales", "ss_customer_sk", 1999,
             ["s2_sk", "s2_id", "s2_year", "s2_total"])
    tc1 = yt("cs", "catalog_sales", "cs_bill_customer_sk", 1998,
             ["c1_sk", "c1_id", "c1_year", "c1_total"])
    tc2 = yt("cs", "catalog_sales", "cs_bill_customer_sk", 1999,
             ["c2_sk", "c2_id", "c2_year", "c2_total"])
    j = _join(flavor, ts1, ts2, ["s1_sk"], ["s2_sk"])
    j = _join(flavor, tc1, j, ["c1_sk"], ["s1_sk"])
    j = _join(flavor, tc2, j, ["c2_sk"], ["c1_sk"])
    cond = FilterExec(
        FilterExec(j, (Col("s1_total") > 0) & (Col("c1_total") > 0)),
        Col("c2_total") / Col("c1_total")
        > Col("s2_total") / Col("s1_total"),
    )
    return _sorted_limit(
        _project_names(cond, ["s1_id"]),
        [SortKey(Col("s1_id"), True, True)],
        100,
    )


def q5(s, flavor):
    """TPC-DS q5 (rollup as explicit grouping-set union): per-channel
    sales/returns/profit, plus the channel and grand totals."""
    def channel(sales_prefix, sales_table, ret_prefix, ret_table,
                ret_amt_col, channel_name, id_prefix):
        sales = ProjectExec(
            s[sales_table](),
            [(Col(f"{sales_prefix}_sold_date_sk"), "date_sk"),
             (Col(f"{sales_prefix}_item_sk"), "id"),
             (Col(f"{sales_prefix}_ext_sales_price"), "sales_price"),
             (Literal(0.0, DataType.float64()), "return_amt")],
        )
        rets = ProjectExec(
            s[ret_table](),
            [(Col(f"{ret_prefix}_returned_date_sk"), "date_sk"),
             (Col(f"{ret_prefix}_item_sk"), "id"),
             (Literal(0.0, DataType.float64()), "sales_price"),
             (Col(ret_amt_col), "return_amt")],
        )
        both = _union([sales, rets])
        dated = _join(
            flavor,
            FilterExec(s["date_dim"](), Col("d_year") == 1998),
            both,
            ["d_date_sk"], ["date_sk"],
        )
        return ProjectExec(
            dated,
            [(Literal(channel_name, DataType.utf8()), "channel"),
             (Col("id"), "id"),
             (Col("sales_price"), "sales_price"),
             (Col("return_amt"), "return_amt")],
        )

    all_ch = _union([
        channel("ss", "store_sales", "sr", "store_returns",
                "sr_return_amt", "store channel", "store"),
        channel("cs", "catalog_sales", "cr", "catalog_returns",
                "cr_return_amount", "catalog channel", "catalog"),
        channel("ws", "web_sales", "wr", "web_returns",
                "wr_return_amt", "web channel", "web"),
    ])
    detail = _agg(
        all_ch,
        keys=[(Col("channel"), "channel"), (Col("id"), "id")],
        aggs=[(AggExpr(AggFn.SUM, Col("sales_price")), "sales"),
              (AggExpr(AggFn.SUM, Col("return_amt")), "returns_")],
    )
    by_channel = ProjectExec(
        _agg(
            detail,
            keys=[(Col("channel"), "channel")],
            aggs=[(AggExpr(AggFn.SUM, Col("sales")), "sales"),
                  (AggExpr(AggFn.SUM, Col("returns_")), "returns_")],
        ),
        [(Col("channel"), "channel"),
         (Literal(None, DataType.int32()), "id"),
         (Col("sales"), "sales"), (Col("returns_"), "returns_")],
    )
    grand = ProjectExec(
        _agg(
            detail,
            keys=[],
            aggs=[(AggExpr(AggFn.SUM, Col("sales")), "sales"),
                  (AggExpr(AggFn.SUM, Col("returns_")), "returns_")],
        ),
        [(Literal(None, DataType.utf8()), "channel"),
         (Literal(None, DataType.int32()), "id"),
         (Col("sales"), "sales"), (Col("returns_"), "returns_")],
    )
    detail_out = _project_names(
        detail, ["channel", "id", "sales", "returns_"]
    )
    return UnionExec([detail_out, by_channel, grand])


def q6(s, flavor):
    """TPC-DS q6: state of customers buying items priced >1.2x their
    category average in one month. Scalar subqueries decorrelated into a
    month_seq semi-join and a per-category AVG join."""
    month = ProjectExec(
        FilterExec(
            s["date_dim"](),
            (Col("d_year") == 1999) & (Col("d_moy") == 1),
        ),
        [(Col("d_month_seq"), "target_seq")],
    )
    target_dates = _semi(
        flavor,
        s["date_dim"](),
        _agg(month, keys=[(Col("target_seq"), "target_seq")], aggs=[]),
        ["d_month_seq"], ["target_seq"],
    )
    cat_avg = ProjectExec(
        _agg(
            FilterExec(s["item"](), IsNotNull(Col("i_category"))),
            keys=[(Col("i_category"), "avg_cat")],
            aggs=[(AggExpr(AggFn.AVG, Col("i_current_price")),
                   "cat_avg_price")],
        ),
        [(Col("avg_cat"), "avg_cat"),
         (Col("cat_avg_price") * 1.2, "price_threshold")],
    )
    pricey = FilterExec(
        _join(flavor, cat_avg, s["item"](), ["avg_cat"], ["i_category"]),
        Col("i_current_price") > Col("price_threshold"),
    )
    sales = _join(
        flavor, target_dates, s["store_sales"](),
        ["d_date_sk"], ["ss_sold_date_sk"],
    )
    sales = _join(flavor, pricey, sales, ["i_item_sk"], ["ss_item_sk"])
    sales = _join(
        flavor, s["customer"](), sales,
        ["c_customer_sk"], ["ss_customer_sk"],
    )
    sales = _join(
        flavor, s["customer_address"](), sales,
        ["ca_address_sk"], ["c_current_addr_sk"],
    )
    agg = FilterExec(
        _agg(
            sales,
            keys=[(Col("ca_state"), "state")],
            aggs=[(AggExpr(AggFn.COUNT_STAR, None), "cnt")],
        ),
        Col("cnt") >= 10,
    )
    return _sorted_limit(
        agg, [SortKey(Col("cnt"), True, True),
              SortKey(Col("state"), True, True)], 100,
    )


def q7(s, flavor):
    """TPC-DS q7: average item stats for one demographic slice with
    email/event promotions."""
    demo = FilterExec(
        s["customer_demographics"](),
        (Col("cd_gender") == "M")
        & (Col("cd_marital_status") == "S")
        & (Col("cd_education_status") == "College"),
    )
    promos = FilterExec(
        s["promotion"](),
        (Col("p_channel_email") == "N") | (Col("p_channel_event") == "N"),
    )
    j = _join(
        flavor,
        FilterExec(s["date_dim"](), Col("d_year") == 2000),
        s["store_sales"](),
        ["d_date_sk"], ["ss_sold_date_sk"],
    )
    j = _join(flavor, demo, j, ["cd_demo_sk"], ["ss_cdemo_sk"])
    j = _join(flavor, promos, j, ["p_promo_sk"], ["ss_promo_sk"])
    j = _join(flavor, s["item"](), j, ["i_item_sk"], ["ss_item_sk"])
    agg = _agg(
        j,
        keys=[(Col("i_item_id"), "i_item_id")],
        aggs=[
            (AggExpr(AggFn.AVG, Col("ss_quantity")), "agg1"),
            (AggExpr(AggFn.AVG, Col("ss_list_price")), "agg2"),
            (AggExpr(AggFn.AVG, Col("ss_coupon_amt")), "agg3"),
            (AggExpr(AggFn.AVG, Col("ss_sales_price")), "agg4"),
        ],
    )
    return _sorted_limit(
        agg, [SortKey(Col("i_item_id"), True, True)], 100
    )


def q8(s, flavor):
    """TPC-DS q8: store sales for stores whose zip-2 prefix appears in
    (literal zip list INTERSECT zips of >10 preferred customers)."""
    zip_list = [f"{(24000 + i * 131) % 90000:05d}" for i in range(0, 400)]
    a_side = ProjectExec(
        FilterExec(
            s["customer_address"](),
            InList(
                ScalarFn(
                    "substring",
                    (Col("ca_zip"), Literal(1, DataType.int32()),
                     Literal(5, DataType.int32())),
                ),
                tuple(
                    Literal(z, DataType.utf8()) for z in zip_list[:200]
                ),
            ),
        ),
        [(ScalarFn(
            "substring",
            (Col("ca_zip"), Literal(1, DataType.int32()),
             Literal(5, DataType.int32())),
        ), "zip5")],
    )
    preferred = FilterExec(
        s["customer"](), Col("c_preferred_cust_flag") == "Y"
    )
    pref_zips = FilterExec(
        _agg(
            _join(
                flavor, s["customer_address"](), preferred,
                ["ca_address_sk"], ["c_current_addr_sk"],
            ),
            keys=[(ScalarFn(
                "substring",
                (Col("ca_zip"), Literal(1, DataType.int32()),
                 Literal(5, DataType.int32())),
            ), "zip5")],
            aggs=[(AggExpr(AggFn.COUNT_STAR, None), "cnt")],
        ),
        Col("cnt") > 10,
    )
    both = _semi(flavor, a_side, pref_zips, ["zip5"], ["zip5"])
    zip2 = _agg(
        ProjectExec(
            both,
            [(ScalarFn(
                "substring",
                (Col("zip5"), Literal(1, DataType.int32()),
                 Literal(2, DataType.int32())),
            ), "zip2")],
        ),
        keys=[(Col("zip2"), "zip2")],
        aggs=[],
    )
    stores = ProjectExec(
        s["store"](),
        [(Col("s_store_sk"), "s_store_sk"),
         (Col("s_store_name"), "s_store_name"),
         (ScalarFn(
             "substring",
             (Col("s_zip"), Literal(1, DataType.int32()),
              Literal(2, DataType.int32())),
         ), "s_zip2")],
    )
    qual_stores = _semi(flavor, stores, zip2, ["s_zip2"], ["zip2"])
    sales = _join(
        flavor,
        FilterExec(
            s["date_dim"](),
            (Col("d_year") == 1998) & (Col("d_moy") == 2),
        ),
        s["store_sales"](),
        ["d_date_sk"], ["ss_sold_date_sk"],
    )
    j = _join(flavor, qual_stores, sales, ["s_store_sk"], ["ss_store_sk"])
    agg = _agg(
        j,
        keys=[(Col("s_store_name"), "s_store_name")],
        aggs=[(AggExpr(AggFn.SUM, Col("ss_net_profit")), "net_profit")],
    )
    return _sorted_limit(
        agg, [SortKey(Col("s_store_name"), True, True)], 100
    )


def q9(s, flavor):
    """TPC-DS q9: five quantity-range buckets choosing count-vs-avg
    expressions; the 15 scalar subqueries become one conditional global
    aggregate, cross-joined with the filtered reason row."""
    buckets = [(1, 20), (21, 40), (41, 60), (61, 80), (81, 100)]
    aggs = []
    for i, (lo, hi) in enumerate(buckets, 1):
        in_range = (Col("ss_quantity") >= lo) & (Col("ss_quantity") <= hi)
        null_f = Literal(None, DataType.float64())
        aggs += [
            (AggExpr(
                AggFn.SUM,
                If(in_range, Literal(1, DataType.int64()),
                   Literal(None, DataType.int64())),
            ), f"cnt_{i}"),
            (AggExpr(
                AggFn.AVG,
                If(in_range, Col("ss_ext_discount_amt"), null_f),
            ), f"avg_disc_{i}"),
            (AggExpr(
                AggFn.AVG,
                If(in_range, Col("ss_net_profit"), null_f),
            ), f"avg_profit_{i}"),
        ]
    stats = ProjectExec(
        _agg(s["store_sales"](), keys=[], aggs=aggs),
        [(Literal(1, DataType.int32()), "k")]
        + [(Col(n), n) for _, n in aggs],
    )
    r = ProjectExec(
        FilterExec(s["reason"](), Col("r_reason_sk") == 1),
        [(Literal(1, DataType.int32()), "k")],
    )
    crossed = _join(flavor, r, stats, ["k"], ["k"])
    outs = []
    for i in range(1, 6):
        outs.append(
            (
                If(
                    Coalesce_int(Col(f"cnt_{i}")) > 7438,
                    Col(f"avg_disc_{i}"),
                    Col(f"avg_profit_{i}"),
                ),
                f"bucket{i}",
            )
        )
    return ProjectExec(crossed, outs)


def Coalesce_int(e):
    from blaze_tpu.exprs import Coalesce

    return Coalesce((e, Literal(0, DataType.int64())))


def q10(s, flavor):
    """TPC-DS q10: demographics of customers active in store AND
    (web OR catalog) channels in a quarter; EXISTS via semi joins, the
    OR-of-EXISTS via a unioned semi-join (Spark's rewrite)."""
    def active(prefix, table, cust):
        j = _join(
            flavor,
            FilterExec(
                s["date_dim"](),
                (Col("d_year") == 2000)
                & (Col("d_moy") >= 1) & (Col("d_moy") <= 4),
            ),
            s[table](),
            ["d_date_sk"], [f"{prefix}_sold_date_sk"],
        )
        return ProjectExec(j, [(Col(cust), "active_sk")])

    store_active = active("ss", "store_sales", "ss_customer_sk")
    other_active = _union([
        active("ws", "web_sales", "ws_bill_customer_sk"),
        active("cs", "catalog_sales", "cs_bill_customer_sk"),
    ])
    cust = _semi(
        flavor,
        _semi(
            flavor,
            s["customer"](),
            _agg(store_active,
                 keys=[(Col("active_sk"), "active_sk")], aggs=[]),
            ["c_customer_sk"], ["active_sk"],
        ),
        _agg(other_active,
             keys=[(Col("active_sk"), "active_sk")], aggs=[]),
        ["c_customer_sk"], ["active_sk"],
    )
    in_counties = _join(
        flavor,
        FilterExec(
            s["customer_address"](),
            InList(Col("ca_county"),
                   (Literal("Rich County", DataType.utf8()),
                    Literal("Walker County", DataType.utf8()))),
        ),
        cust,
        ["ca_address_sk"], ["c_current_addr_sk"],
    )
    j = _join(
        flavor, s["customer_demographics"](), in_counties,
        ["cd_demo_sk"], ["c_current_cdemo_sk"],
    )
    agg = _agg(
        j,
        keys=[(Col("cd_gender"), "cd_gender"),
              (Col("cd_marital_status"), "cd_marital_status"),
              (Col("cd_education_status"), "cd_education_status"),
              (Col("cd_purchase_estimate"), "cd_purchase_estimate"),
              (Col("cd_credit_rating"), "cd_credit_rating")],
        aggs=[(AggExpr(AggFn.COUNT_STAR, None), "cnt")],
    )
    return _sorted_limit(
        agg,
        [SortKey(Col("cd_gender"), True, True),
         SortKey(Col("cd_marital_status"), True, True),
         SortKey(Col("cd_education_status"), True, True),
         SortKey(Col("cd_purchase_estimate"), True, True),
         SortKey(Col("cd_credit_rating"), True, True)],
        100,
    )


QUERIES = {
    "q1": q1, "q2": q2, "q3": q3, "q4": q4, "q5": q5,
    "q6": q6, "q7": q7, "q8": q8, "q9": q9, "q10": q10,
}


# ---------------------------------------------------------------------------
# q11-q20 (q14's cross-channel INTERSECT CTE is deferred)
# ---------------------------------------------------------------------------

def q11(s, flavor):
    """TPC-DS q11: customers whose web-channel growth outpaces store
    growth (2-year year_total self-join, web+store channels)."""
    def yt(prefix, table, cust_col, year, names):
        base = _year_total(s, flavor, prefix, table, cust_col)
        return RenameColumnsExec(
            FilterExec(base, Col("dyear") == year), names
        )

    ts1 = yt("ss", "store_sales", "ss_customer_sk", 1998,
             ["s1_sk", "s1_id", "s1_year", "s1_total"])
    ts2 = yt("ss", "store_sales", "ss_customer_sk", 1999,
             ["s2_sk", "s2_id", "s2_year", "s2_total"])
    tw1 = yt("ws", "web_sales", "ws_bill_customer_sk", 1998,
             ["w1_sk", "w1_id", "w1_year", "w1_total"])
    tw2 = yt("ws", "web_sales", "ws_bill_customer_sk", 1999,
             ["w2_sk", "w2_id", "w2_year", "w2_total"])
    j = _join(flavor, ts1, ts2, ["s1_sk"], ["s2_sk"])
    j = _join(flavor, tw1, j, ["w1_sk"], ["s1_sk"])
    j = _join(flavor, tw2, j, ["w2_sk"], ["w1_sk"])
    cond = FilterExec(
        FilterExec(j, (Col("s1_total") > 0) & (Col("w1_total") > 0)),
        Col("w2_total") / Col("w1_total")
        > Col("s2_total") / Col("s1_total"),
    )
    return _sorted_limit(
        _project_names(cond, ["s1_id"]),
        [SortKey(Col("s1_id"), True, True)],
        100,
    )


def _channel_class_ratio(s, flavor, prefix, table):
    """q12/q20 shape: revenue by item with its share of the CLASS
    revenue via a window sum."""
    from blaze_tpu.ops.window import WindowExec, WindowFn

    j = _join(
        flavor,
        FilterExec(
            s["date_dim"](),
            (Col("d_year") == 1999) & (Col("d_moy") <= 2),
        ),
        s[table](),
        ["d_date_sk"], [f"{prefix}_sold_date_sk"],
    )
    j = _join(
        flavor,
        FilterExec(
            s["item"](),
            InList(Col("i_category"),
                   (Literal("Books", DataType.utf8()),
                    Literal("Home", DataType.utf8()),
                    Literal("Sports", DataType.utf8()))),
        ),
        j,
        ["i_item_sk"], [f"{prefix}_item_sk"],
    )
    rev = _agg(
        j,
        keys=[(Col("i_item_id"), "i_item_id"),
              (Col("i_item_desc"), "i_item_desc"),
              (Col("i_category"), "i_category"),
              (Col("i_current_price"), "i_current_price")],
        aggs=[(AggExpr(AggFn.SUM, Col(f"{prefix}_ext_sales_price")),
               "itemrevenue")],
    )
    w = WindowExec(
        rev,
        partition_by=[Col("i_category")],
        order_by=[],
        functions=[WindowFn("sum", Col("itemrevenue"), "classrev")],
    )
    ratio = ProjectExec(
        w,
        [(Col("i_item_id"), "i_item_id"),
         (Col("i_category"), "i_category"),
         (Col("itemrevenue"), "itemrevenue"),
         (Col("itemrevenue") * 100.0 / Col("classrev"), "revenueratio")],
    )
    return _sorted_limit(
        ratio,
        [SortKey(Col("i_category"), True, True),
         SortKey(Col("i_item_id"), True, True)],
        100,
    )


def q12(s, flavor):
    """TPC-DS q12: web revenue share of class (window ratio)."""
    return _channel_class_ratio(s, flavor, "ws", "web_sales")


def q20(s, flavor):
    """TPC-DS q20: catalog revenue share of class (window ratio)."""
    return _channel_class_ratio(s, flavor, "cs", "catalog_sales")


def q13(s, flavor):
    """TPC-DS q13: OR'd demographic/price bands over store sales."""
    demo = FilterExec(
        s["customer_demographics"](),
        (
            (Col("cd_marital_status") == "M")
            & (Col("cd_education_status") == "College")
        )
        | (
            (Col("cd_marital_status") == "S")
            & (Col("cd_education_status") == "Primary")
        ),
    )
    j = _join(
        flavor,
        FilterExec(s["date_dim"](), Col("d_year") == 2000),
        s["store_sales"](),
        ["d_date_sk"], ["ss_sold_date_sk"],
    )
    j = _join(flavor, demo, j, ["cd_demo_sk"], ["ss_cdemo_sk"])
    j = _join(flavor, s["store"](), j, ["s_store_sk"], ["ss_store_sk"])
    j = FilterExec(
        j,
        ((Col("ss_sales_price") >= 50.0)
         & (Col("ss_sales_price") <= 150.0))
        | ((Col("ss_sales_price") >= 10.0)
           & (Col("ss_sales_price") <= 60.0)),
    )
    return _agg(
        j,
        keys=[],
        aggs=[(AggExpr(AggFn.AVG, Col("ss_quantity")), "avg_qty"),
              (AggExpr(AggFn.AVG, Col("ss_ext_sales_price")), "avg_esp"),
              (AggExpr(AggFn.AVG, Col("ss_ext_wholesale_cost")),
               "avg_wc"),
              (AggExpr(AggFn.SUM, Col("ss_ext_wholesale_cost")),
               "sum_wc")],
    )


def q15(s, flavor):
    """TPC-DS q15: catalog sales by customer zip for qualifying
    zips/states, one quarter."""
    zips = tuple(
        Literal(z, DataType.utf8())
        for z in ("85669", "86197", "88274", "83405", "86475")
    )
    cond = FilterExec(
        _join(
            flavor,
            s["customer_address"](),
            _join(
                flavor,
                s["customer"](),
                _join(
                    flavor,
                    FilterExec(
                        s["date_dim"](),
                        (Col("d_year") == 1999) & (Col("d_moy") >= 1)
                        & (Col("d_moy") <= 3),
                    ),
                    s["catalog_sales"](),
                    ["d_date_sk"], ["cs_sold_date_sk"],
                ),
                ["c_customer_sk"], ["cs_bill_customer_sk"],
            ),
            ["ca_address_sk"], ["c_current_addr_sk"],
        ),
        InList(
            ScalarFn("substring",
                     (Col("ca_zip"), Literal(1, DataType.int32()),
                      Literal(5, DataType.int32()))),
            zips,
        )
        | InList(Col("ca_state"),
                 (Literal("CA", DataType.utf8()),
                  Literal("GA", DataType.utf8())))
        | (Col("cs_ext_sales_price") > 500.0),
    )
    agg = _agg(
        cond,
        keys=[(Col("ca_zip"), "ca_zip")],
        aggs=[(AggExpr(AggFn.SUM, Col("cs_ext_sales_price")), "s")],
    )
    return _sorted_limit(
        agg, [SortKey(Col("ca_zip"), True, True)], 100
    )


def q16(s, flavor):
    """TPC-DS q16 shape: catalog orders in a window shipped to chosen
    counties, with returned orders EXCLUDED (anti join); COUNT(DISTINCT
    order) via the Spark rewrite (distinct group-by then count)."""
    sales = _join(
        flavor,
        FilterExec(
            s["date_dim"](),
            (Col("d_year") == 1999) & (Col("d_moy") >= 2)
            & (Col("d_moy") <= 4),
        ),
        s["catalog_sales"](),
        ["d_date_sk"], ["cs_sold_date_sk"],
    )
    not_returned = SortMergeJoinExec(
        sales, s["catalog_returns"](),
        ["cs_item_sk"], ["cr_item_sk"], JoinType.LEFT_ANTI,
    ) if flavor == "smj" else HashJoinExec(
        sales, s["catalog_returns"](),
        ["cs_item_sk"], ["cr_item_sk"], JoinType.LEFT_ANTI,
    )
    distinct_orders = _agg(
        not_returned,
        keys=[(Col("cs_item_sk"), "order_sk")],
        aggs=[(AggExpr(AggFn.SUM, Col("cs_ext_sales_price")), "net")],
    )
    return _agg(
        distinct_orders,
        keys=[],
        aggs=[(AggExpr(AggFn.COUNT_STAR, None), "order_count"),
              (AggExpr(AggFn.SUM, Col("net")), "total_net")],
    )


def q17(s, flavor):
    """TPC-DS q17 shape: quantity statistics for items sold and then
    returned (store sales joined to store returns), by item."""
    j = _join(
        flavor,
        FilterExec(s["date_dim"](), Col("d_year") == 1998),
        s["store_sales"](),
        ["d_date_sk"], ["ss_sold_date_sk"],
    )
    j = _join(
        flavor, s["store_returns"](), j,
        ["sr_item_sk"], ["ss_item_sk"],
    )
    j = _join(flavor, s["item"](), j, ["i_item_sk"], ["ss_item_sk"])
    agg = _agg(
        j,
        keys=[(Col("i_item_id"), "i_item_id")],
        aggs=[
            (AggExpr(AggFn.COUNT, Col("ss_quantity")), "qty_count"),
            (AggExpr(AggFn.AVG, Col("ss_quantity")), "qty_avg"),
            (AggExpr(AggFn.STDDEV_SAMP, Col("ss_quantity")),
             "qty_stdev"),
        ],
    )
    return _sorted_limit(
        agg, [SortKey(Col("i_item_id"), True, True)], 100
    )


def q18(s, flavor):
    """TPC-DS q18 (rollup as explicit grouping-set union): catalog
    averages by (item, state) plus state and grand totals."""
    j = _join(
        flavor,
        FilterExec(s["date_dim"](), Col("d_year") == 1998),
        s["catalog_sales"](),
        ["d_date_sk"], ["cs_sold_date_sk"],
    )
    j = _join(
        flavor, s["customer"](), j,
        ["c_customer_sk"], ["cs_bill_customer_sk"],
    )
    j = _join(
        flavor, s["customer_address"](), j,
        ["ca_address_sk"], ["c_current_addr_sk"],
    )
    j = _join(flavor, s["item"](), j, ["i_item_sk"], ["cs_item_sk"])
    detail = _agg(
        j,
        keys=[(Col("i_item_id"), "i_item_id"),
              (Col("ca_state"), "ca_state")],
        aggs=[(AggExpr(AggFn.AVG, Col("cs_ext_sales_price")), "a")],
    )
    # rollup levels re-aggregate from the base join (AVG isn't
    # mergeable from averaged details)
    by_state = ProjectExec(
        _agg(
            j,
            keys=[(Col("ca_state"), "ca_state")],
            aggs=[(AggExpr(AggFn.AVG, Col("cs_ext_sales_price")), "a")],
        ),
        [(Literal(None, DataType.utf8()), "i_item_id"),
         (Col("ca_state"), "ca_state"), (Col("a"), "a")],
    )
    grand = ProjectExec(
        _agg(
            j, keys=[],
            aggs=[(AggExpr(AggFn.AVG, Col("cs_ext_sales_price")), "a")],
        ),
        [(Literal(None, DataType.utf8()), "i_item_id"),
         (Literal(None, DataType.utf8()), "ca_state"), (Col("a"), "a")],
    )
    detail_out = _project_names(detail, ["i_item_id", "ca_state", "a"])
    return _union([detail_out, by_state, grand])


def q19(s, flavor):
    """TPC-DS q19 shape: brand revenue for one month/manager band where
    the customer and store sit in different zip prefixes."""
    j = _join(
        flavor,
        FilterExec(
            s["date_dim"](),
            (Col("d_year") == 1999) & (Col("d_moy") == 11),
        ),
        s["store_sales"](),
        ["d_date_sk"], ["ss_sold_date_sk"],
    )
    j = _join(
        flavor,
        FilterExec(s["item"](), Col("i_manager_id") <= 20),
        j,
        ["i_item_sk"], ["ss_item_sk"],
    )
    j = _join(
        flavor, s["customer"](), j,
        ["c_customer_sk"], ["ss_customer_sk"],
    )
    j = _join(
        flavor, s["customer_address"](), j,
        ["ca_address_sk"], ["c_current_addr_sk"],
    )
    j = _join(flavor, s["store"](), j, ["s_store_sk"], ["ss_store_sk"])
    j = FilterExec(
        j,
        ScalarFn("substring",
                 (Col("ca_zip"), Literal(1, DataType.int32()),
                  Literal(5, DataType.int32())))
        != ScalarFn("substring",
                    (Col("s_zip"), Literal(1, DataType.int32()),
                     Literal(5, DataType.int32()))),
    )
    agg = _agg(
        j,
        keys=[(Col("i_brand_id"), "brand_id"),
              (Col("i_brand"), "brand")],
        aggs=[(AggExpr(AggFn.SUM, Col("ss_ext_sales_price")),
               "ext_price")],
    )
    return _sorted_limit(
        agg,
        [SortKey(Col("ext_price"), False, False),
         SortKey(Col("brand_id"), True, True)],
        100,
    )


QUERIES.update({
    "q11": q11, "q12": q12, "q13": q13, "q15": q15, "q16": q16,
    "q17": q17, "q18": q18, "q19": q19, "q20": q20,
})


def q14(s, flavor):
    """TPC-DS q14a shape: cross_items = (brand_id, manufact_id) key
    pairs sold in ALL three channels (semi-join intersect chain - the
    real query intersects (brand,class,category); the generated item
    table has no class column, so the 2-key pair exercises the same
    intersect machinery); avg_sales
    scalar over the three channels; per-channel item sales over
    cross_items filtered above the scalar, with a channel-level rollup
    (grouping-set union, as in q5/q18)."""
    def channel_triples(prefix, table):
        j = _join(
            flavor, s["item"](), s[table](),
            ["i_item_sk"], [f"{prefix}_item_sk"],
        )
        return _agg(
            j,
            keys=[(Col("i_brand_id"), "brand_id"),
                  (Col("i_manufact_id"), "manu_id")],
            aggs=[],
        )

    cross_triples = _semi(
        flavor,
        _semi(
            flavor,
            channel_triples("ss", "store_sales"),
            channel_triples("cs", "catalog_sales"),
            ["brand_id", "manu_id"], ["brand_id", "manu_id"],
        ),
        channel_triples("ws", "web_sales"),
        ["brand_id", "manu_id"], ["brand_id", "manu_id"],
    )
    cross_items = _project_names(
        _semi(
            flavor, s["item"](), cross_triples,
            ["i_brand_id", "i_manufact_id"], ["brand_id", "manu_id"],
        ),
        ["i_item_sk", "i_brand_id", "i_manufact_id"],
    )

    def channel_rev(prefix, table, price_col):
        j = _join(
            flavor,
            FilterExec(s["date_dim"](), Col("d_year") == 1999),
            s[table](),
            ["d_date_sk"], [f"{prefix}_sold_date_sk"],
        )
        return ProjectExec(
            j,
            [(Col(f"{prefix}_item_sk"), "item_sk"),
             (Col(price_col), "sales")],
        )

    all_sales = _union([
        channel_rev("ss", "store_sales", "ss_ext_sales_price"),
        channel_rev("cs", "catalog_sales", "cs_ext_sales_price"),
        channel_rev("ws", "web_sales", "ws_ext_sales_price"),
    ])
    avg_sales = ProjectExec(
        _agg(
            all_sales, keys=[],
            aggs=[(AggExpr(AggFn.AVG, Col("sales")), "avg_sales")],
        ),
        [(Literal(1, DataType.int32()), "k"),
         (Col("avg_sales"), "avg_sales")],
    )
    in_cross = _semi(
        flavor, all_sales, cross_items, ["item_sk"], ["i_item_sk"]
    )
    by_item = _agg(
        _join(flavor, s["item"](), in_cross,
              ["i_item_sk"], ["item_sk"]),
        keys=[(Col("i_brand_id"), "brand_id")],
        aggs=[(AggExpr(AggFn.SUM, Col("sales")), "sales"),
              (AggExpr(AggFn.COUNT_STAR, None), "number_sales")],
    )
    keyed = ProjectExec(
        by_item,
        [(Col("brand_id"), "brand_id"), (Col("sales"), "sales"),
         (Col("number_sales"), "number_sales"),
         (Literal(1, DataType.int32()), "k")],
    )
    over_avg = FilterExec(
        _join(flavor, avg_sales, keyed, ["k"], ["k"]),
        Col("sales") > Col("avg_sales"),
    )
    detail = _project_names(
        over_avg, ["brand_id", "sales", "number_sales"]
    )
    total = ProjectExec(
        _agg(
            detail, keys=[],
            aggs=[(AggExpr(AggFn.SUM, Col("sales")), "sales"),
                  (AggExpr(AggFn.SUM, Col("number_sales")),
                   "number_sales")],
        ),
        [(Literal(None, DataType.int32()), "brand_id"),
         (Col("sales"), "sales"),
         (Col("number_sales"), "number_sales")],
    )
    return _union([detail, total])


QUERIES["q14"] = q14


# ---------------------------------------------------------------------------
# q21-q27 block (inventory/warehouse tier; q23/q24's multi-CTE monsters
# are deferred like q14's full 3-key variant)
# ---------------------------------------------------------------------------

N_WAREHOUSES = 6


def gen_inventory_tables(seed: int = 20260730):
    """inventory + warehouse, deterministic; appended to gen_tables()."""
    rng = np.random.default_rng(seed)
    n_inv = max(N_SALES // 5, 2000)
    warehouse = pd.DataFrame(
        {
            "w_warehouse_sk": np.arange(N_WAREHOUSES, dtype=np.int32),
            "w_warehouse_name": [
                f"warehouse_{i}" for i in range(N_WAREHOUSES)
            ],
            "w_state": pick_from(
                ["TN", "GA", "CA"], N_WAREHOUSES, rng
            ),
        }
    )
    inventory = pd.DataFrame(
        {
            "inv_date_sk": rng.integers(0, N_DATES, n_inv).astype(
                np.int32),
            "inv_item_sk": rng.integers(0, N_ITEMS, n_inv).astype(
                np.int32),
            "inv_warehouse_sk": rng.integers(
                0, N_WAREHOUSES, n_inv).astype(np.int32),
            "inv_quantity_on_hand": rng.integers(
                0, 1000, n_inv).astype(np.int32),
        }
    )
    return {"warehouse": warehouse, "inventory": inventory}


def pick_from(values, size, rng):
    idx = rng.integers(0, len(values), size)
    return np.array([values[i] for i in idx], dtype=object)


_BASE_GEN_TABLES = gen_tables


def gen_tables(seed: int = 20260729):  # noqa: F811 - extend the base set
    t = _BASE_GEN_TABLES(seed)
    t.update(gen_inventory_tables(seed + 2))
    # q26 columns the base catalog_sales generator omits
    cs = t["catalog_sales"]
    rng = np.random.default_rng(seed + 1)
    n_cs = len(cs)
    cs["cs_cdemo_sk"] = rng.integers(0, N_CDEMO, n_cs).astype(np.int32)
    cs["cs_promo_sk"] = rng.integers(0, N_PROMOS, n_cs).astype(np.int32)
    cs["cs_quantity"] = rng.integers(1, 101, n_cs).astype(np.int32)
    cs["cs_list_price"] = np.round(rng.random(n_cs) * 250, 2)
    cs["cs_coupon_amt"] = np.round(rng.random(n_cs) * 50, 2)
    cs["cs_sales_price"] = np.round(rng.random(n_cs) * 200, 2)
    # q30 columns the base web_returns generator omits
    wr = t["web_returns"]
    n_wr = len(wr)
    wr["wr_returning_customer_sk"] = pd.array(
        np.where(
            rng.random(n_wr) < 0.02, np.nan,
            rng.integers(0, N_CUSTOMERS, n_wr).astype(np.float64),
        ),
        dtype=pd.Int32Dtype(),
    )
    # q34/q36 columns: tickets, household demographics, item class
    ss_t = t["store_sales"]
    n_ss = len(ss_t)
    # a ticket belongs to ONE customer (real baskets): ticket id =
    # customer * B + basket slot, with B scaled so the mean basket size
    # stays a few rows at any generator scale (keeps q34's count-band
    # filter non-vacuous)
    baskets_per_cust = max(1, n_ss // (N_CUSTOMERS * 5))
    cust_for_ticket = (
        t["store_sales"]["ss_customer_sk"].fillna(0).to_numpy(
            dtype=np.int64)
    )
    ss_t["ss_ticket_number"] = (
        cust_for_ticket * baskets_per_cust
        + rng.integers(0, baskets_per_cust, n_ss)
    ).astype(np.int64)
    ss_t["ss_hdemo_sk"] = rng.integers(0, N_HDEMO, n_ss).astype(
        np.int32)
    it = t["item"]
    it["i_class"] = np.array(
        [f"class_{x}" for x in rng.integers(0, 8, len(it))],
        dtype=object,
    )
    t["household_demographics"] = pd.DataFrame(
        {
            "hd_demo_sk": np.arange(N_HDEMO, dtype=np.int32),
            "hd_buy_potential": np.array(
                [">10000", "5001-10000", "1001-5000", "0-500"],
                dtype=object,
            )[np.arange(N_HDEMO) % 4],
            "hd_dep_count": (np.arange(N_HDEMO) % 7).astype(np.int32),
            "hd_vehicle_count": (np.arange(N_HDEMO) % 5).astype(
                np.int32),
        }
    )
    # q40: order numbers linking catalog returns to their sale rows
    cs["cs_order_number"] = np.arange(len(cs), dtype=np.int64)
    cr = t["catalog_returns"]
    order_idx = rng.integers(0, len(cs), len(cr))
    cr["cr_order_number"] = order_idx.astype(np.int64)
    cr["cr_item_sk"] = cs["cs_item_sk"].values[order_idx]
    return t


def q21(s, flavor):
    """TPC-DS q21: inventory before/after a pivot date by warehouse and
    item, keeping items whose after/before ratio is in [2/3, 3/2]."""
    pivot = 500  # date_sk pivot
    j = _join(
        flavor,
        FilterExec(
            s["date_dim"](),
            (Col("d_date_sk") >= pivot - 30)
            & (Col("d_date_sk") <= pivot + 30),
        ),
        s["inventory"](),
        ["d_date_sk"], ["inv_date_sk"],
    )
    j = _join(
        flavor, s["warehouse"](), j,
        ["w_warehouse_sk"], ["inv_warehouse_sk"],
    )
    j = _join(flavor, s["item"](), j, ["i_item_sk"], ["inv_item_sk"])
    agg = _agg(
        j,
        keys=[(Col("w_warehouse_name"), "w_warehouse_name"),
              (Col("i_item_id"), "i_item_id")],
        aggs=[
            (
                AggExpr(
                    AggFn.SUM,
                    If(Col("d_date_sk") < pivot,
                       Col("inv_quantity_on_hand"),
                       Literal(0, DataType.int64())),
                ),
                "inv_before",
            ),
            (
                AggExpr(
                    AggFn.SUM,
                    If(Col("d_date_sk") >= pivot,
                       Col("inv_quantity_on_hand"),
                       Literal(0, DataType.int64())),
                ),
                "inv_after",
            ),
        ],
    )
    cond = FilterExec(
        FilterExec(agg, Col("inv_before") > 0),
        (
            Col("inv_after").cast(DataType.float64())
            / Col("inv_before").cast(DataType.float64())
            >= 2.0 / 3.0
        )
        & (
            Col("inv_after").cast(DataType.float64())
            / Col("inv_before").cast(DataType.float64())
            <= 3.0 / 2.0
        ),
    )
    return _sorted_limit(
        cond,
        [SortKey(Col("w_warehouse_name"), True, True),
         SortKey(Col("i_item_id"), True, True)],
        100,
    )


def q22(s, flavor):
    """TPC-DS q22 (rollup as grouping-set union): average quantity on
    hand by (brand, manufact) with brand and grand totals."""
    j = _join(
        flavor,
        FilterExec(
            s["date_dim"](),
            (Col("d_month_seq") >= 1188) & (Col("d_month_seq") <= 1199),
        ),
        s["inventory"](),
        ["d_date_sk"], ["inv_date_sk"],
    )
    j = _join(flavor, s["item"](), j, ["i_item_sk"], ["inv_item_sk"])
    detail = _agg(
        j,
        keys=[(Col("i_brand"), "brand"),
              (Col("i_manufact_id"), "manufact_id")],
        aggs=[(AggExpr(AggFn.AVG, Col("inv_quantity_on_hand")), "qoh")],
    )
    by_brand = ProjectExec(
        _agg(
            j,
            keys=[(Col("i_brand"), "brand")],
            aggs=[(AggExpr(AggFn.AVG, Col("inv_quantity_on_hand")),
                   "qoh")],
        ),
        [(Col("brand"), "brand"),
         (Literal(None, DataType.int32()), "manufact_id"),
         (Col("qoh"), "qoh")],
    )
    grand = ProjectExec(
        _agg(
            j, keys=[],
            aggs=[(AggExpr(AggFn.AVG, Col("inv_quantity_on_hand")),
                   "qoh")],
        ),
        [(Literal(None, DataType.utf8()), "brand"),
         (Literal(None, DataType.int32()), "manufact_id"),
         (Col("qoh"), "qoh")],
    )
    detail_out = _project_names(detail, ["brand", "manufact_id", "qoh"])
    return _union([detail_out, by_brand, grand])


def q25(s, flavor):
    """TPC-DS q25 shape: customers who bought in store, returned, then
    bought the same item from the catalog - 3-way join on (customer,
    item), grouped by item."""
    ss = _join(
        flavor,
        FilterExec(s["date_dim"](), Col("d_year") == 1998),
        s["store_sales"](),
        ["d_date_sk"], ["ss_sold_date_sk"],
    )
    sr = s["store_returns"]()
    j = _join(
        flavor, sr, ss,
        ["sr_customer_sk", "sr_item_sk"],
        ["ss_customer_sk", "ss_item_sk"],
    )
    cs = s["catalog_sales"]()
    j = _join(
        flavor, cs, j,
        ["cs_bill_customer_sk", "cs_item_sk"],
        ["sr_customer_sk", "sr_item_sk"],
    )
    j = _join(flavor, s["item"](), j, ["i_item_sk"], ["ss_item_sk"])
    agg = _agg(
        j,
        keys=[(Col("i_item_id"), "i_item_id")],
        aggs=[
            (AggExpr(AggFn.SUM, Col("ss_net_profit")), "store_profit"),
            (AggExpr(AggFn.SUM, Col("sr_net_loss")), "return_loss"),
            (AggExpr(AggFn.SUM, Col("cs_ext_sales_price")),
             "catalog_sales"),
        ],
    )
    return _sorted_limit(
        agg, [SortKey(Col("i_item_id"), True, True)], 100
    )


def _demo_item_avgs(s, flavor, prefix, table, cdemo_col, promo_col):
    """q7/q26 shape for any channel."""
    demo = FilterExec(
        s["customer_demographics"](),
        (Col("cd_gender") == "F")
        & (Col("cd_marital_status") == "M")
        & (Col("cd_education_status") == "4 yr Degree"),
    )
    promos = FilterExec(
        s["promotion"](),
        (Col("p_channel_email") == "N") | (Col("p_channel_event") == "N"),
    )
    j = _join(
        flavor,
        FilterExec(s["date_dim"](), Col("d_year") == 2000),
        s[table](),
        ["d_date_sk"], [f"{prefix}_sold_date_sk"],
    )
    j = _join(flavor, demo, j, ["cd_demo_sk"], [cdemo_col])
    j = _join(flavor, promos, j, ["p_promo_sk"], [promo_col])
    j = _join(flavor, s["item"](), j, ["i_item_sk"],
              [f"{prefix}_item_sk"])
    return j


def q26(s, flavor):
    """TPC-DS q26: catalog-channel demographic item averages."""
    j = _demo_item_avgs(
        s, flavor, "cs", "catalog_sales", "cs_cdemo_sk", "cs_promo_sk"
    )
    agg = _agg(
        j,
        keys=[(Col("i_item_id"), "i_item_id")],
        aggs=[
            (AggExpr(AggFn.AVG, Col("cs_quantity")), "agg1"),
            (AggExpr(AggFn.AVG, Col("cs_list_price")), "agg2"),
            (AggExpr(AggFn.AVG, Col("cs_coupon_amt")), "agg3"),
            (AggExpr(AggFn.AVG, Col("cs_sales_price")), "agg4"),
        ],
    )
    return _sorted_limit(
        agg, [SortKey(Col("i_item_id"), True, True)], 100
    )


def q27(s, flavor):
    """TPC-DS q27 (rollup as grouping-set union): store-channel
    demographic item averages by (item, state) + state/grand totals."""
    demo = FilterExec(
        s["customer_demographics"](),
        (Col("cd_gender") == "M")
        & (Col("cd_marital_status") == "S")
        & (Col("cd_education_status") == "College"),
    )
    j = _join(
        flavor,
        FilterExec(s["date_dim"](), Col("d_year") == 2000),
        s["store_sales"](),
        ["d_date_sk"], ["ss_sold_date_sk"],
    )
    j = _join(flavor, demo, j, ["cd_demo_sk"], ["ss_cdemo_sk"])
    j = _join(flavor, s["store"](), j, ["s_store_sk"], ["ss_store_sk"])
    j = _join(flavor, s["item"](), j, ["i_item_sk"], ["ss_item_sk"])

    def level(key_exprs):
        return _agg(
            j,
            keys=key_exprs,
            aggs=[(AggExpr(AggFn.AVG, Col("ss_quantity")), "agg1"),
                  (AggExpr(AggFn.AVG, Col("ss_list_price")), "agg2")],
        )

    detail = _project_names(
        level([(Col("i_item_id"), "i_item_id"),
               (Col("s_state"), "s_state")]),
        ["i_item_id", "s_state", "agg1", "agg2"],
    )
    by_item = ProjectExec(
        level([(Col("i_item_id"), "i_item_id")]),
        [(Col("i_item_id"), "i_item_id"),
         (Literal(None, DataType.utf8()), "s_state"),
         (Col("agg1"), "agg1"), (Col("agg2"), "agg2")],
    )
    grand = ProjectExec(
        level([]),
        [(Literal(None, DataType.utf8()), "i_item_id"),
         (Literal(None, DataType.utf8()), "s_state"),
         (Col("agg1"), "agg1"), (Col("agg2"), "agg2")],
    )
    return _union([detail, by_item, grand])


QUERIES.update({
    "q21": q21, "q22": q22, "q25": q25, "q26": q26, "q27": q27,
})


# ---------------------------------------------------------------------------
# q28-q33 block (q31's county quarter matrix deferred)
# ---------------------------------------------------------------------------

def q28(s, flavor):
    """TPC-DS q28 shape: per price-bucket average / count / distinct
    count of list prices (COUNT DISTINCT via the distinct-group-by
    rewrite), unioned into one row set."""
    buckets = [(0, 50), (50, 100), (100, 150), (150, 200), (200, 250),
               (0, 250)]

    def bucket(i, lo, hi):
        f = FilterExec(
            s["store_sales"](),
            (Col("ss_list_price") >= float(lo))
            & (Col("ss_list_price") < float(hi)),
        )
        stats = ProjectExec(
            _agg(
                f, keys=[],
                aggs=[(AggExpr(AggFn.AVG, Col("ss_list_price")), "avg_p"),
                      (AggExpr(AggFn.COUNT_STAR, None), "cnt")],
            ),
            [(Literal(i, DataType.int32()), "bucket"),
             (Col("avg_p"), "avg_p"), (Col("cnt"), "cnt"),
             (Literal(1, DataType.int32()), "k")],
        )
        distinct = ProjectExec(
            _agg(
                _agg(
                    f,  # same filter node feeds both branches
                    keys=[(Col("ss_list_price"), "p")],
                    aggs=[],
                ),
                keys=[],
                aggs=[(AggExpr(AggFn.COUNT_STAR, None), "distinct_cnt")],
            ),
            [(Col("distinct_cnt"), "distinct_cnt"),
             (Literal(1, DataType.int32()), "k2")],
        )
        joined = _join(flavor, stats, distinct, ["k"], ["k2"])
        return _project_names(
            joined, ["bucket", "avg_p", "cnt", "distinct_cnt"]
        )

    return _union([bucket(i, lo, hi)
                   for i, (lo, hi) in enumerate(buckets)])


def q29(s, flavor):
    """TPC-DS q29 shape: quantity flows for store-sold, returned, then
    catalog-repurchased items (q25's join spine, quantity sums)."""
    ss = _join(
        flavor,
        FilterExec(s["date_dim"](), Col("d_year") == 1999),
        s["store_sales"](),
        ["d_date_sk"], ["ss_sold_date_sk"],
    )
    j = _join(
        flavor, s["store_returns"](), ss,
        ["sr_customer_sk", "sr_item_sk"],
        ["ss_customer_sk", "ss_item_sk"],
    )
    j = _join(
        flavor, s["catalog_sales"](), j,
        ["cs_bill_customer_sk", "cs_item_sk"],
        ["sr_customer_sk", "sr_item_sk"],
    )
    j = _join(flavor, s["item"](), j, ["i_item_sk"], ["ss_item_sk"])
    agg = _agg(
        j,
        keys=[(Col("i_item_id"), "i_item_id")],
        aggs=[
            (AggExpr(AggFn.SUM, Col("ss_quantity")), "store_qty"),
            (AggExpr(AggFn.COUNT_STAR, None), "paths"),
        ],
    )
    return _sorted_limit(
        agg, [SortKey(Col("i_item_id"), True, True)], 100
    )


def q30(s, flavor):
    """TPC-DS q30: web-return customers above 1.2x their state's
    average total return (q1's decorrelation over the web channel,
    grouped by customer state)."""
    wr = _join(
        flavor,
        FilterExec(s["date_dim"](), Col("d_year") == 1999),
        s["web_returns"](),
        ["d_date_sk"], ["wr_returned_date_sk"],
    )
    wr = _join(
        flavor, s["customer"](), wr,
        ["c_customer_sk"], ["wr_returning_customer_sk"],
    )
    wr = _join(
        flavor, s["customer_address"](), wr,
        ["ca_address_sk"], ["c_current_addr_sk"],
    )
    ctr = _agg(
        wr,
        keys=[(Col("c_customer_sk"), "ctr_customer_sk"),
              (Col("c_customer_id"), "ctr_customer_id"),
              (Col("ca_state"), "ctr_state")],
        aggs=[(AggExpr(AggFn.SUM, Col("wr_return_amt")),
               "ctr_total_return")],
    )
    avg_by_state = ProjectExec(
        _agg(
            ctr,
            keys=[(Col("ctr_state"), "avg_state")],
            aggs=[(AggExpr(AggFn.AVG, Col("ctr_total_return")),
                   "avg_r")],
        ),
        [(Col("avg_state"), "avg_state"),
         (Col("avg_r") * 1.2, "threshold")],
    )
    over = FilterExec(
        _join(flavor, avg_by_state, ctr, ["avg_state"], ["ctr_state"]),
        Col("ctr_total_return") > Col("threshold"),
    )
    return _sorted_limit(
        _project_names(over, ["ctr_customer_id", "ctr_total_return"]),
        [SortKey(Col("ctr_customer_id"), True, True)],
        100,
    )


def q32(s, flavor):
    """TPC-DS q32: catalog discounts exceeding 1.3x the item's average
    discount in a window (scalar subquery decorrelated per item)."""
    cs = _join(
        flavor,
        FilterExec(
            s["date_dim"](),
            (Col("d_year") == 1999) & (Col("d_moy") <= 3),
        ),
        s["catalog_sales"](),
        ["d_date_sk"], ["cs_sold_date_sk"],
    )
    thresholds = ProjectExec(
        _agg(
            cs,
            keys=[(Col("cs_item_sk"), "t_item_sk")],
            aggs=[(AggExpr(AggFn.AVG, Col("cs_ext_discount_amt")),
                   "avg_disc")],
        ),
        [(Col("t_item_sk"), "t_item_sk"),
         (Col("avg_disc") * 1.3, "threshold")],
    )
    over = FilterExec(
        _join(flavor, thresholds, cs, ["t_item_sk"], ["cs_item_sk"]),
        Col("cs_ext_discount_amt") > Col("threshold"),
    )
    return _agg(
        over,
        keys=[],
        aggs=[(AggExpr(AggFn.SUM, Col("cs_ext_discount_amt")),
               "excess_discount")],
    )


def q33(s, flavor):
    """TPC-DS q33: manufacturer revenue for one category/month summed
    over all three channels (per-channel aggregates unioned, re-summed
    by manufacturer)."""
    def channel(prefix, table):
        j = _join(
            flavor,
            FilterExec(
                s["date_dim"](),
                (Col("d_year") == 1999) & (Col("d_moy") == 3),
            ),
            s[table](),
            ["d_date_sk"], [f"{prefix}_sold_date_sk"],
        )
        j = _join(
            flavor,
            FilterExec(s["item"](), Col("i_category") == "Books"),
            j,
            ["i_item_sk"], [f"{prefix}_item_sk"],
        )
        return _agg(
            j,
            keys=[(Col("i_manufact_id"), "i_manufact_id")],
            aggs=[(AggExpr(AggFn.SUM, Col(f"{prefix}_ext_sales_price")),
                   "total_sales")],
        )

    all_ch = _union([
        channel("ss", "store_sales"),
        channel("cs", "catalog_sales"),
        channel("ws", "web_sales"),
    ])
    agg = _agg(
        all_ch,
        keys=[(Col("i_manufact_id"), "i_manufact_id")],
        aggs=[(AggExpr(AggFn.SUM, Col("total_sales")), "total_sales")],
    )
    return _sorted_limit(
        agg,
        [SortKey(Col("total_sales"), False, False),
         SortKey(Col("i_manufact_id"), True, True)],
        100,
    )


QUERIES.update({
    "q28": q28, "q29": q29, "q30": q30, "q32": q32, "q33": q33,
})


# ---------------------------------------------------------------------------
# q34-q40 block (q35/q39 deferred with the other variants)
# ---------------------------------------------------------------------------

def q34(s, flavor):
    """TPC-DS q34: customers with 3-8 items on one ticket under chosen
    buy-potential bands, with names."""
    hd = FilterExec(
        s["household_demographics"](),
        InList(Col("hd_buy_potential"),
               (Literal(">10000", DataType.utf8()),
                Literal("0-500", DataType.utf8()))),
    )
    j = _join(
        flavor,
        FilterExec(s["date_dim"](), Col("d_year") == 1999),
        s["store_sales"](),
        ["d_date_sk"], ["ss_sold_date_sk"],
    )
    j = _join(flavor, hd, j, ["hd_demo_sk"], ["ss_hdemo_sk"])
    tickets = FilterExec(
        _agg(
            j,
            keys=[(Col("ss_ticket_number"), "ticket"),
                  (Col("ss_customer_sk"), "cust_sk")],
            aggs=[(AggExpr(AggFn.COUNT_STAR, None), "cnt")],
        ),
        (Col("cnt") >= 3) & (Col("cnt") <= 8),
    )
    named = _join(
        flavor, s["customer"](), tickets,
        ["c_customer_sk"], ["cust_sk"],
    )
    return _sorted_limit(
        _project_names(
            named, ["c_last_name", "c_first_name", "ticket", "cnt"]
        ),
        [SortKey(Col("c_last_name"), True, True),
         SortKey(Col("c_first_name"), True, True),
         SortKey(Col("ticket"), True, True)],
        1000,
    )


def q36(s, flavor):
    """TPC-DS q36 (rollup as grouping-set union): gross margin ratio by
    (category, class) with category and grand totals."""
    j = _join(
        flavor,
        FilterExec(s["date_dim"](), Col("d_year") == 1999),
        s["store_sales"](),
        ["d_date_sk"], ["ss_sold_date_sk"],
    )
    j = _join(flavor, s["item"](), j, ["i_item_sk"], ["ss_item_sk"])

    def level(key_exprs):
        agg = _agg(
            j,
            keys=key_exprs,
            aggs=[(AggExpr(AggFn.SUM, Col("ss_net_profit")), "profit"),
                  (AggExpr(AggFn.SUM, Col("ss_ext_sales_price")),
                   "sales")],
        )
        outs = []
        names = ["i_category", "i_class"]
        have = [n for _, n in key_exprs]
        for n in names:
            if n in have:
                outs.append((Col(n), n))
            else:
                outs.append((Literal(None, DataType.utf8()), n))
        outs.append(
            (Col("profit") / Col("sales"), "gross_margin")
        )
        return ProjectExec(agg, outs)

    detail = level([(Col("i_category"), "i_category"),
                    (Col("i_class"), "i_class")])
    by_cat = level([(Col("i_category"), "i_category")])
    grand = level([])
    return _union([detail, by_cat, grand])


def q37(s, flavor):
    """TPC-DS q37: items with 100-500 on-hand inventory in a window
    that also sold on the catalog channel."""
    inv = FilterExec(
        _join(
            flavor,
            FilterExec(
                s["date_dim"](),
                (Col("d_date_sk") >= 400) & (Col("d_date_sk") <= 460),
            ),
            s["inventory"](),
            ["d_date_sk"], ["inv_date_sk"],
        ),
        (Col("inv_quantity_on_hand") >= 100)
        & (Col("inv_quantity_on_hand") <= 500),
    )
    items = _join(
        flavor,
        FilterExec(s["item"](), Col("i_current_price") >= 10.0),
        inv,
        ["i_item_sk"], ["inv_item_sk"],
    )
    sold = _semi(
        flavor, items, s["catalog_sales"](),
        ["i_item_sk"], ["cs_item_sk"],
    )
    agg = _agg(
        sold,
        keys=[(Col("i_item_id"), "i_item_id"),
              (Col("i_item_desc"), "i_item_desc"),
              (Col("i_current_price"), "i_current_price")],
        aggs=[],
    )
    return _sorted_limit(
        agg, [SortKey(Col("i_item_id"), True, True)], 100
    )


def q38(s, flavor):
    """TPC-DS q38: count of customers active in ALL three channels in a
    window (distinct-intersect via semi-join chain + distinct count)."""
    def channel_custs(prefix, table, cust_col):
        j = _join(
            flavor,
            FilterExec(
                s["date_dim"](),
                (Col("d_year") == 1999) & (Col("d_moy") <= 2),
            ),
            s[table](),
            ["d_date_sk"], [f"{prefix}_sold_date_sk"],
        )
        return _agg(
            ProjectExec(j, [(Col(cust_col), "cust_sk")]),
            keys=[(Col("cust_sk"), "cust_sk")],
            aggs=[],
        )

    inter = _semi(
        flavor,
        _semi(
            flavor,
            channel_custs("ss", "store_sales", "ss_customer_sk"),
            channel_custs("cs", "catalog_sales",
                          "cs_bill_customer_sk"),
            ["cust_sk"], ["cust_sk"],
        ),
        channel_custs("ws", "web_sales", "ws_bill_customer_sk"),
        ["cust_sk"], ["cust_sk"],
    )
    return _agg(
        FilterExec(inter, IsNotNull(Col("cust_sk"))),
        keys=[],
        aggs=[(AggExpr(AggFn.COUNT_STAR, None), "num_customers")],
    )


def q40(s, flavor):
    """TPC-DS q40: catalog sales net of returns (LEFT JOIN on order+item)
    by warehouse-less item before/after a pivot date."""
    pivot = 700
    cs = _join(
        flavor,
        FilterExec(
            s["date_dim"](),
            (Col("d_date_sk") >= pivot - 30)
            & (Col("d_date_sk") <= pivot + 30),
        ),
        s["catalog_sales"](),
        ["d_date_sk"], ["cs_sold_date_sk"],
    )
    cr = ProjectExec(
        s["catalog_returns"](),
        [(Col("cr_order_number"), "r_order"),
         (Col("cr_item_sk"), "r_item"),
         (Col("cr_return_amount"), "r_amt")],
    )
    j = SortMergeJoinExec(
        cs, cr, ["cs_order_number", "cs_item_sk"],
        ["r_order", "r_item"], JoinType.LEFT,
    ) if flavor == "smj" else HashJoinExec(
        cr, cs, ["r_order", "r_item"],
        ["cs_order_number", "cs_item_sk"], JoinType.RIGHT,
    )
    j = _join(flavor, s["item"](), j, ["i_item_sk"], ["cs_item_sk"])
    net = ProjectExec(
        j,
        [(Col("i_item_id"), "i_item_id"),
         (Col("d_date_sk"), "d_date_sk"),
         (Col("cs_ext_sales_price")
          - Coalesce((Col("r_amt"), Literal(0.0, DataType.float64()))),
          "net")],
    )
    agg = _agg(
        net,
        keys=[(Col("i_item_id"), "i_item_id")],
        aggs=[
            (
                AggExpr(
                    AggFn.SUM,
                    If(Col("d_date_sk") < pivot, Col("net"),
                       Literal(0.0, DataType.float64())),
                ),
                "sales_before",
            ),
            (
                AggExpr(
                    AggFn.SUM,
                    If(Col("d_date_sk") >= pivot, Col("net"),
                       Literal(0.0, DataType.float64())),
                ),
                "sales_after",
            ),
        ],
    )
    return _sorted_limit(
        agg, [SortKey(Col("i_item_id"), True, True)], 100
    )


QUERIES.update({
    "q34": q34, "q36": q36, "q37": q37, "q38": q38, "q40": q40,
})


# ---------------------------------------------------------------------------
# q42/q43/q52/q55: reporting variants (category/day-name/brand pivots)
# ---------------------------------------------------------------------------

def q42(s, flavor):
    """TPC-DS q42: category revenue for one month."""
    j = _join(
        flavor,
        FilterExec(
            s["date_dim"](),
            (Col("d_year") == 1999) & (Col("d_moy") == 11),
        ),
        s["store_sales"](),
        ["d_date_sk"], ["ss_sold_date_sk"],
    )
    j = _join(
        flavor,
        FilterExec(s["item"](), Col("i_manager_id") == 1),
        j,
        ["i_item_sk"], ["ss_item_sk"],
    )
    agg = _agg(
        j,
        keys=[(Col("d_year"), "d_year"),
              (Col("i_category"), "i_category")],
        aggs=[(AggExpr(AggFn.SUM, Col("ss_ext_sales_price")), "total")],
    )
    return _sorted_limit(
        agg,
        [SortKey(Col("total"), False, False),
         SortKey(Col("d_year"), True, True),
         SortKey(Col("i_category"), True, True)],
        100,
    )


def q43(s, flavor):
    """TPC-DS q43: store sales pivoted by day name for one year."""
    j = _join(
        flavor,
        FilterExec(s["date_dim"](), Col("d_year") == 1999),
        s["store_sales"](),
        ["d_date_sk"], ["ss_sold_date_sk"],
    )
    j = _join(flavor, s["store"](), j, ["s_store_sk"], ["ss_store_sk"])
    days = ["Sunday", "Monday", "Tuesday", "Wednesday", "Thursday",
            "Friday", "Saturday"]
    aggs = [
        (
            AggExpr(
                AggFn.SUM,
                If(Col("d_day_name") == d, Col("ss_ext_sales_price"),
                   Literal(None, DataType.float64())),
            ),
            f"{d.lower()[:3]}_sales",
        )
        for d in days
    ]
    agg = _agg(
        j,
        keys=[(Col("s_store_name"), "s_store_name")],
        aggs=aggs,
    )
    return _sorted_limit(
        agg, [SortKey(Col("s_store_name"), True, True)], 100
    )


def _brand_month_revenue(s, flavor, manager_band):
    j = _join(
        flavor,
        FilterExec(
            s["date_dim"](),
            (Col("d_year") == 1998) & (Col("d_moy") == 12),
        ),
        s["store_sales"](),
        ["d_date_sk"], ["ss_sold_date_sk"],
    )
    j = _join(
        flavor,
        FilterExec(s["item"](), manager_band),
        j,
        ["i_item_sk"], ["ss_item_sk"],
    )
    agg = _agg(
        j,
        keys=[(Col("i_brand_id"), "brand_id"),
              (Col("i_brand"), "brand")],
        aggs=[(AggExpr(AggFn.SUM, Col("ss_ext_sales_price")),
               "ext_price")],
    )
    return _sorted_limit(
        agg,
        [SortKey(Col("ext_price"), False, False),
         SortKey(Col("brand_id"), True, True)],
        100,
    )


def q52(s, flavor):
    """TPC-DS q52: brand revenue for one month (manager 1)."""
    return _brand_month_revenue(s, flavor, Col("i_manager_id") == 1)


def q55(s, flavor):
    """TPC-DS q55: brand revenue for a manager band."""
    return _brand_month_revenue(
        s, flavor,
        (Col("i_manager_id") >= 20) & (Col("i_manager_id") <= 40),
    )


QUERIES.update({"q42": q42, "q43": q43, "q52": q52, "q55": q55})


# ---------------------------------------------------------------------------
# q45/q48/q50: zip-or-item disjunction, demographic bands, return lag
# ---------------------------------------------------------------------------

def q45(s, flavor):
    """TPC-DS q45 shape: web sales by customer zip where the zip is in
    a literal list OR the item is in a chosen id set - the IN-subquery
    arm decorrelates to an InList, so the whole disjunction is ONE
    filter predicate over the joined rows."""
    base = _join(
        flavor,
        FilterExec(
            s["date_dim"](),
            (Col("d_year") == 1999) & (Col("d_moy") >= 1)
            & (Col("d_moy") <= 3),
        ),
        s["web_sales"](),
        ["d_date_sk"], ["ws_sold_date_sk"],
    )
    base = _join(
        flavor, s["customer"](), base,
        ["c_customer_sk"], ["ws_bill_customer_sk"],
    )
    base = _join(
        flavor, s["customer_address"](), base,
        ["ca_address_sk"], ["c_current_addr_sk"],
    )
    zips = tuple(
        Literal(f"{(24000 + (i % 500) * 131) % 90000:05d}",
                DataType.utf8())
        for i in range(0, 40)
    )
    item_ids = tuple(
        Literal(i, DataType.int64()) for i in range(2, 30, 3)
    )
    qual = FilterExec(
        base,
        InList(
            ScalarFn("substring",
                     (Col("ca_zip"), Literal(1, DataType.int32()),
                      Literal(5, DataType.int32()))),
            zips,
        )
        | InList(Col("ws_item_sk").cast(DataType.int64()), item_ids),
    )
    agg = _agg(
        qual,
        keys=[(Col("ca_zip"), "ca_zip")],
        aggs=[(AggExpr(AggFn.SUM, Col("ws_ext_sales_price")), "total")],
    )
    return _sorted_limit(
        agg, [SortKey(Col("ca_zip"), True, True)], 100
    )


def q48(s, flavor):
    """TPC-DS q48: quantity sum over OR'd (demographic x price x state)
    bands."""
    j = _join(
        flavor,
        FilterExec(s["date_dim"](), Col("d_year") == 1999),
        s["store_sales"](),
        ["d_date_sk"], ["ss_sold_date_sk"],
    )
    j = _join(
        flavor, s["customer_demographics"](), j,
        ["cd_demo_sk"], ["ss_cdemo_sk"],
    )
    cust = _join(
        flavor, s["customer"](), j,
        ["c_customer_sk"], ["ss_customer_sk"],
    )
    cust = _join(
        flavor, s["customer_address"](), cust,
        ["ca_address_sk"], ["c_current_addr_sk"],
    )
    band = FilterExec(
        cust,
        (
            (Col("cd_marital_status") == "M")
            & (Col("cd_education_status") == "4 yr Degree")
            & (Col("ss_sales_price") >= 100.0)
            & (Col("ss_sales_price") <= 150.0)
        )
        | (
            (Col("cd_marital_status") == "D")
            & (Col("cd_education_status") == "2 yr Degree")
            & (Col("ss_sales_price") >= 50.0)
            & (Col("ss_sales_price") <= 100.0)
        )
        | (
            InList(Col("ca_state"),
                   (Literal("TN", DataType.utf8()),
                    Literal("GA", DataType.utf8())))
            & (Col("ss_net_profit") >= 0.0)
            & (Col("ss_net_profit") <= 100.0)
        ),
    )
    return _agg(
        band,
        keys=[],
        aggs=[(AggExpr(AggFn.SUM, Col("ss_quantity")), "total_qty")],
    )


def q50(s, flavor):
    """TPC-DS q50 shape: return-lag day buckets per store (sale joined
    to its return on customer+item, lag = return date - sale date)."""
    ss = _join(
        flavor,
        FilterExec(s["date_dim"](), Col("d_year") == 1999),
        s["store_sales"](),
        ["d_date_sk"], ["ss_sold_date_sk"],
    )
    j = _join(
        flavor, s["store_returns"](), ss,
        ["sr_customer_sk", "sr_item_sk"],
        ["ss_customer_sk", "ss_item_sk"],
    )
    j = FilterExec(
        j, Col("sr_returned_date_sk") >= Col("d_date_sk")
    )
    j = _join(flavor, s["store"](), j, ["s_store_sk"], ["ss_store_sk"])
    lag = Col("sr_returned_date_sk") - Col("d_date_sk")

    def bucket(cond, name):
        return (
            AggExpr(
                AggFn.SUM,
                If(cond, Literal(1, DataType.int64()),
                   Literal(0, DataType.int64())),
            ),
            name,
        )

    agg = _agg(
        j,
        keys=[(Col("s_store_name"), "s_store_name")],
        aggs=[
            bucket(lag <= 30, "d30"),
            bucket((lag > 30) & (lag <= 60), "d60"),
            bucket((lag > 60) & (lag <= 90), "d90"),
            bucket(lag > 90, "d90plus"),
        ],
    )
    return _sorted_limit(
        agg, [SortKey(Col("s_store_name"), True, True)], 100
    )


QUERIES.update({"q45": q45, "q48": q48, "q50": q50})


def q51(s, flavor):
    """TPC-DS q51: cumulative per-item daily revenue in web vs store
    channels (running window sums), FULL-outer-joined on (item, day),
    keeping days where the web cumulative exceeds the store one."""
    from blaze_tpu.ops.window import WindowExec, WindowFn

    def cum(prefix, table):
        daily = _agg(
            _join(
                flavor,
                FilterExec(
                    s["date_dim"](),
                    (Col("d_year") == 1999) & (Col("d_moy") <= 2),
                ),
                s[table](),
                ["d_date_sk"], [f"{prefix}_sold_date_sk"],
            ),
            keys=[(Col(f"{prefix}_item_sk"), "item_sk"),
                  (Col("d_date_sk"), "date_sk")],
            aggs=[(AggExpr(AggFn.SUM, Col(f"{prefix}_ext_sales_price")),
                   "rev")],
        )
        return WindowExec(
            daily,
            partition_by=[Col("item_sk")],
            order_by=[SortKey(Col("date_sk"), True, True)],
            functions=[
                WindowFn("sum", Col("rev"), "cume",
                         frame=("rows", None, 0))
            ],
        )

    web = RenameColumnsExec(
        cum("ws", "web_sales"),
        ["w_item", "w_date", "w_rev", "web_cume"],
    )
    store = RenameColumnsExec(
        cum("ss", "store_sales"),
        ["s_item", "s_date", "s_rev", "store_cume"],
    )
    j = SortMergeJoinExec(
        web, store, ["w_item", "w_date"], ["s_item", "s_date"],
        JoinType.FULL,
    ) if flavor == "smj" else HashJoinExec(
        web, store, ["w_item", "w_date"], ["s_item", "s_date"],
        JoinType.FULL,
    )
    over = FilterExec(
        j,
        Coalesce((Col("web_cume"), Literal(0.0, DataType.float64())))
        > Coalesce((Col("store_cume"),
                    Literal(0.0, DataType.float64()))),
    )
    out = ProjectExec(
        over,
        [(Coalesce((Col("w_item").cast(DataType.int64()),
                    Col("s_item").cast(DataType.int64()))), "item_sk"),
         (Coalesce((Col("w_date").cast(DataType.int64()),
                    Col("s_date").cast(DataType.int64()))), "date_sk"),
         (Col("web_cume"), "web_cume"),
         (Col("store_cume"), "store_cume")],
    )
    return _sorted_limit(
        out,
        [SortKey(Col("item_sk"), True, True),
         SortKey(Col("date_sk"), True, True)],
        200,
    )


QUERIES["q51"] = q51


# ---------------------------------------------------------------------------
# q41/q44/q47/q53/q57/q63/q89/q98 block (manager/reporting + window tier)
# ---------------------------------------------------------------------------

_GEN_V2 = gen_tables


def gen_tables(seed: int = 20260729):  # noqa: F811 - extend again
    t = _GEN_V2(seed)
    rng = np.random.default_rng(seed + 7)
    dd = t["date_dim"]
    dd["d_qoy"] = ((dd.d_moy - 1) // 3 + 1).astype(np.int32)
    it = t["item"]
    n_it = len(it)
    it["i_manufact"] = np.array(
        [f"manufact_{m % 50}" for m in it.i_manufact_id], dtype=object)
    it["i_product_name"] = np.array(
        [f"product_{k:06d}" for k in it.i_item_sk], dtype=object)
    it["i_color"] = np.array(
        ["red", "blue", "green", "navy", "khaki", "white"], dtype=object
    )[rng.integers(0, 6, n_it)]
    it["i_size"] = np.array(
        ["small", "medium", "large", "petite", "N/A"], dtype=object
    )[rng.integers(0, 5, n_it)]
    it["i_units"] = np.array(
        ["Oz", "Bunch", "Ton", "Case", "Each"], dtype=object
    )[rng.integers(0, 5, n_it)]
    st = t["store"]
    st["s_company_name"] = np.array(
        [f"company_{i % 3}" for i in range(len(st))], dtype=object)
    cs = t["catalog_sales"]
    cs["cs_call_center_sk"] = rng.integers(0, 4, len(cs)).astype(
        np.int32)
    t["call_center"] = pd.DataFrame(
        {
            "cc_call_center_sk": np.arange(4, dtype=np.int32),
            "cc_name": [f"call_center_{i}" for i in range(4)],
        }
    )
    return t


def _dev_window_query(s, flavor, group_extra, window_part, month_col,
                      sum_col="ss_sales_price"):
    """Shared q53/q63/q89 shape: grouped store sales with a per-window
    AVG and a >10% deviation filter (the reference plans these as
    aggregate -> window -> filter)."""
    from blaze_tpu.ops.window import WindowExec, WindowFn

    j = _join(
        flavor,
        FilterExec(s["date_dim"](), Col("d_year") == 1999),
        s["store_sales"](),
        ["d_date_sk"], ["ss_sold_date_sk"],
    )
    j = _join(flavor, s["item"](), j, ["i_item_sk"], ["ss_item_sk"])
    j = _join(flavor, s["store"](), j, ["s_store_sk"], ["ss_store_sk"])
    cat_filter = InList(
        Col("i_category"),
        (Literal("Books", DataType.utf8()),
         Literal("Home", DataType.utf8()),
         Literal("Sports", DataType.utf8())),
    )
    j = FilterExec(j, cat_filter)
    agg = _agg(
        j,
        keys=[(Col(c), c) for c in group_extra + [month_col]],
        aggs=[(AggExpr(AggFn.SUM, Col(sum_col)), "sum_sales")],
    )
    w = WindowExec(
        agg,
        partition_by=[Col(c) for c in window_part],
        order_by=[],
        functions=[WindowFn("avg", Col("sum_sales"), "avg_sales")],
    )
    dev = FilterExec(
        w,
        If(
            Col("avg_sales") > 0.0,
            ScalarFn(
                "abs", (Col("sum_sales") - Col("avg_sales"),)
            ) / Col("avg_sales") > 0.1,
            Literal(None, DataType.bool_()),
        ),
    )
    return dev


def q53(s, flavor):
    """TPC-DS q53: manufacturer quarterly sales vs the manufacturer's
    average, keeping >10% deviations (aggregate -> window AVG -> HAVING,
    the same decorrelation Spark plans)."""
    dev = _dev_window_query(
        s, flavor, ["i_manufact_id"], ["i_manufact_id"], "d_qoy")
    out = _project_names(
        dev, ["i_manufact_id", "sum_sales", "avg_sales"])
    return _sorted_limit(
        out,
        [SortKey(Col("avg_sales"), True, True),
         SortKey(Col("sum_sales"), True, True),
         SortKey(Col("i_manufact_id"), True, True)],
        100,
    )


def q63(s, flavor):
    """TPC-DS q63: manager monthly sales vs manager average (q53's
    shape keyed by i_manager_id / d_moy)."""
    dev = _dev_window_query(
        s, flavor, ["i_manager_id"], ["i_manager_id"], "d_moy")
    out = _project_names(
        dev, ["i_manager_id", "sum_sales", "avg_sales"])
    return _sorted_limit(
        out,
        [SortKey(Col("i_manager_id"), True, True),
         SortKey(Col("avg_sales"), True, True),
         SortKey(Col("sum_sales"), True, True)],
        100,
    )


def q89(s, flavor):
    """TPC-DS q89: monthly (category,class,brand,store) sales vs the
    (category,brand,store,company) yearly average."""
    dev = _dev_window_query(
        s, flavor,
        ["i_category", "i_class", "i_brand", "s_store_name",
         "s_company_name"],
        ["i_category", "i_brand", "s_store_name", "s_company_name"],
        "d_moy",
    )
    out = _project_names(
        dev,
        ["i_category", "i_class", "i_brand", "s_store_name",
         "s_company_name", "d_moy", "sum_sales", "avg_sales"],
    )
    return _sorted_limit(
        out,
        [SortKey(Col("sum_sales") - Col("avg_sales"), True, True),
         SortKey(Col("s_store_name"), True, True),
         SortKey(Col("i_category"), True, True),
         SortKey(Col("i_class"), True, True),
         SortKey(Col("i_brand"), True, True),
         SortKey(Col("d_moy"), True, True)],
        100,
    )


def q98(s, flavor):
    """TPC-DS q98: store revenue by item with share-of-class ratio
    (store twin of q12/q20; window SUM over class via self-join-free
    two-level aggregate)."""
    dd = FilterExec(
        s["date_dim"](),
        (Col("d_year") == 1999) & (Col("d_moy") <= 2),
    )
    it = FilterExec(
        s["item"](),
        InList(Col("i_category"),
               (Literal("Books", DataType.utf8()),
                Literal("Home", DataType.utf8()),
                Literal("Sports", DataType.utf8()))),
    )
    j = _join(flavor, dd, s["store_sales"](),
              ["d_date_sk"], ["ss_sold_date_sk"])
    j = _join(flavor, it, j, ["i_item_sk"], ["ss_item_sk"])
    rev = _agg(
        j,
        keys=[(Col("i_item_id"), "i_item_id"),
              (Col("i_item_desc"), "i_item_desc"),
              (Col("i_category"), "i_category"),
              (Col("i_class"), "i_class"),
              (Col("i_current_price"), "i_current_price")],
        aggs=[(AggExpr(AggFn.SUM, Col("ss_ext_sales_price")),
               "itemrevenue")],
    )
    from blaze_tpu.ops.window import WindowExec, WindowFn

    w = WindowExec(
        rev,
        partition_by=[Col("i_class")],
        order_by=[],
        functions=[WindowFn("sum", Col("itemrevenue"), "classrev")],
    )
    out = ProjectExec(
        w,
        [(Col("i_item_id"), "i_item_id"),
         (Col("i_item_desc"), "i_item_desc"),
         (Col("i_category"), "i_category"),
         (Col("i_class"), "i_class"),
         (Col("i_current_price"), "i_current_price"),
         (Col("itemrevenue"), "itemrevenue"),
         (Col("itemrevenue") * 100.0 / Col("classrev"),
          "revenueratio")],
    )
    return _sorted_limit(
        out,
        [SortKey(Col("i_category"), True, True),
         SortKey(Col("i_class"), True, True),
         SortKey(Col("i_item_id"), True, True),
         SortKey(Col("i_item_desc"), True, True),
         SortKey(Col("revenueratio"), True, True)],
        100,
    )


QUERIES.update({"q53": q53, "q63": q63, "q89": q89, "q98": q98})


def q41(s, flavor):
    """TPC-DS q41: distinct product names whose manufacturer also makes
    items matching a color/units/size disjunction (correlated EXISTS
    decorrelated into a count-per-manufact semi join)."""
    def slit(v):
        return Literal(v, DataType.utf8())

    branch1 = (
        InList(Col("i_color"), (slit("red"), slit("blue")))
        & InList(Col("i_units"), (slit("Oz"), slit("Case")))
        & InList(Col("i_size"), (slit("small"), slit("large")))
    )
    branch2 = (
        InList(Col("i_color"), (slit("green"), slit("navy")))
        & InList(Col("i_units"), (slit("Ton"), slit("Each")))
        & InList(Col("i_size"), (slit("medium"), slit("petite")))
    )
    qual = FilterExec(s["item"](), branch1 | branch2)
    manufs = ProjectExec(
        _agg(
            qual,
            keys=[(Col("i_manufact"), "q_manufact")],
            aggs=[(AggExpr(AggFn.COUNT_STAR, None), "item_cnt")],
        ),
        [(Col("q_manufact"), "q_manufact")],
    )
    i1 = FilterExec(
        s["item"](),
        (Col("i_manufact_id") >= 100) & (Col("i_manufact_id") <= 140),
    )
    joined = _semi(flavor, i1, manufs, ["i_manufact"], ["q_manufact"])
    distinct = _agg(
        joined,
        keys=[(Col("i_product_name"), "i_product_name")],
        aggs=[(AggExpr(AggFn.COUNT_STAR, None), "_c")],
    )
    return _sorted_limit(
        _project_names(distinct, ["i_product_name"]),
        [SortKey(Col("i_product_name"), True, True)],
        100,
    )


def q44(s, flavor):
    """TPC-DS q44: best and worst 10 items by average store net profit
    at one store, thresholded by 0.9x the null-customer average (scalar
    subquery via constant-key join), asc/desc ranks aligned."""
    from blaze_tpu.ops.window import WindowExec, WindowFn

    base = FilterExec(s["store_sales"](), Col("ss_store_sk") == 4)
    thr = ProjectExec(
        _agg(
            FilterExec(
                s["store_sales"](),
                (Col("ss_store_sk") == 4)
                & ~IsNotNull(Col("ss_customer_sk")),
            ),
            keys=[],
            aggs=[(AggExpr(AggFn.AVG, Col("ss_net_profit")), "nullavg")],
        ),
        [(Literal(1, DataType.int32()), "tk"),
         (Col("nullavg") * 0.9, "threshold")],
    )
    by_item = ProjectExec(
        _agg(
            base,
            keys=[(Col("ss_item_sk"), "item_sk")],
            aggs=[(AggExpr(AggFn.AVG, Col("ss_net_profit")),
                   "rank_col")],
        ),
        [(Col("item_sk"), "item_sk"), (Col("rank_col"), "rank_col"),
         (Literal(1, DataType.int32()), "jk")],
    )
    qualified = ProjectExec(
        FilterExec(
            _join(flavor, thr, by_item, ["tk"], ["jk"]),
            Col("rank_col") > Col("threshold"),
        ),
        [(Col("item_sk"), "item_sk"), (Col("rank_col"), "rank_col")],
    )

    def ranked(asc, out):
        return ProjectExec(
            FilterExec(
                WindowExec(
                    qualified,
                    partition_by=[],
                    order_by=[SortKey(Col("rank_col"), asc, True)],
                    functions=[WindowFn("rank", None, "rnk")],
                ),
                Col("rnk") <= 10,
            ),
            [(Col("rnk").cast(DataType.int64()), f"{out}_rnk"),
             (Col("item_sk"), f"{out}_item")],
        )

    asc = ranked(True, "a")
    desc = ranked(False, "d")
    both = _join(flavor, asc, desc, ["a_rnk"], ["d_rnk"])
    it1 = ProjectExec(
        s["item"](),
        [(Col("i_item_sk"), "i1_sk"),
         (Col("i_product_name"), "best_performing")],
    )
    it2 = ProjectExec(
        s["item"](),
        [(Col("i_item_sk"), "i2_sk"),
         (Col("i_product_name"), "worst_performing")],
    )
    j = _join(flavor, it1, both, ["i1_sk"], ["a_item"])
    j = _join(flavor, it2, j, ["i2_sk"], ["d_item"])
    out = _project_names(
        j, ["a_rnk", "best_performing", "worst_performing"])
    return SortExec(out, [SortKey(Col("a_rnk"), True, True)])


def _q47_like(s, flavor, sales, date_col, sum_col, entity_scan,
              entity_sk, entity_fk, entity_cols):
    """Shared q47/q57 shape: monthly sums per (item brand x entity),
    yearly window AVG, lag/lead neighbours, >10% deviation in the
    center year."""
    from blaze_tpu.ops.window import WindowExec, WindowFn

    j = _join(
        flavor,
        FilterExec(
            s["date_dim"](),
            (Col("d_year") >= 1998) & (Col("d_year") <= 2000),
        ),
        s[sales](),
        ["d_date_sk"], [date_col],
    )
    j = _join(flavor, s["item"](), j, ["i_item_sk"],
              [date_col.split("_")[0] + "_item_sk"])
    j = _join(flavor, entity_scan(), j, [entity_sk], [entity_fk])
    agg = _agg(
        j,
        keys=[(Col("i_category"), "i_category"),
              (Col("i_brand"), "i_brand")]
        + [(Col(c), c) for c in entity_cols]
        + [(Col("d_year"), "d_year"), (Col("d_moy"), "d_moy")],
        aggs=[(AggExpr(AggFn.SUM, Col(sum_col)), "sum_sales")],
    )
    part = ["i_category", "i_brand"] + entity_cols
    w = WindowExec(
        agg,
        partition_by=[Col(c) for c in part + ["d_year"]],
        order_by=[],
        functions=[WindowFn("avg", Col("sum_sales"),
                            "avg_monthly_sales")],
    )
    w = WindowExec(
        w,
        partition_by=[Col(c) for c in part],
        order_by=[SortKey(Col("d_year"), True, True),
                  SortKey(Col("d_moy"), True, True)],
        functions=[WindowFn("lag", Col("sum_sales"), "psum"),
                   WindowFn("lead", Col("sum_sales"), "nsum")],
    )
    kept = FilterExec(
        w,
        (Col("d_year") == 1999)
        & (Col("avg_monthly_sales") > 0.0)
        & (
            ScalarFn(
                "abs", (Col("sum_sales") - Col("avg_monthly_sales"),)
            ) / Col("avg_monthly_sales") > 0.1
        ),
    )
    out = _project_names(
        kept,
        part + ["d_year", "d_moy", "sum_sales", "avg_monthly_sales",
                "psum", "nsum"],
    )
    return _sorted_limit(
        out,
        [SortKey(Col("sum_sales") - Col("avg_monthly_sales"), True,
                 True)]
        + [SortKey(Col(c), True, True) for c in part]
        + [SortKey(Col("d_year"), True, True),
           SortKey(Col("d_moy"), True, True)],
        100,
    )


def q47(s, flavor):
    """TPC-DS q47: store monthly brand sales vs yearly average with
    previous/next month neighbours (v1/v2 self-joins planned as
    lag/lead windows)."""
    return _q47_like(
        s, flavor, "store_sales", "ss_sold_date_sk", "ss_sales_price",
        s["store"], "s_store_sk", "ss_store_sk",
        ["s_store_name", "s_company_name"],
    )


def q57(s, flavor):
    """TPC-DS q57: q47's shape for catalog sales by call center."""
    return _q47_like(
        s, flavor, "catalog_sales", "cs_sold_date_sk",
        "cs_sales_price",
        s["call_center"], "cc_call_center_sk", "cs_call_center_sk",
        ["cc_name"],
    )


QUERIES.update({"q41": q41, "q44": q44, "q47": q47, "q57": q57})


# ---------------------------------------------------------------------------
# q46/q59/q68/q73/q79/q88/q90/q96 block (time-of-day / household tier)
# ---------------------------------------------------------------------------

N_TIMES = 1440  # one row per minute of day

_GEN_V3 = gen_tables


def gen_tables(seed: int = 20260729):  # noqa: F811 - extend again
    t = _GEN_V3(seed)
    rng = np.random.default_rng(seed + 13)
    dd = t["date_dim"]
    dd["d_dow"] = (np.arange(len(dd)) % 7).astype(np.int32)
    t["time_dim"] = pd.DataFrame(
        {
            "t_time_sk": np.arange(N_TIMES, dtype=np.int32),
            "t_hour": (np.arange(N_TIMES) // 60).astype(np.int32),
            "t_minute": (np.arange(N_TIMES) % 60).astype(np.int32),
        }
    )
    ss = t["store_sales"]
    n_ss = len(ss)
    ss["ss_sold_time_sk"] = rng.integers(0, N_TIMES, n_ss).astype(
        np.int32)
    ss["ss_addr_sk"] = pd.array(
        np.where(
            rng.random(n_ss) < 0.02, np.nan,
            rng.integers(0, N_ADDRESSES, n_ss).astype(np.float64),
        ),
        dtype=pd.Int32Dtype(),
    )
    ca = t["customer_address"]
    ca["ca_city"] = np.array(
        ["Midway", "Fairview", "Oakdale", "Riverside", "Centerville",
         "Liberty"], dtype=object,
    )[rng.integers(0, 6, len(ca))]
    st = t["store"]
    st["s_city"] = np.array(
        ["Midway", "Fairview", "Oakdale"], dtype=object
    )[np.arange(len(st)) % 3]
    st["s_store_id"] = [f"S{i:04d}" for i in range(len(st))]
    ws = t["web_sales"]
    n_ws = len(ws)
    ws["ws_sold_time_sk"] = rng.integers(0, N_TIMES, n_ws).astype(
        np.int32)
    ws["ws_web_page_sk"] = rng.integers(0, 20, n_ws).astype(np.int32)
    t["web_page"] = pd.DataFrame(
        {
            "wp_web_page_sk": np.arange(20, dtype=np.int32),
            "wp_char_count": (4000 + np.arange(20) * 120).astype(
                np.int32),
        }
    )
    return t


def _city_ticket_query(s, flavor, hd_pred, amt_col, profit_col):
    """Shared q46/q68/q79 shape: weekend tickets in qualifying cities by
    qualifying households, per-ticket sums, re-joined to the customer's
    current address (bought city <> home city)."""
    dd = FilterExec(
        s["date_dim"](),
        InList(Col("d_dow"), (Literal(6, DataType.int32()),
                              Literal(0, DataType.int32())))
        & (Col("d_year") >= 1998) & (Col("d_year") <= 2000),
    )
    stc = FilterExec(
        s["store"](),
        InList(Col("s_city"),
               (Literal("Midway", DataType.utf8()),
                Literal("Fairview", DataType.utf8()))),
    )
    hd = FilterExec(s["household_demographics"](), hd_pred)
    j = _join(flavor, dd, s["store_sales"](),
              ["d_date_sk"], ["ss_sold_date_sk"])
    j = _join(flavor, stc, j, ["s_store_sk"], ["ss_store_sk"])
    j = _join(flavor, hd, j, ["hd_demo_sk"], ["ss_hdemo_sk"])
    j = _join(
        flavor,
        ProjectExec(s["customer_address"](),
                    [(Col("ca_address_sk"), "b_addr_sk"),
                     (Col("ca_city"), "bought_city")]),
        j, ["b_addr_sk"], ["ss_addr_sk"],
    )
    per_ticket = _agg(
        j,
        keys=[(Col("ss_ticket_number"), "ticket"),
              (Col("ss_customer_sk"), "cust_sk"),
              (Col("bought_city"), "bought_city")],
        aggs=[(AggExpr(AggFn.SUM, Col(amt_col)), "amt"),
              (AggExpr(AggFn.SUM, Col(profit_col)), "profit")],
    )
    cust = _join(
        flavor,
        s["customer"](),
        per_ticket,
        ["c_customer_sk"], ["cust_sk"],
    )
    home = _join(
        flavor,
        ProjectExec(s["customer_address"](),
                    [(Col("ca_address_sk"), "h_addr_sk"),
                     (Col("ca_city"), "home_city")]),
        cust, ["h_addr_sk"], ["c_current_addr_sk"],
    )
    return FilterExec(
        home, ~(Col("home_city") == Col("bought_city"))
    )


def q46(s, flavor):
    """TPC-DS q46: weekend dining-out tickets where the purchase city
    differs from the customer's home city (dep=4 or vehicles=3)."""
    res = _city_ticket_query(
        s, flavor,
        (Col("hd_dep_count") == 4) | (Col("hd_vehicle_count") == 3),
        "ss_coupon_amt", "ss_net_profit",
    )
    out = _project_names(
        res,
        ["c_last_name", "c_first_name", "ticket", "bought_city",
         "amt", "profit"],
    )
    return _sorted_limit(
        out,
        [SortKey(Col("c_last_name"), True, True),
         SortKey(Col("c_first_name"), True, True),
         SortKey(Col("bought_city"), True, True),
         SortKey(Col("ticket"), True, True)],
        100,
    )


def q68(s, flavor):
    """TPC-DS q68: q46's shape with dep=5/vehicles=3 households and
    sales/list price sums."""
    res = _city_ticket_query(
        s, flavor,
        (Col("hd_dep_count") == 5) | (Col("hd_vehicle_count") == 3),
        "ss_ext_sales_price", "ss_ext_list_price",
    )
    out = _project_names(
        res,
        ["c_last_name", "c_first_name", "ticket", "bought_city",
         "amt", "profit"],
    )
    return _sorted_limit(
        out,
        [SortKey(Col("c_last_name"), True, True),
         SortKey(Col("ticket"), True, True)],
        100,
    )


def q79(s, flavor):
    """TPC-DS q79: per-ticket store profits for large-household or
    motorized customers, keyed by store city."""
    dd = FilterExec(
        s["date_dim"](),
        (Col("d_dow") == 1) & (Col("d_year") >= 1998)
        & (Col("d_year") <= 2000),
    )
    hd = FilterExec(
        s["household_demographics"](),
        (Col("hd_dep_count") == 6) | (Col("hd_vehicle_count") > 2),
    )
    j = _join(flavor, dd, s["store_sales"](),
              ["d_date_sk"], ["ss_sold_date_sk"])
    j = _join(
        flavor,
        ProjectExec(s["store"](),
                    [(Col("s_store_sk"), "s_sk"),
                     (Col("s_city"), "s_city")]),
        j, ["s_sk"], ["ss_store_sk"],
    )
    j = _join(flavor, hd, j, ["hd_demo_sk"], ["ss_hdemo_sk"])
    per_ticket = _agg(
        j,
        keys=[(Col("ss_ticket_number"), "ticket"),
              (Col("ss_customer_sk"), "cust_sk"),
              (Col("s_city"), "city")],
        aggs=[(AggExpr(AggFn.SUM, Col("ss_coupon_amt")), "amt"),
              (AggExpr(AggFn.SUM, Col("ss_net_profit")), "profit")],
    )
    cust = _join(flavor, s["customer"](), per_ticket,
                 ["c_customer_sk"], ["cust_sk"])
    out = _project_names(
        cust,
        ["c_last_name", "c_first_name", "city", "profit", "ticket",
         "amt"],
    )
    return _sorted_limit(
        out,
        [SortKey(Col("c_last_name"), True, True),
         SortKey(Col("c_first_name"), True, True),
         SortKey(Col("city"), True, True),
         SortKey(Col("profit"), True, True),
         SortKey(Col("ticket"), True, True)],
        100,
    )


def q73(s, flavor):
    """TPC-DS q73: customers with 1-5 item tickets from high-potential
    motorized households."""
    dd = FilterExec(
        s["date_dim"](),
        (Col("d_dom") >= 1) & (Col("d_dom") <= 2)
        & (Col("d_year") >= 1998) & (Col("d_year") <= 2000),
    )
    hd = FilterExec(
        s["household_demographics"](),
        InList(Col("hd_buy_potential"),
               (Literal(">10000", DataType.utf8()),
                Literal("0-500", DataType.utf8())))
        & (Col("hd_vehicle_count") > 0),
    )
    j = _join(flavor, dd, s["store_sales"](),
              ["d_date_sk"], ["ss_sold_date_sk"])
    j = _join(flavor, hd, j, ["hd_demo_sk"], ["ss_hdemo_sk"])
    per_ticket = FilterExec(
        _agg(
            j,
            keys=[(Col("ss_ticket_number"), "ticket"),
                  (Col("ss_customer_sk"), "cust_sk")],
            aggs=[(AggExpr(AggFn.COUNT_STAR, None), "cnt")],
        ),
        (Col("cnt") >= 1) & (Col("cnt") <= 5),
    )
    cust = _join(flavor, s["customer"](), per_ticket,
                 ["c_customer_sk"], ["cust_sk"])
    out = _project_names(
        cust,
        ["c_last_name", "c_first_name", "ticket", "cnt"],
    )
    return SortExec(
        out,
        [SortKey(Col("cnt"), False, True),
         SortKey(Col("c_last_name"), True, True),
         SortKey(Col("ticket"), True, True)],
    )


def _time_band_count(s, flavor, h_lo, m_lo, h_hi, m_hi, dep, out):
    """One q88-style half-hour store-traffic counter (scalar)."""
    td = FilterExec(
        s["time_dim"](),
        ((Col("t_hour") > h_lo)
         | ((Col("t_hour") == h_lo) & (Col("t_minute") >= m_lo)))
        & ((Col("t_hour") < h_hi)
           | ((Col("t_hour") == h_hi) & (Col("t_minute") < m_hi))),
    )
    hd = FilterExec(s["household_demographics"](),
                    Col("hd_dep_count") == dep)
    stq = FilterExec(s["store"](), Col("s_store_name") == "store_0")
    j = _join(flavor, td, s["store_sales"](),
              ["t_time_sk"], ["ss_sold_time_sk"])
    j = _join(flavor, hd, j, ["hd_demo_sk"], ["ss_hdemo_sk"])
    j = _join(flavor, stq, j, ["s_store_sk"], ["ss_store_sk"])
    return ProjectExec(
        _agg(j, keys=[],
             aggs=[(AggExpr(AggFn.COUNT_STAR, None), out)]),
        [(Literal(1, DataType.int32()), f"{out}_k"),
         (Col(out), out)],
    )


def q88(s, flavor):
    """TPC-DS q88: store traffic in eight half-hour bands, one scalar
    subquery each, cross-joined into a single row."""
    bands = [
        (8, 30, 9, 0, 4, "h8_30_to_9"),
        (9, 0, 9, 30, 3, "h9_to_9_30"),
        (9, 30, 10, 0, 2, "h9_30_to_10"),
        (10, 0, 10, 30, 4, "h10_to_10_30"),
        (10, 30, 11, 0, 3, "h10_30_to_11"),
        (11, 0, 11, 30, 2, "h11_to_11_30"),
        (11, 30, 12, 0, 4, "h11_30_to_12"),
        (12, 0, 12, 30, 3, "h12_to_12_30"),
    ]
    cur = None
    for h1, m1, h2, m2, dep, out in bands:
        nxt = _time_band_count(s, flavor, h1, m1, h2, m2, dep, out)
        if cur is None:
            cur = nxt
        else:
            cur = _join(flavor, cur, nxt,
                        [prev_k], [f"{out}_k"])
        prev_k = f"{out}_k"
    return _project_names(cur, [b[5] for b in bands])


def q90(s, flavor):
    """TPC-DS q90: morning-to-evening web traffic ratio for mid-size
    pages (two scalar counts joined on a constant)."""
    def half(h_lo, h_hi, out):
        td = FilterExec(
            s["time_dim"](),
            (Col("t_hour") >= h_lo) & (Col("t_hour") < h_hi),
        )
        wp = FilterExec(
            s["web_page"](),
            (Col("wp_char_count") >= 4500)
            & (Col("wp_char_count") <= 5500),
        )
        j = _join(flavor, td, s["web_sales"](),
                  ["t_time_sk"], ["ws_sold_time_sk"])
        j = _join(flavor, wp, j, ["wp_web_page_sk"], ["ws_web_page_sk"])
        return ProjectExec(
            _agg(j, keys=[],
                 aggs=[(AggExpr(AggFn.COUNT_STAR, None), out)]),
            [(Literal(1, DataType.int32()), f"{out}_k"), (Col(out), out)],
        )

    am = half(7, 9, "amc")
    pm = half(19, 21, "pmc")
    both = _join(flavor, am, pm, ["amc_k"], ["pmc_k"])
    return ProjectExec(
        both,
        [(Col("amc").cast(DataType.float64())
          / Col("pmc").cast(DataType.float64()), "am_pm_ratio")],
    )


def q96(s, flavor):
    """TPC-DS q96: count of evening store sales by seven-dependent
    households at one store."""
    td = FilterExec(
        s["time_dim"](),
        (Col("t_hour") == 20) & (Col("t_minute") >= 30),
    )
    hd = FilterExec(s["household_demographics"](),
                    Col("hd_dep_count") == 6)
    stq = FilterExec(s["store"](), Col("s_store_name") == "store_1")
    j = _join(flavor, td, s["store_sales"](),
              ["t_time_sk"], ["ss_sold_time_sk"])
    j = _join(flavor, hd, j, ["hd_demo_sk"], ["ss_hdemo_sk"])
    j = _join(flavor, stq, j, ["s_store_sk"], ["ss_store_sk"])
    return _agg(
        j, keys=[],
        aggs=[(AggExpr(AggFn.COUNT_STAR, None), "cnt")],
    )


def q59(s, flavor):
    """TPC-DS q59: store weekly day-of-week sales, this year vs the
    next (aligned at +52 weeks), as per-day ratios."""
    days = ["Sunday", "Monday", "Tuesday", "Wednesday", "Thursday",
            "Friday", "Saturday"]
    cols = [d.lower()[:3] + "_sales" for d in days]

    def day_sum(day):
        return AggExpr(
            AggFn.SUM,
            If(Col("d_day_name") == day, Col("ss_sales_price"),
               Literal(None, DataType.float64())),
        )

    j = _join(flavor, s["date_dim"](), s["store_sales"](),
              ["d_date_sk"], ["ss_sold_date_sk"])
    wss = _agg(
        j,
        keys=[(Col("d_week_seq"), "d_week_seq"),
              (Col("ss_store_sk"), "store_sk")],
        aggs=[(day_sum(d), c) for d, c in zip(days, cols)],
    )
    wss = _join(
        flavor,
        ProjectExec(s["store"](),
                    [(Col("s_store_sk"), "s_sk"),
                     (Col("s_store_id"), "s_store_id"),
                     (Col("s_store_name"), "s_store_name")]),
        wss, ["s_sk"], ["store_sk"],
    )
    y1 = ProjectExec(
        FilterExec(wss, (Col("d_week_seq") >= 5)
                   & (Col("d_week_seq") <= 20)),
        [(Col("s_store_id"), "id1"),
         (Col("s_store_name"), "name1"),
         (Col("d_week_seq"), "wk1")]
        + [(Col(c), c + "1") for c in cols],
    )
    y2 = ProjectExec(
        FilterExec(wss, (Col("d_week_seq") >= 57)
                   & (Col("d_week_seq") <= 72)),
        [(Col("s_store_id"), "id2"),
         (Col("d_week_seq") - 52, "wk2")]
        + [(Col(c), c + "2") for c in cols],
    )
    m = _join(flavor, y1, y2, ["id1", "wk1"], ["id2", "wk2"])
    out = ProjectExec(
        m,
        [(Col("name1"), "s_store_name"),
         (Col("id1"), "s_store_id"),
         (Col("wk1"), "d_week_seq")]
        + [(Col(c + "1") / Col(c + "2"), c + "_r") for c in cols],
    )
    return _sorted_limit(
        out,
        [SortKey(Col("s_store_name"), True, True),
         SortKey(Col("s_store_id"), True, True),
         SortKey(Col("d_week_seq"), True, True)],
        100,
    )


QUERIES.update({
    "q46": q46, "q59": q59, "q68": q68, "q73": q73, "q79": q79,
    "q88": q88, "q90": q90, "q96": q96,
})


# ---------------------------------------------------------------------------
# q31/q35/q39/q49/q65/q69/q74/q92/q93/q97 block (growth ratios, returns
# linkage, statistical inventory)
# ---------------------------------------------------------------------------

_GEN_V4 = gen_tables


def gen_tables(seed: int = 20260729):  # noqa: F811 - extend again
    t = _GEN_V4(seed)
    rng = np.random.default_rng(seed + 19)
    ws = t["web_sales"]
    n_ws = len(ws)
    ws["ws_bill_addr_sk"] = pd.array(
        np.where(
            rng.random(n_ws) < 0.02, np.nan,
            rng.integers(0, N_ADDRESSES, n_ws).astype(np.float64),
        ),
        dtype=pd.Int32Dtype(),
    )
    ws["ws_order_number"] = np.arange(n_ws, dtype=np.int64)
    ws["ws_quantity"] = rng.integers(1, 101, n_ws).astype(np.int32)
    wr = t["web_returns"]
    n_wr = len(wr)
    widx = rng.integers(0, n_ws, n_wr)
    wr["wr_order_number"] = widx.astype(np.int64)
    wr["wr_item_sk"] = ws["ws_item_sk"].values[widx]
    wr["wr_return_quantity"] = rng.integers(1, 30, n_wr).astype(
        np.int32)
    cr = t["catalog_returns"]
    cr["cr_return_quantity"] = rng.integers(1, 30, len(cr)).astype(
        np.int32)
    sr = t["store_returns"]
    n_sr = len(sr)
    ss = t["store_sales"]
    sidx = rng.integers(0, len(ss), n_sr)
    sr["sr_ticket_number"] = ss["ss_ticket_number"].values[sidx]
    sr["sr_item_sk"] = ss["ss_item_sk"].values[sidx]
    sr["sr_return_quantity"] = rng.integers(1, 30, n_sr).astype(
        np.int32)
    sr["sr_reason_sk"] = rng.integers(1, 10, n_sr).astype(np.int32)
    return t


def q31(s, flavor):
    """TPC-DS q31: counties where web sales grew faster than store
    sales across consecutive quarters (six quarterly aggregates joined
    on county)."""
    def county_q(sales, date_col, addr_col, qoy, out):
        j = _join(
            flavor,
            FilterExec(
                s["date_dim"](),
                (Col("d_year") == 1999) & (Col("d_qoy") == qoy),
            ),
            s[sales](),
            ["d_date_sk"], [date_col],
        )
        j = _join(
            flavor,
            s["customer_address"](),
            j, ["ca_address_sk"], [addr_col],
        )
        return _agg(
            j,
            keys=[(Col("ca_county"), f"county_{out}")],
            aggs=[(AggExpr(
                AggFn.SUM,
                Col("ss_ext_sales_price" if sales == "store_sales"
                    else "ws_ext_sales_price")), out)],
        )

    ss1 = county_q("store_sales", "ss_sold_date_sk", "ss_addr_sk",
                   1, "ss1")
    ss2 = county_q("store_sales", "ss_sold_date_sk", "ss_addr_sk",
                   2, "ss2")
    ss3 = county_q("store_sales", "ss_sold_date_sk", "ss_addr_sk",
                   3, "ss3")
    ws1 = county_q("web_sales", "ws_sold_date_sk", "ws_bill_addr_sk",
                   1, "ws1")
    ws2 = county_q("web_sales", "ws_sold_date_sk", "ws_bill_addr_sk",
                   2, "ws2")
    ws3 = county_q("web_sales", "ws_sold_date_sk", "ws_bill_addr_sk",
                   3, "ws3")
    j = _join(flavor, ss1, ss2, ["county_ss1"], ["county_ss2"])
    j = _join(flavor, j, ss3, ["county_ss1"], ["county_ss3"])
    j = _join(flavor, j, ws1, ["county_ss1"], ["county_ws1"])
    j = _join(flavor, j, ws2, ["county_ss1"], ["county_ws2"])
    j = _join(flavor, j, ws3, ["county_ss1"], ["county_ws3"])
    grew = FilterExec(
        j,
        ((Col("ws2") / Col("ws1")) > (Col("ss2") / Col("ss1")))
        & ((Col("ws3") / Col("ws2")) > (Col("ss3") / Col("ss2"))),
    )
    out = ProjectExec(
        grew,
        [(Col("county_ss1"), "ca_county"),
         (Col("ws2") / Col("ws1"), "web_q1_q2_increase"),
         (Col("ss2") / Col("ss1"), "store_q1_q2_increase"),
         (Col("ws3") / Col("ws2"), "web_q2_q3_increase"),
         (Col("ss3") / Col("ss2"), "store_q2_q3_increase")],
    )
    return SortExec(out, [SortKey(Col("ca_county"), True, True)])


def q35(s, flavor):
    """TPC-DS q35: demographic profile (count + min/max/avg dependents)
    of customers active in store AND (web OR catalog)."""
    def active(prefix, table, cust):
        j = _join(
            flavor,
            FilterExec(
                s["date_dim"](),
                (Col("d_year") == 1999) & (Col("d_qoy") < 4),
            ),
            s[table](),
            ["d_date_sk"], [f"{prefix}_sold_date_sk"],
        )
        return ProjectExec(j, [(Col(cust), "active_sk")])

    cust = _semi(
        flavor,
        _semi(
            flavor,
            s["customer"](),
            _agg(active("ss", "store_sales", "ss_customer_sk"),
                 keys=[(Col("active_sk"), "active_sk")], aggs=[]),
            ["c_customer_sk"], ["active_sk"],
        ),
        _agg(
            _union([
                active("ws", "web_sales", "ws_bill_customer_sk"),
                active("cs", "catalog_sales", "cs_bill_customer_sk"),
            ]),
            keys=[(Col("active_sk"), "active_sk")], aggs=[],
        ),
        ["c_customer_sk"], ["active_sk"],
    )
    j = _join(
        flavor, s["customer_demographics"](), cust,
        ["cd_demo_sk"], ["c_current_cdemo_sk"],
    )
    keys = ["cd_gender", "cd_marital_status", "cd_dep_count",
            "cd_dep_employed_count", "cd_dep_college_count"]
    agg = _agg(
        j,
        keys=[(Col(k), k) for k in keys],
        aggs=[(AggExpr(AggFn.COUNT_STAR, None), "cnt"),
              (AggExpr(AggFn.MIN, Col("cd_dep_count")), "min_dep"),
              (AggExpr(AggFn.MAX, Col("cd_dep_count")), "max_dep"),
              (AggExpr(AggFn.AVG, Col("cd_dep_count")), "avg_dep")],
    )
    return _sorted_limit(
        agg,
        [SortKey(Col(k), True, True) for k in keys],
        100,
    )


def q39(s, flavor):
    """TPC-DS q39: items whose warehouse inventory is volatile
    (stdev/mean > 1) in consecutive months, self-joined pairwise."""
    def inv_stats(moy, suffix):
        j = _join(
            flavor,
            FilterExec(
                s["date_dim"](),
                (Col("d_year") == 1999) & (Col("d_moy") == moy),
            ),
            s["inventory"](),
            ["d_date_sk"], ["inv_date_sk"],
        )
        stats = _agg(
            j,
            keys=[(Col("inv_warehouse_sk"), f"w_{suffix}"),
                  (Col("inv_item_sk"), f"i_{suffix}")],
            aggs=[(AggExpr(AggFn.AVG, Col("inv_quantity_on_hand")),
                   f"mean_{suffix}"),
                  (AggExpr(AggFn.STDDEV_SAMP,
                           Col("inv_quantity_on_hand")),
                   f"stdev_{suffix}")],
        )
        return FilterExec(
            stats,
            If(
                Col(f"mean_{suffix}") == 0.0,
                Literal(None, DataType.bool_()),
                Col(f"stdev_{suffix}") / Col(f"mean_{suffix}") > 1.0,
            ),
        )

    m1 = inv_stats(1, "m1")
    m2 = inv_stats(2, "m2")
    pair = _join(flavor, m1, m2, ["w_m1", "i_m1"], ["w_m2", "i_m2"])
    out = ProjectExec(
        pair,
        [(Col("w_m1"), "w_warehouse_sk"), (Col("i_m1"), "i_item_sk"),
         (Col("mean_m1"), "mean1"),
         (Col("stdev_m1") / Col("mean_m1"), "cov1"),
         (Col("mean_m2"), "mean2"),
         (Col("stdev_m2") / Col("mean_m2"), "cov2")],
    )
    return SortExec(
        out,
        [SortKey(Col("w_warehouse_sk"), True, True),
         SortKey(Col("i_item_sk"), True, True)],
    )


def q49(s, flavor):
    """TPC-DS q49: worst return ratios per channel - currency and
    quantity ranks, rank<=10 either way, channels unioned."""
    from blaze_tpu.ops.window import WindowExec, WindowFn

    def channel(label, sales, rets, s_keys, r_keys, item_col, qty,
                amt, r_qty, r_amt):
        j = _join(flavor, s[sales](), s[rets](), s_keys, r_keys,
                  JoinType.LEFT)
        ratios = ProjectExec(
            _agg(
                j,
                keys=[(Col(item_col), "item")],
                aggs=[
                    (AggExpr(AggFn.SUM, Coalesce(
                        (Col(r_qty), Literal(0, DataType.int32())))),
                     "ret_qty"),
                    (AggExpr(AggFn.SUM, Col(qty)), "qty"),
                    (AggExpr(AggFn.SUM, Coalesce(
                        (Col(r_amt), Literal(0.0, DataType.float64())))),
                     "ret_amt"),
                    (AggExpr(AggFn.SUM, Col(amt)), "amt"),
                ],
            ),
            [(Col("item"), "item"),
             (Col("ret_qty").cast(DataType.float64())
              / Col("qty").cast(DataType.float64()), "qty_ratio"),
             (Col("ret_amt") / Col("amt"), "amt_ratio")],
        )
        ranked = WindowExec(
            WindowExec(
                ratios,
                partition_by=[],
                order_by=[SortKey(Col("qty_ratio"), True, True)],
                functions=[WindowFn("rank", None, "qty_rank")],
            ),
            partition_by=[],
            order_by=[SortKey(Col("amt_ratio"), True, True)],
            functions=[WindowFn("rank", None, "amt_rank")],
        )
        top = FilterExec(
            ranked,
            (Col("qty_rank") <= 10) | (Col("amt_rank") <= 10),
        )
        return ProjectExec(
            top,
            [(Literal(label, DataType.utf8()), "channel"),
             (Col("item").cast(DataType.int64()), "item"),
             (Col("amt_ratio"), "return_ratio"),
             (Col("qty_rank").cast(DataType.int64()), "return_rank"),
             (Col("amt_rank").cast(DataType.int64()), "currency_rank")],
        )

    web = channel(
        "web", "web_sales", "web_returns",
        ["ws_order_number", "ws_item_sk"],
        ["wr_order_number", "wr_item_sk"],
        "ws_item_sk", "ws_quantity", "ws_ext_sales_price",
        "wr_return_quantity", "wr_return_amt",
    )
    catalog = channel(
        "catalog", "catalog_sales", "catalog_returns",
        ["cs_order_number", "cs_item_sk"],
        ["cr_order_number", "cr_item_sk"],
        "cs_item_sk", "cs_quantity", "cs_ext_sales_price",
        "cr_return_quantity", "cr_return_amount",
    )
    store = channel(
        "store", "store_sales", "store_returns",
        ["ss_ticket_number", "ss_item_sk"],
        ["sr_ticket_number", "sr_item_sk"],
        "ss_item_sk", "ss_quantity", "ss_ext_sales_price",
        "sr_return_quantity", "sr_return_amt",
    )
    both = _union([web, catalog, store])
    return _sorted_limit(
        both,
        [SortKey(Col("channel"), True, True),
         SortKey(Col("return_rank"), True, True),
         SortKey(Col("currency_rank"), True, True),
         SortKey(Col("item"), True, True)],
        100,
    )


def q65(s, flavor):
    """TPC-DS q65: (store, item) pairs whose revenue is at most 10% of
    the store's average item revenue (two-level aggregate join)."""
    j = _join(
        flavor,
        FilterExec(
            s["date_dim"](),
            (Col("d_month_seq") >= 1188) & (Col("d_month_seq") <= 1199),
        ),
        s["store_sales"](),
        ["d_date_sk"], ["ss_sold_date_sk"],
    )
    sb = _agg(
        j,
        keys=[(Col("ss_store_sk"), "store_sk"),
              (Col("ss_item_sk"), "item_sk")],
        aggs=[(AggExpr(AggFn.SUM, Col("ss_sales_price")), "revenue")],
    )
    sc = ProjectExec(
        _agg(
            sb,
            keys=[(Col("store_sk"), "a_store_sk")],
            aggs=[(AggExpr(AggFn.AVG, Col("revenue")), "ave")],
        ),
        [(Col("a_store_sk"), "a_store_sk"), (Col("ave") * 0.1, "cap")],
    )
    low = FilterExec(
        _join(flavor, sc, sb, ["a_store_sk"], ["store_sk"]),
        Col("revenue") <= Col("cap"),
    )
    j2 = _join(flavor, s["store"](), low,
               ["s_store_sk"], ["store_sk"])
    j2 = _join(flavor, s["item"](), j2, ["i_item_sk"], ["item_sk"])
    out = _project_names(
        j2, ["s_store_name", "i_item_desc", "revenue", "i_current_price",
             "i_brand"],
    )
    return _sorted_limit(
        out,
        [SortKey(Col("s_store_name"), True, True),
         SortKey(Col("i_item_desc"), True, True),
         SortKey(Col("revenue"), True, True)],
        100,
    )


def q69(s, flavor):
    """TPC-DS q69: demographics of store customers in three states with
    NO web or catalog activity in the window (anti joins)."""
    def active(prefix, table, cust):
        j = _join(
            flavor,
            FilterExec(
                s["date_dim"](),
                (Col("d_year") == 2000)
                & (Col("d_moy") >= 1) & (Col("d_moy") <= 3),
            ),
            s[table](),
            ["d_date_sk"], [f"{prefix}_sold_date_sk"],
        )
        return _agg(
            ProjectExec(j, [(Col(cust), "active_sk")]),
            keys=[(Col("active_sk"), "active_sk")], aggs=[],
        )

    in_states = _join(
        flavor,
        FilterExec(
            s["customer_address"](),
            InList(Col("ca_state"),
                   (Literal("TN", DataType.utf8()),
                    Literal("GA", DataType.utf8()),
                    Literal("CA", DataType.utf8()))),
        ),
        s["customer"](),
        ["ca_address_sk"], ["c_current_addr_sk"],
    )
    cust = _semi(
        flavor, in_states,
        active("ss", "store_sales", "ss_customer_sk"),
        ["c_customer_sk"], ["active_sk"],
    )
    for prefix, table, cc in (
        ("ws", "web_sales", "ws_bill_customer_sk"),
        ("cs", "catalog_sales", "cs_bill_customer_sk"),
    ):
        cust = _join(flavor, cust, active(prefix, table, cc),
                     ["c_customer_sk"], ["active_sk"],
                     JoinType.LEFT_ANTI)
    j = _join(
        flavor, s["customer_demographics"](), cust,
        ["cd_demo_sk"], ["c_current_cdemo_sk"],
    )
    keys = ["cd_gender", "cd_marital_status", "cd_education_status",
            "cd_purchase_estimate", "cd_credit_rating"]
    agg = _agg(
        j,
        keys=[(Col(k), k) for k in keys],
        aggs=[(AggExpr(AggFn.COUNT_STAR, None), "cnt")],
    )
    return _sorted_limit(
        agg, [SortKey(Col(k), True, True) for k in keys], 100,
    )


def q74(s, flavor):
    """TPC-DS q74: store-vs-web year-over-year growth per customer
    (q11's shape on ss_sales_price totals with name output)."""
    def year_total(prefix, table, cust, amt):
        j = _join(
            flavor,
            FilterExec(
                s["date_dim"](),
                (Col("d_year") >= 1998) & (Col("d_year") <= 1999),
            ),
            s[table](),
            ["d_date_sk"], [f"{prefix}_sold_date_sk"],
        )
        j = _join(
            flavor,
            s["customer"](),
            j, ["c_customer_sk"], [cust],
        )
        return _agg(
            j,
            keys=[(Col("c_customer_sk"), "sk"),
                  (Col("c_customer_id"), "cid"),
                  (Col("c_first_name"), "first"),
                  (Col("c_last_name"), "last"),
                  (Col("d_year"), "year")],
            aggs=[(AggExpr(AggFn.SUM, Col(amt)), "year_total")],
        )

    s_yt = year_total("ss", "store_sales", "ss_customer_sk",
                      "ss_sales_price")
    w_yt = year_total("ws", "web_sales", "ws_bill_customer_sk",
                      "ws_ext_sales_price")

    def pick(src, year, names):
        return RenameColumnsExec(
            ProjectExec(
                FilterExec(src, Col("year") == year),
                [(Col("sk"), "sk"), (Col("cid"), "cid"),
                 (Col("first"), "first"), (Col("last"), "last"),
                 (Col("year_total"), "yt")],
            ),
            names,
        )

    s1 = pick(s_yt, 1998, ["sk1", "cid1", "first1", "last1", "yt_s1"])
    s2 = pick(s_yt, 1999, ["sk2", "cid2", "first2", "last2", "yt_s2"])
    w1 = pick(w_yt, 1998, ["sk3", "cid3", "first3", "last3", "yt_w1"])
    w2 = pick(w_yt, 1999, ["sk4", "cid4", "first4", "last4", "yt_w2"])
    m = _join(flavor, s1, s2, ["sk1"], ["sk2"])
    m = _join(flavor, m, w1, ["sk1"], ["sk3"])
    m = _join(flavor, m, w2, ["sk1"], ["sk4"])
    kept = FilterExec(
        m,
        (Col("yt_s1") > 0.0) & (Col("yt_w1") > 0.0)
        & ((Col("yt_w2") / Col("yt_w1"))
           > (Col("yt_s2") / Col("yt_s1"))),
    )
    out = ProjectExec(
        kept,
        [(Col("cid1"), "customer_id"), (Col("first1"), "first_name"),
         (Col("last1"), "last_name")],
    )
    return _sorted_limit(
        out,
        [SortKey(Col("customer_id"), True, True)],
        100,
    )


def q92(s, flavor):
    """TPC-DS q92: web discounts above 1.3x the item's window average
    (q32's shape on web sales)."""
    ws = _join(
        flavor,
        FilterExec(
            s["date_dim"](),
            (Col("d_year") == 1999) & (Col("d_moy") <= 3),
        ),
        s["web_sales"](),
        ["d_date_sk"], ["ws_sold_date_sk"],
    )
    thresholds = ProjectExec(
        _agg(
            ws,
            keys=[(Col("ws_item_sk"), "t_item_sk")],
            aggs=[(AggExpr(AggFn.AVG, Col("ws_ext_discount_amt")),
                   "avg_disc")],
        ),
        [(Col("t_item_sk"), "t_item_sk"),
         (Col("avg_disc") * 1.3, "threshold")],
    )
    over = FilterExec(
        _join(flavor, thresholds, ws, ["t_item_sk"], ["ws_item_sk"]),
        Col("ws_ext_discount_amt") > Col("threshold"),
    )
    return _agg(
        over,
        keys=[],
        aggs=[(AggExpr(AggFn.SUM, Col("ws_ext_discount_amt")),
               "excess_discount")],
    )


def q93(s, flavor):
    """TPC-DS q93: per-customer store revenue with reason-specific
    return netting (sale rows LEFT-joined to their returns by
    ticket+item)."""
    sr_r = _join(
        flavor,
        s["reason"](),
        s["store_returns"](),
        ["r_reason_sk"], ["sr_reason_sk"],
    )
    sr_r = ProjectExec(
        sr_r,
        [(Col("sr_ticket_number"), "r_ticket"),
         (Col("sr_item_sk"), "r_item"),
         (Col("sr_return_quantity"), "r_qty"),
         (Col("r_reason_desc"), "r_desc")],
    )
    j = _join(flavor, s["store_sales"](), sr_r,
              ["ss_ticket_number", "ss_item_sk"],
              ["r_ticket", "r_item"], JoinType.LEFT)
    act = ProjectExec(
        j,
        [(Col("ss_customer_sk"), "cust"),
         (If(
             Col("r_desc") == "reason 3",
             (Col("ss_quantity").cast(DataType.float64())
              - Col("r_qty").cast(DataType.float64()))
             * Col("ss_sales_price"),
             Col("ss_quantity").cast(DataType.float64())
             * Col("ss_sales_price"),
         ), "act_sales")],
    )
    agg = _agg(
        act,
        keys=[(Col("cust"), "ss_customer_sk")],
        aggs=[(AggExpr(AggFn.SUM, Col("act_sales")), "sumsales")],
    )
    return _sorted_limit(
        agg,
        [SortKey(Col("sumsales"), True, True),
         SortKey(Col("ss_customer_sk"), True, True)],
        100,
    )


def q97(s, flavor):
    """TPC-DS q97: store/catalog purchase overlap - distinct
    (customer, item) pairs per channel FULL-outer-joined, counted by
    presence."""
    def pairs(prefix, table, cust, ren):
        j = _join(
            flavor,
            FilterExec(
                s["date_dim"](),
                (Col("d_month_seq") >= 1188)
                & (Col("d_month_seq") <= 1199),
            ),
            s[table](),
            ["d_date_sk"], [f"{prefix}_sold_date_sk"],
        )
        return RenameColumnsExec(
            _agg(
                j,
                keys=[(Col(cust), "c"), (Col(f"{prefix}_item_sk"), "i")],
                aggs=[],
            ),
            ren,
        )

    ssci = pairs("ss", "store_sales", "ss_customer_sk",
                 ["s_cust", "s_item"])
    csci = pairs("cs", "catalog_sales", "cs_bill_customer_sk",
                 ["c_cust", "c_item"])
    j = _join(flavor, ssci, csci, ["s_cust", "s_item"],
              ["c_cust", "c_item"], JoinType.FULL)
    flags = ProjectExec(
        j,
        [(If(IsNotNull(Col("s_cust")) & ~IsNotNull(Col("c_cust")),
             Literal(1, DataType.int64()), Literal(0, DataType.int64())),
          "store_only"),
         (If(~IsNotNull(Col("s_cust")) & IsNotNull(Col("c_cust")),
             Literal(1, DataType.int64()), Literal(0, DataType.int64())),
          "catalog_only"),
         (If(IsNotNull(Col("s_cust")) & IsNotNull(Col("c_cust")),
             Literal(1, DataType.int64()), Literal(0, DataType.int64())),
          "both")],
    )
    return _agg(
        flags,
        keys=[],
        aggs=[(AggExpr(AggFn.SUM, Col("store_only")), "store_only"),
              (AggExpr(AggFn.SUM, Col("catalog_only")), "catalog_only"),
              (AggExpr(AggFn.SUM, Col("both")), "store_and_catalog")],
    )


QUERIES.update({
    "q31": q31, "q35": q35, "q39": q39, "q49": q49, "q65": q65,
    "q69": q69, "q74": q74, "q92": q92, "q93": q93, "q97": q97,
})


# ---------------------------------------------------------------------------
# q56/q58/q60/q61/q62/q71/q82/q86/q87/q91/q99 block (cross-channel item
# sets, shipping latency, call-center returns)
# ---------------------------------------------------------------------------

_GEN_V5 = gen_tables

N_SHIP_MODES = 5
N_WEB_SITES = 6


def gen_tables(seed: int = 20260729):  # noqa: F811 - extend again
    t = _GEN_V5(seed)
    rng = np.random.default_rng(seed + 23)
    cs = t["catalog_sales"]
    n_cs = len(cs)
    cs["cs_bill_addr_sk"] = pd.array(
        np.where(
            rng.random(n_cs) < 0.02, np.nan,
            rng.integers(0, N_ADDRESSES, n_cs).astype(np.float64),
        ),
        dtype=pd.Int32Dtype(),
    )
    cs["cs_sold_time_sk"] = rng.integers(0, N_TIMES, n_cs).astype(
        np.int32)
    # shipping: ship date lags the sale by 1-120 days
    for pre, frame in (("cs", cs), ("ws", t["web_sales"])):
        n = len(frame)
        sold = frame[f"{pre}_sold_date_sk"].to_numpy(
            dtype=np.float64, na_value=np.nan)
        lag = rng.integers(1, 121, n)
        ship = sold + lag
        frame[f"{pre}_ship_date_sk"] = pd.array(
            ship, dtype=pd.Int32Dtype())
        frame[f"{pre}_ship_mode_sk"] = rng.integers(
            0, N_SHIP_MODES, n).astype(np.int32)
        frame[f"{pre}_warehouse_sk"] = rng.integers(
            0, N_WAREHOUSES, n).astype(np.int32)
    t["web_sales"]["ws_web_site_sk"] = rng.integers(
        0, N_WEB_SITES, len(t["web_sales"])).astype(np.int32)
    t["ship_mode"] = pd.DataFrame(
        {
            "sm_ship_mode_sk": np.arange(N_SHIP_MODES, dtype=np.int32),
            "sm_type": np.array(
                ["EXPRESS", "OVERNIGHT", "REGULAR", "TWO DAY", "LIBRARY"],
                dtype=object),
        }
    )
    t["web_site"] = pd.DataFrame(
        {
            "web_site_sk": np.arange(N_WEB_SITES, dtype=np.int32),
            "web_name": [f"site_{i}" for i in range(N_WEB_SITES)],
        }
    )
    pr = t["promotion"]
    n_pr = len(pr)
    pr["p_channel_dmail"] = np.array(
        ["Y", "N"], dtype=object)[rng.integers(0, 2, n_pr)]
    pr["p_channel_tv"] = np.array(
        ["Y", "N"], dtype=object)[rng.integers(0, 2, n_pr)]
    cr = t["catalog_returns"]
    n_cr = len(cr)
    cr["cr_call_center_sk"] = rng.integers(0, 4, n_cr).astype(np.int32)
    cr["cr_returning_customer_sk"] = pd.array(
        np.where(
            rng.random(n_cr) < 0.02, np.nan,
            rng.integers(0, N_CUSTOMERS, n_cr).astype(np.float64),
        ),
        dtype=pd.Int32Dtype(),
    )
    t["customer"]["c_current_hdemo_sk"] = rng.integers(
        0, N_HDEMO, len(t["customer"])).astype(np.int32)
    return t


def _item_set_channels(s, flavor, item_pred, out_key):
    """q56/q60 shape: revenue of an item-attribute-selected set summed
    across all three channels (item set via i_item_id semi join)."""
    ids = _agg(
        FilterExec(s["item"](), item_pred),
        keys=[(Col("i_item_id"), "sel_id")], aggs=[],
    )

    def channel(prefix, table):
        j = _join(
            flavor,
            FilterExec(
                s["date_dim"](),
                (Col("d_year") == 1999) & (Col("d_moy") == 2),
            ),
            s[table](),
            ["d_date_sk"], [f"{prefix}_sold_date_sk"],
        )
        j = _join(flavor, s["item"](), j,
                  ["i_item_sk"], [f"{prefix}_item_sk"])
        j = _semi(flavor, j, ids, ["i_item_id"], ["sel_id"])
        return _agg(
            j,
            keys=[(Col("i_item_id"), out_key)],
            aggs=[(AggExpr(AggFn.SUM, Col(f"{prefix}_ext_sales_price")),
                   "total_sales")],
        )

    all_ch = _union([
        channel("ss", "store_sales"),
        channel("cs", "catalog_sales"),
        channel("ws", "web_sales"),
    ])
    return _agg(
        all_ch,
        keys=[(Col(out_key), out_key)],
        aggs=[(AggExpr(AggFn.SUM, Col("total_sales")), "total_sales")],
    )


def q56(s, flavor):
    """TPC-DS q56: cross-channel revenue of color-selected items."""
    def slit(v):
        return Literal(v, DataType.utf8())

    agg = _item_set_channels(
        s, flavor,
        InList(Col("i_color"), (slit("red"), slit("navy"),
                                slit("khaki"))),
        "i_item_id",
    )
    return _sorted_limit(
        agg,
        [SortKey(Col("total_sales"), True, True),
         SortKey(Col("i_item_id"), True, True)],
        100,
    )


def q60(s, flavor):
    """TPC-DS q60: cross-channel revenue of one category's items."""
    agg = _item_set_channels(
        s, flavor, Col("i_category") == "Music", "i_item_id",
    )
    return _sorted_limit(
        agg,
        [SortKey(Col("i_item_id"), True, True),
         SortKey(Col("total_sales"), True, True)],
        100,
    )


def q58(s, flavor):
    """TPC-DS q58: items whose one-week revenue is within 10% across
    all three channels simultaneously."""
    def channel(prefix, table, out):
        j = _join(
            flavor,
            FilterExec(
                s["date_dim"](),
                (Col("d_week_seq") == 60),
            ),
            s[table](),
            ["d_date_sk"], [f"{prefix}_sold_date_sk"],
        )
        j = _join(flavor, s["item"](), j,
                  ["i_item_sk"], [f"{prefix}_item_sk"])
        return _agg(
            j,
            keys=[(Col("i_item_id"), f"id_{out}")],
            aggs=[(AggExpr(AggFn.SUM, Col(f"{prefix}_ext_sales_price")),
                   out)],
        )

    ss = channel("ss", "store_sales", "ss_rev")
    cs = channel("cs", "catalog_sales", "cs_rev")
    ws = channel("ws", "web_sales", "ws_rev")
    j = _join(flavor, ss, cs, ["id_ss_rev"], ["id_cs_rev"])
    j = _join(flavor, j, ws, ["id_ss_rev"], ["id_ws_rev"])
    avg3 = (Col("ss_rev") + Col("cs_rev") + Col("ws_rev")) / 3.0
    within = FilterExec(
        ProjectExec(
            j,
            [(Col("id_ss_rev"), "item_id"),
             (Col("ss_rev"), "ss_rev"), (Col("cs_rev"), "cs_rev"),
             (Col("ws_rev"), "ws_rev"), (avg3, "average")],
        ),
        (Col("ss_rev") >= Col("average") * 0.9)
        & (Col("ss_rev") <= Col("average") * 1.1)
        & (Col("cs_rev") >= Col("average") * 0.9)
        & (Col("cs_rev") <= Col("average") * 1.1)
        & (Col("ws_rev") >= Col("average") * 0.9)
        & (Col("ws_rev") <= Col("average") * 1.1),
    )
    return _sorted_limit(
        within,
        [SortKey(Col("item_id"), True, True),
         SortKey(Col("ss_rev"), True, True)],
        100,
    )


def q61(s, flavor):
    """TPC-DS q61: promotional store revenue share (two scalar sums on
    a constant key)."""
    def base(promo):
        j = _join(
            flavor,
            FilterExec(
                s["date_dim"](),
                (Col("d_year") == 1999) & (Col("d_moy") == 11),
            ),
            s["store_sales"](),
            ["d_date_sk"], ["ss_sold_date_sk"],
        )
        j = _join(
            flavor,
            FilterExec(s["item"](), Col("i_category") == "Books"),
            j, ["i_item_sk"], ["ss_item_sk"],
        )
        if promo:
            pr = FilterExec(
                s["promotion"](),
                (Col("p_channel_dmail") == "Y")
                | (Col("p_channel_email") == "Y")
                | (Col("p_channel_tv") == "Y"),
            )
            j = _join(flavor, pr, j, ["p_promo_sk"], ["ss_promo_sk"])
        name = "promotions" if promo else "total"
        return ProjectExec(
            _agg(j, keys=[],
                 aggs=[(AggExpr(AggFn.SUM, Col("ss_ext_sales_price")),
                        name)]),
            [(Literal(1, DataType.int32()), f"{name}_k"),
             (Col(name), name)],
        )

    both = _join(flavor, base(True), base(False),
                 ["promotions_k"], ["total_k"])
    return ProjectExec(
        both,
        [(Col("promotions"), "promotions"), (Col("total"), "total"),
         (Col("promotions") / Col("total") * 100.0, "pct")],
    )


def _ship_latency(s, flavor, prefix, sales, entity_scan, entity_sk,
                  entity_fk, entity_name):
    """q62/q99 shape: shipping-lag day buckets by warehouse, ship mode
    and site/call-center."""
    j = _join(
        flavor,
        FilterExec(
            s["date_dim"](),
            (Col("d_year") == 1999),
        ),
        s[sales](),
        ["d_date_sk"], [f"{prefix}_ship_date_sk"],
    )
    j = _join(flavor, s["warehouse"](), j,
              ["w_warehouse_sk"], [f"{prefix}_warehouse_sk"])
    j = _join(flavor, s["ship_mode"](), j,
              ["sm_ship_mode_sk"], [f"{prefix}_ship_mode_sk"])
    j = _join(flavor, entity_scan(), j, [entity_sk], [entity_fk])
    lag = (Col(f"{prefix}_ship_date_sk").cast(DataType.int64())
           - Col(f"{prefix}_sold_date_sk").cast(DataType.int64()))

    def bucket(lo, hi, name):
        if lo is None:
            cond = lag <= hi
        elif hi is None:
            cond = lag > lo
        else:
            cond = (lag > lo) & (lag <= hi)
        return (AggExpr(AggFn.SUM, If(
            cond, Literal(1, DataType.int64()),
            Literal(0, DataType.int64()))), name)

    return _agg(
        j,
        keys=[(Col("w_warehouse_name"), "warehouse"),
              (Col("sm_type"), "sm_type"),
              (Col(entity_name), "site")],
        aggs=[bucket(None, 30, "d30"), bucket(30, 60, "d60"),
              bucket(60, 90, "d90"), bucket(90, 120, "d120"),
              bucket(120, None, "dmore")],
    )


def q62(s, flavor):
    """TPC-DS q62: web shipping-latency buckets."""
    agg = _ship_latency(
        s, flavor, "ws", "web_sales",
        s["web_site"], "web_site_sk", "ws_web_site_sk", "web_name",
    )
    return _sorted_limit(
        agg,
        [SortKey(Col("warehouse"), True, True),
         SortKey(Col("sm_type"), True, True),
         SortKey(Col("site"), True, True)],
        100,
    )


def q99(s, flavor):
    """TPC-DS q99: catalog shipping-latency buckets by call center."""
    agg = _ship_latency(
        s, flavor, "cs", "catalog_sales",
        s["call_center"], "cc_call_center_sk", "cs_call_center_sk",
        "cc_name",
    )
    return _sorted_limit(
        agg,
        [SortKey(Col("warehouse"), True, True),
         SortKey(Col("sm_type"), True, True),
         SortKey(Col("site"), True, True)],
        100,
    )


def q71(s, flavor):
    """TPC-DS q71: one manager's brand revenue by breakfast/dinner
    hours across channels."""
    def channel(prefix, table, time_col):
        j = _join(
            flavor,
            FilterExec(
                s["date_dim"](),
                (Col("d_year") == 1999) & (Col("d_moy") == 12),
            ),
            s[table](),
            ["d_date_sk"], [f"{prefix}_sold_date_sk"],
        )
        return ProjectExec(
            j,
            [(Col(f"{prefix}_ext_sales_price"), "ext_price"),
             (Col(f"{prefix}_item_sk"), "sold_item_sk"),
             (Col(time_col), "time_sk")],
        )

    all_ch = _union([
        channel("ws", "web_sales", "ws_sold_time_sk"),
        channel("cs", "catalog_sales", "cs_sold_time_sk"),
        channel("ss", "store_sales", "ss_sold_time_sk"),
    ])
    j = _join(
        flavor,
        FilterExec(s["item"](), Col("i_manager_id") == 1),
        all_ch,
        ["i_item_sk"], ["sold_item_sk"],
    )
    td = FilterExec(
        s["time_dim"](),
        ((Col("t_hour") >= 7) & (Col("t_hour") < 9))
        | ((Col("t_hour") >= 18) & (Col("t_hour") < 20)),
    )
    j = _join(flavor, td, j, ["t_time_sk"], ["time_sk"])
    agg = _agg(
        j,
        keys=[(Col("i_brand_id"), "brand_id"),
              (Col("i_brand"), "brand"),
              (Col("t_hour"), "t_hour"),
              (Col("t_minute"), "t_minute")],
        aggs=[(AggExpr(AggFn.SUM, Col("ext_price")), "ext_price")],
    )
    return SortExec(
        agg,
        [SortKey(Col("ext_price"), False, False),
         SortKey(Col("brand_id"), True, True),
         SortKey(Col("t_hour"), True, True),
         SortKey(Col("t_minute"), True, True)],
    )


def q82(s, flavor):
    """TPC-DS q82: store items with 100-500 units on hand in a price
    window (q37's shape on store sales)."""
    it = FilterExec(
        s["item"](),
        (Col("i_current_price") >= 30.0)
        & (Col("i_current_price") <= 60.0)
        & InList(Col("i_manufact_id"),
                 tuple(Literal(v, DataType.int32())
                       for v in (10, 20, 30, 40, 50, 60))),
    )
    inv = FilterExec(
        s["inventory"](),
        (Col("inv_quantity_on_hand") >= 100)
        & (Col("inv_quantity_on_hand") <= 500),
    )
    j = _join(flavor, it, inv, ["i_item_sk"], ["inv_item_sk"])
    j = _join(
        flavor,
        FilterExec(s["date_dim"](), Col("d_year") == 1999),
        j, ["d_date_sk"], ["inv_date_sk"],
    )
    j = _join(flavor, j, s["store_sales"](),
              ["i_item_sk"], ["ss_item_sk"])
    distinct = _agg(
        j,
        keys=[(Col("i_item_id"), "i_item_id"),
              (Col("i_item_desc"), "i_item_desc"),
              (Col("i_current_price"), "i_current_price")],
        aggs=[],
    )
    return _sorted_limit(
        distinct, [SortKey(Col("i_item_id"), True, True)], 100,
    )


def q86(s, flavor):
    """TPC-DS q86 (rollup as grouping-set union): web revenue by
    category/class with rollup rows and a within-parent rank."""
    from blaze_tpu.ops.window import WindowExec, WindowFn

    j = _join(
        flavor,
        FilterExec(
            s["date_dim"](),
            (Col("d_month_seq") >= 1188) & (Col("d_month_seq") <= 1199),
        ),
        s["web_sales"](),
        ["d_date_sk"], ["ws_sold_date_sk"],
    )
    j = _join(flavor, s["item"](), j, ["i_item_sk"], ["ws_item_sk"])
    base = _agg(
        j,
        keys=[(Col("i_category"), "i_category"),
              (Col("i_class"), "i_class")],
        aggs=[(AggExpr(AggFn.SUM, Col("ws_ext_sales_price")),
               "total_sum")],
    )
    lvl1 = ProjectExec(
        _agg(
            base,
            keys=[(Col("i_category"), "i_category")],
            aggs=[(AggExpr(AggFn.SUM, Col("total_sum")), "total_sum")],
        ),
        [(Col("i_category"), "i_category"),
         (Literal(None, DataType.utf8()), "i_class"),
         (Col("total_sum"), "total_sum"),
         (Literal(1, DataType.int64()), "lochierarchy")],
    )
    lvl0 = ProjectExec(
        base,
        [(Col("i_category"), "i_category"), (Col("i_class"), "i_class"),
         (Col("total_sum"), "total_sum"),
         (Literal(0, DataType.int64()), "lochierarchy")],
    )
    lvl2 = ProjectExec(
        _agg(base, keys=[],
             aggs=[(AggExpr(AggFn.SUM, Col("total_sum")),
                    "total_sum")]),
        [(Literal(None, DataType.utf8()), "i_category"),
         (Literal(None, DataType.utf8()), "i_class"),
         (Col("total_sum"), "total_sum"),
         (Literal(2, DataType.int64()), "lochierarchy")],
    )
    rolled = _union([lvl0, lvl1, lvl2])
    ranked = WindowExec(
        rolled,
        partition_by=[Col("lochierarchy"), If(
            Col("lochierarchy") == 0, Col("i_category"),
            Literal(None, DataType.utf8()))],
        order_by=[SortKey(Col("total_sum"), False, False)],
        functions=[WindowFn("rank", None, "rank_within_parent")],
    )
    return _sorted_limit(
        ranked,
        [SortKey(Col("lochierarchy"), False, False),
         SortKey(Col("i_category"), True, True),
         SortKey(Col("i_class"), True, True),
         SortKey(Col("rank_within_parent"), True, True)],
        100,
    )


def q87(s, flavor):
    """TPC-DS q87: store customer-days never seen in web or catalog
    (EXCEPT as anti joins on composite keys)."""
    def pairs(prefix, table, cust, ren):
        j = _join(
            flavor,
            FilterExec(
                s["date_dim"](),
                (Col("d_month_seq") >= 1188)
                & (Col("d_month_seq") <= 1199),
            ),
            s[table](),
            ["d_date_sk"], [f"{prefix}_sold_date_sk"],
        )
        return RenameColumnsExec(
            _agg(
                j,
                keys=[(Col(cust), "c"), (Col("d_date_sk"), "d")],
                aggs=[],
            ),
            ren,
        )

    ssd = pairs("ss", "store_sales", "ss_customer_sk", ["sc", "sd"])
    wsd = pairs("ws", "web_sales", "ws_bill_customer_sk", ["wc", "wd"])
    csd = pairs("cs", "catalog_sales", "cs_bill_customer_sk",
                ["cc", "cd"])
    rem = _join(flavor, ssd, wsd, ["sc", "sd"], ["wc", "wd"],
                JoinType.LEFT_ANTI)
    rem = _join(flavor, rem, csd, ["sc", "sd"], ["cc", "cd"],
                JoinType.LEFT_ANTI)
    return _agg(
        rem, keys=[],
        aggs=[(AggExpr(AggFn.COUNT_STAR, None), "num_store_only")],
    )


def q91(s, flavor):
    """TPC-DS q91: call-center catalog return losses by demographic
    segment and buy potential."""
    j = _join(
        flavor,
        FilterExec(
            s["date_dim"](),
            (Col("d_year") == 1999) & (Col("d_moy") == 11),
        ),
        s["catalog_returns"](),
        ["d_date_sk"], ["cr_returned_date_sk"],
    )
    j = _join(flavor, s["call_center"](), j,
              ["cc_call_center_sk"], ["cr_call_center_sk"])
    j = _join(flavor, j, s["customer"](),
              ["cr_returning_customer_sk"], ["c_customer_sk"])
    cd = FilterExec(
        s["customer_demographics"](),
        ((Col("cd_marital_status") == "M")
         & (Col("cd_education_status") == "College"))
        | ((Col("cd_marital_status") == "S")
           & (Col("cd_education_status") == "Primary")),
    )
    j = _join(flavor, cd, j, ["cd_demo_sk"], ["c_current_cdemo_sk"])
    hd = FilterExec(
        s["household_demographics"](),
        Col("hd_buy_potential") == ">10000",
    )
    j = _join(flavor, hd, j, ["hd_demo_sk"], ["c_current_hdemo_sk"])
    agg = _agg(
        j,
        keys=[(Col("cc_name"), "call_center"),
              (Col("cd_marital_status"), "marital"),
              (Col("cd_education_status"), "education")],
        aggs=[(AggExpr(AggFn.SUM, Col("cr_net_loss")), "net_loss")],
    )
    return SortExec(
        agg,
        [SortKey(Col("net_loss"), False, False),
         SortKey(Col("call_center"), True, True),
         SortKey(Col("marital"), True, True),
         SortKey(Col("education"), True, True)],
    )


QUERIES.update({
    "q56": q56, "q58": q58, "q60": q60, "q61": q61, "q62": q62,
    "q71": q71, "q82": q82, "q86": q86, "q87": q87, "q91": q91,
    "q99": q99,
})


# ---------------------------------------------------------------------------
# q66/q67/q70/q72/q75/q76/q77/q78 block (pivots, rollups, channel P&L)
# ---------------------------------------------------------------------------

_GEN_V6 = gen_tables


def gen_tables(seed: int = 20260729):  # noqa: F811 - extend again
    t = _GEN_V6(seed)
    rng = np.random.default_rng(seed + 29)
    st = t["store"]
    st["s_county"] = np.array(
        ["Rich County", "Ziebach County", "Walker County"],
        dtype=object)[np.arange(len(st)) % 3]
    cs = t["catalog_sales"]
    n_cs = len(cs)
    cs["cs_bill_hdemo_sk"] = rng.integers(0, N_HDEMO, n_cs).astype(
        np.int32)
    cs["cs_bill_cdemo_sk"] = rng.integers(0, N_CDEMO, n_cs).astype(
        np.int32)
    wr = t["web_returns"]
    wr["wr_web_page_sk"] = rng.integers(0, 20, len(wr)).astype(
        np.int32)
    return t


def q66(s, flavor):
    """TPC-DS q66: warehouse monthly shipped value for two carriers,
    web+catalog unioned, pivoted into 12 month columns."""
    def channel(prefix, table):
        j = _join(
            flavor,
            FilterExec(s["date_dim"](), Col("d_year") == 1999),
            s[table](),
            ["d_date_sk"], [f"{prefix}_sold_date_sk"],
        )
        j = _join(
            flavor,
            FilterExec(
                s["ship_mode"](),
                InList(Col("sm_type"),
                       (Literal("EXPRESS", DataType.utf8()),
                        Literal("REGULAR", DataType.utf8()))),
            ),
            j, ["sm_ship_mode_sk"], [f"{prefix}_ship_mode_sk"],
        )
        j = _join(flavor, s["warehouse"](), j,
                  ["w_warehouse_sk"], [f"{prefix}_warehouse_sk"])
        amt = Col(f"{prefix}_ext_sales_price")
        return _agg(
            j,
            keys=[(Col("w_warehouse_name"), "wname")],
            aggs=[
                (AggExpr(AggFn.SUM, If(
                    Col("d_moy") == m, amt,
                    Literal(None, DataType.float64()))), f"m{m}_sales")
                for m in range(1, 13)
            ],
        )

    both = _union([channel("ws", "web_sales"),
                   channel("cs", "catalog_sales")])
    total = _agg(
        both,
        keys=[(Col("wname"), "w_warehouse_name")],
        aggs=[(AggExpr(AggFn.SUM, Col(f"m{m}_sales")), f"m{m}_sales")
              for m in range(1, 13)],
    )
    return _sorted_limit(
        total, [SortKey(Col("w_warehouse_name"), True, True)], 100,
    )


def q67(s, flavor):
    """TPC-DS q67 (rollup as grouping-set union): store sales over the
    full (category,class,brand,product,year,qoy,moy,store) hierarchy,
    rank<=100 within category."""
    from blaze_tpu.ops.window import WindowExec, WindowFn

    j = _join(
        flavor,
        FilterExec(
            s["date_dim"](),
            (Col("d_month_seq") >= 1188) & (Col("d_month_seq") <= 1199),
        ),
        s["store_sales"](),
        ["d_date_sk"], ["ss_sold_date_sk"],
    )
    j = _join(flavor, s["item"](), j, ["i_item_sk"], ["ss_item_sk"])
    j = _join(
        flavor,
        ProjectExec(s["store"](), [(Col("s_store_sk"), "st_sk"),
                                   (Col("s_store_id"), "s_store_id")]),
        j, ["st_sk"], ["ss_store_sk"],
    )
    base_cols = ["i_category", "i_class", "i_brand", "i_product_name",
                 "d_year", "d_qoy", "d_moy", "s_store_id"]
    sales_expr = Col("ss_sales_price") * Col("ss_quantity").cast(
        DataType.float64())
    base = _agg(
        j,
        keys=[(Col(c), c) for c in base_cols],
        aggs=[(AggExpr(AggFn.SUM, sales_expr), "sumsales")],
    )

    def level(k):
        """Rollup level keeping the first k hierarchy columns."""
        keep = base_cols[:k]
        exprs = [(Col(c), c) for c in keep]
        for c in base_cols[k:]:
            dt = (DataType.utf8() if c.startswith(("i_", "s_"))
                  else DataType.int32())
            exprs.append((Literal(None, dt), c))
        exprs.append((Col("sumsales"), "sumsales"))
        if k == len(base_cols):
            return ProjectExec(base, exprs)
        agg = _agg(
            base,
            keys=[(Col(c), c) for c in keep],
            aggs=[(AggExpr(AggFn.SUM, Col("sumsales")), "sumsales")],
        )
        return ProjectExec(agg, exprs)

    rolled = _union([level(k) for k in range(len(base_cols) + 1)])
    ranked = WindowExec(
        rolled,
        partition_by=[Col("i_category")],
        order_by=[SortKey(Col("sumsales"), False, False)],
        functions=[WindowFn("rank", None, "rk")],
    )
    top = FilterExec(ranked, Col("rk") <= 100)
    return _sorted_limit(
        top,
        [SortKey(Col("i_category"), True, True),
         SortKey(Col("i_class"), True, True),
         SortKey(Col("i_brand"), True, True),
         SortKey(Col("i_product_name"), True, True),
         SortKey(Col("d_year"), True, True),
         SortKey(Col("d_qoy"), True, True),
         SortKey(Col("d_moy"), True, True),
         SortKey(Col("s_store_id"), True, True),
         SortKey(Col("sumsales"), True, True),
         SortKey(Col("rk"), True, True)],
        100,
    )


def q70(s, flavor):
    """TPC-DS q70: store profit rollup over top-5-profit states
    (ranked state subquery feeds a semi join)."""
    from blaze_tpu.ops.window import WindowExec, WindowFn

    def profit_base():
        j = _join(
            flavor,
            FilterExec(
                s["date_dim"](),
                (Col("d_month_seq") >= 1188)
                & (Col("d_month_seq") <= 1199),
            ),
            s["store_sales"](),
            ["d_date_sk"], ["ss_sold_date_sk"],
        )
        return _join(
            flavor,
            ProjectExec(s["store"](),
                        [(Col("s_store_sk"), "st_sk"),
                         (Col("s_state"), "s_state"),
                         (Col("s_county"), "s_county")]),
            j, ["st_sk"], ["ss_store_sk"],
        )

    by_state = _agg(
        profit_base(),
        keys=[(Col("s_state"), "r_state")],
        aggs=[(AggExpr(AggFn.SUM, Col("ss_net_profit")), "sp")],
    )
    ranked_states = ProjectExec(
        FilterExec(
            WindowExec(
                by_state,
                partition_by=[],
                order_by=[SortKey(Col("sp"), False, False)],
                functions=[WindowFn("rank", None, "rnk")],
            ),
            Col("rnk") <= 5,
        ),
        [(Col("r_state"), "r_state")],
    )
    qualified = _semi(
        flavor, profit_base(), ranked_states,
        ["s_state"], ["r_state"],
    )
    base = _agg(
        qualified,
        keys=[(Col("s_state"), "s_state"), (Col("s_county"), "s_county")],
        aggs=[(AggExpr(AggFn.SUM, Col("ss_net_profit")),
               "total_sum")],
    )
    lvl0 = ProjectExec(
        base,
        [(Col("s_state"), "s_state"), (Col("s_county"), "s_county"),
         (Col("total_sum"), "total_sum"),
         (Literal(0, DataType.int64()), "lochierarchy")],
    )
    lvl1 = ProjectExec(
        _agg(base, keys=[(Col("s_state"), "s_state")],
             aggs=[(AggExpr(AggFn.SUM, Col("total_sum")),
                    "total_sum")]),
        [(Col("s_state"), "s_state"),
         (Literal(None, DataType.utf8()), "s_county"),
         (Col("total_sum"), "total_sum"),
         (Literal(1, DataType.int64()), "lochierarchy")],
    )
    lvl2 = ProjectExec(
        _agg(base, keys=[],
             aggs=[(AggExpr(AggFn.SUM, Col("total_sum")),
                    "total_sum")]),
        [(Literal(None, DataType.utf8()), "s_state"),
         (Literal(None, DataType.utf8()), "s_county"),
         (Col("total_sum"), "total_sum"),
         (Literal(2, DataType.int64()), "lochierarchy")],
    )
    rolled = _union([lvl0, lvl1, lvl2])
    ranked = WindowExec(
        rolled,
        partition_by=[Col("lochierarchy"), If(
            Col("lochierarchy") == 0, Col("s_state"),
            Literal(None, DataType.utf8()))],
        order_by=[SortKey(Col("total_sum"), False, False)],
        functions=[WindowFn("rank", None, "rank_within_parent")],
    )
    return _sorted_limit(
        ranked,
        [SortKey(Col("lochierarchy"), False, False),
         SortKey(Col("s_state"), True, True),
         SortKey(Col("s_county"), True, True),
         SortKey(Col("rank_within_parent"), True, True)],
        100,
    )


def q72(s, flavor):
    """TPC-DS q72: catalog orders whose warehouse stock in the sale
    week cannot cover the ordered quantity, by buy-potential/marital
    segment, only slow shipments (>5 day lag)."""
    j = _join(
        flavor,
        ProjectExec(
            FilterExec(s["date_dim"](), Col("d_year") == 1999),
            [(Col("d_date_sk"), "sold_sk"),
             (Col("d_week_seq"), "sold_week")],
        ),
        s["catalog_sales"](),
        ["sold_sk"], ["cs_sold_date_sk"],
    )
    j = FilterExec(
        j,
        (Col("cs_ship_date_sk").cast(DataType.int64())
         - Col("cs_sold_date_sk").cast(DataType.int64())) > 5,
    )
    inv = _join(
        flavor, s["warehouse"](), s["inventory"](),
        ["w_warehouse_sk"], ["inv_warehouse_sk"],
    )
    inv = _join(
        flavor,
        ProjectExec(s["date_dim"](),
                    [(Col("d_date_sk"), "inv_d_sk"),
                     (Col("d_week_seq"), "inv_week")]),
        inv, ["inv_d_sk"], ["inv_date_sk"],
    )
    j = _join(
        flavor, j, inv, ["cs_item_sk"], ["inv_item_sk"],
    )
    j = FilterExec(
        j,
        (Col("inv_quantity_on_hand") < Col("cs_quantity"))
        & (Col("inv_week") == Col("sold_week")),
    )
    hd = FilterExec(
        s["household_demographics"](),
        Col("hd_buy_potential") == ">10000",
    )
    j = _join(flavor, hd, j, ["hd_demo_sk"], ["cs_bill_hdemo_sk"])
    cd = FilterExec(
        s["customer_demographics"](), Col("cd_marital_status") == "M",
    )
    j = _join(flavor, cd, j, ["cd_demo_sk"], ["cs_bill_cdemo_sk"])
    j = _join(flavor, s["item"](), j, ["i_item_sk"], ["cs_item_sk"])
    agg = _agg(
        j,
        keys=[(Col("i_item_desc"), "i_item_desc"),
              (Col("w_warehouse_name"), "w_warehouse_name"),
              (Col("sold_week"), "d_week_seq")],
        aggs=[(AggExpr(AggFn.COUNT_STAR, None), "no_promo")],
    )
    return _sorted_limit(
        agg,
        [SortKey(Col("no_promo"), False, False),
         SortKey(Col("i_item_desc"), True, True),
         SortKey(Col("w_warehouse_name"), True, True),
         SortKey(Col("d_week_seq"), True, True)],
        100,
    )


def q75(s, flavor):
    """TPC-DS q75: brand-level net sales (sales minus returned
    quantity/amount) per channel, year-over-year decline."""
    def channel(prefix, table, rets, s_keys, r_keys, qty, amt, r_qty,
                r_amt):
        sales = _join(
            flavor,
            FilterExec(
                s["date_dim"](),
                (Col("d_year") >= 1998) & (Col("d_year") <= 1999),
            ),
            s[table](),
            ["d_date_sk"], [f"{prefix}_sold_date_sk"],
        )
        sales = _join(
            flavor,
            FilterExec(s["item"](), Col("i_category") == "Books"),
            sales, ["i_item_sk"], [f"{prefix}_item_sk"],
        )
        j = _join(flavor, sales, s[rets](), s_keys, r_keys,
                  JoinType.LEFT)
        return ProjectExec(
            j,
            [(Col("d_year"), "d_year"),
             (Col("i_brand_id"), "i_brand_id"),
             (Col(qty) - Coalesce(
                 (Col(r_qty), Literal(0, DataType.int32()))),
              "sales_cnt"),
             (Col(amt) - Coalesce(
                 (Col(r_amt), Literal(0.0, DataType.float64()))),
              "sales_amt")],
        )

    allch = _union([
        channel("cs", "catalog_sales", "catalog_returns",
                ["cs_order_number", "cs_item_sk"],
                ["cr_order_number", "cr_item_sk"],
                "cs_quantity", "cs_ext_sales_price",
                "cr_return_quantity", "cr_return_amount"),
        channel("ss", "store_sales", "store_returns",
                ["ss_ticket_number", "ss_item_sk"],
                ["sr_ticket_number", "sr_item_sk"],
                "ss_quantity", "ss_ext_sales_price",
                "sr_return_quantity", "sr_return_amt"),
        channel("ws", "web_sales", "web_returns",
                ["ws_order_number", "ws_item_sk"],
                ["wr_order_number", "wr_item_sk"],
                "ws_quantity", "ws_ext_sales_price",
                "wr_return_quantity", "wr_return_amt"),
    ])
    by_year = _agg(
        allch,
        keys=[(Col("d_year"), "d_year"),
              (Col("i_brand_id"), "i_brand_id")],
        aggs=[(AggExpr(AggFn.SUM, Col("sales_cnt")), "sales_cnt"),
              (AggExpr(AggFn.SUM, Col("sales_amt")), "sales_amt")],
    )
    prev = RenameColumnsExec(
        FilterExec(by_year, Col("d_year") == 1998),
        ["py", "pb", "prev_cnt", "prev_amt"],
    )
    curr = RenameColumnsExec(
        FilterExec(by_year, Col("d_year") == 1999),
        ["cy", "cb", "curr_cnt", "curr_amt"],
    )
    m = _join(flavor, prev, curr, ["pb"], ["cb"])
    decline = FilterExec(
        m,
        Col("curr_cnt").cast(DataType.float64())
        / Col("prev_cnt").cast(DataType.float64()) < 0.9,
    )
    out = ProjectExec(
        decline,
        [(Col("py"), "prev_year"), (Col("cy"), "year"),
         (Col("pb"), "i_brand_id"),
         (Col("prev_cnt"), "prev_yr_cnt"),
         (Col("curr_cnt"), "curr_yr_cnt"),
         (Col("curr_cnt") - Col("prev_cnt"), "sales_cnt_diff"),
         (Col("curr_amt") - Col("prev_amt"), "sales_amt_diff")],
    )
    return _sorted_limit(
        out,
        [SortKey(Col("sales_cnt_diff"), True, True),
         SortKey(Col("i_brand_id"), True, True)],
        100,
    )


def q76(s, flavor):
    """TPC-DS q76: volume and value of sales rows with NULL keys,
    per channel/year/category."""
    def channel(label, prefix, table, null_col, amt):
        j = _join(
            flavor,
            s["date_dim"](),
            FilterExec(s[table](), ~IsNotNull(Col(null_col))),
            ["d_date_sk"], [f"{prefix}_sold_date_sk"],
        )
        j = _join(flavor, s["item"](), j,
                  ["i_item_sk"], [f"{prefix}_item_sk"])
        return ProjectExec(
            j,
            [(Literal(label, DataType.utf8()), "channel"),
             (Literal(null_col, DataType.utf8()), "col_name"),
             (Col("d_year"), "d_year"),
             (Col("i_category"), "i_category"),
             (Col(amt), "ext_sales_price")],
        )

    allch = _union([
        channel("store", "ss", "store_sales", "ss_customer_sk",
                "ss_ext_sales_price"),
        channel("web", "ws", "web_sales", "ws_bill_customer_sk",
                "ws_ext_sales_price"),
        channel("catalog", "cs", "catalog_sales", "cs_bill_addr_sk",
                "cs_ext_sales_price"),
    ])
    agg = _agg(
        allch,
        keys=[(Col("channel"), "channel"),
              (Col("col_name"), "col_name"),
              (Col("d_year"), "d_year"),
              (Col("i_category"), "i_category")],
        aggs=[(AggExpr(AggFn.COUNT_STAR, None), "sales_cnt"),
              (AggExpr(AggFn.SUM, Col("ext_sales_price")),
               "sales_amt")],
    )
    return _sorted_limit(
        agg,
        [SortKey(Col("channel"), True, True),
         SortKey(Col("col_name"), True, True),
         SortKey(Col("d_year"), True, True),
         SortKey(Col("i_category"), True, True)],
        100,
    )


def q77(s, flavor):
    """TPC-DS q77: per-channel profit & loss (sales vs returns) with
    channel totals (rollup as union)."""
    dd = lambda: FilterExec(  # noqa: E731
        s["date_dim"](),
        (Col("d_year") == 1999) & (Col("d_moy") <= 2),
    )

    def side(table, date_col, key_col, out_key, aggs):
        j = _join(flavor, dd(), s[table](), ["d_date_sk"], [date_col])
        return _agg(
            j, keys=[(Col(key_col), out_key)], aggs=aggs,
        )

    ss = side("store_sales", "ss_sold_date_sk", "ss_store_sk", "s_sk",
              [(AggExpr(AggFn.SUM, Col("ss_ext_sales_price")), "sales"),
               (AggExpr(AggFn.SUM, Col("ss_net_profit")), "profit")])
    sr = side("store_returns", "sr_returned_date_sk", "sr_store_sk",
              "r_sk",
              [(AggExpr(AggFn.SUM, Col("sr_return_amt")), "returns_"),
               (AggExpr(AggFn.SUM, Col("sr_net_loss")), "loss")])
    store = ProjectExec(
        _join(flavor, ss, sr, ["s_sk"], ["r_sk"], JoinType.LEFT),
        [(Literal("store channel", DataType.utf8()), "channel"),
         (Col("s_sk").cast(DataType.int64()), "id"),
         (Col("sales"), "sales"),
         (Coalesce((Col("returns_"),
                    Literal(0.0, DataType.float64()))), "returns_"),
         (Col("profit") - Coalesce(
             (Col("loss"), Literal(0.0, DataType.float64()))),
          "profit")],
    )
    cs_tot = ProjectExec(
        _agg(_join(flavor, dd(), s["catalog_sales"](),
                   ["d_date_sk"], ["cs_sold_date_sk"]),
             keys=[],
             aggs=[(AggExpr(AggFn.SUM, Col("cs_ext_sales_price")),
                    "sales"),
                   (AggExpr(AggFn.SUM, Col("cs_ext_discount_amt")),
                    "profit")]),
        [(Literal(1, DataType.int32()), "k"), (Col("sales"), "sales"),
         (Col("profit"), "profit")],
    )
    cr_tot = ProjectExec(
        _agg(_join(flavor, dd(), s["catalog_returns"](),
                   ["d_date_sk"], ["cr_returned_date_sk"]),
             keys=[],
             aggs=[(AggExpr(AggFn.SUM, Col("cr_return_amount")),
                    "returns_"),
                   (AggExpr(AggFn.SUM, Col("cr_net_loss")), "loss")]),
        [(Literal(1, DataType.int32()), "rk"),
         (Col("returns_"), "returns_"), (Col("loss"), "loss")],
    )
    catalog = ProjectExec(
        _join(flavor, cs_tot, cr_tot, ["k"], ["rk"]),
        [(Literal("catalog channel", DataType.utf8()), "channel"),
         (Literal(None, DataType.int64()), "id"),
         (Col("sales"), "sales"), (Col("returns_"), "returns_"),
         (Col("profit") - Col("loss"), "profit")],
    )
    ws_side = side("web_sales", "ws_sold_date_sk", "ws_web_page_sk",
                   "p_sk",
                   [(AggExpr(AggFn.SUM, Col("ws_ext_sales_price")),
                     "sales"),
                    (AggExpr(AggFn.SUM, Col("ws_ext_discount_amt")),
                     "profit")])
    wr_side = side("web_returns", "wr_returned_date_sk",
                   "wr_web_page_sk", "rp_sk",
                   [(AggExpr(AggFn.SUM, Col("wr_return_amt")),
                     "returns_"),
                    (AggExpr(AggFn.SUM, Col("wr_net_loss")), "loss")])
    web = ProjectExec(
        _join(flavor, ws_side, wr_side, ["p_sk"], ["rp_sk"],
              JoinType.LEFT),
        [(Literal("web channel", DataType.utf8()), "channel"),
         (Col("p_sk").cast(DataType.int64()), "id"),
         (Col("sales"), "sales"),
         (Coalesce((Col("returns_"),
                    Literal(0.0, DataType.float64()))), "returns_"),
         (Col("profit") - Coalesce(
             (Col("loss"), Literal(0.0, DataType.float64()))),
          "profit")],
    )
    detail = _union([store, catalog, web])
    by_channel = ProjectExec(
        _agg(detail,
             keys=[(Col("channel"), "channel")],
             aggs=[(AggExpr(AggFn.SUM, Col("sales")), "sales"),
                   (AggExpr(AggFn.SUM, Col("returns_")), "returns_"),
                   (AggExpr(AggFn.SUM, Col("profit")), "profit")]),
        [(Col("channel"), "channel"),
         (Literal(None, DataType.int64()), "id"),
         (Col("sales"), "sales"), (Col("returns_"), "returns_"),
         (Col("profit"), "profit")],
    )
    grand = ProjectExec(
        _agg(detail, keys=[],
             aggs=[(AggExpr(AggFn.SUM, Col("sales")), "sales"),
                   (AggExpr(AggFn.SUM, Col("returns_")), "returns_"),
                   (AggExpr(AggFn.SUM, Col("profit")), "profit")]),
        [(Literal(None, DataType.utf8()), "channel"),
         (Literal(None, DataType.int64()), "id"),
         (Col("sales"), "sales"), (Col("returns_"), "returns_"),
         (Col("profit"), "profit")],
    )
    rolled = _union([detail, by_channel, grand])
    return _sorted_limit(
        rolled,
        [SortKey(Col("channel"), True, True),
         SortKey(Col("id"), True, True),
         SortKey(Col("sales"), True, True)],
        100,
    )


def q78(s, flavor):
    """TPC-DS q78: customer-item yearly sales with NO return, store vs
    web ratio (anti-joined returns, FULL-ish comparison via inner join
    on both channels present)."""
    def channel(prefix, table, rets, s_keys, r_keys, cust, qty, amt,
                ren):
        sales = _join(
            flavor,
            FilterExec(
                s["date_dim"](),
                (Col("d_year") == 1999),
            ),
            s[table](),
            ["d_date_sk"], [f"{prefix}_sold_date_sk"],
        )
        no_ret = _join(flavor, sales, s[rets](), s_keys, r_keys,
                       JoinType.LEFT_ANTI)
        return RenameColumnsExec(
            _agg(
                no_ret,
                keys=[(Col(f"{prefix}_item_sk"), "item"),
                      (Col(cust), "cust")],
                aggs=[(AggExpr(AggFn.SUM, Col(qty)), "qty"),
                      (AggExpr(AggFn.SUM, Col(amt)), "amt")],
            ),
            ren,
        )

    ss = channel("ss", "store_sales", "store_returns",
                 ["ss_ticket_number", "ss_item_sk"],
                 ["sr_ticket_number", "sr_item_sk"],
                 "ss_customer_sk", "ss_quantity",
                 "ss_ext_sales_price",
                 ["ss_item", "ss_cust", "ss_qty", "ss_amt"])
    ws = channel("ws", "web_sales", "web_returns",
                 ["ws_order_number", "ws_item_sk"],
                 ["wr_order_number", "wr_item_sk"],
                 "ws_bill_customer_sk", "ws_quantity",
                 "ws_ext_sales_price",
                 ["ws_item", "ws_cust", "ws_qty", "ws_amt"])
    m = _join(flavor, ws, ss, ["ws_item", "ws_cust"],
              ["ss_item", "ss_cust"])
    out = ProjectExec(
        m,
        [(Col("ss_item").cast(DataType.int64()), "item"),
         (Col("ss_cust").cast(DataType.int64()), "cust"),
         (Col("ss_qty"), "ss_qty"),
         (Col("ws_qty").cast(DataType.float64())
          / Col("ss_qty").cast(DataType.float64()), "ratio"),
         (Col("ss_amt"), "ss_amt"), (Col("ws_amt"), "ws_amt")],
    )
    return _sorted_limit(
        out,
        [SortKey(Col("ratio"), True, True),
         SortKey(Col("item"), True, True),
         SortKey(Col("cust"), True, True)],
        100,
    )


QUERIES.update({
    "q66": q66, "q67": q67, "q70": q70, "q72": q72, "q75": q75,
    "q76": q76, "q77": q77, "q78": q78,
})


# ---------------------------------------------------------------------------
# final block: q23/q24/q54/q64/q80/q81/q83/q84/q85/q94/q95
# (the multi-CTE monsters; completes the reference CI's 99-query matrix,
# tpcds.yml:105-114)
# ---------------------------------------------------------------------------

_GEN_V7 = gen_tables
N_INCOME_BANDS = 20


def gen_tables(seed: int = 20260729):  # noqa: F811 - extend again
    t = _GEN_V7(seed)
    rng = np.random.default_rng(seed + 37)

    t["income_band"] = pd.DataFrame(
        {
            "ib_income_band_sk": np.arange(
                N_INCOME_BANDS, dtype=np.int32),
            "ib_lower_bound": (
                np.arange(N_INCOME_BANDS) * 10_000).astype(np.int32),
            "ib_upper_bound": (
                (np.arange(N_INCOME_BANDS) + 1) * 10_000).astype(
                np.int32),
        }
    )
    hd = t["household_demographics"]
    hd["hd_income_band_sk"] = rng.integers(
        0, N_INCOME_BANDS, len(hd)).astype(np.int32)

    ss = t["store_sales"]
    ss["ss_net_paid"] = np.round(rng.random(len(ss)) * 250, 2)

    ws = t["web_sales"]
    n_ws = len(ws)
    ws["ws_sales_price"] = np.round(rng.random(n_ws) * 200, 2)
    ws["ws_list_price"] = np.round(rng.random(n_ws) * 250, 2)
    ws["ws_promo_sk"] = rng.integers(0, N_PROMOS, n_ws).astype(np.int32)
    ws["ws_net_profit"] = np.round(rng.random(n_ws) * 300 - 50, 2)
    ws["ws_ship_addr_sk"] = rng.integers(
        0, N_ADDRESSES, n_ws).astype(np.int32)
    ws["ws_ext_ship_cost"] = np.round(rng.random(n_ws) * 80, 2)

    cs = t["catalog_sales"]
    cs["cs_net_profit"] = np.round(rng.random(len(cs)) * 300 - 50, 2)

    sr = t["store_returns"]
    sr["sr_cdemo_sk"] = rng.integers(0, N_CDEMO, len(sr)).astype(
        np.int32)

    wr = t["web_returns"]
    n_wr = len(wr)
    wr["wr_reason_sk"] = rng.integers(1, 10, n_wr).astype(np.int32)
    wr["wr_refunded_cdemo_sk"] = rng.integers(
        0, N_CDEMO, n_wr).astype(np.int32)
    wr["wr_returning_cdemo_sk"] = rng.integers(
        0, N_CDEMO, n_wr).astype(np.int32)
    wr["wr_refunded_addr_sk"] = rng.integers(
        0, N_ADDRESSES, n_wr).astype(np.int32)
    wr["wr_fee"] = np.round(rng.random(n_wr) * 40, 2)
    wr["wr_refunded_cash"] = np.round(rng.random(n_wr) * 120, 2)

    cr = t["catalog_returns"]
    cr["cr_returning_addr_sk"] = rng.integers(
        0, N_ADDRESSES, len(cr)).astype(np.int32)

    cust = t["customer"]
    countries = np.array(
        ["UNITED STATES", "CANADA", "MEXICO", "FRANCE"], dtype=object)
    cust["c_birth_country"] = countries[
        rng.integers(0, 4, len(cust))]
    ca = t["customer_address"]
    ca["ca_country"] = countries[rng.integers(0, 4, len(ca))]

    st = t["store"]
    st["s_market_id"] = (np.arange(len(st)) % 10 + 1).astype(np.int32)

    # q94/q95 need multi-row web orders (so an order can touch several
    # warehouses). Earlier blocks made order == row index; collapsing
    # 3 rows per order keeps web-return alignment (wr_order_number was
    # the ws row index) by the same division.
    ws["ws_order_number"] = (
        np.arange(n_ws, dtype=np.int64) // 3
    )
    wr["wr_order_number"] = (
        wr["wr_order_number"].to_numpy(dtype=np.int64) // 3
    )
    return t


def q81(s, flavor):
    """TPC-DS q81: catalog-return customers whose state-total returns
    exceed 1.2x their state's average (q1's shape over catalog returns
    + address state), reported for GA-resident customers."""
    def ctr():
        j = _join(
            flavor,
            FilterExec(s["date_dim"](), Col("d_year") == 2000),
            s["catalog_returns"](),
            ["d_date_sk"], ["cr_returned_date_sk"],
        )
        j = _join(
            flavor, s["customer_address"](), j,
            ["ca_address_sk"], ["cr_returning_addr_sk"],
        )
        return _agg(
            j,
            keys=[(Col("cr_returning_customer_sk"),
                   "ctr_customer_sk"),
                  (Col("ca_state"), "ctr_state")],
            aggs=[(AggExpr(AggFn.SUM, Col("cr_return_amount")),
                   "ctr_total_return")],
        )

    avg_by_state = ProjectExec(
        _agg(
            ctr(),
            keys=[(Col("ctr_state"), "avg_state")],
            aggs=[(AggExpr(AggFn.AVG, Col("ctr_total_return")),
                   "avg_r")],
        ),
        [(Col("avg_state"), "avg_state"),
         (Col("avg_r") * 1.2, "threshold")],
    )
    over = FilterExec(
        _join(flavor, avg_by_state, ctr(),
              ["avg_state"], ["ctr_state"]),
        Col("ctr_total_return") > Col("threshold"),
    )
    cust = _join(
        flavor, over, s["customer"](),
        ["ctr_customer_sk"], ["c_customer_sk"],
    )
    ga = _join(
        flavor,
        FilterExec(s["customer_address"](), Col("ca_state") == "GA"),
        cust,
        ["ca_address_sk"], ["c_current_addr_sk"],
    )
    out = _project_names(
        ga, ["c_customer_id", "c_first_name", "c_last_name",
             "ctr_total_return"],
    )
    return _sorted_limit(
        out,
        [SortKey(Col("c_customer_id"), True, True),
         SortKey(Col("ctr_total_return"), True, True)],
        100,
    )


def q83(s, flavor):
    """TPC-DS q83: returned quantity per item across the three return
    channels for a fixed set of weeks, each channel's share of the
    three-channel average."""
    weeks = (Literal(20, DataType.int32()),
             Literal(60, DataType.int32()),
             Literal(100, DataType.int32()))

    def channel(table, date_col, item_col, qty_col, out_name):
        dates = FilterExec(
            s["date_dim"](), InList(Col("d_week_seq"), weeks)
        )
        j = _join(flavor, dates, s[table](),
                  ["d_date_sk"], [date_col])
        j = _join(flavor, s["item"](), j,
                  ["i_item_sk"], [item_col])
        return _agg(
            j,
            keys=[(Col("i_item_id"), "item_id")],
            aggs=[(AggExpr(AggFn.SUM, Col(qty_col)), out_name)],
        )

    sr = channel("store_returns", "sr_returned_date_sk",
                 "sr_item_sk", "sr_return_quantity", "sr_qty")
    cr = RenameColumnsExec(
        channel("catalog_returns", "cr_returned_date_sk",
                "cr_item_sk", "cr_return_quantity", "cr_qty"),
        ["cr_item_id", "cr_qty"],
    )
    wr = RenameColumnsExec(
        channel("web_returns", "wr_returned_date_sk",
                "wr_item_sk", "wr_return_quantity", "wr_qty"),
        ["wr_item_id", "wr_qty"],
    )
    j = _join(flavor, sr, cr, ["item_id"], ["cr_item_id"])
    j = _join(flavor, j, wr, ["item_id"], ["wr_item_id"])
    total3 = (
        (Col("sr_qty") + Col("cr_qty") + Col("wr_qty"))
        .cast(DataType.float64()) / 3.0
    )
    out = ProjectExec(
        j,
        [(Col("item_id"), "item_id"),
         (Col("sr_qty"), "sr_qty"),
         (Col("sr_qty").cast(DataType.float64()) / total3 * 100.0,
          "sr_dev"),
         (Col("cr_qty"), "cr_qty"),
         (Col("cr_qty").cast(DataType.float64()) / total3 * 100.0,
          "cr_dev"),
         (Col("wr_qty"), "wr_qty"),
         (Col("wr_qty").cast(DataType.float64()) / total3 * 100.0,
          "wr_dev"),
         (total3, "average")],
    )
    return _sorted_limit(
        out,
        [SortKey(Col("item_id"), True, True),
         SortKey(Col("sr_qty"), True, True)],
        100,
    )


def q84(s, flavor):
    """TPC-DS q84: customers in one city whose household income band
    sits in a bounded range, linked to their store returns through the
    demographics row."""
    ib = FilterExec(
        s["income_band"](),
        (Col("ib_lower_bound") >= 30_000)
        & (Col("ib_upper_bound") <= 80_000),
    )
    hd = _join(flavor, ib, s["household_demographics"](),
               ["ib_income_band_sk"], ["hd_income_band_sk"])
    cust = _join(
        flavor,
        FilterExec(s["customer_address"](),
                   Col("ca_city") == "Midway"),
        s["customer"](),
        ["ca_address_sk"], ["c_current_addr_sk"],
    )
    cust = _join(flavor, hd, cust,
                 ["hd_demo_sk"], ["c_current_hdemo_sk"])
    cust = _join(flavor, s["customer_demographics"](), cust,
                 ["cd_demo_sk"], ["c_current_cdemo_sk"])
    j = _join(flavor, cust, s["store_returns"](),
              ["cd_demo_sk"], ["sr_cdemo_sk"])
    out = ProjectExec(
        j,
        [(Col("c_customer_id"), "customer_id"),
         (Col("c_last_name"), "customername")],
    )
    return _sorted_limit(
        out, [SortKey(Col("customer_id"), True, True)], 100,
    )


def _ws_shipped_base(s, flavor, state):
    """q94/q95 shared base: web orders shipped in a date window to one
    state through one site."""
    j = _join(
        flavor,
        FilterExec(s["date_dim"](), Col("d_year") == 1999),
        s["web_sales"](),
        ["d_date_sk"], ["ws_ship_date_sk"],
    )
    j = _join(
        flavor,
        FilterExec(s["customer_address"](), Col("ca_state") == state),
        j,
        ["ca_address_sk"], ["ws_ship_addr_sk"],
    )
    return _join(
        flavor,
        FilterExec(s["web_site"](), Col("web_name") == "site_0"),
        j,
        ["web_site_sk"], ["ws_web_site_sk"],
    )


def _order_count_stats(base, flavor):
    """count(distinct order) + sums over the filtered rows, cross-joined
    (constant key) into one row - the Spark plan for q94/q95's scalar
    trio. GLOBAL aggregates (no keys) so an empty filtered base still
    yields SQL's single row (count 0, NULL sums)."""
    per_order = _agg(
        ProjectExec(base, [(Col("ws_order_number"), "o")]),
        keys=[(Col("o"), "o")],
        aggs=[(AggExpr(AggFn.COUNT_STAR, None), "dummy")],
    )
    n_orders = ProjectExec(
        _agg(
            per_order, keys=[],
            aggs=[(AggExpr(AggFn.COUNT_STAR, None), "order_count")],
        ),
        [(Literal(1, DataType.int32()), "k"),
         (Col("order_count"), "order_count")],
    )
    sums = ProjectExec(
        _agg(
            base, keys=[],
            aggs=[(AggExpr(AggFn.SUM, Col("ws_ext_ship_cost")),
                   "total_shipping_cost"),
                  (AggExpr(AggFn.SUM, Col("ws_net_profit")),
                   "total_net_profit")],
        ),
        [(Literal(1, DataType.int32()), "k2"),
         (Col("total_shipping_cost"), "total_shipping_cost"),
         (Col("total_net_profit"), "total_net_profit")],
    )
    crossed = _join(flavor, n_orders, sums, ["k"], ["k2"])
    return _project_names(
        crossed,
        ["order_count", "total_shipping_cost", "total_net_profit"],
    )


def _multi_wh_orders(s):
    """Orders touching >= 2 distinct warehouses: dedupe
    (order, warehouse), keep orders with > 1 surviving row (the
    `exists ws2 ... different warehouse` rewrite shared by q94/q95)."""
    return FilterExec(
        _agg(
            _agg(
                _project_names(s["web_sales"](),
                               ["ws_order_number", "ws_warehouse_sk"]),
                keys=[(Col("ws_order_number"), "o"),
                      (Col("ws_warehouse_sk"), "w")],
                aggs=[(AggExpr(AggFn.COUNT_STAR, None), "c1")],
            ),
            keys=[(Col("o"), "o")],
            aggs=[(AggExpr(AggFn.COUNT_STAR, None), "n_wh")],
        ),
        Col("n_wh") > 1,
    )


def q94(s, flavor):
    """TPC-DS q94: shipped web orders that span >= 2 warehouses and were
    never returned; count distinct orders + cost/profit totals."""
    base = _ws_shipped_base(s, flavor, "CA")
    base = _semi(flavor, base, _multi_wh_orders(s),
                 ["ws_order_number"], ["o"])
    # not exists wr
    base = _join(
        flavor, base, s["web_returns"](),
        ["ws_order_number"], ["wr_order_number"],
        JoinType.LEFT_ANTI,
    )
    return _order_count_stats(base, flavor)


def q95(s, flavor):
    """TPC-DS q95: shipped web orders where BOTH the order and its
    return ride the multi-warehouse order set."""
    base = _ws_shipped_base(s, flavor, "TX")
    base = _semi(flavor, base, _multi_wh_orders(s),
                 ["ws_order_number"], ["o"])
    returned_multi = _semi(
        flavor,
        _agg(
            _project_names(s["web_returns"](), ["wr_order_number"]),
            keys=[(Col("wr_order_number"), "ro")],
            aggs=[(AggExpr(AggFn.COUNT_STAR, None), "cr1")],
        ),
        _multi_wh_orders(s),
        ["ro"], ["o"],
    )
    base = _semi(flavor, base, returned_multi,
                 ["ws_order_number"], ["ro"])
    return _order_count_stats(base, flavor)


QUERIES.update({
    "q81": q81, "q83": q83, "q84": q84, "q94": q94, "q95": q95,
})


def _slit(v):
    return Literal(v, DataType.utf8())


def q23(s, flavor):
    """TPC-DS q23 (single-variant): catalog+web revenue in one month
    from frequently-store-sold items bought by the best store
    customers - three CTEs (frequent item set, max per-customer store
    sales as a global scalar, best-customer set) feeding a unioned
    final aggregate."""
    frequent = FilterExec(
        _agg(
            _join(
                flavor,
                FilterExec(s["date_dim"](), Col("d_year") == 2000),
                s["store_sales"](),
                ["d_date_sk"], ["ss_sold_date_sk"],
            ),
            keys=[(Col("ss_item_sk"), "fi_item_sk")],
            aggs=[(AggExpr(AggFn.COUNT_STAR, None), "cnt")],
        ),
        Col("cnt") > 2,
    )

    def cust_sales():
        # NULL customers are filtered BEFORE grouping (the best-customer
        # set feeds a semi join where NULL can never match; the synthetic
        # data's 1% NULL rate would otherwise make the NULL group the
        # max and empty the whole result)
        return _agg(
            _join(
                flavor,
                FilterExec(
                    s["date_dim"](),
                    InList(Col("d_year"),
                           (Literal(2000, DataType.int32()),
                            Literal(2001, DataType.int32()))),
                ),
                FilterExec(s["store_sales"](),
                           IsNotNull(Col("ss_customer_sk"))),
                ["d_date_sk"], ["ss_sold_date_sk"],
            ),
            keys=[(Col("ss_customer_sk"), "csales_cust")],
            aggs=[(AggExpr(
                AggFn.SUM,
                Col("ss_quantity").cast(DataType.float64())
                * Col("ss_sales_price")), "csales")],
        )

    max_sales = ProjectExec(
        _agg(
            cust_sales(), keys=[],
            aggs=[(AggExpr(AggFn.MAX, Col("csales")), "tpcds_cmax")],
        ),
        [(Literal(1, DataType.int32()), "mk"),
         (Col("tpcds_cmax"), "tpcds_cmax")],
    )
    best = ProjectExec(
        FilterExec(
            _join(
                flavor, max_sales,
                ProjectExec(
                    cust_sales(),
                    [(Literal(1, DataType.int32()), "ck"),
                     (Col("csales_cust"), "csales_cust"),
                     (Col("csales"), "csales")],
                ),
                ["mk"], ["ck"],
            ),
            Col("csales") > Col("tpcds_cmax") * 0.5,
        ),
        [(Col("csales_cust"), "best_cust")],
    )

    def channel(table, prefix, cust_col):
        sales = _join(
            flavor,
            FilterExec(
                s["date_dim"](),
                (Col("d_year") == 2000) & (Col("d_moy") == 3),
            ),
            s[table](),
            ["d_date_sk"], [f"{prefix}_sold_date_sk"],
        )
        sales = _semi(flavor, sales, frequent,
                      [f"{prefix}_item_sk"], ["fi_item_sk"])
        sales = _semi(flavor, sales, best, [cust_col], ["best_cust"])
        return ProjectExec(
            sales,
            [(Col(f"{prefix}_quantity").cast(DataType.float64())
              * Col(f"{prefix}_list_price"), "sales")],
        )

    both = _union([
        channel("catalog_sales", "cs", "cs_bill_customer_sk"),
        channel("web_sales", "ws", "ws_bill_customer_sk"),
    ])
    total = _agg(
        both, keys=[],
        aggs=[(AggExpr(AggFn.SUM, Col("sales")), "total")],
    )
    return LimitExec(total, 100)


def q24(s, flavor):
    """TPC-DS q24: per-customer store revenue by item color through a
    sales-returns ticket join, reported where a customer+store's paid
    total beats 5% of the global average (scalar cross join)."""
    j = _join(
        flavor, s["store_sales"](), s["store_returns"](),
        ["ss_ticket_number", "ss_item_sk"],
        ["sr_ticket_number", "sr_item_sk"],
    )
    j = _join(
        flavor,
        FilterExec(s["store"](), Col("s_market_id") <= 5),
        j,
        ["s_store_sk"], ["ss_store_sk"],
    )
    j = _join(flavor, s["item"](), j, ["i_item_sk"], ["ss_item_sk"])
    j = _join(flavor, s["customer"](), j,
              ["c_customer_sk"], ["ss_customer_sk"])
    # customer lives in the store's state (the query's zip linkage,
    # state-keyed here): multi-key join incl. a string key
    j = _join(
        flavor, j, s["customer_address"](),
        ["c_current_addr_sk", "s_state"],
        ["ca_address_sk", "ca_state"],
    )
    ssales = _agg(
        j,
        keys=[(Col("c_last_name"), "c_last_name"),
              (Col("c_first_name"), "c_first_name"),
              (Col("s_store_name"), "s_store_name"),
              (Col("i_color"), "i_color")],
        aggs=[(AggExpr(AggFn.SUM, Col("ss_net_paid")), "netpaid")],
    )
    avg_paid = ProjectExec(
        _agg(
            ssales, keys=[],
            aggs=[(AggExpr(AggFn.AVG, Col("netpaid")), "avg_paid")],
        ),
        [(Literal(1, DataType.int32()), "ak"),
         (Col("avg_paid"), "avg_paid")],
    )
    keyed = ProjectExec(
        ssales,
        [(Literal(1, DataType.int32()), "sk_"),
         (Col("c_last_name"), "c_last_name"),
         (Col("c_first_name"), "c_first_name"),
         (Col("s_store_name"), "s_store_name"),
         (Col("i_color"), "i_color"),
         (Col("netpaid"), "netpaid")],
    )
    out = FilterExec(
        _join(flavor, avg_paid, keyed, ["ak"], ["sk_"]),
        Col("netpaid") > Col("avg_paid") * 0.05,
    )
    out = _project_names(
        out, ["c_last_name", "c_first_name", "s_store_name",
              "i_color", "netpaid"],
    )
    return _sorted_limit(
        out,
        [SortKey(Col("c_last_name"), True, True),
         SortKey(Col("c_first_name"), True, True),
         SortKey(Col("s_store_name"), True, True),
         SortKey(Col("i_color"), True, True)],
        100,
    )


def q54(s, flavor):
    """TPC-DS q54: customers who bought Books from catalog/web in one
    month, their store revenue in the following quarter at home-county
    stores, histogrammed into $50 segments."""
    def channel(table, prefix, cust_col):
        return ProjectExec(
            s[table](),
            [(Col(f"{prefix}_sold_date_sk"), "sold_date_sk"),
             (Col(f"{prefix}_item_sk"), "item_sk"),
             (Col(cust_col), "customer_sk")],
        )

    both = _union([
        channel("catalog_sales", "cs", "cs_bill_customer_sk"),
        channel("web_sales", "ws", "ws_bill_customer_sk"),
    ])
    j = _join(
        flavor,
        FilterExec(s["item"](), Col("i_category") == "Books"),
        both, ["i_item_sk"], ["item_sk"],
    )
    j = _join(
        flavor,
        FilterExec(
            s["date_dim"](),
            (Col("d_year") == 1999) & (Col("d_moy") == 3),
        ),
        j, ["d_date_sk"], ["sold_date_sk"],
    )
    my_customers = _agg(
        j,
        keys=[(Col("customer_sk"), "c_sk")],
        aggs=[(AggExpr(AggFn.COUNT_STAR, None), "c1")],
    )
    cust = _join(flavor, my_customers, s["customer"](),
                 ["c_sk"], ["c_customer_sk"])
    cust = _join(flavor, cust, s["customer_address"](),
                 ["c_current_addr_sk"], ["ca_address_sk"])
    cust = _join(
        flavor, cust, s["store"](),
        ["ca_county", "ca_state"], ["s_county", "s_state"],
    )
    # the county/state join is semi-join-shaped: stores sharing a
    # (county, state) pair must not duplicate a customer (the SQL is
    # `WHERE EXISTS`-equivalent; the oracle dedupes both sides)
    cust = _agg(
        cust,
        keys=[(Col("c_sk"), "c_sk")],
        aggs=[(AggExpr(AggFn.COUNT_STAR, None), "c2")],
    )
    # month_seq of 1999-03 is (1999-1900)*12 + 2 = 1190; the revenue
    # window is the following quarter (Spark constant-folds the
    # subqueries to these literals)
    rev = _join(
        flavor,
        FilterExec(
            s["date_dim"](),
            (Col("d_month_seq") >= 1191)
            & (Col("d_month_seq") <= 1193),
        ),
        s["store_sales"](),
        ["d_date_sk"], ["ss_sold_date_sk"],
    )
    rev = _join(flavor, cust, rev, ["c_sk"], ["ss_customer_sk"])
    per_cust = _agg(
        rev,
        keys=[(Col("c_sk"), "c_sk")],
        aggs=[(AggExpr(AggFn.SUM, Col("ss_ext_sales_price")),
               "revenue")],
    )
    seg = ProjectExec(
        per_cust,
        [((Col("revenue") / 50.0).cast(DataType.int32()), "segment")],
    )
    hist = _agg(
        seg,
        keys=[(Col("segment"), "segment")],
        aggs=[(AggExpr(AggFn.COUNT_STAR, None), "num_customers")],
    )
    out = ProjectExec(
        hist,
        [(Col("segment"), "segment"),
         (Col("num_customers"), "num_customers"),
         (Col("segment") * 50, "segment_base")],
    )
    return _sorted_limit(
        out,
        [SortKey(Col("segment"), True, True),
         SortKey(Col("num_customers"), True, True)],
        100,
    )


def q64(s, flavor):
    """TPC-DS q64: cross-channel item resale - store sales+returns of
    items whose catalog refunds stay under a third of catalog revenue,
    decorated with household income band and both addresses, self-joined
    across two years on (item, store) requiring the second year's count
    not to grow."""
    cs_ui = ProjectExec(
        FilterExec(
            _agg(
                _join(
                    flavor, s["catalog_sales"](), s["catalog_returns"](),
                    ["cs_order_number", "cs_item_sk"],
                    ["cr_order_number", "cr_item_sk"],
                ),
                keys=[(Col("cs_item_sk"), "ui_item_sk")],
                aggs=[
                    (AggExpr(AggFn.SUM, Col("cs_ext_list_price")),
                     "sale"),
                    (AggExpr(AggFn.SUM,
                             Col("cr_return_amount")
                             + Col("cr_net_loss")), "refund"),
                ],
            ),
            Col("sale") > Col("refund") * 2.0,
        ),
        [(Col("ui_item_sk"), "ui_item_sk")],
    )

    def cross_sales(year, prefix):
        j = _join(
            flavor, s["store_sales"](), s["store_returns"](),
            ["ss_ticket_number", "ss_item_sk"],
            ["sr_ticket_number", "sr_item_sk"],
        )
        j = _semi(flavor, j, cs_ui, ["ss_item_sk"], ["ui_item_sk"])
        j = _join(
            flavor,
            FilterExec(s["date_dim"](), Col("d_year") == year),
            j, ["d_date_sk"], ["ss_sold_date_sk"],
        )
        j = _join(flavor, s["store"](), j,
                  ["s_store_sk"], ["ss_store_sk"])
        j = _join(flavor, s["customer"](), j,
                  ["c_customer_sk"], ["ss_customer_sk"])
        j = _join(flavor, s["household_demographics"](), j,
                  ["hd_demo_sk"], ["c_current_hdemo_sk"])
        j = _join(flavor, s["income_band"](), j,
                  ["ib_income_band_sk"], ["hd_income_band_sk"])
        j = _join(flavor, j, s["customer_address"](),
                  ["c_current_addr_sk"], ["ca_address_sk"])
        ca2 = RenameColumnsExec(
            _project_names(s["customer_address"](),
                           ["ca_address_sk", "ca_state"]),
            ["ca2_address_sk", "ca2_state"],
        )
        j = _join(flavor, j, ca2, ["ss_addr_sk"], ["ca2_address_sk"])
        j = _join(
            flavor,
            FilterExec(
                s["item"](),
                InList(Col("i_color"),
                       (_slit("red"), _slit("navy"), _slit("khaki"))),
            ),
            j, ["i_item_sk"], ["ss_item_sk"],
        )
        return _agg(
            j,
            keys=[(Col("i_product_name"), f"{prefix}_product_name"),
                  (Col("i_item_sk"), f"{prefix}_item_sk"),
                  (Col("s_store_name"), f"{prefix}_store_name"),
                  (Col("s_zip"), f"{prefix}_store_zip")],
            aggs=[
                (AggExpr(AggFn.COUNT_STAR, None), f"{prefix}_cnt"),
                (AggExpr(AggFn.SUM, Col("ss_ext_wholesale_cost")),
                 f"{prefix}_s1"),
                (AggExpr(AggFn.SUM, Col("ss_ext_list_price")),
                 f"{prefix}_s2"),
                (AggExpr(AggFn.SUM, Col("ss_coupon_amt")),
                 f"{prefix}_s3"),
            ],
        )

    cs1 = cross_sales(1999, "y1")
    cs2 = cross_sales(2000, "y2")
    j = _join(
        flavor, cs1, cs2,
        ["y1_item_sk", "y1_store_name", "y1_store_zip"],
        ["y2_item_sk", "y2_store_name", "y2_store_zip"],
    )
    j = FilterExec(j, Col("y2_cnt") <= Col("y1_cnt"))
    out = _project_names(
        j,
        ["y1_product_name", "y1_store_name", "y1_store_zip",
         "y1_cnt", "y1_s1", "y2_cnt", "y2_s1"],
    )
    return _sorted_limit(
        out,
        [SortKey(Col("y1_product_name"), True, True),
         SortKey(Col("y1_store_name"), True, True),
         SortKey(Col("y1_s1"), True, True)],
        100,
    )


def q80(s, flavor):
    """TPC-DS q80: per-channel per-outlet sales/returns/profit for one
    month of promoted high-ticket items; sales LEFT-join returns, three
    channels unioned."""
    dates = FilterExec(
        s["date_dim"](),
        (Col("d_year") == 2000) & (Col("d_moy") == 8),
    )
    items = FilterExec(s["item"](), Col("i_current_price") > 50.0)
    promos = FilterExec(s["promotion"](), Col("p_channel_tv") == "N")

    def channel(label, sales_t, ret_t, skeys, rkeys, prefix, rprefix,
                id_col, ret_amt, ret_loss):
        j = _join(flavor, s[sales_t](), s[ret_t](), skeys, rkeys,
                  JoinType.LEFT)
        j = _join(flavor, dates, j,
                  ["d_date_sk"], [f"{prefix}_sold_date_sk"])
        j = _join(flavor, items, j, ["i_item_sk"],
                  [f"{prefix}_item_sk"])
        j = _join(flavor, promos, j, ["p_promo_sk"],
                  [f"{prefix}_promo_sk"])
        pre = ProjectExec(
            j,
            [(_slit(label), "channel"),
             (Col(id_col).cast(DataType.int64()), "id"),
             (Col(f"{prefix}_ext_sales_price"), "sales"),
             (Coalesce((Col(ret_amt),
                        Literal(0.0, DataType.float64()))), "returns"),
             (Col(f"{prefix}_net_profit")
              - Coalesce((Col(ret_loss),
                          Literal(0.0, DataType.float64()))),
              "profit")],
        )
        return pre

    both = _union([
        channel("store channel", "store_sales", "store_returns",
                ["ss_ticket_number", "ss_item_sk"],
                ["sr_ticket_number", "sr_item_sk"],
                "ss", "sr", "ss_store_sk",
                "sr_return_amt", "sr_net_loss"),
        channel("catalog channel", "catalog_sales", "catalog_returns",
                ["cs_order_number", "cs_item_sk"],
                ["cr_order_number", "cr_item_sk"],
                "cs", "cr", "cs_call_center_sk",
                "cr_return_amount", "cr_net_loss"),
        channel("web channel", "web_sales", "web_returns",
                ["ws_order_number", "ws_item_sk"],
                ["wr_order_number", "wr_item_sk"],
                "ws", "wr", "ws_web_site_sk",
                "wr_return_amt", "wr_net_loss"),
    ])
    out = _agg(
        both,
        keys=[(Col("channel"), "channel"), (Col("id"), "id")],
        aggs=[(AggExpr(AggFn.SUM, Col("sales")), "sales"),
              (AggExpr(AggFn.SUM, Col("returns")), "returns"),
              (AggExpr(AggFn.SUM, Col("profit")), "profit")],
    )
    return _sorted_limit(
        out,
        [SortKey(Col("channel"), True, True),
         SortKey(Col("id"), True, True)],
        100,
    )


def q85(s, flavor):
    """TPC-DS q85: web returns linked to their sale rows, double
    demographics join (refunding + returning person must share marital
    status), address/state bands OR'd with profit bands, grouped by
    return reason."""
    j = _join(
        flavor, s["web_sales"](), s["web_returns"](),
        ["ws_order_number", "ws_item_sk"],
        ["wr_order_number", "wr_item_sk"],
    )
    j = _join(flavor, s["web_page"](), j,
              ["wp_web_page_sk"], ["ws_web_page_sk"])
    cd1 = RenameColumnsExec(
        _project_names(
            s["customer_demographics"](),
            ["cd_demo_sk", "cd_marital_status", "cd_education_status"],
        ),
        ["cd1_demo_sk", "cd1_marital", "cd1_edu"],
    )
    j = _join(flavor, cd1, j,
              ["cd1_demo_sk"], ["wr_refunded_cdemo_sk"])
    # returning person must match the refunded person's marital status
    j = _join(
        flavor, j, s["customer_demographics"](),
        ["wr_returning_cdemo_sk", "cd1_marital"],
        ["cd_demo_sk", "cd_marital_status"],
    )
    j = _join(flavor, s["customer_address"](), j,
              ["ca_address_sk"], ["wr_refunded_addr_sk"])
    j = _join(
        flavor,
        FilterExec(s["date_dim"](), Col("d_year") == 2000),
        j, ["d_date_sk"], ["ws_sold_date_sk"],
    )
    j = _join(flavor, s["reason"](), j,
              ["r_reason_sk"], ["wr_reason_sk"])
    band = (
        ((Col("cd1_marital") == "M")
         & (Col("cd1_edu") == "4 yr Degree")
         & (Col("ws_sales_price") >= 100.0)
         & (Col("ws_sales_price") <= 150.0))
        | ((Col("cd1_marital") == "S")
           & (Col("cd1_edu") == "College")
           & (Col("ws_sales_price") >= 50.0)
           & (Col("ws_sales_price") <= 100.0))
    )
    geo = (
        (InList(Col("ca_state"), (_slit("TN"), _slit("GA")))
         & (Col("ws_net_profit") >= 100.0))
        | (InList(Col("ca_state"), (_slit("CA"), _slit("TX")))
           & (Col("ws_net_profit") >= 50.0))
    )
    j = FilterExec(j, band & geo)
    out = _agg(
        j,
        keys=[(Col("r_reason_desc"), "reason")],
        aggs=[(AggExpr(AggFn.AVG,
                       Col("ws_quantity").cast(DataType.float64())),
               "avg_qty"),
              (AggExpr(AggFn.AVG, Col("wr_refunded_cash")), "avg_cash"),
              (AggExpr(AggFn.AVG, Col("wr_fee")), "avg_fee")],
    )
    return _sorted_limit(
        out, [SortKey(Col("reason"), True, True)], 100,
    )


QUERIES.update({
    "q23": q23, "q24": q24, "q54": q54, "q64": q64, "q80": q80,
    "q85": q85,
})


# ---------------------------------------------------------------------------
# table cache: every xdist worker of the gate, and every child process
# a test starts around the jaxlib compile-volume segfault, loads the
# corpus; without caching each would regenerate all of it.
# Frames round-trip through feather on disk, keyed by (row scale, seed,
# generator-source hash) - a generator change invalidates the cache.
# ---------------------------------------------------------------------------

_gen_tables_uncached = gen_tables


def gen_tables(seed: int = 20260729):  # noqa: F811 - caching wrapper
    import hashlib
    import tempfile

    import pyarrow as _pa

    n = os.environ.get("BLAZE_TPCDS_ROWS", "")
    src_tag = hashlib.sha256(
        open(__file__, "rb").read()
    ).hexdigest()[:12]
    root = os.path.join(
        tempfile.gettempdir(),
        f"blaze_tpcds_cache_{n or 'default'}_{seed}_{src_tag}",
    )
    marker = os.path.join(root, "DONE")
    if os.path.exists(marker):
        out = {}
        for fn in sorted(os.listdir(root)):
            if fn.endswith(".feather"):
                with _pa.ipc.open_file(os.path.join(root, fn)) as r:
                    out[fn[:-8]] = r.read_pandas()
        return out
    tables = _gen_tables_uncached(seed)
    # normalize EVERY process's view through the Arrow round trip:
    # without this, the cache-building process would test pandas
    # extension dtypes (Float64/pd.NA) while cache-hit processes test
    # plain numpy float64/NaN - run-order-dependent frames
    arrow_tables = {
        name: _pa.Table.from_pandas(df, preserve_index=False)
        for name, df in tables.items()
    }
    tables = {name: t.to_pandas() for name, t in arrow_tables.items()}
    try:  # publish best-effort; concurrent builders race benignly
        tmp = root + f".tmp{os.getpid()}"
        os.makedirs(tmp, exist_ok=True)
        for name, tbl in arrow_tables.items():
            with _pa.ipc.new_file(
                os.path.join(tmp, f"{name}.feather"), tbl.schema
            ) as w:
                w.write_table(tbl)
        open(os.path.join(tmp, "DONE"), "w").close()
        if not os.path.exists(marker):
            os.rename(tmp, root)
        else:
            import shutil

            shutil.rmtree(tmp, ignore_errors=True)
    except OSError:
        pass
    return tables
