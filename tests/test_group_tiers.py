"""The group-capacity tiers on the sort core (`run_grouped_kernel`): the
grouping program runs once at the input's capacity whatever the group
count, and its states leave cut to the smallest tier that holds the
count. On the scatter core a tier sizes the hash table, so the ladder of
programs stays (`tests/test_ops.py::test_group_capacity_ladder`). The
sort core is what `auto` resolves to on a TPU; here it is pinned."""

import collections
import contextlib
import dataclasses
import datetime
import decimal
import json
import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from blaze_tpu import ColumnBatch
from blaze_tpu.config import get_config, set_config
from blaze_tpu.exprs import AggExpr, AggFn, Col
from blaze_tpu.ops import (
    AggMode, FilterExec, HashAggregateExec, MemoryScanExec,
)
from blaze_tpu.ops.base import ExecContext
from blaze_tpu.runtime import dispatch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@contextlib.contextmanager
def pinned(tier1=None, **cfg):
    """The engine's configuration and the first tier for one test."""
    prior_cfg = get_config()
    prior_env = os.environ.pop("BLAZE_AGG_TIER1", None)
    if tier1 is not None:
        os.environ["BLAZE_AGG_TIER1"] = str(tier1)
    set_config(dataclasses.replace(prior_cfg, **cfg))
    try:
        yield
    finally:
        set_config(prior_cfg)
        os.environ.pop("BLAZE_AGG_TIER1", None)
        if prior_env is not None:
            os.environ["BLAZE_AGG_TIER1"] = prior_env


@pytest.fixture
def launches(monkeypatch):
    """{kernel cache key: launches} of the grouping programs that
    `run_grouped_kernel` dispatched."""
    from blaze_tpu.ops import hash_aggregate

    seen = collections.Counter()
    real = hash_aggregate.cached_kernel

    def spy(key, build, **kw):
        fn = real(key, build, **kw)

        def launch(*args):
            seen[key] += 1
            return fn(*args)

        return launch

    monkeypatch.setattr(hash_aggregate, "cached_kernel", spy)
    return seen


def int_batch(n_groups, rows=40000, seed=13):
    rng = np.random.default_rng(seed)
    g = rng.integers(0, n_groups, rows).astype(np.int64)
    v = rng.integers(0, 1000, rows).astype(np.int64)
    return g, v, ColumnBatch.from_arrow(pa.record_batch({"g": g, "v": v}))


def sum_count(child, mode=AggMode.COMPLETE):
    return HashAggregateExec(
        child, keys=[(Col("g"), "g")],
        aggs=[(AggExpr(AggFn.SUM, Col("v")), "s"),
              (AggExpr(AggFn.COUNT_STAR, None), "c")],
        mode=mode,
    )


def same_as_pandas(got: pd.DataFrame, g, v):
    got = got.sort_values("g").reset_index(drop=True)
    exp = (pd.DataFrame({"g": g, "v": v}).groupby("g")
           .agg(s=("v", "sum"), c=("v", "size")).reset_index())
    assert len(got) == len(exp)
    for name in ("g", "s", "c"):
        assert (got[name].to_numpy() == exp[name].to_numpy()).all(), name


# 40,000 rows in a 65,536-row bucket under tiers [4096, 16384, None]
BUCKET = dict(batch_size=1 << 16, shape_buckets=(1 << 16,),
              agg_group_capacity=16384)
CASES = [
    pytest.param(300, 4096, id="below_the_first_tier"),
    pytest.param(9000, 16384, id="between_the_tiers"),
    pytest.param(30000, 1 << 16, id="above_the_configured_capacity"),
]


@pytest.mark.parametrize("n_groups,slots", CASES)
def test_sort_core_groups_once_and_cuts(n_groups, slots, launches):
    ctx = ExecContext()
    with pinned(group_core="sort", **BUCKET), dispatch.task_scope(ctx):
        g, v, cb = int_batch(n_groups)
        out = sum_count(MemoryScanExec([[cb]], cb.schema))\
            ._aggregate_batch(cb)
    n = len(np.unique(g))
    assert out.num_rows == n
    # the smallest tier that holds the count, as a kernel built at that
    # tier returned it
    assert slots == next((t for t in (4096, 16384) if n <= t), 1 << 16)
    for c in out.columns:
        assert c.values.shape[0] == slots
    # one program, launched once, built at no tier but cut to both
    assert sum(launches.values()) == 1
    (key,) = launches
    assert key[-2:] == (False, (4096, 16384))
    assert ctx.metrics.counters["agg_tier_retries"] == 0
    same_as_pandas(out.to_arrow().to_pandas(), g, v)


@pytest.mark.parametrize("n_groups,retries", [(300, 0), (9000, 1),
                                              (30000, 2)])
def test_scatter_core_climbs_its_tables(n_groups, retries, launches):
    """The other core: a tier sizes the hash table, so a count that
    outgrows it launches the next program, and the counter says so."""
    ctx = ExecContext()
    with pinned(group_core="scatter", **BUCKET), dispatch.task_scope(ctx):
        g, v, cb = int_batch(n_groups)
        out = sum_count(MemoryScanExec([[cb]], cb.schema))\
            ._aggregate_batch(cb)
    assert sum(launches.values()) == 1 + retries
    assert [k[-1] for k in launches] == [4096, 16384, None][:1 + retries]
    assert ctx.metrics.counters["agg_tier_retries"] == retries
    same_as_pandas(out.to_arrow().to_pandas(), g, v)


def test_keyless_stays_at_one_slot(launches):
    ctx = ExecContext()
    with pinned(group_core="sort", **BUCKET), dispatch.task_scope(ctx):
        _, v, cb = int_batch(10)
        out = HashAggregateExec(
            MemoryScanExec([[cb]], cb.schema), keys=[],
            aggs=[(AggExpr(AggFn.SUM, Col("v")), "s"),
                  (AggExpr(AggFn.COUNT_STAR, None), "c")],
            mode=AggMode.COMPLETE,
        )._aggregate_batch(cb)
    # built at group_cap 1 (a reduce, not a scatter): no cut, no count
    (key,) = launches
    assert key[-2:] == (False, 1) and launches[key] == 1
    assert [c.values.shape[0] for c in out.columns] == [1, 1]
    assert "agg_tier_retries" not in ctx.metrics.counters
    assert out.to_arrow().to_pydict() == {"s": [int(v.sum())],
                                          "c": [len(v)]}


def fused_complete(batches):
    """COMPLETE aggregate over a filter, as the planner rewrites it:
    HostFinalAggExec over FusedAggregateExec(fetch_host=True)."""
    from blaze_tpu.ops.fused import (
        FusedAggregateExec, HostFinalAggExec, fuse_pipelines,
    )

    plan = fuse_pipelines(sum_count(
        FilterExec(MemoryScanExec([batches], batches[0].schema),
                   Col("v") >= 0)))
    assert isinstance(plan, HostFinalAggExec)
    assert isinstance(plan.children[0], FusedAggregateExec)
    assert plan.children[0].fetch_host
    return plan


def test_packed_first_fetch_climbs_cuts_not_programs(launches):
    """`_run_agg`'s first fetch reads count and states in one packed
    transfer: above the first tier it packs the next cut of the same
    result and launches no second grouping program."""
    ctx = ExecContext()
    with pinned(group_core="sort", **BUCKET), dispatch.task_scope(ctx):
        g, v, cb = int_batch(9000)
        plan = fused_complete([cb])
        with dispatch.counting() as c:
            (state,) = plan.children[0].execute(0, ctx)
    assert sum(launches.values()) == 1
    # two packs: the 4,096-slot cut with the count, then the cut that
    # holds 8,891 groups
    assert c.counts["d2h_fetches"] == 2 and "d2h_syncs" not in c.counts
    assert state.num_rows == len(np.unique(g))
    for col in state.columns:
        assert isinstance(col.values, np.ndarray)
        assert col.values.shape[0] == 16384
    assert ctx.metrics.counters["agg_tier_retries"] == 0
    with pinned(group_core="sort", **BUCKET):
        got = pa.Table.from_batches(
            [b.to_arrow() for b in plan.execute(0, ExecContext())])
    same_as_pandas(got.to_pandas(), g, v)


def q1_batches(rows, batches, seed, first_customer):
    """Query 1's shape, small: two nullable int keys, decimal(7,2)
    amounts with NULLs, about 0.7 groups a row, so nearly nothing
    aggregates away and some groups hold NULL amounts alone. Stores are
    0-6; customers start at `first_customer`."""
    rng = np.random.default_rng(seed)
    n = rows * batches
    cents = rng.integers(-99999, 9999999, n)
    frame = pd.DataFrame({
        "c": pd.array(first_customer + rng.integers(0, int(n * 0.6), n),
                      dtype="Int64"),
        "s": pd.array(rng.integers(0, 7, n), dtype="Int64"),
        "cents": pd.array(cents, dtype="Int64"),
    })
    for name in frame.columns:
        frame.loc[rng.random(n) < 0.045, name] = pd.NA
    table = pa.table({
        "c": pa.array(frame["c"], type=pa.int32(), from_pandas=True),
        "s": pa.array(frame["s"], type=pa.int32(), from_pandas=True),
        "amt": pa.array(
            [None if x is pd.NA else decimal.Decimal(int(x)).scaleb(-2)
             for x in frame["cents"]], type=pa.decimal128(7, 2)),
    })
    return frame, [
        ColumnBatch.from_arrow(b)
        for b in table.to_batches(max_chunksize=rows)
    ]


def q1_keyed(df, total, cents=int):
    """{(customer, store): cents}, None for NULL."""
    def or_none(x, to=int):
        return None if pd.isna(x) else to(x)

    return {(or_none(c), or_none(s)): or_none(t, cents)
            for c, s, t in zip(df["c"], df["s"], df[total])}


# The narrow-key program sorts by Spark's hash of the keys, which skips
# a NULL: (NULL, k) and (k, NULL) hash alike, the program reports the
# collision and the lexsort program answers. Customers from 1,000 never
# meet a store's number; customers from 0 do.
@pytest.mark.parametrize("first_customer,merge_programs", [
    pytest.param(1000, [False], id="one_merge"),
    pytest.param(0, [False, True], id="collision_then_lexsort"),
])
def test_final_merge_of_decimal_partials_runs_once(
        first_customer, merge_programs, launches):
    """Query 1's task at a few thousand rows: per-batch partial sums of
    decimal(7,2), then the FINAL merge of all partial rows in a bucket
    whose tiers [256, 1024, None] the count outgrows: each merge program
    is launched once, cut at both tiers, and every sum, NULL key and
    all-NULL group is exact."""
    from blaze_tpu.ops.fused import fuse_pipelines

    ctx = ExecContext()
    with pinned(tier1=256, group_core="sort", batch_size=1024,
                shape_buckets=(1024, 8192), agg_group_capacity=1024), \
            dispatch.task_scope(ctx):
        frame, batches = q1_batches(1024, 6, 5, first_customer)
        plan = fuse_pipelines(HashAggregateExec(
            FilterExec(MemoryScanExec([batches], batches[0].schema),
                       Col("s").is_not_null() | Col("s").is_null()),
            keys=[(Col("c"), "c"), (Col("s"), "s")],
            aggs=[(AggExpr(AggFn.SUM, Col("amt")), "total")],
            mode=AggMode.COMPLETE,
        ))
        got = pa.Table.from_batches(
            [b.to_arrow() for b in plan.execute(0, ctx)]).to_pandas()
    exp = (frame.groupby(["c", "s"], dropna=False)["cents"]
           .sum(min_count=1).reset_index())
    assert len(exp) > 1024 and exp["cents"].isna().sum() > 10
    assert exp["c"].isna().any() and exp["s"].isna().any()
    # six per-batch programs (a 1,024-row bucket has no tier)
    assert sum(n for k, n in launches.items()
               if k[0][0] == "fusedagg_packed"
               and k[-2:] == (False, ())) == 6
    merges = {k: n for k, n in launches.items() if k[0] == "hashagg"}
    assert [k[-2:] for k in merges] == [
        (force_lexsort, (256, 1024)) for force_lexsort in merge_programs]
    assert set(merges.values()) == {1}
    assert ctx.metrics.counters["agg_tier_retries"] == 0
    assert len(got) == len(exp)
    assert q1_keyed(got, "total", lambda d: int(d.scaleb(2))) \
        == q1_keyed(exp, "cents")


# ---- POLL ---------------------------------------------------------------

def split_of(config_name, table, tmp_path, seed=11):
    from perfbench import datagen

    with open(os.path.join(ROOT, "perfbench", "configs",
                           config_name + ".json")) as f:
        config = json.load(f)
    data = config["data"]
    data["tables"][table]["split_rows"] = config["rehearsal_split_rows"]
    data["tables"][table]["splits"] = 1
    frame = datagen.gen_tables(data, config["generator"], seed)[table][0]
    path = str(tmp_path / f"{table}.parquet")
    datagen._write(frame, path, config["parquet"])
    return frame, path


@pytest.fixture
def client():
    from blaze_tpu.runtime.gateway import TaskGatewayServer
    from blaze_tpu.service import QueryService, ServiceClient

    # one device's path: with the eight virtual devices of conftest.py
    # the default would lower query 1's task onto the mesh group-by,
    # which has no tiers (tests/test_mesh_group_q1.py)
    with QueryService(max_concurrency=2, mesh_mode="off") as svc:
        with TaskGatewayServer(service=svc) as srv:
            with ServiceClient(*srv.address) as c:
                yield c


# the scatter core climbs two tiers in each of the split's three
# per-batch programs (until PR 33 the eight virtual devices sent this
# task to the mesh group-by, which fell back to the unfused aggregate
# for the NULLs: one merge, two climbs)
@pytest.mark.parametrize("core,retries,running", [("sort", 0, 3),
                                                  ("scatter", 6, 0)])
def test_poll_of_a_keyed_aggregate_carries_the_count(core, retries,
                                                     running, client,
                                                     tmp_path):
    """Query 1's task through the served path (the cell `q1_group.s4`'s
    plan over a rehearsal split): exact against the benchmark's plain
    reference, and POLL says how many grouping programs ran again. The
    merge's 32,768-row bucket holds about 23,000 groups under tiers
    [4096, 8192, None]."""
    from perfbench.templates import q1_group

    frame, path = split_of("tpcds_sf1000_store_returns", "store_returns",
                           tmp_path)
    params = {"year": 2000, "agg_field": "sr_return_amt"}
    with pinned(group_core=core, agg_group_capacity=8192):
        st = client.submit(q1_group.build(path, params, {}))
        got = q1_group.answer(client.fetch(st["query_id"]), {})
        poll = client.poll(st["query_id"])
    want = q1_group.reference(frame, params)
    assert len(want["values"]["ctr_customer_sk"]) > 8192
    assert q1_group.compare(want, got) == {
        "groups_wrong": 0, "answer_shape_wrong": 0}
    assert poll["state"] == "DONE" and not poll.get("cache_hits")
    assert poll["agg_tier_retries"] == retries
    # two per-batch programs and the merge read their integer sums off a
    # running sum on the sort core; the scatter core scatters them
    assert poll["agg_running_sum_launches"] == running
    # the merge's one concatenation of the two partial states, written
    # whole at their offsets on either core (ops/util.py: _concat_many)
    assert poll["concat_slice_parts"] == 2


def test_poll_without_a_keyed_aggregate_has_no_count(client, tmp_path):
    from perfbench.templates import q1_group, q6_scan, q9_scalar

    frame, path = split_of("tpcds_sf1000_store_sales_128m", "store_sales",
                           tmp_path)
    # the month the split's first sale falls in (a Julian day number)
    day = datetime.date.fromordinal(
        int(frame["values"]["ss_sold_date_sk"].min()) - q1_group.JULIAN)
    blobs = [
        q9_scalar.build(path, {"lo": 1, "hi": 20, "agg": "avg",
                               "column": "ss_net_paid"}, {}),
        q6_scan.build(path, {"year": day.year, "month": day.month}, {}),
    ]
    for blob in blobs:
        st = client.submit(blob)
        client.fetch(st["query_id"])
        poll = client.poll(st["query_id"])
        assert poll["state"] == "DONE" and poll["task_dispatches"] > 0
        assert "agg_tier_retries" not in poll
        assert "agg_running_sum_launches" not in poll
        assert "concat_slice_parts" not in poll
