"""Distributed tier tests on the virtual 8-device CPU mesh: exchange
operators (file tier), all_to_all repartition, sharded group-by (ICI
tier)."""

import numpy as np
import pyarrow as pa
import pytest

import jax
import jax.numpy as jnp

from blaze_tpu import ColumnBatch
from blaze_tpu.exprs import AggExpr, AggFn, Col
from blaze_tpu.exprs.ir import bind
from blaze_tpu.ops import (
    AggMode,
    ExecContext,
    HashAggregateExec,
    MemoryScanExec,
)
from blaze_tpu.parallel import (
    BroadcastExchangeExec,
    CoalescedShuffleReader,
    ShuffleExchangeExec,
    get_mesh,
)
from blaze_tpu.parallel.repartition import all_to_all_repartition
from blaze_tpu.parallel.sharded import DistAgg, DistributedGroupBy
from blaze_tpu.runtime.executor import run_plan


def test_mesh_has_8_devices():
    assert len(jax.devices()) == 8


def multi_partition_scan(n_parts=4, rows_per=100):
    parts = []
    schema = None
    for p in range(n_parts):
        cb = ColumnBatch.from_pydict(
            {
                "k": [(p * rows_per + i) % 10 for i in range(rows_per)],
                "v": [p * rows_per + i for i in range(rows_per)],
            }
        )
        schema = cb.schema
        parts.append([cb])
    return MemoryScanExec(parts, schema)


def test_shuffle_exchange_end_to_end(tmp_path):
    scan = multi_partition_scan()
    ex = ShuffleExchangeExec(
        scan, [Col("k")], 5, shuffle_dir=str(tmp_path)
    )
    # distributed two-phase aggregate across the exchange
    final = HashAggregateExec(
        ex,
        keys=[(Col("k"), "k")],
        aggs=[(AggExpr(AggFn.SUM, Col("v")), "s"),
              (AggExpr(AggFn.COUNT_STAR, None), "n")],
        mode=AggMode.COMPLETE,
    )
    out = run_plan(final).to_pydict()
    got = dict(zip(out["k"], out["s"]))
    all_rows = [(i % 10, i) for i in range(400)]
    exp = {}
    for k, v in all_rows:
        exp[k] = exp.get(k, 0) + v
    assert got == exp
    assert sum(out["n"]) == 400


def test_coalesced_reader(tmp_path):
    scan = multi_partition_scan()
    ex = ShuffleExchangeExec(
        scan, [Col("k")], 8, shuffle_dir=str(tmp_path)
    )
    rd = CoalescedShuffleReader(ex, [(0, 4), (4, 8)])
    assert rd.partition_count == 2
    total = sum(
        b.num_rows
        for p in range(2)
        for b in rd.execute(p, ExecContext())
    )
    assert total == 400


def test_broadcast_exchange():
    scan = multi_partition_scan(2, 10)
    bc = BroadcastExchangeExec(scan, num_partitions=3)
    ctx = ExecContext()
    rows_per_consumer = [
        sum(b.num_rows for b in bc.execute(p, ctx)) for p in range(3)
    ]
    assert rows_per_consumer == [20, 20, 20]  # full copy everywhere


def test_all_to_all_repartition():
    mesh = get_mesh()
    n_dev, cap = 8, 32
    rng = np.random.default_rng(3)
    vals = jnp.asarray(rng.integers(0, 1000, (n_dev, cap)))
    target = jnp.asarray(rng.integers(0, n_dev, (n_dev, cap)),
                         dtype=jnp.int32)
    live = jnp.asarray(rng.random((n_dev, cap)) < 0.7)
    (out_vals,), out_live = all_to_all_repartition(
        mesh, [vals], target, live
    )
    # every live row lands on its target device exactly once
    v_np, t_np, l_np = map(np.asarray, (vals, target, live))
    ov, ol = np.asarray(out_vals), np.asarray(out_live)
    for d in range(n_dev):
        expected = sorted(v_np[l_np & (t_np == d)].tolist())
        got = sorted(ov[d][ol[d]].tolist())
        assert got == expected, d


def test_distributed_group_by():
    mesh = get_mesh()
    n_dev, cap = 8, 64
    rng = np.random.default_rng(11)
    keys = rng.integers(0, 13, (n_dev, cap)).astype(np.int64)
    vals = rng.integers(0, 100, (n_dev, cap)).astype(np.int64)
    num_rows = rng.integers(10, cap + 1, n_dev).astype(np.int32)

    from blaze_tpu.types import DataType, Field, Schema

    schema = Schema(
        [Field("k", DataType.int64()), Field("v", DataType.int64())]
    )
    gb = DistributedGroupBy(
        mesh, schema,
        keys=[Col("k")],
        aggs=[DistAgg(AggFn.SUM, Col("v")),
              DistAgg(AggFn.COUNT_STAR, None),
              DistAgg(AggFn.MIN, Col("v")),
              DistAgg(AggFn.AVG, Col("v"))],
        filter_pred=Col("v") >= 10,
    )
    key_out, agg_out, counts = gb(
        [jnp.asarray(keys), jnp.asarray(vals)], jnp.asarray(num_rows)
    )
    # flatten device-owned groups
    got = {}
    ko = np.asarray(key_out[0])
    sums, cnts, mins, avgs = map(np.asarray, agg_out)
    cn = np.asarray(counts)
    for d in range(n_dev):
        for g in range(int(cn[d])):
            k = int(ko[d, g])
            assert k not in got, "group split across devices"
            got[k] = (
                int(sums[d, g]), int(cnts[d, g]), int(mins[d, g]),
                float(avgs[d, g]),
            )
    # differential reference
    exp = {}
    for d in range(n_dev):
        for i in range(int(num_rows[d])):
            if vals[d, i] < 10:
                continue
            k = int(keys[d, i])
            s, c, m = exp.get(k, (0, 0, 10**9))
            exp[k] = (s + int(vals[d, i]), c + 1,
                      min(m, int(vals[d, i])))
    exp_full = {
        k: (s, c, m, s / c) for k, (s, c, m) in exp.items()
    }
    assert set(got) == set(exp_full)
    for k in exp_full:
        assert got[k][:3] == exp_full[k][:3], k
        np.testing.assert_allclose(got[k][3], exp_full[k][3])


def test_distributed_broadcast_join():
    from blaze_tpu.parallel.sharded import DistributedBroadcastJoin
    from blaze_tpu.types import DataType, Field, Schema

    mesh = get_mesh()
    n_dev, p_cap, b_cap = 8, 32, 8
    rng = np.random.default_rng(21)
    # build: 8*8 slots, unique keys 0..n_build-1 scattered over shards
    build_rows = rng.integers(2, b_cap + 1, n_dev).astype(np.int32)
    all_keys = rng.permutation(500)[: int(build_rows.sum())]
    bk = np.zeros((n_dev, b_cap), dtype=np.int64)
    bv = np.zeros((n_dev, b_cap), dtype=np.int64)
    it = iter(all_keys)
    for d in range(n_dev):
        for i in range(int(build_rows[d])):
            k = int(next(it))
            bk[d, i] = k
            bv[d, i] = k * 100
    probe_rows = rng.integers(5, p_cap + 1, n_dev).astype(np.int32)
    pk = rng.integers(0, 500, (n_dev, p_cap)).astype(np.int64)
    pv = rng.integers(0, 10, (n_dev, p_cap)).astype(np.int64)

    p_schema = Schema([Field("pk", DataType.int64()),
                       Field("pv", DataType.int64())])
    b_schema = Schema([Field("bk", DataType.int64()),
                       Field("bv", DataType.int64())])
    from blaze_tpu.exprs import Col

    j = DistributedBroadcastJoin(
        mesh, p_schema, b_schema, Col("pk"), Col("bk")
    )
    hit, build_out = j(
        [jnp.asarray(pk), jnp.asarray(pv)], jnp.asarray(probe_rows),
        [jnp.asarray(bk), jnp.asarray(bv)], jnp.asarray(build_rows),
    )
    hit = np.asarray(hit)
    got_bv = np.asarray(build_out[1])
    key_set = set(int(k) for k in all_keys)
    for d in range(n_dev):
        for i in range(int(probe_rows[d])):
            expected = int(pk[d, i]) in key_set
            assert bool(hit[d, i]) == expected, (d, i)
            if expected:
                assert int(got_bv[d, i]) == int(pk[d, i]) * 100
        assert not hit[d, int(probe_rows[d]):].any()


def test_mesh_group_by_exec():
    from blaze_tpu.parallel.mesh_ops import MeshGroupByExec
    from blaze_tpu.runtime.executor import run_plan

    scan = multi_partition_scan(6, 80)  # 6 partitions <= 8 devices
    op = MeshGroupByExec(
        scan,
        keys=[(Col("k"), "k")],
        aggs=[(AggExpr(AggFn.SUM, Col("v")), "s"),
              (AggExpr(AggFn.COUNT_STAR, None), "n")],
    )
    out = run_plan(op).to_pandas().sort_values("k").reset_index(drop=True)
    import pandas as pd

    rows = [(i % 10, i) for i in range(480)]
    ref = (
        pd.DataFrame(rows, columns=["k", "v"])
        .groupby("k")
        .agg(s=("v", "sum"), n=("v", "size"))
        .reset_index()
    )
    np.testing.assert_array_equal(out["k"], ref["k"])
    np.testing.assert_array_equal(out["s"], ref["s"])
    np.testing.assert_array_equal(out["n"], ref["n"])


def test_all_to_all_repartition_slack_and_skew_retry():
    """Slack-sized buckets shrink the exchanged footprint; pathological
    skew (every row to one device) overflows them and the retry at
    worst-case capacity keeps the result exact."""
    mesh = get_mesh()
    n_dev, cap = 8, 512
    rng = np.random.default_rng(7)
    vals = jnp.asarray(rng.integers(0, 1000, (n_dev, cap)))
    live = jnp.ones((n_dev, cap), dtype=bool)

    # uniform targets: slack path, exchanged rows per shard shrink
    # (slack must cover the max-of-buckets statistical spread)
    target_u = jnp.asarray(
        rng.integers(0, n_dev, (n_dev, cap)), dtype=jnp.int32
    )
    (out_u,), live_u = all_to_all_repartition(
        mesh, [vals], target_u, live, slack=2.0
    )
    assert out_u.shape[1] < n_dev * cap  # slack buckets, not worst-case
    v_np, t_np = np.asarray(vals), np.asarray(target_u)
    for d in range(n_dev):
        expected = sorted(v_np[t_np == d].tolist())
        got = sorted(
            np.asarray(out_u)[d][np.asarray(live_u)[d]].tolist()
        )
        assert got == expected, d

    # full skew: everything to device 3 -> overflow -> retry, exact
    target_s = jnp.full((n_dev, cap), 3, dtype=jnp.int32)
    (out_s,), live_s = all_to_all_repartition(
        mesh, [vals], target_s, live, slack=2.0
    )
    got3 = sorted(np.asarray(out_s)[3][np.asarray(live_s)[3]].tolist())
    assert got3 == sorted(v_np.reshape(-1).tolist())
    for d in range(n_dev):
        if d != 3:
            assert not np.asarray(live_s)[d].any()


def test_lower_to_mesh_complete_aggregate():
    """planner.distribute.lower_to_mesh sends a COMPLETE grouped
    aggregate (the shape a decoded single-stage TaskDefinition carries)
    to MeshGroupByExec, and the mesh result matches the per-partition
    engine result merged in pandas."""
    from blaze_tpu.parallel.mesh_ops import MeshGroupByExec
    from blaze_tpu.planner.distribute import lower_to_mesh

    scan = multi_partition_scan(n_parts=8, rows_per=300)
    plan = HashAggregateExec(
        scan,
        keys=[(Col("k"), "k")],
        aggs=[(AggExpr(AggFn.SUM, Col("v")), "s"),
              (AggExpr(AggFn.COUNT_STAR, None), "n")],
        mode=AggMode.COMPLETE,
    )
    lowered = lower_to_mesh(plan)
    assert isinstance(lowered, MeshGroupByExec)
    got = (
        run_plan(lowered).to_pandas().sort_values("k")
        .reset_index(drop=True)
    )
    df = run_plan(scan).to_pandas()
    want = (
        df.groupby("k").agg(s=("v", "sum"), n=("v", "size"))
        .reset_index().sort_values("k").reset_index(drop=True)
    )
    np.testing.assert_array_equal(got["k"], want["k"])
    np.testing.assert_allclose(got["s"], want["s"])
    np.testing.assert_array_equal(got["n"], want["n"])


def test_lower_to_mesh_exchange_sandwich_and_fallback():
    """The FINAL-over-hash-exchange-over-PARTIAL sandwich that
    insert_exchanges plants lowers to ONE MeshGroupByExec; string-keyed
    aggregates stay on the file-shuffle tier (tryConvert fallback)."""
    from blaze_tpu.exprs.ir import AggExpr as _AE
    from blaze_tpu.parallel.mesh_ops import MeshGroupByExec
    from blaze_tpu.planner.distribute import insert_exchanges, lower_to_mesh

    scan = multi_partition_scan(n_parts=4, rows_per=200)
    plan = HashAggregateExec(
        scan,
        keys=[(Col("k"), "k")],
        aggs=[(AggExpr(AggFn.SUM, Col("v")), "s"),
              (AggExpr(AggFn.MAX, Col("v")), "m")],
        mode=AggMode.COMPLETE,
    )
    import tempfile

    sandwich = insert_exchanges(plan, 4,
                                shuffle_dir=tempfile.mkdtemp())
    # sanity: insert_exchanges really made FINAL / exchange / PARTIAL
    assert sandwich.mode is AggMode.FINAL
    lowered = lower_to_mesh(sandwich)
    assert isinstance(lowered, MeshGroupByExec)
    got = (
        run_plan(lowered).to_pandas().sort_values("k")
        .reset_index(drop=True)
    )
    df = run_plan(multi_partition_scan(n_parts=4,
                                       rows_per=200)).to_pandas()
    want = (
        df.groupby("k").agg(s=("v", "sum"), m=("v", "max"))
        .reset_index().sort_values("k").reset_index(drop=True)
    )
    np.testing.assert_allclose(got["s"], want["s"])
    np.testing.assert_array_equal(got["m"], want["m"])

    # string keys gate out (host hashing tier): node left untouched
    strings = pa.record_batch(
        {"name": pa.array(["a", "b", "a", "c"]).dictionary_encode(),
         "v": pa.array([1, 2, 3, 4], type=pa.int64())}
    )
    cb = ColumnBatch.from_arrow(strings)
    splan = HashAggregateExec(
        MemoryScanExec([[cb]], cb.schema),
        keys=[(Col("name"), "name")],
        aggs=[(AggExpr(AggFn.SUM, Col("v")), "s")],
        mode=AggMode.COMPLETE,
    )
    assert lower_to_mesh(splan) is splan


@pytest.mark.parametrize("join", ["inner", "left", "full"])
def test_exchanges_with_empty_partitions_on_both_sides(join, tmp_path):
    """A fact side whose keys are {1, 2} joined to a dimension whose
    keys are {2, 3}, through insert_exchanges at four partitions: one
    partition is empty on both sides of the shuffle, key 1's holds left
    rows only and key 3's right rows only. The SMJ over the co-partitioned files and the
    PARTIAL / exchange / FINAL aggregate above it give pandas' answer.
    (The TPC-DS exchange matrix met such partitions by chance on its
    small dimensions while it cut every plan in four.)"""
    import pandas as pd

    from blaze_tpu.exprs.hashing import hash_long_host
    from blaze_tpu.ops.joins import JoinType, SortMergeJoinExec
    from blaze_tpu.planner.distribute import insert_exchanges

    n_parts = 4

    def pmod(k):
        return int(np.int32(np.uint32(hash_long_host(k) & 0xFFFFFFFF))
                   ) % n_parts

    left = pd.DataFrame({"lk": [1, 2] * 50, "lv": np.arange(100)})
    right = pd.DataFrame({"rk": [2, 3] * 5, "rv": np.arange(10) * 7})
    # three keys in three different partitions: one of the four is
    # empty on both sides, one on the right only, one on the left only
    assert len({pmod(1), pmod(2), pmod(3)}) == 3

    def scan(df):
        cb = ColumnBatch.from_arrow(
            pa.RecordBatch.from_pandas(df, preserve_index=False))
        return MemoryScanExec([[cb]], cb.schema)

    plan = HashAggregateExec(
        SortMergeJoinExec(scan(left), scan(right), ["lk"], ["rk"],
                          JoinType(join)),
        keys=[(Col("lk"), "lk"), (Col("rk"), "rk")],
        aggs=[(AggExpr(AggFn.SUM, Col("lv")), "s"),
              (AggExpr(AggFn.COUNT_STAR, None), "n")],
        mode=AggMode.COMPLETE,
    )
    plan = insert_exchanges(plan, n_parts, shuffle_dir=str(tmp_path))
    assert plan.mode is AggMode.FINAL
    got = run_plan(plan).to_pandas()

    how = {"inner": "inner", "left": "left", "full": "outer"}[join]
    want = (
        left.merge(right, left_on="lk", right_on="rk", how=how)
        .groupby(["lk", "rk"], dropna=False)
        .agg(s=("lv", lambda v: v.sum(min_count=1)), n=("lv", "size"))
        .reset_index()
    )

    def rows(df):
        return sorted(
            tuple(-1 if pd.isna(x) else int(x) for x in r)  # -1: NULL
            for r in df[["lk", "rk", "s", "n"]].itertuples(index=False)
        )

    assert rows(got) == rows(want)
