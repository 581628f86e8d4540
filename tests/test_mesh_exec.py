"""Mesh execution tier (ISSUE 7): the cost-guarded planner pass, the
mesh-vs-single-device differential battery (skewed keys, empty
partitions, forced 1/2/8 host device counts), the chaos `mesh.exchange`
seam (TRANSIENT retry / degrade-to-single-device), and the QueryService
acceptance pin (mesh mode end to end with `mesh.exchange.*` metrics and
per-device spans in a validate_chrome-clean trace).

Runs under the repo conftest's forced 8-device virtual CPU mesh; the
1/2/8 differential spawns its own subprocesses because the device count
freezes at first backend init.
"""

import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import jax

from blaze_tpu import ColumnBatch
from blaze_tpu.exprs import AggExpr, AggFn, Col
from blaze_tpu.ops import (
    AggMode,
    ExecContext,
    FilterExec,
    HashAggregateExec,
    MemoryScanExec,
    ProjectExec,
)
from blaze_tpu.ops.joins import HashJoinExec, JoinType
from blaze_tpu.parallel.mesh_exec import (
    MeshBroadcastJoinExec,
    MeshPipelineExec,
)
from blaze_tpu.parallel.mesh_ops import MeshGroupByExec
from blaze_tpu.planner.distribute import (
    estimate_rows,
    insert_exchanges,
    lower_plan_to_mesh,
)
from blaze_tpu.runtime.executor import run_plan
from blaze_tpu.testing import chaos

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def scan(n_parts=4, rows=200, keys=10, empty=()):
    """Multi-partition in-memory source; partitions in `empty` carry
    zero rows (the empty-partition edge)."""
    parts, schema = [], None
    for p in range(n_parts):
        n = 0 if p in empty else rows
        cb = ColumnBatch.from_arrow(pa.record_batch({
            "k": np.asarray(
                [(p * rows + i) % keys for i in range(n)],
                dtype=np.int64,
            ),
            "v": np.asarray(
                [p * rows + i for i in range(n)], dtype=np.int64
            ),
        }))
        schema = cb.schema
        parts.append([cb])
    return MemoryScanExec(parts, schema)


def agg_plan(source):
    return HashAggregateExec(
        source,
        keys=[(Col("k"), "k")],
        aggs=[(AggExpr(AggFn.SUM, Col("v")), "s"),
              (AggExpr(AggFn.COUNT_STAR, None), "n")],
        mode=AggMode.COMPLETE,
    )


def sandwich(source, n=4):
    return insert_exchanges(agg_plan(source),
                            n, shuffle_dir=tempfile.mkdtemp())


def table_sorted(plan, by="k"):
    return (
        run_plan(plan).to_pandas().sort_values(by)
        .reset_index(drop=True)
    )


# ---------------------------------------------------------------------------
# planner pass
# ---------------------------------------------------------------------------


def test_lower_plan_refuses_multi_partition_complete():
    """A bare COMPLETE aggregate over a multi-partition child has
    per-partition grouping semantics; the production pass must not
    silently turn it into a global aggregate."""
    plan = agg_plan(scan())
    assert lower_plan_to_mesh(plan, mode="on") is plan


def test_lower_plan_sandwich_and_modes(monkeypatch):
    sw = sandwich(scan())
    assert isinstance(lower_plan_to_mesh(sw, mode="on"),
                      MeshGroupByExec)
    # off: untouched
    sw2 = sandwich(scan())
    assert lower_plan_to_mesh(sw2, mode="off") is sw2
    # auto + cost guard: this tiny plan stays single-device under a
    # high row floor, lowers under a zero floor
    monkeypatch.setenv("BLAZE_MESH_MIN_ROWS", "10000000")
    sw3 = sandwich(scan())
    assert lower_plan_to_mesh(sw3, mode="auto") is sw3
    monkeypatch.setenv("BLAZE_MESH_MIN_ROWS", "0")
    assert isinstance(
        lower_plan_to_mesh(sandwich(scan()), mode="auto"),
        MeshGroupByExec,
    )


def test_estimate_rows_leaves():
    src = scan(n_parts=3, rows=100)
    assert estimate_rows(src) == 300
    assert estimate_rows(agg_plan(src)) == 300


def test_pick_mesh_axis_from_plan_shape():
    """Partition-axis width follows the child partition count (capped
    by the device pool); a 1-partition child takes the full mesh."""
    sw = sandwich(scan(n_parts=4), n=4)
    low = lower_plan_to_mesh(sw, mode="on")
    assert isinstance(low, MeshGroupByExec)
    assert low.partition_count == 4
    one = lower_plan_to_mesh(agg_plan(scan(n_parts=1)), mode="on")
    assert isinstance(one, MeshGroupByExec)
    assert one.partition_count == len(jax.devices())


# ---------------------------------------------------------------------------
# differential battery (in-process, 8 devices)
# ---------------------------------------------------------------------------


def test_mesh_groupby_differential_vs_single_device():
    want = table_sorted(sandwich(scan()))
    got = table_sorted(lower_plan_to_mesh(sandwich(scan()),
                                          mode="on"))
    pd.testing.assert_frame_equal(got, want, check_dtype=False)


@pytest.mark.parametrize("dtype", [np.int32, np.float32])
def test_mesh_groupby_sum_widens_like_single_device(dtype):
    """SUM over a narrow column: the mesh result carries the
    single-device aggregate's type (int64 / float64) and accumulates in
    it - 4 x 2^30 wraps in int32, and 2^24 + 1 + 1 + 1 stays 2^24 in
    f32."""
    vals = ([1 << 30] * 4 if dtype is np.int32
            else [float(1 << 24), 1.0, 1.0, 1.0])
    cb = ColumnBatch.from_arrow(pa.record_batch({
        "k": np.zeros(4, dtype=np.int64),
        "v": np.asarray(vals, dtype=dtype),
    }))

    def plan():
        return agg_plan(MemoryScanExec([[cb]], cb.schema))

    want = table_sorted(plan())
    low = lower_plan_to_mesh(plan(), mode="on")
    assert isinstance(low, MeshGroupByExec)
    got = table_sorted(low)
    pd.testing.assert_frame_equal(got, want, check_dtype=True)
    assert got["s"][0] == sum(vals)


def test_mesh_groupby_skewed_keys():
    """Every row hashes to ONE owner device: the all_to_all exchange
    funnels all partial states to a single shard."""
    parts, schema = [], None
    rng = np.random.default_rng(7)
    for p in range(8):
        k = np.full(300, 7, dtype=np.int64)
        k[:3] = [1, 2, 3]  # a few stragglers
        cb = ColumnBatch.from_arrow(pa.record_batch(
            {"k": k, "v": rng.integers(0, 100, 300).astype(np.int64)}
        ))
        schema = cb.schema
        parts.append([cb])
    src = MemoryScanExec(parts, schema)
    want = table_sorted(sandwich(src, n=8))
    src2 = MemoryScanExec(parts, schema)
    low = lower_plan_to_mesh(sandwich(src2, n=8), mode="on")
    assert isinstance(low, MeshGroupByExec)
    got = table_sorted(low)
    pd.testing.assert_frame_equal(got, want, check_dtype=False)


def test_mesh_groupby_empty_partitions():
    src = scan(n_parts=6, rows=150, empty=(1, 4))
    want = table_sorted(sandwich(src, n=6))
    low = lower_plan_to_mesh(
        sandwich(scan(n_parts=6, rows=150, empty=(1, 4)), n=6),
        mode="on",
    )
    assert isinstance(low, MeshGroupByExec)
    got = table_sorted(low)
    pd.testing.assert_frame_equal(got, want, check_dtype=False)


def nullable_scan(n_parts=1, rows=6000, with_string=False):
    """A source whose key, second key and amounts each hold NULLs, the
    amounts `decimal(7,2)`; `with_string` adds a string column."""
    import decimal

    rng = np.random.default_rng(29)
    parts, frames, schema = [], [], None
    for _ in range(n_parts):
        def holes(values):
            return pd.Series(values, dtype=object).where(
                rng.random(rows) > 0.1, None)

        frame = pd.DataFrame({
            "k": holes(rng.integers(0, 40, rows)),
            "j": holes(rng.integers(0, 3, rows)),
            "cents": holes(rng.integers(-5000, 90000, rows)),
        })
        # one group's amounts are all NULL
        frame.loc[frame["k"] == 7, "cents"] = None
        frames.append(frame)
        cols = {
            "k": pa.array(frame["k"], pa.int32()),
            "j": pa.array(frame["j"], pa.int32()),
            "amt": pa.array(
                [None if c is None else decimal.Decimal(int(c)).scaleb(-2)
                 for c in frame["cents"]], pa.decimal128(7, 2)),
        }
        if with_string:
            cols["t"] = pa.array(["x"] * rows, pa.string())
        cb = ColumnBatch.from_arrow(pa.record_batch(cols))
        schema = cb.schema
        parts.append([cb])
    return MemoryScanExec(parts, schema), pd.concat(frames)


def nullable_agg(source):
    return HashAggregateExec(
        source,
        keys=[(Col("k"), "k"), (Col("j"), "j")],
        aggs=[(AggExpr(AggFn.SUM, Col("amt")), "total"),
              (AggExpr(AggFn.COUNT, Col("amt")), "n")],
        mode=AggMode.COMPLETE,
    )


def test_mesh_groupby_takes_nullable_fixed_width_input():
    """NULL keys on either column and on both, a group of NULL amounts
    alone, `decimal(7,2)` summed exactly as `decimal(17,2)`: on the
    mesh, nothing degraded, and a single partition's rows dealt over
    the devices."""
    source, frame = nullable_scan()
    low = lower_plan_to_mesh(nullable_agg(source), mode="on")
    assert isinstance(low, MeshGroupByExec)
    ctx = ExecContext()
    got = run_plan(low, ctx)
    assert ctx.metrics.counters.get("mesh.degraded") is None
    assert ctx.metrics.counters["mesh_group_runs"] == 1
    assert ctx.metrics.counters["mesh_rows_in"] == len(frame)
    assert got.schema.field("total").type == pa.decimal128(17, 2)
    exp = frame.groupby(["k", "j"], dropna=False).agg(
        total=("cents", lambda c: c.dropna().sum() if c.notna().any()
               else None),
        n=("cents", "count")).reset_index()

    def keyed(rows):
        return {(None if pd.isna(k) else int(k),
                 None if pd.isna(j) else int(j)):
                (None if pd.isna(t) else int(t), int(n))
                for k, j, t, n in rows}

    g = got.to_pandas()
    have = keyed(zip(g["k"], g["j"],
                     [None if t is None else t.scaleb(2) for t in
                      g["total"]], g["n"]))
    want = keyed(zip(exp["k"], exp["j"], exp["total"], exp["n"]))
    assert len(g) == len(want) and have == want
    assert any(k is None and j is None for k, j in want)
    assert any(t is None for t, _ in want.values())


@pytest.mark.parametrize("shape", ["string column", "more partitions"])
def test_mesh_groupby_still_falls_back(shape):
    """What the mesh group-by leaves to the other tiers: a child with a
    string column is not lowered at all, and a child with more
    partitions than the mesh has devices keeps its exchange."""
    if shape == "string column":
        source, _ = nullable_scan(with_string=True)
        plan = nullable_agg(source)
        assert lower_plan_to_mesh(plan, mode="on") is plan
        with pytest.raises(NotImplementedError):
            MeshGroupByExec(source, plan.keys, plan.aggs)
    else:
        source, _ = nullable_scan(n_parts=len(jax.devices()) + 1,
                                  rows=50)
        sw = insert_exchanges(nullable_agg(source), 4,
                              shuffle_dir=tempfile.mkdtemp())
        assert lower_plan_to_mesh(sw, mode="on") is sw


def test_mesh_pipeline_differential():
    def chain(src):
        return ProjectExec(
            FilterExec(src, Col("v") >= 100),
            [(Col("k"), "k"), (Col("v") * Col("v"), "v2")],
        )

    low = lower_plan_to_mesh(chain(scan()), mode="on")
    assert isinstance(low, MeshPipelineExec)
    got = table_sorted(low, by="v2")
    want = table_sorted(chain(scan()), by="v2")
    pd.testing.assert_frame_equal(got, want, check_dtype=False)


def test_mesh_pipeline_empty_and_fully_filtered_partitions():
    def chain(src):
        # partition 0's rows all fail the predicate (v < 200)
        return FilterExec(src, Col("v") >= 200)

    src = scan(n_parts=5, rows=200, empty=(2,))
    want = table_sorted(chain(src), by="v")
    low = lower_plan_to_mesh(
        chain(scan(n_parts=5, rows=200, empty=(2,))), mode="on"
    )
    assert isinstance(low, MeshPipelineExec)
    got = table_sorted(low, by="v")
    pd.testing.assert_frame_equal(got, want, check_dtype=False)


def test_mesh_broadcast_join_differential():
    items = ColumnBatch.from_arrow(pa.record_batch({
        "ik": np.arange(10, dtype=np.int64),
        "iv": (np.arange(10, dtype=np.int64) * 100),
    }))

    def join(probe):
        return HashJoinExec(
            MemoryScanExec([[items]], items.schema), probe,
            ["ik"], ["k"], JoinType.INNER,
        )

    low = lower_plan_to_mesh(join(scan()), mode="on")
    assert isinstance(low, MeshBroadcastJoinExec)
    got = table_sorted(low, by="v")
    want = table_sorted(join(scan()), by="v")
    pd.testing.assert_frame_equal(
        got[sorted(got.columns)], want[sorted(want.columns)],
        check_dtype=False,
    )


def test_mesh_broadcast_join_duplicate_build_keys_degrade():
    """Duplicate build keys are only detectable at execution: the op
    degrades to the original HashJoinExec and the result is exactly
    the per-partition join's."""
    dup = ColumnBatch.from_arrow(pa.record_batch({
        "ik": np.asarray([1, 2, 2, 3], dtype=np.int64),
        "iv": np.asarray([10, 20, 21, 30], dtype=np.int64),
    }))

    def join(probe):
        return HashJoinExec(
            MemoryScanExec([[dup]], dup.schema), probe,
            ["ik"], ["k"], JoinType.INNER,
        )

    low = lower_plan_to_mesh(join(scan()), mode="on")
    assert isinstance(low, MeshBroadcastJoinExec)
    ctx = ExecContext()
    got = (
        run_plan(low, ctx).to_pandas()
        .sort_values(["v", "iv"]).reset_index(drop=True)
    )
    want = (
        run_plan(join(scan())).to_pandas()
        .sort_values(["v", "iv"]).reset_index(drop=True)
    )
    pd.testing.assert_frame_equal(
        got[sorted(got.columns)], want[sorted(want.columns)],
        check_dtype=False,
    )
    assert ctx.metrics.counters.get("mesh.degraded") == 1


# ---------------------------------------------------------------------------
# chaos: the mesh.exchange seam
# ---------------------------------------------------------------------------


def test_chaos_mesh_exchange_degrades_to_single_device():
    low = lower_plan_to_mesh(sandwich(scan()), mode="on")
    want = table_sorted(sandwich(scan()))
    ctx = ExecContext()
    with chaos.active(
        [chaos.Fault(site="mesh.exchange",
                     klass="RESOURCE_EXHAUSTED", times=1)],
        seed=11,
    ) as plan:
        got = (
            run_plan(low, ctx).to_pandas().sort_values("k")
            .reset_index(drop=True)
        )
    assert plan.fired("mesh.exchange") == 1
    pd.testing.assert_frame_equal(got, want, check_dtype=False)
    assert ctx.metrics.counters.get("mesh.degraded") == 1
    assert "mesh.exchange.all_to_all" not in ctx.metrics.counters


def test_chaos_mesh_exchange_transient_propagates_then_mesh_retries():
    low = lower_plan_to_mesh(sandwich(scan()), mode="on")
    want = table_sorted(sandwich(scan()))
    ctx = ExecContext()
    with chaos.active(
        [chaos.Fault(site="mesh.exchange", klass="TRANSIENT",
                     times=1)],
        seed=11,
    ):
        from blaze_tpu.errors import ErrorClass, classify

        with pytest.raises(Exception) as ei:
            run_plan(low, ctx)
        assert classify(ei.value) is ErrorClass.TRANSIENT
        # the retry (scheduler tier re-runs the task) stays ON the
        # mesh: fault budget consumed, program re-runs clean
        got = (
            run_plan(low, ctx).to_pandas().sort_values("k")
            .reset_index(drop=True)
        )
    pd.testing.assert_frame_equal(got, want, check_dtype=False)
    assert ctx.metrics.counters.get("mesh.degraded") is None
    assert ctx.metrics.counters.get("mesh.exchange.all_to_all") == 1


def test_service_chaos_transient_retry_lands_in_attempt_journal():
    """Through the serving tier: one injected TRANSIENT at
    mesh.exchange retries via the classified policy and the query
    still answers from the mesh."""
    from blaze_tpu.service import QueryService

    svc = QueryService(enable_cache=False, enable_trace=False,
                       mesh_mode="on")
    try:
        with chaos.active(
            [chaos.Fault(site="mesh.exchange", klass="TRANSIENT",
                         times=1)],
            seed=5,
        ):
            q = svc.submit_plan(
                lower_plan_to_mesh(sandwich(scan()), mode="on")
            )
            batches = svc.result(q.query_id, timeout=120)
        got = (
            pa.Table.from_batches(batches).to_pandas()
            .sort_values("k").reset_index(drop=True)
        )
        want = table_sorted(sandwich(scan()))
        pd.testing.assert_frame_equal(got, want, check_dtype=False)
        assert any(a["action"] == "retry" for a in q.attempts)
        assert not q.degraded
        assert q.ctx.metrics.counters.get(
            "mesh.exchange.all_to_all") == 1
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# serving-tier acceptance: mesh mode end to end
# ---------------------------------------------------------------------------


def _grouped_task_blob(path):
    from blaze_tpu.ops.parquet_scan import FileRange, ParquetScanExec
    from blaze_tpu.plan.serde import task_to_proto

    return task_to_proto(
        agg_plan(ParquetScanExec([[FileRange(path)]])), 0
    )


def _canonical_bytes(batches):
    df = (
        pa.Table.from_batches(batches).to_pandas()
        .sort_values("k").reset_index(drop=True)
    )
    tbl = pa.Table.from_pandas(df, preserve_index=False) \
        .combine_chunks()
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, tbl.schema) as w:
        w.write_table(tbl)
    return sink.getvalue().to_pybytes()


def test_service_mesh_acceptance(tmp_path):
    """ISSUE 7 acceptance: a grouped-aggregation query through
    QueryService on the forced 8-device host mesh produces results
    byte-equal to single-device execution, the exchange is visible as
    `mesh.exchange.*` metrics, and the trace carries per-device spans
    in one validate_chrome-clean document."""
    from blaze_tpu.obs.metrics import REGISTRY
    from blaze_tpu.obs.trace import validate_chrome
    from blaze_tpu.service import QueryService

    rng = np.random.default_rng(3)
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({
        "k": rng.integers(0, 97, 20000).astype(np.int64),
        "v": rng.integers(0, 1000, 20000).astype(np.int64),
    }), path)

    def run_service(mode):
        svc = QueryService(enable_cache=False, mesh_mode=mode)
        try:
            q = svc.submit_task(_grouped_task_blob(path))
            batches = svc.result(q.query_id, timeout=120)
            doc = svc.trace(q.query_id)
            return _canonical_bytes(batches), q, doc
        finally:
            svc.close()

    off_bytes, _, _ = run_service("off")
    on_bytes, q, doc = run_service("on")
    assert on_bytes == off_bytes  # byte-equal after canonical order
    # the exchange is visible in the metric tree + process registry
    c = q.ctx.metrics.counters
    assert c.get("mesh.exchange.all_to_all") == 1
    assert c.get("mesh.exchange.rows") == 20000
    assert c.get("mesh.devices") == 8
    assert REGISTRY.get("blaze_mesh_exchange_total",
                        kind="all_to_all") >= 1
    # per-device spans in ONE validate_chrome-clean trace
    names = [s.name for s in q.tracer.spans]
    assert "mesh_execute" in names
    assert names.count("mesh_device") == 8
    dev_tags = sorted(
        s.tags.get("device") for s in q.tracer.spans
        if s.name == "mesh_device"
    )
    assert dev_tags == list(range(8))
    assert validate_chrome(doc) == []


def test_service_mesh_fault_degrades_to_correct_result(tmp_path):
    """ISSUE 7 acceptance: an injected mesh.exchange fault degrades to
    a correct single-device result (not the host engine - `degraded`
    stays False; the mesh op's own fallback absorbed it)."""
    from blaze_tpu.service import QueryService

    rng = np.random.default_rng(9)
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({
        "k": rng.integers(0, 31, 8000).astype(np.int64),
        "v": rng.integers(0, 100, 8000).astype(np.int64),
    }), path)

    def run_service(mode, faults=()):
        svc = QueryService(enable_cache=False, enable_trace=False,
                           mesh_mode=mode)
        try:
            if faults:
                with chaos.active(list(faults), seed=13):
                    q = svc.submit_task(_grouped_task_blob(path))
                    batches = svc.result(q.query_id, timeout=120)
            else:
                q = svc.submit_task(_grouped_task_blob(path))
                batches = svc.result(q.query_id, timeout=120)
            return _canonical_bytes(batches), q
        finally:
            svc.close()

    want, _ = run_service("off")
    got, q = run_service("on", faults=[
        chaos.Fault(site="mesh.exchange", klass="RESOURCE_EXHAUSTED",
                    times=1),
    ])
    assert got == want
    assert not q.degraded  # single-device fallback, not host engine
    assert q.ctx.metrics.counters.get("mesh.degraded") == 1


def test_run_plan_parallel_mesh_mode():
    from blaze_tpu.runtime.scheduler import run_plan_parallel

    want = (
        run_plan_parallel(sandwich(scan()), parallelism=2)
        .to_pandas().sort_values("k").reset_index(drop=True)
    )
    got = (
        run_plan_parallel(sandwich(scan()), parallelism=2, mesh="on")
        .to_pandas().sort_values("k").reset_index(drop=True)
    )
    pd.testing.assert_frame_equal(got, want, check_dtype=False)


# ---------------------------------------------------------------------------
# forced 1/2/8 device-count differential (subprocesses)
# ---------------------------------------------------------------------------

_DIFF_SCRIPT = r"""
import json, sys, tempfile
import os
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
import pyarrow as pa
from blaze_tpu.exprs import AggExpr, AggFn, Col
from blaze_tpu.ops import AggMode, HashAggregateExec
from blaze_tpu.ops.parquet_scan import FileRange, ParquetScanExec
from blaze_tpu.planner.distribute import (
    insert_exchanges, lower_plan_to_mesh,
)
from blaze_tpu.runtime.executor import run_plan

files = json.loads(sys.argv[1])
out = sys.argv[2]
plan = insert_exchanges(
    HashAggregateExec(
        ParquetScanExec([[FileRange(f)] for f in files]),
        keys=[(Col("k"), "k")],
        aggs=[(AggExpr(AggFn.SUM, Col("v")), "s"),
              (AggExpr(AggFn.COUNT_STAR, None), "n"),
              (AggExpr(AggFn.MIN, Col("v")), "lo"),
              (AggExpr(AggFn.MAX, Col("v")), "hi")],
        mode=AggMode.COMPLETE),
    len(files), shuffle_dir=tempfile.mkdtemp())
lowered = lower_plan_to_mesh(plan, mode="on")
df = (run_plan(lowered).to_pandas().sort_values("k")
      .reset_index(drop=True))
tbl = pa.Table.from_pandas(df, preserve_index=False).combine_chunks()
sink = pa.BufferOutputStream()
with pa.ipc.new_stream(sink, tbl.schema) as w:
    w.write_table(tbl)
with open(out, "wb") as f:
    f.write(sink.getvalue().to_pybytes())
print("LOWERED:" + type(lowered).__name__)
"""


def test_differential_across_1_2_8_forced_devices(tmp_path):
    """Same query, same rows: results byte-equal across 1, 2 and 8
    forced host devices - with skewed keys and an empty partition in
    the inputs. 1 device executes the single-device exchange tier;
    2 and 8 lower onto the mesh."""
    rng = np.random.default_rng(21)
    skew = np.full(30000, 7, dtype=np.int64)
    skew[:40] = rng.integers(0, 13, 40)
    f0 = str(tmp_path / "p0.parquet")
    pq.write_table(pa.table({
        "k": skew,
        "v": rng.integers(0, 1000, 30000).astype(np.int64),
    }), f0)
    f1 = str(tmp_path / "p1.parquet")  # the empty partition
    pq.write_table(pa.table({
        "k": pa.array([], type=pa.int64()),
        "v": pa.array([], type=pa.int64()),
    }), f1)
    files = json.dumps([f0, f1])

    results = {}
    for n_dev in (1, 2, 8):
        out = str(tmp_path / f"out_{n_dev}.arrow")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={n_dev}"
        )
        env["PYTHONPATH"] = (
            REPO + os.pathsep + env.get("PYTHONPATH", "")
        )
        p = subprocess.run(
            [sys.executable, "-c", _DIFF_SCRIPT, files, out],
            capture_output=True, text=True, timeout=300, env=env,
            cwd=REPO,
        )
        assert p.returncode == 0, p.stderr[-2000:]
        lowered = next(
            ln.split(":", 1)[1] for ln in p.stdout.splitlines()
            if ln.startswith("LOWERED:")
        )
        if n_dev == 1:
            assert lowered == "HashAggregateExec"
        else:
            assert lowered == "MeshGroupByExec", lowered
        with open(out, "rb") as f:
            results[n_dev] = f.read()
    assert results[1] == results[2] == results[8]


@pytest.mark.slow
def test_mesh_dryrun_cli(tmp_path):
    """`python -m blaze_tpu mesh-dryrun` emits the MULTICHIP_r*.json
    artifact shape (the versioned, testable generator)."""
    out = str(tmp_path / "MULTICHIP.json")
    p = subprocess.run(
        [sys.executable, "-m", "blaze_tpu", "mesh-dryrun",
         "--devices", "2", "--timeout", "240", "-o", out],
        capture_output=True, text=True, timeout=300,
        cwd=REPO,
        env={**os.environ,
             "PYTHONPATH": REPO + os.pathsep
             + os.environ.get("PYTHONPATH", "")},
    )
    assert p.returncode == 0, (p.stdout, p.stderr)
    with open(out) as f:
        doc = json.load(f)
    assert set(doc) == {"n_devices", "rc", "ok", "skipped", "tail"}
    assert doc["n_devices"] == 2
    assert doc["ok"] is True and doc["skipped"] is False
    assert "dryrun_multichip OK" in doc["tail"]


def test_mesh_relational_fused_kernels_byte_equal_and_pin():
    """ISSUE 13: the mesh tier inherits the fused relational kernels
    for free. MeshGroupByExec and MeshBroadcastJoinExec results are
    BYTE-equal (canonical total order, serialized IPC) to the mesh-off
    path - which now runs the fused grouped-carry / join kernels - and
    the mesh-stage dispatch pin (ONE program launch per stage) is
    unchanged by the fusion work."""
    from blaze_tpu.ops.fused import fuse_pipelines
    from blaze_tpu.runtime import dispatch

    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device (forced-host) mesh")

    def canon(table):
        df = table.to_pandas()
        df = df.sort_values(list(df.columns)).reset_index(drop=True)
        tbl = pa.Table.from_pandas(df, preserve_index=False) \
            .combine_chunks()
        sink = pa.BufferOutputStream()
        with pa.ipc.new_stream(sink, tbl.schema) as w:
            w.write_table(tbl)
        return sink.getvalue().to_pybytes()

    # grouped aggregate: mesh-off exchange sandwich (whose per-device
    # sub-plans run the fused grouped-carry kernels) vs MeshGroupByExec
    want = canon(run_plan(fuse_pipelines(sandwich(scan(n_parts=8),
                                                  n=8))))
    low = lower_plan_to_mesh(sandwich(scan(n_parts=8), n=8), mode="on")
    assert isinstance(low, MeshGroupByExec)
    assert canon(run_plan(low)) == want
    low._result = None
    run_plan(low)  # warm

    def run_grouped():
        low._result = None
        return run_plan(low)

    with dispatch.counting() as c:
        run_grouped()
    assert c.counts.get("mesh_dispatches", 0) == 1, c.counts

    # broadcast join: mesh-off fused pipeline vs MeshBroadcastJoinExec
    items = ColumnBatch.from_arrow(pa.record_batch({
        "ik": np.arange(10, dtype=np.int64),
        "iv": (np.arange(10, dtype=np.int64) * 100),
    }))

    def join(probe):
        return HashJoinExec(
            MemoryScanExec([[items]], items.schema), probe,
            ["ik"], ["k"], JoinType.INNER,
        )

    jwant = canon(run_plan(fuse_pipelines(join(scan()))))
    jlow = lower_plan_to_mesh(join(scan()), mode="on")
    assert isinstance(jlow, MeshBroadcastJoinExec)
    assert canon(run_plan(jlow)) == jwant
    jlow._result = None
    run_plan(jlow)  # warm

    def run_join():
        jlow._result = None
        return run_plan(jlow)

    with dispatch.counting() as c:
        run_join()
    assert c.counts.get("mesh_dispatches", 0) == 1, c.counts


# ---------------------------------------------------------------------------
# mesh sort / window shapes (ISSUE 20 satellite)
# ---------------------------------------------------------------------------


def test_mesh_sort_differential_byte_equal():
    """Global sort lowered to the mesh (per-shard device lexsorts +
    host run-merge) is row-for-row equal to the single-device oracle,
    unique keys so the total order is fully determined."""
    from blaze_tpu.ops.sort import SortExec, SortKey
    from blaze_tpu.parallel.mesh_exec import MeshSortExec

    def mk():
        return insert_exchanges(
            SortExec(scan(), [SortKey(Col("v"))]),
            4, shuffle_dir=tempfile.mkdtemp(),
        )

    want = run_plan(mk()).to_pandas()
    low = lower_plan_to_mesh(mk(), mode="on")
    assert isinstance(low, MeshSortExec)
    got = run_plan(low).to_pandas()
    pd.testing.assert_frame_equal(got, want, check_dtype=False)


def test_mesh_sort_ties_keep_oracle_order():
    """Stability pin: duplicate keys keep earlier partitions first,
    matching the single-device stable sort."""
    from blaze_tpu.ops.sort import SortExec, SortKey
    from blaze_tpu.parallel.mesh_exec import MeshSortExec

    def mk(fetch=None):
        return insert_exchanges(
            SortExec(scan(), [SortKey(Col("k"))], fetch=fetch),
            4, shuffle_dir=tempfile.mkdtemp(),
        )

    want = run_plan(mk()).to_pandas()
    low = lower_plan_to_mesh(mk(), mode="on")
    assert isinstance(low, MeshSortExec)
    got = run_plan(low).to_pandas()
    pd.testing.assert_frame_equal(got, want, check_dtype=False)
    # top-n: fetch applies after the merge
    wantn = run_plan(mk(fetch=17)).to_pandas()
    lown = lower_plan_to_mesh(mk(fetch=17), mode="on")
    assert isinstance(lown, MeshSortExec)
    gotn = run_plan(lown).to_pandas()
    assert len(gotn) == 17
    pd.testing.assert_frame_equal(gotn, wantn, check_dtype=False)


def test_mesh_window_repartition_differential():
    """A partitioned window's hash exchange swaps for the mesh
    all_to_all repartition; the frames compute whole and the result
    matches the file-shuffle oracle after canonical order."""
    from blaze_tpu.ops.sort import SortKey
    from blaze_tpu.ops.window import WindowExec, WindowFn
    from blaze_tpu.parallel.mesh_exec import MeshRepartitionExec

    def mk():
        return insert_exchanges(
            WindowExec(
                scan(),
                partition_by=[Col("k")],
                order_by=[SortKey(Col("v"))],
                functions=[
                    WindowFn("row_number", None, "rn"),
                    WindowFn("sum", Col("v"), "run",
                             frame=("rows", None, 0)),
                ],
            ),
            4, shuffle_dir=tempfile.mkdtemp(),
        )

    def canon(t):
        return (t.to_pandas().sort_values(["k", "v"])
                .reset_index(drop=True))

    want = canon(run_plan(mk()))
    low = lower_plan_to_mesh(mk(), mode="on")
    assert isinstance(low.children[0], MeshRepartitionExec)
    got = canon(run_plan(low))
    pd.testing.assert_frame_equal(got, want, check_dtype=False)


# ---------------------------------------------------------------------------
# fingerprint-keyed program cache (ISSUE 20 satellite): a SECOND
# QueryService in the same process reuses the first one's traced mesh
# programs - zero fresh traces, zero retraces, mesh_trace p50 ~ 0
# ---------------------------------------------------------------------------


def test_program_cache_kills_cross_service_retrace():
    from blaze_tpu.obs import meshprof
    from blaze_tpu.obs.metrics import REGISTRY
    from blaze_tpu.service import QueryService

    def run_once():
        with QueryService(enable_cache=False, enable_trace=False,
                          mesh_mode="on") as svc:
            q = svc.submit_plan(
                lower_plan_to_mesh(sandwich(scan()), mode="on")
            )
            return pa.Table.from_batches(
                svc.result(q.query_id, timeout=120)
            )

    t1 = run_once()  # may trace (cold in THIS process order)
    trace0 = REGISTRY.get("blaze_mesh_trace_total", op="mesh.groupby")
    retrace0 = REGISTRY.get("blaze_mesh_retrace_total",
                            op="mesh.groupby")

    t2 = run_once()  # FRESH QueryService, fresh op instances

    assert REGISTRY.get("blaze_mesh_retrace_total",
                        op="mesh.groupby") == retrace0
    # stronger than retrace delta 0: the warm service never traced at
    # all - the fingerprint-keyed program cache handed it the compiled
    # executable
    assert REGISTRY.get("blaze_mesh_trace_total",
                        op="mesh.groupby") == trace0
    # the warm stage's mesh_trace sub-phase is ~0 (no trace ran)
    warm_trace_s = meshprof.ROLLUP._ops["mesh.groupby"]["sub"][
        "mesh_trace"][-1]
    assert warm_trace_s < 0.05, warm_trace_s
    g1 = t1.to_pandas().sort_values("k").reset_index(drop=True)
    g2 = t2.to_pandas().sort_values("k").reset_index(drop=True)
    pd.testing.assert_frame_equal(g1, g2, check_dtype=False)
