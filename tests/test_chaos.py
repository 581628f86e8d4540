"""Chaos harness + failure taxonomy tests (ISSUE 3 tentpole).

Pins the acceptance criteria:
  * a seeded TRANSIENT fault in one partition of an 8-partition plan
    completes with correct results and EXACTLY ONE retry in the REPORT
  * a PLAN_INVALID fault fails on the first attempt with zero retries
  * injected device-memory-pressure completes via the host-engine
    degradation path with degraded=True in the REPORT
plus the per-site injection seams and the classified-retry semantics
of the standalone scheduler.
"""

import os
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from blaze_tpu.batch import ColumnBatch
from blaze_tpu.errors import ErrorClass, classify
from blaze_tpu.exprs import AggExpr, AggFn, Col
from blaze_tpu.ops import (
    AggMode,
    FilterExec,
    HashAggregateExec,
    MemoryScanExec,
    ProjectExec,
)
from blaze_tpu.ops.base import ExecContext
from blaze_tpu.ops.parquet_scan import FileRange, ParquetScanExec
from blaze_tpu.plan.serde import task_to_proto
from blaze_tpu.runtime.executor import TaskExecutionError
from blaze_tpu.runtime.scheduler import run_plan_parallel
from blaze_tpu.service import QueryService, QueryState
from blaze_tpu.testing import chaos
from blaze_tpu.testing.chaos import Fault, FaultPlan


def multi_scan(n_parts=8, rows=40):
    parts, schema = [], None
    for p in range(n_parts):
        cb = ColumnBatch.from_pydict(
            {"a": list(range(p * rows, (p + 1) * rows))}
        )
        schema = cb.schema
        parts.append([cb])
    return MemoryScanExec(parts, schema)


def filtered(n_parts=8, rows=40):
    return FilterExec(multi_scan(n_parts, rows), Col("a") % 3 == 0)


def expected_rows(n_parts=8, rows=40):
    return [a for a in range(n_parts * rows) if a % 3 == 0]


# ---------------------------------------------------------------------------
# the harness itself
# ---------------------------------------------------------------------------


def test_chaos_off_by_default():
    assert not chaos.ACTIVE
    assert chaos.current() is None


def test_fault_plan_determinism():
    """Same seed -> same probabilistic firing sequence."""

    def seq(seed):
        plan = FaultPlan(
            [Fault("s", times=0, probability=0.5)], seed=seed
        )
        out = []
        for _ in range(32):
            try:
                plan.fire("s")
                out.append(0)
            except chaos.InjectedTransient:
                out.append(1)
        return out

    assert seq(7) == seq(7)
    assert seq(7) != seq(8)  # and the seed actually matters


def test_fault_matching_and_times():
    plan = FaultPlan([
        Fault("a", times=2, partition=1),
        Fault("b", times=1, match="special"),
    ])
    plan.fire("a", partition=0)  # wrong partition: no fire
    with pytest.raises(chaos.InjectedTransient):
        plan.fire("a", partition=1)
    with pytest.raises(chaos.InjectedTransient):
        plan.fire("a", partition=1)
    plan.fire("a", partition=1)  # times exhausted
    plan.fire("b", path="/plain/file")  # no match
    with pytest.raises(chaos.InjectedTransient):
        plan.fire("b", path="/special/file")
    assert plan.fired("a") == 2 and plan.fired("b") == 1


def test_env_plan_round_trip():
    plan = chaos.plan_from_json(
        '{"seed": 7, "faults": [{"site": "task.execute", '
        '"klass": "RESOURCE_EXHAUSTED", "partition": 3, "times": 2}]}'
    )
    assert plan.seed == 7
    f = plan.faults[0]
    assert (f.site, f.klass, f.partition, f.times) == (
        "task.execute", "RESOURCE_EXHAUSTED", 3, 2
    )
    with pytest.raises(ValueError, match="unknown fault class"):
        chaos.plan_from_json(
            '{"faults": [{"site": "x", "klass": "NOPE"}]}'
        )


def test_injected_faults_are_classified():
    assert classify(chaos.InjectedTransient("x")) is \
        ErrorClass.TRANSIENT
    assert classify(chaos.InjectedResourceExhausted("x")) is \
        ErrorClass.RESOURCE_EXHAUSTED
    assert classify(chaos.InjectedPlanInvalid("x")) is \
        ErrorClass.PLAN_INVALID
    assert classify(chaos.InjectedDrop("x")) is ErrorClass.TRANSIENT


# ---------------------------------------------------------------------------
# acceptance: service-level taxonomy semantics
# ---------------------------------------------------------------------------


def test_transient_fault_one_retry_exact_result():
    """ISSUE 3 acceptance: TRANSIENT fault in one partition of an
    8-partition plan -> completes, correct results, EXACTLY one retry
    in the query REPORT."""
    with chaos.active(
        [Fault("task.execute", klass="TRANSIENT",
               partition=3, times=1)],
        seed=7,
    ) as plan:
        with QueryService(
            max_concurrency=1, enable_cache=False,
            retry_backoff_s=0.005,
        ) as svc:
            q = svc.submit_plan(filtered(8))
            batches = svc.result(q.query_id, timeout=60)
            report = svc.report(q.query_id)
    got = pa.Table.from_batches(batches).to_pydict()["a"]
    assert got == expected_rows(8)
    st = q.status()
    assert st["retries"] == 1
    assert st["attempts"] == [{
        "partition": 3, "attempt": 0,
        "error_class": "TRANSIENT",
        "error": st["attempts"][0]["error"], "action": "retry",
    }]
    assert "attempt p3#0: TRANSIENT -> retry" in report
    assert plan.fired("task.execute") == 1
    assert q.state is QueryState.DONE and not q.degraded


def test_plan_invalid_fails_first_attempt_zero_retries():
    """ISSUE 3 acceptance: PLAN_INVALID fault -> FAILED on the first
    attempt, zero retries."""
    with chaos.active(
        [Fault("task.execute", klass="PLAN_INVALID",
               partition=0, times=0)],  # unlimited: retries WOULD fire
        seed=7,
    ) as plan:
        with QueryService(
            max_concurrency=1, enable_cache=False
        ) as svc:
            q = svc.submit_plan(filtered(8))
            with pytest.raises(RuntimeError, match="FAILED"):
                svc.result(q.query_id, timeout=60)
    assert q.state is QueryState.FAILED
    assert q.error_class == "PLAN_INVALID"
    st = q.status()
    assert st.get("retries", 0) == 0
    assert [a["action"] for a in st["attempts"]] == ["fail"]
    # the fault site was hit exactly once: no retry ever ran
    assert plan.fired("task.execute") == 1


def test_resource_exhausted_degrades_to_host_engine():
    """ISSUE 3 acceptance: injected device-memory-pressure completes
    through the host-engine path with degraded=True in the REPORT."""
    with chaos.active(
        [Fault("task.execute", klass="RESOURCE_EXHAUSTED",
               partition=1, times=0)],  # unlimited: a retry would die
        seed=7,
    ):
        with QueryService(
            max_concurrency=1, enable_cache=False
        ) as svc:
            q = svc.submit_plan(filtered(4))
            batches = svc.result(q.query_id, timeout=60)
            report = svc.report(q.query_id)
    got = pa.Table.from_batches(batches).to_pydict()["a"]
    assert got == expected_rows(4)
    assert q.state is QueryState.DONE
    assert q.degraded
    assert q.status()["degraded"] is True
    assert "degraded=True" in report
    assert q.ctx.metrics.counters["degraded_partitions"] == 1
    assert [a["action"] for a in q.status()["attempts"]] == ["degrade"]


def test_internal_error_not_retried():
    """Unclassified (INTERNAL) failures fail fast: retries are
    reserved for TRANSIENT."""

    calls = {"n": 0}

    class Weird(MemoryScanExec):
        def execute(self, partition, ctx):
            calls["n"] += 1
            raise ArithmeticError("engine bug")  # maps to INTERNAL
            yield

    base = multi_scan(1)
    op = Weird(base.partitions, base.schema)
    assert classify(ArithmeticError("x")) is ErrorClass.INTERNAL
    with QueryService(max_concurrency=1, enable_cache=False) as svc:
        q = svc.submit_plan(op)
        with pytest.raises(RuntimeError, match="FAILED"):
            svc.result(q.query_id, timeout=60)
    assert q.error_class == "INTERNAL"
    assert calls["n"] == 1


# ---------------------------------------------------------------------------
# scheduler-level classified retries
# ---------------------------------------------------------------------------


def test_scheduler_transient_retry_and_backoff():
    attempts = []
    with chaos.active(
        [Fault("task.execute", klass="TRANSIENT",
               partition=2, times=2)],
        seed=7,
    ):
        ctx = ExecContext()
        out = run_plan_parallel(
            filtered(4), ctx=ctx, parallelism=2,
            retry_backoff_s=0.005, on_attempt=attempts.append,
        )
    assert out.to_pydict()["a"] == expected_rows(4)
    assert ctx.metrics.counters["task_retries"] == 2
    assert [a["action"] for a in attempts] == ["retry", "retry"]
    assert all(a["partition"] == 2 for a in attempts)


def test_scheduler_plan_invalid_fails_fast():
    with chaos.active(
        [Fault("task.execute", klass="PLAN_INVALID",
               partition=0, times=0)],
        seed=7,
    ) as plan:
        with pytest.raises(TaskExecutionError) as ei:
            run_plan_parallel(filtered(2), parallelism=2,
                              max_attempts=3)
    assert ei.value.error_class is ErrorClass.PLAN_INVALID
    # zero retries despite max_attempts=3 and an unlimited fault
    assert plan.fired("task.execute") == 1


def test_scheduler_resource_exhausted_degrades():
    with chaos.active(
        [Fault("task.execute", klass="RESOURCE_EXHAUSTED",
               partition=1, times=0)],
        seed=7,
    ):
        ctx = ExecContext()
        out = run_plan_parallel(filtered(4), ctx=ctx, parallelism=2)
    assert out.to_pydict()["a"] == expected_rows(4)
    assert ctx.metrics.counters["degraded_partitions"] == 1


def test_scheduler_degradation_unavailable_surfaces_original():
    """A tree with no host mapping (custom op) re-raises the original
    RESOURCE_EXHAUSTED instead of degrading."""

    from blaze_tpu.ops.base import PhysicalOp

    class Opaque(PhysicalOp):  # not isinstance of any mapped op
        def __init__(self, child):
            self.children = [child]

        @property
        def schema(self):
            return self.children[0].schema

        def execute(self, partition, ctx):
            yield from self.children[0].execute(partition, ctx)

    op = Opaque(multi_scan(2))
    with chaos.active(
        [Fault("task.execute", klass="RESOURCE_EXHAUSTED",
               partition=0, times=0)],
        seed=7,
    ):
        with pytest.raises(TaskExecutionError) as ei:
            run_plan_parallel(op, parallelism=2)
    assert ei.value.error_class is ErrorClass.RESOURCE_EXHAUSTED


def test_degradation_translates_union_partitions():
    """A union partition IS one child partition (positional append);
    degrading it must re-run exactly that child subtree, not the whole
    union (review finding: the untranslated index silently duplicated
    every row)."""
    from blaze_tpu.ops import UnionExec

    op = UnionExec([multi_scan(2, 10), multi_scan(2, 10)])
    # partition 2 = second child's partition 0
    with chaos.active(
        [Fault("task.execute", klass="RESOURCE_EXHAUSTED",
               partition=2, times=0)],
        seed=7,
    ):
        ctx = ExecContext()
        out = run_plan_parallel(op, ctx=ctx, parallelism=2)
    assert ctx.metrics.counters["degraded_partitions"] == 1
    # 4 partitions x 10 rows, NO duplication
    assert sorted(out.to_pydict()["a"]) == sorted(
        list(range(20)) + list(range(20))
    )


def test_wire_task_degradation_survives_inplace_fusion(tmp_path):
    """Review finding: prepare_decoded_task fuses the decoded tree IN
    PLACE, so degradation must re-decode from the task bytes - a union
    root (whose children fuse in place) submitted over the wire must
    still degrade."""
    from blaze_tpu.ops import UnionExec

    p = str(tmp_path / "u.parquet")
    pq.write_table(pa.table({"a": list(range(30))}), p)

    def scan():
        return FilterExec(
            ParquetScanExec([[FileRange(p)]]), Col("a") % 2 == 0
        )

    blob = task_to_proto(UnionExec([scan(), scan()]), 0)
    with chaos.active(
        [Fault("task.execute", klass="RESOURCE_EXHAUSTED", times=0)],
        seed=7,
    ):
        with QueryService(
            max_concurrency=1, enable_cache=False
        ) as svc:
            q = svc.submit_task(blob)
            batches = svc.result(q.query_id, timeout=120)
    assert q.degraded
    got = pa.Table.from_batches(batches).to_pydict()["a"]
    assert got == [a for a in range(30) if a % 2 == 0]


def test_failed_attempt_output_not_double_counted():
    """Review finding: a retried partition's abandoned partial output
    must not inflate the query's output_rows/output_batches."""

    calls = {"n": 0}

    class FailMidStream(MemoryScanExec):
        def execute(self, partition, ctx):
            calls["n"] += 1
            yield self.partitions[partition][0]
            if calls["n"] == 1:
                raise IOError("transient mid-stream")

    base = multi_scan(1, 25)
    op = FailMidStream(base.partitions, base.schema)
    with QueryService(max_concurrency=1, enable_cache=False,
                      retry_backoff_s=0.005) as svc:
        q = svc.submit_plan(op)
        svc.result(q.query_id, timeout=60)
    assert calls["n"] == 2
    assert q.ctx.metrics.counters["output_rows"] == 25
    assert q.ctx.metrics.counters["output_batches"] == 1


def test_degradation_refuses_misaligned_partition_index():
    from blaze_tpu.planner.host_engine import op_to_spec

    op = multi_scan(2, 10)
    assert op_to_spec(op, partition=5) is None  # out of range: refuse
    assert op_to_spec(op, partition=1) is not None


# ---------------------------------------------------------------------------
# per-site seams
# ---------------------------------------------------------------------------


def test_parquet_decode_fault_retried(tmp_path):
    p = str(tmp_path / "t.parquet")
    rng = np.random.default_rng(3)
    pq.write_table(
        pa.table({"k": rng.integers(0, 8, 2000).astype(np.int32),
                  "v": rng.random(2000)}),
        p,
    )
    plan = HashAggregateExec(
        ParquetScanExec([[FileRange(p)]]),
        keys=[(Col("k"), "k")],
        aggs=[(AggExpr(AggFn.SUM, Col("v")), "s")],
        mode=AggMode.COMPLETE,
    )
    blob = task_to_proto(plan, 0)
    with QueryService(max_concurrency=1, enable_cache=False,
                      retry_backoff_s=0.005) as svc:
        base = svc.result(
            svc.submit_task(blob).query_id, timeout=120
        )
        with chaos.active(
            [Fault("parquet.decode", klass="TRANSIENT", times=1)],
            seed=7,
        ) as cplan:
            q = svc.submit_task(blob)
            got = svc.result(q.query_id, timeout=120)
        assert cplan.fired("parquet.decode") == 1
    t0 = pa.Table.from_batches(base).to_pydict()
    t1 = pa.Table.from_batches(got).to_pydict()
    assert t0 == t1
    assert q.status()["retries"] == 1


def test_h2d_transfer_seam():
    from blaze_tpu.runtime.pack import put_packed

    with chaos.active(
        [Fault("h2d.transfer", klass="TRANSIENT", times=1)], seed=7
    ):
        with pytest.raises(chaos.InjectedTransient):
            put_packed([np.arange(8, dtype=np.int64)])
        # times exhausted: the transfer works again
        out = put_packed([np.arange(8, dtype=np.int64)])
    assert np.asarray(out[0]).tolist() == list(range(8))


def test_kernel_dispatch_fault_retried():
    with chaos.active(
        [Fault("kernel.dispatch", klass="TRANSIENT", times=1)],
        seed=7,
    ):
        ctx = ExecContext()
        out = run_plan_parallel(
            filtered(2), ctx=ctx, parallelism=1,
            retry_backoff_s=0.005,
        )
    assert out.to_pydict()["a"] == expected_rows(2)
    assert ctx.metrics.counters["task_retries"] == 1


def test_device_memory_seam():
    from blaze_tpu.runtime.memory import DeviceMemoryTracker

    tr = DeviceMemoryTracker(budget=1000)
    with chaos.active(
        [Fault("device.memory", klass="RESOURCE_EXHAUSTED", times=1)],
        seed=7,
    ):
        with pytest.raises(chaos.InjectedResourceExhausted):
            tr.track(1, 100)
        tr.track(1, 100)  # exhausted: accounting works again
    assert tr.total_used() == 100


def test_cache_spill_fault_degrades_gracefully(tmp_path):
    """An injected spill IO error keeps the entry in MEMORY (served
    normally) instead of failing the query path."""
    from blaze_tpu.runtime.memory import MemoryPool
    from blaze_tpu.service.cache import ResultCache

    rb = pa.record_batch(
        {"a": pa.array(np.arange(1000, dtype=np.int64))}
    )
    pool = MemoryPool(budget=rb.nbytes // 2)  # any put overflows
    cache = ResultCache(max_bytes=1 << 20, ttl_s=60, pool=pool,
                        spill_dir=str(tmp_path))
    with chaos.active(
        [Fault("cache.spill", klass="TRANSIENT", times=1)], seed=7
    ):
        assert cache.put(("fp", 0), [rb])
    st = cache.stats()
    assert st["spill_errors"] == 1
    assert st["spilled_entries"] == 0  # stayed in memory
    got = cache.get(("fp", 0))
    assert got is not None and got[0].equals(rb)
    assert not os.listdir(str(tmp_path))  # no truncated spill files
    cache.close()


def test_heartbeat_stall_seam(tmp_path, monkeypatch):
    from blaze_tpu.runtime import cluster as cl

    monkeypatch.setattr(cl, "_HEARTBEAT_S", 0.02)
    path = str(tmp_path / "hb")
    open(path, "w").close()
    old = time.time() - 100
    os.utime(path, (old, old))
    with chaos.active(
        [Fault("cluster.heartbeat", klass="TRANSIENT", times=0)],
        seed=7,
    ):
        with cl._Heartbeat(path):
            time.sleep(0.15)
        assert os.path.getmtime(path) == pytest.approx(old)
    # chaos off: the same heartbeat advances the mtime
    with cl._Heartbeat(path):
        time.sleep(0.15)
    assert os.path.getmtime(path) > old


# ---------------------------------------------------------------------------
# --chaos smoke: fault-free == chaos-with-retry, per battery shape
# ---------------------------------------------------------------------------


def _battery_shapes(tmp_path):
    rng = np.random.default_rng(5)
    p = str(tmp_path / "b.parquet")
    pq.write_table(
        pa.table({"k": rng.integers(0, 16, 3000).astype(np.int32),
                  "v": rng.random(3000)}),
        p,
    )

    def scan_agg():
        return HashAggregateExec(
            ParquetScanExec([[FileRange(p)]]),
            keys=[(Col("k"), "k")],
            aggs=[(AggExpr(AggFn.SUM, Col("v")), "s")],
            mode=AggMode.COMPLETE,
        )

    def filter_project():
        return ProjectExec(
            FilterExec(multi_scan(4), Col("a") % 2 == 0),
            [(Col("a") + 1, "a1")],
        )

    def keyless_agg():
        return HashAggregateExec(
            multi_scan(4),
            keys=[],
            aggs=[(AggExpr(AggFn.COUNT_STAR, None), "n")],
            mode=AggMode.COMPLETE,
        )

    return {"scan_agg": scan_agg, "filter_project": filter_project,
            "keyless_agg": keyless_agg}


def test_battery_shapes_identical_under_transient_chaos(tmp_path):
    """The chaos suite's core (`python -m pytest tests/test_chaos.py`):
    each battery shape, executed with a
    fixed chaos seed injecting ONE transient fault, produces results
    identical to the fault-free run (the retry machinery is invisible
    to correctness)."""
    shapes = _battery_shapes(tmp_path)
    for name, mk in shapes.items():
        baseline = run_plan_parallel(mk(), parallelism=2)
        with chaos.active(
            [Fault("task.execute", klass="TRANSIENT",
                   partition=0, times=1)],
            seed=7,
        ) as plan:
            ctx = ExecContext()
            chaotic = run_plan_parallel(
                mk(), ctx=ctx, parallelism=2, retry_backoff_s=0.005,
            )
            assert plan.fired("task.execute") == 1, name
            assert ctx.metrics.counters["task_retries"] == 1, name
        bl = baseline.sort_by(baseline.column_names[0]).to_pydict()
        ch = chaotic.sort_by(chaotic.column_names[0]).to_pydict()
        assert bl == ch, f"shape {name} diverged under chaos"


# ---------------------------------------------------------------------------
# --chaos smoke: fused relational kernels == unfused ladder, byte-equal
# ---------------------------------------------------------------------------


def _relational_shapes():
    """join_agg / grouped_agg plan builders (ISSUE 13): the two shapes
    whose fused kernels (probe fold + grouped streaming carry) replace
    the multi-dispatch ladder. Multi-chunk input so the keyed carry's
    merge path runs, not just the single-batch hot path."""
    from blaze_tpu.exprs.ir import Literal
    from blaze_tpu.ops.joins import HashJoinExec, JoinType
    from blaze_tpu.types import DataType

    rng = np.random.default_rng(13)
    n, chunks = 1 << 12, 3
    fact_parts = []
    for _ in range(chunks):
        fact_parts.append(ColumnBatch.from_arrow(pa.record_batch({
            "item": rng.integers(0, 256, n).astype(np.int32),
            "qty": rng.integers(1, 10, n).astype(np.int32),
            "price": (rng.random(n) * 100).astype(np.float32),
        })))
    items = ColumnBatch.from_arrow(pa.record_batch({
        "i_item": np.arange(256, dtype=np.int32),
        "i_brand": rng.integers(0, 32, 256).astype(np.int32),
    }))
    fschema = fact_parts[0].schema

    def join_agg():
        return HashAggregateExec(
            ProjectExec(
                HashJoinExec(
                    MemoryScanExec([[items]], items.schema),
                    ProjectExec(
                        FilterExec(
                            MemoryScanExec([fact_parts], fschema),
                            Col("qty") > Literal(2, DataType.int32()),
                        ),
                        [(Col("item"), "item"),
                         (Col("price"), "price")],
                    ),
                    [Col("i_item")], [Col("item")], JoinType.INNER,
                ),
                [(Col("i_brand"), "brand"), (Col("price"), "price")],
            ),
            keys=[(Col("brand"), "brand")],
            aggs=[(AggExpr(AggFn.SUM, Col("price")), "rev"),
                  (AggExpr(AggFn.COUNT_STAR, None), "cnt")],
            mode=AggMode.COMPLETE,
        )

    def grouped_agg():
        return HashAggregateExec(
            ProjectExec(
                MemoryScanExec([fact_parts], fschema),
                [(Col("item") % Literal(64, DataType.int32()), "g"),
                 (Col("price"), "price"), (Col("qty"), "qty")],
            ),
            keys=[(Col("g"), "g")],
            aggs=[(AggExpr(AggFn.SUM, Col("price")), "s"),
                  (AggExpr(AggFn.MIN, Col("price")), "lo"),
                  (AggExpr(AggFn.MAX, Col("qty")), "hi"),
                  (AggExpr(AggFn.AVG, Col("qty")), "aq")],
            mode=AggMode.COMPLETE,
        )

    return {"join_agg": join_agg, "grouped_agg": grouped_agg}


def _canon_bytes(t: pa.Table):
    """Canonical order + one chunk -> serialized IPC bytes, the
    byte-equality form of the differential."""
    t = t.sort_by([(t.column_names[0], "ascending")]).combine_chunks()
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, t.schema) as w:
        w.write_table(t)
    return t, sink.getvalue().to_pybytes()


def test_fused_relational_byte_equal_and_chaos_parity():
    """Member of the seed sweep (`BLAZE_CHAOS_SEED_OFFSET=N python -m
    pytest tests/test_chaos.py`; ISSUE 13): for each
    relational-core shape, the FUSED plan's Arrow output is BYTE-equal
    (canonical order, serialized IPC) to the unfused operator ladder -
    and stays byte-equal when a transient kernel.dispatch fault fires
    through the new fused kernels' shared chaos seam and the retry
    machinery re-runs the partition."""
    from blaze_tpu.ops.fused import fuse_pipelines
    from blaze_tpu.runtime.executor import run_plan

    for name, mk in _relational_shapes().items():
        ref, ref_bytes = _canon_bytes(run_plan(mk()))
        fused, fused_bytes = _canon_bytes(run_plan(fuse_pipelines(mk())))
        assert fused.schema.equals(ref.schema), name
        assert fused_bytes == ref_bytes, \
            f"shape {name}: fused output diverged from unfused ladder"

        with chaos.active(
            [Fault("kernel.dispatch", klass="TRANSIENT", times=1)],
            seed=11,
        ) as plan:
            ctx = ExecContext()
            chaotic = run_plan_parallel(
                fuse_pipelines(mk()), ctx=ctx, parallelism=1,
                retry_backoff_s=0.005,
            )
            assert plan.fired("kernel.dispatch") == 1, name
            assert ctx.metrics.counters["task_retries"] == 1, name
        _, chaos_bytes = _canon_bytes(chaotic)
        assert chaos_bytes == ref_bytes, \
            f"shape {name} diverged under chaos retry"
