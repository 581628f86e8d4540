"""ISSUE 35: who sets a task's pace. The scan queue's two waits
(`runtime/prefetch.py`: `wait_batch`, `wait_room`, spans opened only on a
call that blocks) and every program launch counted and timed on the task
that made it (`runtime/dispatch.py`: `launches`, `launch_ns`,
`launch_buffers`, tracing on or off); the per-launch span is gone."""

import functools
import sys
import threading
import time
import types

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import jax
import jax.numpy as jnp

from blaze_tpu.batch import ColumnBatch
from blaze_tpu.exprs import AggExpr, AggFn, Col
from blaze_tpu.obs import phases, trace
from blaze_tpu.ops import AggMode, FilterExec, HashAggregateExec
from blaze_tpu.ops.base import ExecContext
from blaze_tpu.ops.parquet_scan import FileRange, ParquetScanExec
from blaze_tpu.runtime import dispatch
from blaze_tpu.runtime.prefetch import prefetch
from blaze_tpu.service import QueryService

SLEEP_S = 0.05


@pytest.fixture
def traced_task():
    """A task whose spans record: tracing on, a recorder on its
    context."""
    trace.enable()
    ctx = ExecContext(task_id="t")
    ctx.tracer = trace.TraceRecorder("t")
    return ctx


def drain(ctx, it, pause=0.0):
    """Consume a prefetch of `it` as the task's draining thread does."""
    out = []
    with dispatch.task_scope(ctx):
        for item in prefetch(it, depth=2):
            out.append(item)
            time.sleep(pause)
    return out


def waits(rec, name):
    return [s for s in rec.spans if s.name == name]


def slow_items(n, pause):
    for i in range(n):
        time.sleep(pause)
        yield i


# ---------------------------------------------------------------------------
# the two waits at the prefetch queue
# ---------------------------------------------------------------------------


def test_slow_producer_records_wait_batch(traced_task):
    assert drain(traced_task, slow_items(4, SLEEP_S)) == [0, 1, 2, 3]
    got = waits(traced_task.tracer, "wait_batch")
    assert len(got) >= 1
    # the consumer waited at least one of the producer's sleeps
    assert max(s.end_ns - s.start_ns for s in got) >= SLEEP_S * 1e9 * 0.9
    assert sum(s.end_ns - s.start_ns for s in got) >= SLEEP_S * 1e9
    assert all(s.cpu_ns == 0 for s in got)  # no CPU reading on a wait


def test_slow_consumer_records_wait_room(traced_task):
    assert drain(traced_task, iter(range(6)), pause=SLEEP_S) == \
        list(range(6))
    got = waits(traced_task.tracer, "wait_room")
    assert len(got) >= 1
    assert sum(s.end_ns - s.start_ns for s in got) >= SLEEP_S * 1e9 * 0.9
    # the worker's spans land on the worker's own track
    assert {s.tid for s in got} != {threading.get_ident()}


def test_calls_that_do_not_block_make_no_span(traced_task):
    produced = threading.Event()

    def items():
        yield from range(3)
        produced.set()

    with dispatch.task_scope(traced_task):
        it = prefetch(items(), depth=8)  # room for every item
        assert next(it) == 0  # may block once: the worker starts
        assert produced.wait(10)
        time.sleep(0.2)  # the sentinel follows the last item at once
        n_spans = len(traced_task.tracer.spans)
        assert list(it) == [1, 2]
    assert len(traced_task.tracer.spans) == n_spans
    assert waits(traced_task.tracer, "wait_room") == []


def test_tracing_off_builds_no_span():
    assert not trace.ACTIVE
    ctx = ExecContext(task_id="t")
    ctx.tracer = trace.TraceRecorder("t")
    drain(ctx, slow_items(3, 0.01))
    drain(ctx, iter(range(5)), pause=0.01)
    assert [s.name for s in ctx.tracer.spans] == ["query"]


def test_no_task_no_span():
    trace.enable()
    assert drain(None, slow_items(3, 0.01), pause=0.0) == [0, 1, 2]


def test_producer_exception_reaches_the_consumer(traced_task):
    def items():
        yield 1
        time.sleep(SLEEP_S)
        raise ValueError("decode failed")

    with pytest.raises(ValueError, match="decode failed"):
        drain(traced_task, items())
    # the consumer waited for the error, in a wait span that ended
    assert all(s.end_ns is not None
               for s in waits(traced_task.tracer, "wait_batch"))


def test_early_close_stops_a_blocked_producer(traced_task):
    state = {"made": 0}

    def endless():
        state["thread"] = threading.current_thread()
        while True:
            state["made"] += 1
            yield state["made"]

    with dispatch.task_scope(traced_task):
        it = prefetch(endless(), depth=2)
        assert next(it) == 1
        time.sleep(SLEEP_S)  # the producer fills the queue and waits
        it.close()
    worker = state["thread"]
    worker.join(10)
    assert not worker.is_alive()
    assert state["made"] <= 6
    # its wait for room ended with the drain
    got = waits(traced_task.tracer, "wait_room")
    assert got and all(s.end_ns is not None for s in got)


def test_wait_spans_lie_on_the_profilers_side(traced_task, monkeypatch):
    log = []

    class Annotation:
        @staticmethod
        def is_enabled():
            return True

        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    monkeypatch.setattr(trace, "_TRACE_ME", Annotation)
    drain(traced_task, slow_items(2, SLEEP_S))
    assert ("enter", "blaze.wait_batch") in log
    assert ("exit", "blaze.wait_batch") in log


def test_chrome_export_of_wait_spans_validates(traced_task):
    with trace.span("execute_partition", rec=traced_task.tracer):
        drain(traced_task, slow_items(3, 0.01), pause=0.0)
        drain(traced_task, iter(range(6)), pause=0.01)
    traced_task.tracer.finish(state="DONE")
    names = {s.name for s in traced_task.tracer.spans}
    assert {"wait_batch", "wait_room"} <= names
    doc = trace.chrome_trace(traced_task.tracer)
    assert trace.validate_chrome(doc) == []


def test_stage_table_keeps_waits_and_stages_apart():
    """A wait inside a stage is not taken out of it, and the wait's own
    row is whole."""
    rec = trace.TraceRecorder("t")
    with trace.span("mesh_stage_in", rec=rec) as outer:
        with trace.span("wait_batch") as inner:
            time.sleep(0.002)
    table = rec.phase_totals(phases.POLL_PHASE, stage_table=True)
    whole = (outer.end_ns - outer.start_ns) / 1e9
    wait = (inner.end_ns - inner.start_ns) / 1e9
    assert table["mesh_stage_in"]["wall_s"] == pytest.approx(whole,
                                                             abs=2e-6)
    assert table["wait_batch"]["wall_s"] == pytest.approx(wait, abs=2e-6)
    assert table["wait_batch"]["cpu_s"] == 0


# ---------------------------------------------------------------------------
# every launch, on the task that made it
# ---------------------------------------------------------------------------


def launch_counts(ctx):
    return (ctx.launches, ctx.launch_ns, ctx.launch_buffers)


def three_outputs():
    def k(x):
        return x + 1, x * 2, jnp.sum(x)

    return k


def test_cached_kernel_counts_on_its_task_alone():
    fn = dispatch.cached_kernel(("test_waits.three",), three_outputs)
    mine, other = ExecContext(), ExecContext()
    x = jnp.arange(8)
    fn(x)  # no task: compiles, counts nowhere
    with dispatch.task_scope(mine):
        fn(x)
        fn(x)
    assert mine.launches == 2 and mine.launch_buffers == 6
    assert mine.launch_ns > 0 and mine.task_dispatches == 2
    assert launch_counts(other) == (0, 0, 0)


def test_prefetch_workers_launches_count_on_its_consumer():
    fn = dispatch.cached_kernel(("test_waits.three",), three_outputs)
    ctx = ExecContext()

    def produce():
        for i in range(3):
            yield fn(jnp.arange(8) + i)

    drain(ctx, produce())
    assert (ctx.launches, ctx.launch_buffers, ctx.task_dispatches) == \
        (3, 9, 3)


def _batch(n=100):
    rng = np.random.default_rng(3)
    return ColumnBatch.from_arrow(pa.record_batch({
        "a": pa.array(rng.integers(0, 9, n).astype(np.int32),
                      mask=rng.random(n) < 0.1),
        "b": rng.random(n),
    }))


# each makes its inputs and returns (the one call, the arrays it hands
# back): the inputs' own upload is a cached kernel's launch


def _take():
    from blaze_tpu.ops.util import take_batch

    cb, idx = _batch(), jnp.arange(16, dtype=jnp.int32)
    return lambda: take_batch(cb, idx, 16), 3  # a, a's validity, b


def _compact():
    from blaze_tpu.ops.util import _compact

    cb = _batch()
    mask = cb.columns[1].values > 0.5
    # the index program (indices, count), then the gather of 3 buffers
    return lambda: _compact(cb, mask), 2 + 3


def _concat():
    from blaze_tpu.ops.util import concat_batches

    parts = [_batch(100), _batch(60)]
    # 2 columns and the one mask: `b` has no NULL
    return lambda: concat_batches(parts), 2 + 1


def _pallas():
    from blaze_tpu.exprs.ir import BoundCol
    from blaze_tpu.ops import shuffle_writer
    from blaze_tpu.ops.kernels import murmur3_pallas as mp

    shim = types.SimpleNamespace(
        supports=mp.supports,
        partition_ids_int32=functools.partial(
            mp.partition_ids_int32, interpret=True),
        partition_ids_int64=functools.partial(
            mp.partition_ids_int64, interpret=True),
    )
    cb = ColumnBatch.from_arrow(pa.record_batch({
        "k": np.arange(16384, dtype=np.int32)}))

    def call():
        orig = shuffle_writer._pallas_murmur3
        shuffle_writer._pallas_murmur3 = lambda: shim
        try:
            shuffle_writer.spark_partition_ids(
                cb, [BoundCol(0, cb.schema.fields[0].dtype)], 200)
        finally:
            shuffle_writer._pallas_murmur3 = orig

    return call, 1


def _mesh():
    from blaze_tpu.parallel import get_mesh
    from blaze_tpu.parallel.sharded import DistAgg, DistributedGroupBy
    from blaze_tpu.types import DataType, Field, Schema

    schema = Schema([Field("k", DataType.int64()),
                     Field("v", DataType.int64())])
    gb = DistributedGroupBy(get_mesh(), schema, keys=[Col("k")],
                            aggs=[DistAgg(AggFn.SUM, Col("v"))])
    n_dev = len(jax.devices())
    cols = [jnp.zeros((n_dev, 16), jnp.int64)] * 2
    rows = jnp.full(n_dev, 16, jnp.int32)
    gb.prepare(cols, rows)  # the trace and compile: no launch
    # key values and validity, the sum and its count, groups, overflow
    return lambda: gb.run(cols, rows), 6


@pytest.mark.parametrize("entry, launches", [
    (_take, 1), (_compact, 2), (_concat, 1), (_pallas, 1), (_mesh, 1),
], ids=["take_many", "compact_indices", "concat_many",
        "pallas_murmur3", "mesh_groupby"])
def test_plain_jit_entry_points_count_on_their_task(entry, launches):
    call, buffers = entry()
    call()  # compiles, with no task in scope: counts nowhere
    mine, other = ExecContext(), ExecContext()
    before = dispatch.snapshot().get("dispatches", 0)
    with dispatch.task_scope(mine):
        call()
    assert mine.launches == launches
    assert mine.launch_buffers == buffers
    assert mine.launch_ns > 0
    assert launch_counts(other) == (0, 0, 0)
    # a plain jit is no cached kernel: the old counters do not move
    assert mine.task_dispatches == 0
    assert dispatch.snapshot().get("dispatches", 0) == before


def test_concurrent_launches_on_one_task_lose_no_count():
    """The prefetch worker and its consumer launch for one task at
    once: the counts are taken under a lock."""
    ctx = ExecContext()
    n_threads, per = 16, 200
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def run():
            with dispatch.task_scope(ctx):
                for _ in range(per):
                    dispatch.launch(lambda: (1, 2))

        threads = [threading.Thread(target=run) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert ctx.launches == n_threads * per
    assert ctx.launch_buffers == 2 * n_threads * per


# ---------------------------------------------------------------------------
# served tasks: POLL, the old counters, no per-launch span
# ---------------------------------------------------------------------------


@pytest.fixture
def keyed_parquet(tmp_path):
    rng = np.random.default_rng(25)
    n = 40000  # three batches of spark.blaze.batchSize
    p = str(tmp_path / "keyed.parquet")
    pq.write_table(pa.table({
        "k": pa.array(rng.integers(0, 500, n).astype(np.int32),
                      mask=rng.random(n) < 0.05),
        "v": rng.integers(0, 100, n).astype(np.int32),
    }), p)
    return p


def served_plans(path, tmp_path):
    from blaze_tpu.ops.shuffle_writer import ShuffleWriterExec

    def scan():
        return ParquetScanExec([[FileRange(path)]])

    return {
        "scan": FilterExec(scan(), Col("v") > 10),
        "shuffle_write": ShuffleWriterExec(
            scan(), [Col("k")], 8, str(tmp_path / "s.data"),
            str(tmp_path / "s.index")),
        "keyed_aggregate": HashAggregateExec(
            FilterExec(scan(), Col("v") > 10),
            keys=[(Col("k"), "k")],
            aggs=[(AggExpr(AggFn.SUM, Col("v")), "s")],
            mode=AggMode.COMPLETE),
    }


@pytest.fixture
def cached_kernel_calls(monkeypatch):
    """Every `cached_kernel` call from here on, counted apart from the
    counters under test: what `task_dispatches` and `dispatches` counted
    at the parent of this change."""
    calls = []
    real = dispatch._wrap_dispatch

    def spy(fn, kind):
        wrapped = real(fn, kind)

        def counted(*args, **kw):
            calls.append(kind)
            return wrapped(*args, **kw)

        return counted

    monkeypatch.setattr(dispatch, "_wrap_dispatch", spy)
    dispatch.clear_kernel_cache()  # rebuilt through the spy
    yield calls
    dispatch.clear_kernel_cache()  # no spy outlives the test


@pytest.mark.parametrize("shape", ["scan", "shuffle_write",
                                   "keyed_aggregate"])
def test_served_tasks_old_counters_unchanged_and_no_launch_span(
        shape, keyed_parquet, tmp_path, cached_kernel_calls):
    from blaze_tpu.plan.serde import task_to_proto

    blob = task_to_proto(served_plans(keyed_parquet, tmp_path)[shape], 0)
    phases.ROLLUP._reset_for_tests()
    # mesh off: conftest.py's eight virtual devices would take the
    # keyed aggregate to the mesh tier
    with QueryService(max_concurrency=1, enable_cache=False,
                      mesh_mode="off") as svc:
        for _ in range(2):  # the first builds the programs
            del cached_kernel_calls[:]
            q = svc.submit_task(blob, use_cache=False)
            svc.result(q.query_id, timeout=120)
        poll = q.status()
        names = {s.name for s in q.tracer.spans}
        snap = svc.stats()["phases"]
    want = len(cached_kernel_calls)
    assert want > 0
    assert poll["task_dispatches"] == poll["dispatches"] == want
    assert poll["launches"] >= want
    assert poll["launch_buffers"] >= poll["launches"]
    assert poll["launch_s"] > 0
    assert not {n for n in names if n.endswith("_dispatch")}
    # STATS `phases` still has `dispatch`, from the launch counter
    assert snap[phases.ALL_CLASS]["dispatch"]["n"] == 2
    assert "group" not in snap[phases.ALL_CLASS]


@pytest.mark.parametrize("traced", [True, False], ids=["trace", "no_trace"])
@pytest.mark.parametrize("wire_plane", ["async", "threaded"])
def test_poll_carries_waits_and_launches(wire_plane, traced,
                                         keyed_parquet, tmp_path):
    from blaze_tpu.plan.serde import task_to_proto
    from blaze_tpu.runtime.gateway import TaskGatewayServer
    from blaze_tpu.service import ServiceClient

    plan = served_plans(keyed_parquet, tmp_path)["scan"]
    with QueryService(max_concurrency=1, enable_trace=traced) as svc:
        with TaskGatewayServer(service=svc, wire=wire_plane) as srv:
            with ServiceClient(*srv.address) as c:
                st = c.submit(task_to_proto(plan, 0))
                c.fetch(st["query_id"])
                poll = c.poll(st["query_id"])
    assert poll["state"] == "DONE"
    assert poll["launches"] >= poll["task_dispatches"] > 0
    assert poll["launch_buffers"] >= poll["launches"]
    assert 0 < poll["launch_s"] <= poll["execution_s"]
    if not traced:
        assert "waits" not in poll and "stages" not in poll
        return
    assert set(poll["waits"]) == {"wait_batch", "wait_room"}
    for row in poll["waits"].values():
        assert set(row) == {"wall_s", "n"}
        assert row["n"] >= 0 and 0 <= row["wall_s"] <= poll["execution_s"]
    # the stage table is the table it was: no wait in it
    assert not set(poll["stages"]) & {"wait_batch", "wait_room"}


def test_a_threads_stages_and_waits_fit_in_the_execution(keyed_parquet,
                                                         tmp_path):
    """The scan's prefetch thread decodes and waits for room; the
    draining thread reads back and waits for batches. On each thread
    stages and waits never overlap."""
    from blaze_tpu.ops import LimitExec

    plan = LimitExec(FilterExec(
        ParquetScanExec([[FileRange(keyed_parquet)]]), Col("v") > 10),
        39000)
    with QueryService(max_concurrency=1, enable_cache=False) as svc:
        q = svc.submit_plan(plan, use_cache=False)
        svc.result(q.query_id, timeout=120)
        poll = q.status()
    stages, w = poll["stages"], poll["waits"]

    def wall(table, names):
        return sum(table.get(n, {}).get("wall_s", 0.0) for n in names)

    prefetch_thread = wall(stages, ("decode_batch", "h2d")) + \
        w["wait_room"]["wall_s"]
    draining_thread = wall(stages, ("compact", "d2h")) + \
        w["wait_batch"]["wall_s"]
    assert prefetch_thread <= poll["execution_s"]
    assert draining_thread <= poll["execution_s"]


def test_obs_off_rollup_folds_dispatch_from_the_counter(keyed_parquet,
                                                         tmp_path):
    phases.ROLLUP._reset_for_tests()
    plan = served_plans(keyed_parquet, tmp_path)["scan"]
    with QueryService(max_concurrency=1, enable_cache=False,
                      enable_trace=False) as svc:
        q = svc.submit_plan(plan, use_cache=False)
        svc.result(q.query_id, timeout=120)
        poll = q.status()
    snap = phases.ROLLUP.snapshot()[phases.ALL_CLASS]
    assert snap["dispatch"]["p50"] == pytest.approx(poll["launch_s"],
                                                    abs=1e-5)


def test_compare_accepts_the_retired_join_and_group_phases():
    """A baseline taken before the per-launch span went carries `join`
    and `group`; a live rollup has neither, and that is no regression."""
    cell = {"n": 5, "p50": 0.01, "p95": 0.01, "mean": 0.01}
    base = {"_all": {"join": cell, "group": cell, "dispatch": cell}}
    live = {"_all": {"dispatch": cell}}
    assert phases.compare(live, base) == []
    assert "join" not in phases.PHASES and "group" not in phases.PHASES
    assert "join" not in phases.PHASE_BANDS
