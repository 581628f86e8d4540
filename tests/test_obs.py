"""Observability-layer tests (ISSUE 4): span trees + Chrome-trace
export validity, the Prometheus metrics registry, the runtime-history
store, predicted-unmeetability shedding, the structured STATS payload,
the slow-query log, the METRICS/REPORT wire surface, cross-process
trace stitching, and the obs-off wall-overhead guarantee.

`python -m pytest tests/test_obs.py -k trace` selects the
`trace`-named subset: the
chaos-retried multi-partition query whose exported trace must validate
against the minimal Chrome-trace-event schema (matched B/E pairs,
monotonic ts, attempt spans present)."""

import json
import logging
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from blaze_tpu.exprs import AggExpr, AggFn, Col
from blaze_tpu.obs import trace
from blaze_tpu.obs.history import RuntimeHistory
from blaze_tpu.obs.metrics import MetricsRegistry, REGISTRY
from blaze_tpu.ops import (
    AggMode,
    FilterExec,
    HashAggregateExec,
)
from blaze_tpu.ops.base import PhysicalOp
from blaze_tpu.ops.parquet_scan import FileRange, ParquetScanExec
from blaze_tpu.service import QueryService
from blaze_tpu.testing import chaos


def wait_for(cond, timeout=10.0, tick=0.005):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if cond():
            return True
        time.sleep(tick)
    return False


@pytest.fixture
def two_part_plan(tmp_path):
    """A 2-partition parquet aggregate with a STABLE fingerprint (so
    the cache probes and the runtime history both engage)."""
    rng = np.random.default_rng(7)
    paths = []
    for i in range(2):
        p = str(tmp_path / f"t{i}.parquet")
        pq.write_table(pa.table({"v": rng.random(2000)}), p)
        paths.append(p)

    def make():
        return HashAggregateExec(
            FilterExec(
                ParquetScanExec([[FileRange(p)] for p in paths]),
                Col("v") > 0.5,
            ),
            keys=[],
            aggs=[(AggExpr(AggFn.SUM, Col("v")), "s")],
            mode=AggMode.COMPLETE,
        )

    return make


# ---------------------------------------------------------------------------
# span tree + export primitives
# ---------------------------------------------------------------------------


def test_span_tree_nests_and_exports_valid_chrome_trace():
    rec = trace.begin_trace("t-unit")
    with trace.span("outer", rec=rec, partition=0) as outer:
        with trace.span("inner") as inner:  # thread-current recorder
            inner.event("tick", n=1)
        outer.tag(done=True)
    rec.finish(state="DONE")
    assert trace.get_trace("t-unit") is rec
    # structure: inner's parent is outer, outer's parent is root
    by_name = {s.name: s for s in rec.spans}
    assert by_name["inner"].parent_id == by_name["outer"].span_id
    assert by_name["outer"].parent_id == rec.root.span_id
    doc = trace.chrome_trace(rec)
    assert trace.validate_chrome(doc) == []
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "B"}
    assert {"query", "outer", "inner"} <= names
    assert any(e["ph"] == "i" and e["name"] == "tick"
               for e in doc["traceEvents"])


def test_span_exit_tags_error_class():
    from blaze_tpu.errors import TransientError

    rec = trace.begin_trace("t-err")
    with pytest.raises(TransientError):
        with trace.span("attempt", rec=rec, attempt=0):
            raise TransientError("flaky")
    sp = next(s for s in rec.spans if s.name == "attempt")
    assert sp.tags["error_class"] == "TRANSIENT"
    assert sp.end_ns is not None


def test_chrome_validator_rejects_malformed():
    bad = {"traceEvents": [
        {"ph": "B", "name": "a", "pid": 1, "tid": 1, "ts": 5},
        {"ph": "E", "name": "a", "pid": 1, "tid": 1, "ts": 2},
        {"ph": "E", "name": "b", "pid": 1, "tid": 1, "ts": 9},
        {"ph": "B", "name": "c", "pid": 1, "tid": 2, "ts": 1},
    ]}
    problems = trace.validate_chrome(bad)
    assert any("non-monotonic" in p for p in problems)
    assert any("without matching B" in p for p in problems)
    assert any("unclosed B" in p for p in problems)
    assert trace.validate_chrome({}) != []


def test_span_cap_degrades_to_null_spans():
    old = trace.MAX_SPANS_PER_TRACE
    trace.MAX_SPANS_PER_TRACE = 3
    try:
        rec = trace.begin_trace("t-cap")
        for i in range(6):
            with trace.span(f"s{i}", rec=rec):
                pass
        assert len(rec.spans) == 3
        assert rec.dropped == 4
        assert trace.validate_chrome(trace.chrome_trace(rec)) == []
    finally:
        trace.MAX_SPANS_PER_TRACE = old


def test_attach_subtree_stitches_remote_spans():
    worker = trace.TraceRecorder("task-1", root_name="worker_task")
    with trace.span("execute", rec=worker):
        with trace.span("h2d"):
            pass
    worker.finish(state="DONE")
    dicts = worker.to_dicts()
    # simulate the wire: JSON round trip
    dicts = json.loads(json.dumps(dicts))

    driver = trace.begin_trace("q-driver")
    n = driver.attach_subtree(dicts)
    assert n == len(dicts)
    by_name = {s.name: s for s in driver.spans}
    # subtree root re-parents under the driver root; inner links hold
    assert by_name["worker_task"].parent_id == driver.root.span_id
    assert by_name["execute"].parent_id == by_name["worker_task"].span_id
    assert trace.validate_chrome(trace.chrome_trace(driver)) == []


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_metrics_registry_prometheus_exposition():
    r = MetricsRegistry()
    r.inc("blaze_queries_total", state="DONE")
    r.inc("blaze_queries_total", 2, state="FAILED")
    r.observe("blaze_query_wall_seconds", 0.004)
    r.observe("blaze_query_wall_seconds", 3.0)
    r.register_collector(
        "t", lambda: [("blaze_admission_queued", {}, 5, "gauge")]
    )
    txt = r.render_prometheus()
    assert '# TYPE blaze_queries_total counter' in txt
    assert 'blaze_queries_total{state="DONE"} 1' in txt
    assert 'blaze_queries_total{state="FAILED"} 2' in txt
    assert 'blaze_admission_queued 5' in txt
    assert 'blaze_query_wall_seconds_count 2' in txt
    assert 'le="+Inf"} 2' in txt
    # bucket counts are cumulative
    assert 'blaze_query_wall_seconds_sum 3.004' in txt
    r.unregister_collector("t")
    assert "blaze_admission_queued" not in r.render_prometheus()
    # a crashing collector degrades to a CUMULATIVE error counter
    # (a literal 1 would make rate() blind to persistent failure)
    r.register_collector("boom", lambda: 1 / 0)
    assert ('blaze_collector_errors_total{collector="boom"} 1'
            in r.render_prometheus())
    assert ('blaze_collector_errors_total{collector="boom"} 2'
            in r.render_prometheus())


def test_two_live_services_render_distinct_series():
    """Two QueryServices share the process registry; their samples
    must stay distinct series (the instance label) - duplicate
    name+labelset pairs would fail a whole Prometheus scrape."""
    with QueryService(max_concurrency=1):
        with QueryService(max_concurrency=1):
            txt = REGISTRY.render_prometheus()
    series = [ln.rsplit(" ", 1)[0] for ln in txt.splitlines()
              if ln and not ln.startswith("#")]
    dupes = {s for s in series if series.count(s) > 1}
    assert not dupes, dupes


def test_global_registry_folds_dispatch_counters():
    from blaze_tpu.runtime import dispatch

    dispatch.record("dispatches", 0)  # ensure the family exists
    txt = REGISTRY.render_prometheus()
    assert 'blaze_dispatch_total{kind="dispatches"}' in txt


# ---------------------------------------------------------------------------
# runtime history
# ---------------------------------------------------------------------------


def test_runtime_history_estimates_and_bounds():
    h = RuntimeHistory(max_fingerprints=2, samples_per_fp=4)
    assert h.estimate("fp0") is None
    assert h.p50("fp0") is None
    for v in (0.1, 0.2, 0.3):
        h.record("fp0", v)
    assert h.p50("fp0") == pytest.approx(0.2)
    assert h.p50("fp0", min_samples=4) is None  # sample floor
    for v in (1.0, 1.0, 9.0, 9.0, 9.0, 9.0):
        h.record("fp0", v)  # ring: only the last 4 remain
    assert h.p50("fp0") == pytest.approx(9.0)
    h.record("fp1", 1.0)
    h.record("fp2", 1.0)  # LRU-evicts fp0 (capacity 2)
    assert h.estimate("fp0") is None
    s = h.summary()
    assert s["fingerprints"] == 2
    assert all("p50" in t for t in s["top"])


# ---------------------------------------------------------------------------
# the service trace: chaos-retried multi-partition export (CI --trace)
# ---------------------------------------------------------------------------


def test_trace_chaos_retried_query_exports_valid_perfetto_json(
    two_part_plan,
):
    """ISSUE 4 acceptance: a chaos-retried multi-partition query's
    exported trace is schema-valid Chrome JSON containing queue-wait,
    per-attempt execution (one span per attempt, failures tagged with
    error_class), and cache-probe spans, with the injected fault
    visible as a span event carrying the plan seed."""
    with chaos.active(
        [chaos.Fault(site="task.execute", klass="TRANSIENT",
                     partition=1, times=1)],
        seed=42,
    ) as plan:
        with QueryService(max_concurrency=2,
                          retry_backoff_s=0.005) as svc:
            q = svc.submit_plan(two_part_plan())
            svc.result(q.query_id, timeout=60)
            doc = svc.trace(q.query_id)
        assert plan.fired("task.execute") == 1
    assert doc is not None
    assert trace.validate_chrome(doc) == []
    begins = [e for e in doc["traceEvents"] if e["ph"] == "B"]
    names = {e["name"] for e in begins}
    assert {"query", "queue_wait", "admission", "attempt",
            "cache_probe", "execute_partition"} <= names
    # partition 1 ran twice: a failed attempt tagged TRANSIENT + the
    # retry (partition 0 contributes its own single attempt)
    attempts = [e for e in begins if e["name"] == "attempt"]
    assert len(attempts) == 3
    failed = [e for e in attempts
              if e.get("args", {}).get("error_class") == "TRANSIENT"]
    assert len(failed) == 1
    faults = [e for e in doc["traceEvents"]
              if e["ph"] == "i" and e["name"] == "chaos.fault"]
    assert len(faults) == 1
    assert faults[0]["args"]["seed"] == 42
    # the root span covers the WHOLE query: its exported E must not be
    # truncated below the last attempt's end (the retroactive
    # queue_wait span starts at SUBMIT, before the root was built -
    # the recorder backdates the root so the nesting sweep cannot
    # clamp it)
    ends = {}
    for e in doc["traceEvents"]:
        if e["ph"] == "E":
            ends.setdefault(e["name"], e["ts"])
            ends[e["name"]] = max(ends[e["name"]], e["ts"])
    assert ends["query"] >= ends["attempt"]
    # the trace is genuinely Perfetto-loadable JSON (round-trips)
    assert trace.validate_chrome(json.loads(json.dumps(doc))) == []


def test_trace_parquet_decode_fault_lands_on_its_span(two_part_plan):
    """A chaos fault injected at the parquet.decode seam (which runs
    on the prefetch thread) must still land as a chaos.fault event
    inside the parquet_decode span's trace."""
    with chaos.active(
        [chaos.Fault(site="parquet.decode", klass="TRANSIENT",
                     times=1)],
        seed=11,
    ) as plan:
        with QueryService(max_concurrency=1,
                          retry_backoff_s=0.005) as svc:
            q = svc.submit_plan(two_part_plan())
            svc.result(q.query_id, timeout=60)
            doc = svc.trace(q.query_id)
        assert plan.fired("parquet.decode") == 1
    assert trace.validate_chrome(doc) == []
    faults = [e for e in doc["traceEvents"]
              if e["ph"] == "i" and e["name"] == "chaos.fault"]
    assert len(faults) == 1
    assert faults[0]["args"]["site"] == "parquet.decode"


def test_trace_off_records_nothing(two_part_plan):
    assert not trace.ACTIVE
    with QueryService(max_concurrency=1, enable_trace=False) as svc:
        q = svc.submit_plan(two_part_plan())
        svc.result(q.query_id, timeout=60)
        assert q.tracer is None
        assert svc.trace(q.query_id) is None


# ---------------------------------------------------------------------------
# predicted-unmeetability shedding
# ---------------------------------------------------------------------------


def test_predicted_unmeetable_shed(two_part_plan):
    # cache OFF: shedding semantics without cache interference
    with QueryService(max_concurrency=1, enable_cache=False) as svc:
        plan = two_part_plan()
        fp = plan.fingerprint()
        # fewer than 3 samples: never shed on prediction
        svc.history.record(fp, 60.0)
        svc.history.record(fp, 60.0)
        q_ok = svc.submit_plan(two_part_plan(), deadline_s=30.0)
        assert wait_for(lambda: q_ok.done)
        assert q_ok.state.value == "DONE"
        # >= 3 samples of a p50 far beyond the slack: shed at
        # admission with the DISTINCT counter, before any execution
        for _ in range(3):
            svc.history.record(fp, 60.0)
        q = svc.submit_plan(two_part_plan(), deadline_s=5.0)
        assert wait_for(lambda: q.done)
        assert q.state.value == "TIMED_OUT"
        assert "predicted unmeetable" in q.error
        st = svc.stats()
        assert st["admission"]["shed_predicted"] == 1
        assert st["admission"]["shed_deadline"] == 0
        # the shed query must NOT count as admitted (next_admissible
        # popped it, but the shed path takes the admit back) - only
        # q_ok has genuinely been admitted at this point
        assert st["admission"]["admitted"] == 1
        # a deadline-less query with the same fingerprint still runs
        q2 = svc.submit_plan(two_part_plan())
        assert wait_for(lambda: q2.done)
        assert q2.state.value == "DONE"


def test_predicted_shed_skipped_when_cache_covers(two_part_plan):
    """A fully-cached fingerprint must NOT be shed on its (slow)
    runtime estimate: the cache serves it inside any deadline, and a
    shed would pin the stale estimate forever (sheds never execute,
    so no faster sample could ever be recorded)."""
    with QueryService(max_concurrency=1) as svc:
        warm = svc.submit_plan(two_part_plan())
        svc.result(warm.query_id, timeout=60)  # populates the cache
        fp = two_part_plan().fingerprint()
        for _ in range(3):
            svc.history.record(fp, 60.0)  # p50 far beyond any slack
        q = svc.submit_plan(two_part_plan(), deadline_s=2.0)
        assert wait_for(lambda: q.done)
        assert q.state.value == "DONE"  # served from cache, not shed
        st = svc.stats()
        assert st["admission"]["shed_predicted"] == 0
        assert st["cache"]["hits"] == 2  # both partitions


def test_queued_deadline_timeout_snapshots_error(two_part_plan,
                                                 caplog):
    """The terminal hook fires INSIDE the transition, so q.error must
    be assigned before it: a query timed out while QUEUED has the
    deadline message in its slow-query log line, not null."""
    with caplog.at_level(logging.WARNING, logger="blaze_tpu.slowlog"):
        with chaos.active(
            [chaos.Fault(site="service.admit", klass="STALL",
                         stall_s=0.6)],
            seed=2,
        ):
            with QueryService(max_concurrency=1,
                              slow_query_s=1e-6) as svc:
                blocker = svc.submit_plan(two_part_plan())
                # the blocker must HOLD the single slot (stalled at
                # the service.admit seam) before the deadlined query
                # is enqueued, else the dispatcher may admit the
                # deadlined query first and it times out "before
                # start" instead of "while queued"
                assert wait_for(
                    lambda: blocker.state.value != "QUEUED",
                    timeout=20,
                )
                q = svc.submit_plan(two_part_plan(), deadline_s=0.15)
                assert wait_for(lambda: q.done, timeout=20)
                assert q.state.value == "TIMED_OUT"
                assert q.error == "deadline exceeded while queued"
                svc.result(blocker.query_id, timeout=60)
    lines = [json.loads(r.message) for r in caplog.records
             if r.name == "blaze_tpu.slowlog"]
    timed_out = [p for p in lines if p["query_id"] == q.query_id]
    assert timed_out and timed_out[0]["error"] == (
        "deadline exceeded while queued"
    )


def test_runtime_history_records_service_executions(two_part_plan):
    with QueryService(max_concurrency=1, enable_cache=False) as svc:
        for _ in range(3):
            q = svc.submit_plan(two_part_plan())
            svc.result(q.query_id, timeout=60)
        fp = two_part_plan().fingerprint()
        est = svc.history.estimate(fp)
        assert est is not None and est["n"] == 3
        assert svc.history.p50(fp) is not None


# ---------------------------------------------------------------------------
# structured STATS
# ---------------------------------------------------------------------------


def test_stats_structured_payload(two_part_plan):
    with QueryService(max_concurrency=1) as svc:
        q = svc.submit_plan(two_part_plan())
        svc.result(q.query_id, timeout=60)
        st = svc.stats()
    assert isinstance(st["admission"]["headroom"], int)
    assert "queued" in st["admission"]
    assert st["queries"]["by_state"].get("DONE") == 1
    assert st["queries"]["live"] == 0
    for k in ("degraded_queries", "retried_queries", "slow_queries"):
        assert k in st["queries"]
    assert st["cache"]["hits"] == 0
    assert st["runtime_history"]["fingerprints"] == 1
    assert "workers_total" in st["quarantine"]
    assert st["service"]["trace_enabled"] is True
    json.dumps(st)  # the whole payload is wire-serializable


# ---------------------------------------------------------------------------
# slow-query log
# ---------------------------------------------------------------------------


def test_slow_query_log_emits_one_json_line(two_part_plan, caplog):
    with caplog.at_level(logging.WARNING, logger="blaze_tpu.slowlog"):
        with QueryService(max_concurrency=1,
                          slow_query_s=0.000001) as svc:
            q = svc.submit_plan(two_part_plan())
            svc.result(q.query_id, timeout=60)
            assert wait_for(
                lambda: svc.obs_counters["slow_queries"] >= 1
            )
    lines = [r.message for r in caplog.records
             if r.name == "blaze_tpu.slowlog"]
    assert len(lines) == 1
    payload = json.loads(lines[0])
    assert payload["event"] == "slow_query"
    assert payload["query_id"] == q.query_id
    assert payload["state"] == "DONE"
    assert payload["wall_s"] > 0
    assert "execution_s" in payload["phases"]
    assert "queue_wait_s" in payload["phases"]
    assert "fingerprint" in payload
    # the per-span rollup: where execution time went
    assert payload["spans"]["attempt"]["count"] == 2


def test_slow_query_log_flags_retries_and_threshold_off(
    two_part_plan, caplog,
):
    with caplog.at_level(logging.WARNING, logger="blaze_tpu.slowlog"):
        with chaos.active(
            [chaos.Fault(site="task.execute", klass="TRANSIENT",
                         partition=0, times=1)],
            seed=3,
        ):
            with QueryService(max_concurrency=1, slow_query_s=1e-6,
                              retry_backoff_s=0.005) as svc:
                q = svc.submit_plan(two_part_plan())
                svc.result(q.query_id, timeout=60)
                assert wait_for(
                    lambda: svc.obs_counters["slow_queries"] >= 1
                )
    payload = json.loads(
        [r.message for r in caplog.records
         if r.name == "blaze_tpu.slowlog"][0]
    )
    assert payload["retries"] == 1
    caplog.clear()
    # threshold <= 0 disables the log entirely
    with caplog.at_level(logging.WARNING, logger="blaze_tpu.slowlog"):
        with QueryService(max_concurrency=1, slow_query_s=0.0) as svc:
            q = svc.submit_plan(two_part_plan())
            svc.result(q.query_id, timeout=60)
    assert not [r for r in caplog.records
                if r.name == "blaze_tpu.slowlog"]


# ---------------------------------------------------------------------------
# wire surface: METRICS verb + trace-through-REPORT + the trace CLI
# ---------------------------------------------------------------------------


def test_wire_metrics_verb_and_trace_report(two_part_plan, tmp_path):
    from blaze_tpu.plan.serde import task_to_proto
    from blaze_tpu.runtime.gateway import TaskGatewayServer
    from blaze_tpu.service import ServiceClient

    blob = task_to_proto(two_part_plan(), 0)
    with QueryService(max_concurrency=2) as svc:
        with TaskGatewayServer(service=svc) as srv:
            host, port = srv.address
            with ServiceClient(host, port) as c:
                st = c.submit(blob)
                qid = st["query_id"]
                c.fetch(qid)
                # METRICS verb: Prometheus text with dispatch.* and
                # admission counters (ISSUE 4 acceptance)
                txt = c.metrics()
                assert 'blaze_dispatch_total{kind="dispatches"}' in txt
                # admission samples carry a service instance label
                # (several services may share the process registry)
                assert ('blaze_admission_events_total'
                        '{event="admitted",service="') in txt
                assert 'blaze_queries_total{state="DONE"}' in txt
                # trace rides the REPORT verb, OPT-IN via flags bit 0:
                # a text-only report poll must not pay the span-tree
                # serialization
                assert "trace" not in c.report_full(
                    qid, include_trace=False
                )
                full = c.report_full(qid)
                assert "DONE" in full["report"]
                doc = full["trace"]
                assert trace.validate_chrome(doc) == []
                names = {e["name"] for e in doc["traceEvents"]
                         if e["ph"] == "B"}
                assert {"queue_wait", "attempt",
                        "result_stream"} <= names
            # the CLI export path writes the same document
            from blaze_tpu.__main__ import main as cli_main

            out = str(tmp_path / "q.trace.json")
            rc = cli_main(["trace", qid, "--host", host,
                           "--port", str(port), "-o", out])
            assert rc == 0
            with open(out) as f:
                assert trace.validate_chrome(json.load(f)) == []
            # unknown id: the CLI surfaces the server's in-band
            # error, not a misleading tracing diagnosis
            rc = cli_main(["trace", "no-such-query", "--host", host,
                           "--port", str(port), "-o", out])
            assert rc == 1


def test_wire_report_raw_span_dicts_flag(two_part_plan):
    """REPORT flags bit 1 (ISSUE 6): the RAW span dicts ride the wire
    for the router's cross-hop graft - id/parent links intact, NOT
    the rendered Chrome document (and not unless asked)."""
    from blaze_tpu.plan.serde import task_to_proto
    from blaze_tpu.runtime.gateway import TaskGatewayServer
    from blaze_tpu.service import ServiceClient

    blob = task_to_proto(two_part_plan(), 0)
    with QueryService(max_concurrency=2) as svc:
        with TaskGatewayServer(service=svc) as srv:
            host, port = srv.address
            with ServiceClient(host, port) as c:
                st = c.submit(blob)
                qid = st["query_id"]
                c.fetch(qid)
                plain = c.report_full(qid, include_trace=False)
                assert "trace_spans" not in plain
                resp = c.report_full(qid, include_trace=False,
                                     include_spans=True)
                spans = resp["trace_spans"]
                assert "trace" not in resp
                assert isinstance(spans, list) and spans
                ids = {s["span_id"] for s in spans}
                # a self-consistent subtree: every parent link
                # resolves inside the payload (root's parent is 0)
                assert all(
                    s["parent_id"] in ids or s["parent_id"] == 0
                    for s in spans
                )
                names = {s["name"] for s in spans}
                assert {"query", "queue_wait", "attempt"} <= names
                # and it grafts cleanly into another recorder
                rec = trace.TraceRecorder("re-graft")
                assert rec.attach_subtree(spans) == len(spans)
                rec.finish(state="DONE")
                assert trace.validate_chrome(
                    trace.chrome_trace(rec)
                ) == []


# ---------------------------------------------------------------------------
# cross-process stitching (cluster workers)
# ---------------------------------------------------------------------------


def test_trace_cluster_worker_spans_stitch_into_driver(tmp_path):
    from blaze_tpu.ops import LimitExec
    from blaze_tpu.plan.serde import task_to_proto
    from blaze_tpu.runtime.cluster import MiniCluster

    p = str(tmp_path / "c.parquet")
    pq.write_table(pa.table({"v": np.arange(100, dtype=np.int64)}), p)
    blob = task_to_proto(
        LimitExec(ParquetScanExec([[FileRange(p)]]), 10), 0
    )
    trace.enable()
    try:
        driver = trace.begin_trace("q-cluster")
        with trace.span("cluster_run", rec=driver):
            with MiniCluster(
                num_workers=1, env={"BLAZE_TRACE": "1"}
            ) as mc:
                tables = mc.run_tasks([blob], timeout=120)
        driver.finish(state="DONE")
    finally:
        trace.disable()
    assert tables[0].num_rows == 10
    pids = {s.pid for s in driver.spans}
    assert len(pids) == 2  # driver + worker process
    names = {s.name for s in driver.spans}
    assert "worker_task" in names and "execute" in names
    doc = trace.chrome_trace(driver)
    assert trace.validate_chrome(doc) == []
    # worker spans keep their own pid track in the export
    assert len({e["pid"] for e in doc["traceEvents"]}) == 2


# ---------------------------------------------------------------------------
# ISSUE 25: one clock - stage spans beside the profiler's, the per-task
# stage table and dispatch count in POLL
# ---------------------------------------------------------------------------


class _AnnotationStub:
    """In place of jax.profiler.TraceAnnotation: what was built,
    entered and exited, in order, while a profiler session "runs"."""

    log = []
    session = True

    @staticmethod
    def is_enabled():
        return _AnnotationStub.session

    def __init__(self, name):
        self.name = name
        _AnnotationStub.log.append(("new", name))

    def __enter__(self):
        _AnnotationStub.log.append(("enter", self.name))

    def __exit__(self, *exc):
        _AnnotationStub.log.append(("exit", self.name))


@pytest.fixture
def annotations(monkeypatch):
    monkeypatch.setattr(trace, "_TRACE_ME", _AnnotationStub)
    monkeypatch.setattr(_AnnotationStub, "session", True)
    _AnnotationStub.log = []
    return _AnnotationStub.log


@pytest.mark.parametrize("name", sorted(trace.STAGE_SPANS))
def test_stage_span_lies_on_the_profilers_side(name, annotations):
    rec = trace.TraceRecorder("t")
    with trace.span("attempt", rec=rec):
        with trace.span(name) as sp:
            sum(range(20000))
    label = "blaze." + name
    assert annotations == [("new", label), ("enter", label),
                           ("exit", label)]
    assert sp.cpu_ns > 0 and sp.end_ns - sp.start_ns >= sp.cpu_ns // 2


def test_stage_span_without_a_profiler_session_builds_nothing(
        annotations, monkeypatch):
    """No session: the stage pays TraceMe's own check and no object;
    its CPU time is still taken."""
    monkeypatch.setattr(_AnnotationStub, "session", False)
    rec = trace.TraceRecorder("t")
    with trace.span("d2h", rec=rec) as sp:
        sum(range(20000))
    assert annotations == [] and sp.cpu_ns > 0


def test_stage_annotation_is_jax_profilers():
    import jax

    trace._TRACE_ME = None
    assert trace._stage_annotation("d2h") is None  # no session runs
    assert trace._TRACE_ME is jax.profiler.TraceAnnotation


@pytest.mark.parametrize(
    "name", ["execute_partition", "attempt", "execute",
             "plan_decode", "parquet_decode", "record_span"])
def test_other_spans_stay_off_the_profilers_side(name, annotations):
    rec = trace.TraceRecorder("t")
    if name == "record_span":
        for lifecycle in ("queue_wait", "admission", "result_stream"):
            rec.record_span(lifecycle, 1.0, 2.0)
        # not even under a stage's name: the async wire's drain wait
        rec.record_span("frame_send", 1.0, 2.0)
    else:
        with trace.span(name, rec=rec) as sp:
            pass
        assert sp.cpu_ns == 0
    assert annotations == []


def test_obs_trace_alone_does_not_import_jax():
    """The router imports obs.trace and stays off JAX: a stage span in
    a process without jax records, and imports nothing for it."""
    import subprocess
    import sys

    code = (
        "import importlib.util, sys\n"
        "spec = importlib.util.spec_from_file_location('t', sys.argv[1])\n"
        "t = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(t)\n"
        "rec = t.TraceRecorder('x')\n"
        "with t.span('d2h', rec=rec) as sp:\n"
        "    pass\n"
        "assert sp.end_ns is not None and 'jax' not in sys.modules\n"
        "assert rec.phase_totals({'d2h': 'd2h'}, stage_table=True)"
        "['d2h']['n'] == 1\n"
    )
    subprocess.run([sys.executable, "-c", code, trace.__file__],
                   check=True, timeout=60)


def test_stage_table_takes_a_nested_stage_out_of_its_parent():
    rec = trace.TraceRecorder("t")
    with trace.span("decode_batch", rec=rec) as outer:
        with trace.span("h2d") as inner:
            time.sleep(0.002)
    from blaze_tpu.obs.phases import STAGE_PHASE

    table = rec.phase_totals(STAGE_PHASE, stage_table=True)
    whole = (outer.end_ns - outer.start_ns) / 1e9
    h2d = (inner.end_ns - inner.start_ns) / 1e9
    assert table["h2d"]["wall_s"] == pytest.approx(h2d, abs=2e-6)
    assert table["decode_batch"]["wall_s"] == pytest.approx(
        whole - h2d, abs=2e-6)
    # the rollup's fold stays inclusive
    assert rec.phase_totals(STAGE_PHASE)["decode_batch"] == \
        pytest.approx(whole, abs=2e-6)


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


SCAN_STAGES = {"decode_batch", "h2d", "d2h"}
SHUFFLE_STAGES = {"shuffle_partition", "shuffle_encode",
                  "shuffle_finalize"}
WIRE_STAGES = {"frame_encode", "frame_send"}


@pytest.mark.parametrize("wire_plane", ["async", "threaded"])
@pytest.mark.parametrize("shape", ["q6_scan", "limit_over_filter",
                                   "shuffle_write"])
def test_poll_stage_table(shape, wire_plane, keyed_parquet, tmp_path):
    from blaze_tpu.ops import LimitExec
    from blaze_tpu.ops.shuffle_writer import ShuffleWriterExec
    from blaze_tpu.plan.serde import task_to_proto
    from blaze_tpu.runtime.gateway import TaskGatewayServer
    from blaze_tpu.service import ServiceClient

    scan = ParquetScanExec([[FileRange(keyed_parquet)]])
    if shape == "q6_scan":
        # the result sink reads a filtered batch back whole and trims
        # it on the host: no compaction on the device
        plan = FilterExec(scan, Col("v") > 10)
        want = SCAN_STAGES | WIRE_STAGES
    elif shape == "limit_over_filter":
        # a limit goes on computing with the rows, so it packs them
        # on the device first and the sink sees no selection
        plan = LimitExec(FilterExec(scan, Col("v") > 10), 39000)
        want = SCAN_STAGES | {"compact"} | WIRE_STAGES
    else:
        plan = ShuffleWriterExec(
            scan, [Col("k")], 8, str(tmp_path / "s.data"),
            str(tmp_path / "s.index"))
        want = SCAN_STAGES | SHUFFLE_STAGES
    with QueryService(max_concurrency=1) as svc:
        with TaskGatewayServer(service=svc,
                               wire=wire_plane) as srv:
            with ServiceClient(*srv.address) as c:
                st = c.submit(task_to_proto(plan, 0))
                c.fetch(st["query_id"])
                poll = c.poll(st["query_id"])
    assert poll["state"] == "DONE"
    stages = poll["stages"]
    assert set(stages) == want
    for name, row in stages.items():
        assert row["n"] > 0 and row["wall_s"] >= 0, name
        assert 0 <= row["cpu_s"], name
    assert stages["d2h"]["n"] == 3  # one a batch
    # a thread's stages never overlap, so each thread's sum fits in
    # the execution; the scan's prefetch worker decodes batch n+1
    # while the draining thread works on batch n, so the two sums
    # together may not
    prefetch = {"decode_batch", "h2d"}
    for thread in (prefetch, set(stages) - prefetch - WIRE_STAGES):
        assert sum(stages[name]["wall_s"] for name in thread) \
            <= poll["execution_s"], thread
    assert poll["task_dispatches"] == poll["dispatches"] > 0
    assert poll.get("sink_trim_batches") == \
        (3 if shape == "q6_scan" else None)
    if shape == "limit_over_filter":
        assert stages["compact"]["n"] == 3
    if shape != "shuffle_write":
        assert "shuffle_segments" not in poll
    else:
        # the parts the task encoded are the parts in its files: the
        # split's three batches staged, one part a partition
        from blaze_tpu.io.ipc import partition_ranges, read_file_segment

        parts = sum(
            len(list(read_file_segment(
                str(tmp_path / "s.data"), off, length)))
            for off, length in partition_ranges(
                str(tmp_path / "s.index"))
        )
        assert poll["shuffle_segments"] == parts == 8
        # a span a batch for staging and one for finalize's freezes
        assert stages["shuffle_encode"]["n"] == 4
        assert stages["shuffle_finalize"]["n"] == 1


@pytest.mark.parametrize("core, fetches", [("scatter", 1), ("sort", 3)])
def test_poll_stage_table_names_the_grouped_aggregates_waits(
        core, fetches, keyed_parquet, monkeypatch):
    """`agg_fetch` in a grouped aggregate: on the sort core (what `auto`
    resolves to on a TPU) `FusedAggregateExec._run_agg` waits for every
    batch's program, a stage a batch; on the scatter core the grouped
    carry syncs without a stage and `HostFinalAggExec` finalizes the one
    state batch in one."""
    from blaze_tpu.exprs import AggExpr, AggFn
    from blaze_tpu.ops import AggMode, HashAggregateExec
    from blaze_tpu.plan.serde import task_to_proto
    from blaze_tpu.runtime.gateway import TaskGatewayServer
    from blaze_tpu.service import ServiceClient

    monkeypatch.setenv("BLAZE_GROUP_CORE", core)
    plan = HashAggregateExec(
        FilterExec(ParquetScanExec([[FileRange(keyed_parquet)]]),
                   Col("v") > 10),
        keys=[(Col("k"), "k")],
        aggs=[(AggExpr(AggFn.SUM, Col("v")), "s")],
        mode=AggMode.COMPLETE,
    )
    # mesh off: a chip's `serve` has one device, conftest.py's eight
    # virtual ones would take a grouped aggregate to the mesh tier
    with QueryService(max_concurrency=1, mesh_mode="off") as svc:
        with TaskGatewayServer(service=svc) as srv:
            with ServiceClient(*srv.address) as c:
                st = c.submit(task_to_proto(plan, 0))
                rows = sum(b.num_rows for b in c.fetch(st["query_id"]))
                poll = c.poll(st["query_id"])
    assert poll["state"] == "DONE" and rows == 501  # 500 keys and NULL
    stages = poll["stages"]
    assert stages["agg_fetch"]["n"] == fetches
    assert "compact" not in stages
    assert stages["agg_fetch"]["wall_s"] <= poll["execution_s"]
    # the keyless carry's counter is not a grouped task's
    assert "agg_carry_batches" not in poll


class _MeetAfterFirstBatch(PhysicalOp):
    """Passes its child's batches through and waits at a barrier once
    the first is out: two tasks built on one barrier are in flight
    together from there on, whatever the scheduler does."""

    def __init__(self, child, barrier):
        self.children = [child]
        self._barrier = barrier

    @property
    def schema(self):
        return self.children[0].schema

    def execute(self, partition, ctx):
        for i, cb in enumerate(self.children[0].execute(partition,
                                                        ctx)):
            yield cb
            if i == 0:
                self._barrier.wait(timeout=60)


def test_task_dispatches_are_the_tasks_own(keyed_parquet):
    import threading

    def plan(barrier):
        return _MeetAfterFirstBatch(
            FilterExec(ParquetScanExec([[FileRange(keyed_parquet)]]),
                       Col("v") > 10), barrier)

    def run(svc, n):
        barrier = threading.Barrier(n)
        qs = [svc.submit_plan(plan(barrier), use_cache=False)
              for _ in range(n)]
        for q in qs:
            svc.result(q.query_id, timeout=120)
        return [q.status() for q in qs]

    with QueryService(max_concurrency=2, enable_trace=False) as svc:
        run(svc, 1)  # builds the kernels
        (alone,) = run(svc, 1)
        both = run(svc, 2)
    assert alone["task_dispatches"] == alone["dispatches"] > 0
    assert [p["task_dispatches"] for p in both] == \
        [alone["task_dispatches"]] * 2
    # the process-wide delta takes in the neighbour's launches
    assert max(p["dispatches"] for p in both) > alone["dispatches"]


def test_trace_off_poll_counts_launches_and_builds_no_annotation(
        two_part_plan, annotations):
    assert not trace.ACTIVE
    with QueryService(max_concurrency=1, enable_trace=False) as svc:
        q = svc.submit_plan(two_part_plan())
        svc.result(q.query_id, timeout=60)
        poll = q.status()
    assert poll["task_dispatches"] > 0
    assert "stages" not in poll
    assert annotations == []


# ---------------------------------------------------------------------------
# the disabled-path guarantee: wall overhead (budget pins live in
# test_dispatch_budget.py)
# ---------------------------------------------------------------------------


def test_obs_wall_overhead_under_2_percent():
    """ISSUE 4 satellite: the wall-overhead smoke. Strong form of the
    disabled-path guarantee: even tracing ON (recorder installed, all
    seams live) must cost <2% wall on a battery-style shape - so the
    off path, which only pays the attribute checks, certainly does.
    Interleaved best-of-k pairs with a small absolute slack absorb
    shared-host scheduling noise; the comparison retries before
    failing so one noisy window cannot redden the suite."""
    from blaze_tpu.batch import ColumnBatch
    from blaze_tpu.ops import MemoryScanExec, ProjectExec
    from blaze_tpu.ops.fused import fuse_pipelines
    from blaze_tpu.runtime.executor import run_plan

    assert not trace.ACTIVE
    rng = np.random.default_rng(11)
    n = 1 << 16
    cb = ColumnBatch.from_arrow(pa.record_batch({
        "price": (rng.random(n) * 100).astype(np.float32),
        "qty": rng.integers(1, 10, n).astype(np.int32),
    }))

    def mk():
        return fuse_pipelines(HashAggregateExec(
            ProjectExec(
                MemoryScanExec([[cb]], cb.schema),
                [(Col("price"), "p")],
            ),
            keys=[],
            aggs=[(AggExpr(AggFn.SUM, Col("p")), "s")],
            mode=AggMode.COMPLETE,
        ))

    def once():
        run_plan(mk())

    def once_traced():
        trace.enable()
        try:
            rec = trace.begin_trace("overhead-probe")
            with trace.span("battery", rec=rec):
                run_plan(mk())
            rec.finish(state="DONE")
        finally:
            trace.disable()

    once()  # warm: compile + kernel-cache fill
    once_traced()
    for attempt in range(3):
        k = 7 * (attempt + 1)
        off = [0.0] * k
        on = [0.0] * k
        for i in range(k):  # interleaved: drift hits both sides
            t0 = time.perf_counter()
            once()
            off[i] = time.perf_counter() - t0
            t0 = time.perf_counter()
            once_traced()
            on[i] = time.perf_counter() - t0
        best_off, best_on = min(off), min(on)
        if best_on <= best_off * 1.02 + 0.002:
            assert not trace.ACTIVE
            return
    raise AssertionError(
        f"obs wall overhead over budget: obs-off best {best_off:.6f}s"
        f" vs obs-on best {best_on:.6f}s (> 2% + 2ms)"
    )
