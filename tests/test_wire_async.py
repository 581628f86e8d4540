"""Async wire data plane (service/wire_async.py): event-loop verb
serving must be protocol-identical to the threaded tier while holding
ZERO OS threads per parked connection.

Covers the PR's acceptance seams:
  * connection churn leaks nothing (fds, loop tasks, the
    blaze_connections{tier} gauge),
  * a slow reader mid-stream parks a coroutine - the process thread
    count stays flat while N clients stall,
  * cancel-on-disconnect and DRAINING rejections behave identically
    under wire="threaded" and wire="async" (the threaded tier is the
    differential oracle),
  * chaos seams (gateway.stream, service.admit) fire on the async
    path,
  * the router's fleet-wide relay budget (--stream-total-bytes)
    blocks over-budget streams (stream_total_waits) and returns the
    buffered-bytes gauge to zero after the streams drain.
"""

import os
import socket
import struct
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from blaze_tpu.batch import ColumnBatch
from blaze_tpu.ops.parquet_scan import FileRange, ParquetScanExec
from blaze_tpu.plan.serde import task_to_proto
from blaze_tpu.runtime.gateway import _FLAG_SERVICE, TaskGatewayServer
from blaze_tpu.runtime.transport import _recv_exact
from blaze_tpu.service import QueryService, ServiceClient
from blaze_tpu.service import wire as wire_mod
from blaze_tpu.service.wire import VERB_FETCH
from blaze_tpu.service.wire_async import dispatch_pool
from blaze_tpu.testing import chaos
from blaze_tpu.testing.chaos import Fault
from tests.test_service import GatedScan, wait_for
from tests.test_service_gateway import tiny_wire_task

_U64 = struct.Struct("<Q")


@pytest.fixture
def big_dataset(tmp_path):
    """A multi-part, multi-MB result: 4 scan partitions over ~1.5MB of
    rows each, plan = bare scan (no aggregation shrinking the
    output), so FETCH streams enough bytes to overflow kernel socket
    buffers and exercise backpressure."""
    rng = np.random.default_rng(7)
    n = 400_000
    p = str(tmp_path / "big.parquet")
    pq.write_table(
        pa.table(
            {
                "k": pa.array(rng.integers(0, 1 << 30, n), pa.int64()),
                "v": pa.array(rng.random(n), pa.float64()),
            }
        ),
        p,
    )

    def blob(parts=4):
        plan = ParquetScanExec([[FileRange(p)] for _ in range(parts)])
        return task_to_proto(plan, 0)

    return blob


def _service_conns() -> int:
    with wire_mod._CONN_LOCK:
        return wire_mod._CONNECTIONS.get("service", 0)


def _open_fds() -> int:
    return len(os.listdir("/proc/self/fd"))


def _threads_outside_dispatch_pools() -> int:
    return sum(
        not t.name.startswith("blaze-verb-dispatch-")
        for t in threading.enumerate()
    )


def test_connection_churn_no_leaks():
    """200 connect/verb/close cycles: fd count, thread count, and the
    blaze_connections{tier="service"} gauge all return to baseline."""
    cb = ColumnBatch.from_pydict({"a": [1, 2, 3]})
    blob = tiny_wire_task(cb)
    with QueryService(max_concurrency=2) as svc:
        with TaskGatewayServer(service=svc, wire="async") as srv:
            # warm-up: populate the dispatch pool + loop machinery so
            # the baseline snapshot includes one-time allocations
            with ServiceClient(*srv.address) as c:
                c.run(blob)
            assert wait_for(lambda: _service_conns() == 0)
            fds0 = _open_fds()
            threads0 = _threads_outside_dispatch_pools()
            for _ in range(200):
                with ServiceClient(*srv.address) as c:
                    st = c.submit(blob)
                    c.fetch(st["query_id"])
            assert wait_for(lambda: _service_conns() == 0)
            # closed fds are reclaimed promptly; allow a little slack
            # for loop-internal churn mid-collection
            assert wait_for(lambda: _open_fds() <= fds0 + 8)
            # the dispatch pool grows a thread a task up to its cap
            # (bounded, by design): a connection that left a thread
            # behind shows outside it
            assert _threads_outside_dispatch_pools() <= threads0 + 4
            pool = dispatch_pool("service")
            assert len(pool._threads) <= pool._max_workers


def test_slow_reader_parks_threadless(big_dataset):
    """N clients stalling mid-stream park N coroutines, not N OS
    threads: the thread count stays flat while every stream is wedged
    against a full socket buffer (the threaded tier would hold one
    blocked thread per connection here)."""
    blob = big_dataset()
    n_slow = 12
    with QueryService(max_concurrency=2,
                      stream_stall_s=60.0) as svc:
        with TaskGatewayServer(service=svc, wire="async") as srv:
            with ServiceClient(*srv.address) as c:
                st = c.submit(blob, detach=True)
                qid = st["query_id"]
                c.fetch(qid)  # warm-up: result cached + pool threads
            threads0 = threading.active_count()
            socks = []
            try:
                for _ in range(n_slow):
                    s = socket.create_connection(srv.address)
                    # shrink the receive window so a multi-MB part
                    # wedges fast
                    s.setsockopt(socket.SOL_SOCKET,
                                 socket.SO_RCVBUF, 16384)
                    s.sendall(
                        _U64.pack(_FLAG_SERVICE)
                        + ServiceClient._id_verb(VERB_FETCH, qid,
                                                 60_000)
                    )
                    assert len(s.recv(8)) == 8  # first bytes flowed
                    socks.append(s)  # ...then stop reading: parked
                # give every stream time to wedge against the buffers
                time.sleep(1.0)
                assert threading.active_count() <= threads0 + 4, (
                    "parked streams must not hold OS threads"
                )
            finally:
                for s in socks:
                    s.close()
            assert wait_for(lambda: _service_conns() == 0)


@pytest.mark.parametrize("wire", ["threaded", "async"])
def test_cancel_on_disconnect_parity(wire):
    """A vanished client's non-detached queries get cancelled on both
    planes - the wire semantic the router's session tier depends on."""
    release = threading.Event()
    blocker = GatedScan(release)
    try:
        with QueryService(max_concurrency=1,
                          enable_cache=False) as svc:
            with TaskGatewayServer(service=svc, wire=wire) as srv:
                svc.submit_plan(blocker, estimated_bytes=0)
                assert wait_for(lambda: blocker.started.is_set())
                cb = ColumnBatch.from_pydict({"a": [1]})
                c = ServiceClient(*srv.address)
                st = c.submit(tiny_wire_task(cb))
                qid = st["query_id"]
                assert st["state"] == "QUEUED"
                c.close()
                assert wait_for(
                    lambda: svc.poll(qid)["state"] == "CANCELLED"
                )
    finally:
        release.set()


def test_draining_and_error_replies_identical_across_planes():
    """DRAINING rejections, unknown-query errors, and stats shapes are
    reply-identical between the threaded oracle and the async plane
    (zero client-visible protocol change)."""
    cb = ColumnBatch.from_pydict({"a": [1]})
    blob = tiny_wire_task(cb)
    replies = {}
    for wire in ("threaded", "async"):
        with QueryService(max_concurrency=1) as svc:
            svc.draining = True
            with TaskGatewayServer(service=svc, wire=wire) as srv:
                with ServiceClient(*srv.address) as c:
                    # submit_raw: the cooked submit() retries DRAINING
                    # rejections with backoff - here the raw reply IS
                    # the assertion target
                    sub = c.submit_raw(blob, meta={})
                    poll = c.poll("no-such-query")
                    replies[wire] = (sub["state"], sub["error"], poll)
    assert replies["threaded"] == replies["async"]
    state, error, poll = replies["async"]
    assert state == "REJECTED_OVERLOADED"
    assert error.startswith("DRAINING:")
    assert "unknown query" in poll["error"]


def test_chaos_seams_fire_on_async_path(big_dataset):
    """gateway.stream and service.admit chaos seams keep firing when
    the verbs ride the event loop; a DROP on gateway.stream aborts
    the connection but leaves the part for a resume re-FETCH."""
    blob = big_dataset(parts=2)
    with chaos.active([
        Fault("service.admit", klass="STALL", stall_s=0.01, times=1),
        Fault("gateway.stream", klass="STALL", stall_s=0.01,
              times=1),
    ]) as plan:
        with QueryService(max_concurrency=1) as svc:
            with TaskGatewayServer(service=svc, wire="async") as srv:
                with ServiceClient(*srv.address) as c:
                    st = c.submit(blob, detach=True)
                    qid = st["query_id"]
                    parts = c.fetch(qid)
                    assert len(parts) > 0
        assert plan.fired("service.admit") == 1
        assert plan.fired("gateway.stream") == 1

    with QueryService(max_concurrency=1) as svc:
        with TaskGatewayServer(service=svc, wire="async") as srv:
            with ServiceClient(*srv.address) as c:
                st = c.submit(blob, detach=True)
                qid = st["query_id"]
                clean_parts = len(c.fetch(qid))
            with chaos.active([
                Fault("gateway.stream", klass="DROP", times=1),
            ]) as plan:
                with ServiceClient(*srv.address,
                                   reconnect_attempts=0) as c:
                    with pytest.raises((ConnectionError, OSError)):
                        c.fetch(qid)
                # the dropped connection is dead; a fresh one resumes
                # and collects the full retained result
                with ServiceClient(*srv.address) as c:
                    assert len(c.fetch(qid)) == clean_parts
                assert plan.fired("gateway.stream") == 1


def test_router_stream_total_budget(big_dataset):
    """Fleet-wide relay cap: with --stream-total-bytes below one part,
    a stream parks one part (progress beats the bound) and its reader
    waits for the second (stream_total_waits > 0) instead of buffering
    past the budget; every part still arrives, and the buffered-bytes
    gauge drains back to zero. The clients read nothing until the
    relay has waited, so the wait comes about whatever the host's
    kernel buffers absorb short of the whole 6.4 MB stream."""
    from blaze_tpu.config import EngineConfig, set_config
    from blaze_tpu.router.proxy import Router, RouterServer

    # the scan's own batches, one wire part each, whatever config an
    # earlier module of this worker installed
    set_config(EngineConfig())
    blob = big_dataset()
    with QueryService(max_concurrency=2) as svc:
        with TaskGatewayServer(service=svc, wire="async") as srv:
            with ServiceClient(*srv.address) as c:
                clean_parts = len(c.fetch(
                    c.submit(blob, detach=True)["query_id"]
                ))
            assert clean_parts > 4
            router = Router(
                ["%s:%d" % srv.address],
                poll_interval_s=0.1,
                heartbeat_timeout_s=2.0,
                start=False,
                stream_window=4,
                stream_total_bytes=64 << 10,
            )
            router.registry.poll_now()
            rsrv = RouterServer(router, wire="async").start()
            try:
                with ServiceClient(*rsrv.address) as c0:
                    qids = [
                        c0.submit(blob, detach=True)["query_id"]
                        for _ in range(2)
                    ]
                peak = [0]

                def relay_waited():
                    peak[0] = max(peak[0], router._stream_buffered)
                    return router.counters["stream_total_waits"] > 0

                def stalled_fetch(qid):
                    # raw socket with a tiny receive window (set
                    # BEFORE connect) that reads nothing until the
                    # relay has had to hold a part back
                    sock = socket.socket(socket.AF_INET,
                                         socket.SOCK_STREAM)
                    sock.setsockopt(socket.SOL_SOCKET,
                                    socket.SO_RCVBUF, 16384)
                    sock.connect(rsrv.address)
                    try:
                        sock.sendall(
                            _U64.pack(_FLAG_SERVICE)
                            + ServiceClient._id_verb(
                                VERB_FETCH, qid, 120_000
                            )
                        )
                        wait_for(relay_waited, timeout=30.0)
                        got, largest = 0, 0
                        while True:
                            (ln,) = _U64.unpack(
                                _recv_exact(sock, 8)
                            )
                            if ln == 0:
                                return got, largest
                            _recv_exact(sock, ln)
                            got += 1
                            largest = max(largest, ln)
                            relay_waited()
                    finally:
                        sock.close()

                results = [None, None]
                ts = [
                    threading.Thread(
                        target=lambda i=i, q=q: results.__setitem__(
                            i, stalled_fetch(q)
                        )
                    )
                    for i, q in enumerate(qids)
                ]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join(timeout=120)
                assert router.counters["stream_total_waits"] > 0
                assert [r[0] for r in results] == [clean_parts] * 2
                # under a budget below one part, one parked part a
                # stream is all the relay may hold
                largest = max(r[1] for r in results)
                assert largest > router.stream_total_bytes
                assert peak[0] <= len(qids) * largest
                assert wait_for(
                    lambda: router._stream_buffered == 0
                )
            finally:
                rsrv.stop()
                router.close()


def test_router_fanin_exceeding_dispatch_pool_no_deadlock():
    """Cross-tier dispatch-pool regression pin: router verb handlers
    park their pool thread on downstream replica calls, so sharing ONE
    pool across tiers let N >= pool_size concurrent router clients
    starve the replicas they were waiting on (total wire deadlock when
    both tiers share a process - the bench fleet shape). Per-tier
    pools keep the router->service supply graph acyclic: a fan-in
    wider than the pool must still complete promptly."""
    from blaze_tpu.router.proxy import Router, RouterServer

    pool_width = dispatch_pool("router")._max_workers
    conc = pool_width + 8  # strictly wider than any one pool
    cb = ColumnBatch.from_pydict({"x": list(range(64))})
    blob = tiny_wire_task(cb)
    svcs = [QueryService(max_concurrency=4) for _ in range(2)]
    srvs = [
        TaskGatewayServer(service=s, wire="async").start()
        for s in svcs
    ]
    router = Router(
        ["%s:%d" % s.address for s in srvs],
        poll_interval_s=0.1,
        start=False,
    )
    router.registry.poll_now()
    rsrv = RouterServer(router, wire="async").start()
    errs: list = []
    try:
        host, port = rsrv.address

        def client():
            try:
                # short socket timeout: a recurrence of the deadlock
                # fails the test in seconds, not pytest's global
                # timeout
                with ServiceClient(host, port, timeout=30.0) as cl:
                    for _ in range(2):
                        cl.run(blob)
            except Exception as e:  # noqa: BLE001 - assert below
                errs.append(repr(e))

        ts = [threading.Thread(target=client) for _ in range(conc)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=90)
        assert not any(t.is_alive() for t in ts), "fan-in wedged"
        assert errs == []
    finally:
        rsrv.stop()
        router.close()
        for s in srvs:
            s.stop()
        for s in svcs:
            s.close()
