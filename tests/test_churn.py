"""Fleet churn tests (ISSUE 9 acceptance): rolling restarts must be
client-invisible.

Two tiers:
  * in-process (tier-1): a 2-replica fleet behind one Router; each
    replica is drained (finish in-flight, DRAINING-reject new work),
    LEAVEs, and a replacement JOINs - all while a repeated-query mix
    runs through the router. Zero client-visible failures.
  * subprocess e2e (slow; `python -m pytest tests/test_churn.py -m
    slow`): three `serve`
    processes that JOIN a bootstrap-empty `route` CLI, SIGTERM-drained
    and respawned in turn under a live query mix - zero failures,
    drained replicas rejoin via JOIN - then the affinity home of a hot
    fingerprint is SIGKILLed and its repeat is served WARM
    (0 dispatches) from the survivor holding the replicated result.
"""

import json
import os
import re
import socket
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from blaze_tpu.router import Router, RouterServer
from blaze_tpu.runtime.gateway import TaskGatewayServer
from blaze_tpu.service import QueryService, ServiceClient
from tests.test_router import Fleet, _reap, _spawn, wait_done
from tests.test_service import wait_for

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TERMINAL_BAD = ("FAILED", "CANCELLED", "TIMED_OUT",
                "REJECTED_OVERLOADED")


@pytest.fixture
def dataset(tmp_path):
    rng = np.random.default_rng(9)
    p = str(tmp_path / "churn.parquet")
    pq.write_table(
        pa.table(
            {
                "k": pa.array(rng.integers(0, 25, 5000), pa.int32()),
                "v": pa.array(rng.random(5000), pa.float64()),
            }
        ),
        p,
    )

    def blob(threshold=0.5):
        from blaze_tpu.exprs import AggExpr, AggFn, Col
        from blaze_tpu.ops import (
            AggMode,
            FilterExec,
            HashAggregateExec,
        )
        from blaze_tpu.ops.parquet_scan import (
            FileRange,
            ParquetScanExec,
        )
        from blaze_tpu.plan.serde import task_to_proto

        plan = HashAggregateExec(
            FilterExec(
                ParquetScanExec([[FileRange(p)]]),
                Col("v") > threshold,
            ),
            keys=[(Col("k"), "k")],
            aggs=[(AggExpr(AggFn.SUM, Col("v")), "s")],
            mode=AggMode.COMPLETE,
        )
        return task_to_proto(plan, 0)

    return blob


def scan_blob(tmp_path, rows=120_000, name="stream.parquet"):
    """Multi-part streaming payload: a plain filter-scan over enough
    rows that the default batch size yields many result parts - the
    churn rounds need a stream that is genuinely OPEN for a while."""
    from blaze_tpu.exprs import Col
    from blaze_tpu.ops import FilterExec
    from blaze_tpu.ops.parquet_scan import FileRange, ParquetScanExec
    from blaze_tpu.plan.serde import task_to_proto

    rng = np.random.default_rng(31)
    p = str(tmp_path / name)
    pq.write_table(
        pa.table({
            "k": pa.array(rng.integers(0, 100, rows), pa.int32()),
            "v": pa.array(rng.random(rows), pa.float64()),
        }),
        p,
    )
    plan = FilterExec(
        ParquetScanExec([[FileRange(p)]]), Col("v") >= 0.0
    )
    return task_to_proto(plan, 0), rows


def test_inprocess_drain_during_open_stream_is_client_invisible(
    tmp_path,
):
    """ISSUE 14 drain integration: SIGTERM-style drain of the replica
    that is actively streaming a multi-part result holds for the open
    stream - the client reads every part, the table is complete, and
    the drain then finishes cleanly. Zero client-visible failures."""
    blob, rows = scan_blob(tmp_path)
    with Fleet() as fl:
        fl.router.registry.start()
        with RouterServer(fl.router) as rs:
            with ServiceClient(*rs.address, timeout=60.0) as c:
                st = c.submit(blob)
                qid = st["query_id"]
                owner = fl.router.get(qid).replica_id
                svc = fl.by_id[owner][0]
                parts = []
                drained = []
                td = None
                for rb in c.fetch_stream(qid):
                    parts.append(rb)
                    if td is None:
                        # first part in hand: drain the replica NOW,
                        # mid-stream
                        td = threading.Thread(
                            target=lambda: drained.append(
                                svc.drain(timeout_s=60)
                            )
                        )
                        td.start()
                    time.sleep(0.02)  # keep the stream open a while
                td.join(60)
                assert drained == [True]
                assert len(parts) > 1
                assert sum(rb.num_rows for rb in parts) == rows


def test_inprocess_rolling_drain_is_client_invisible(dataset):
    """Drain each replica in turn (drain -> LEAVE -> a replacement
    JOINs) while a repeated-query mix runs through the router: every
    query completes DONE - drains spill, departures re-point affinity,
    nothing surfaces to the client."""
    blobs = [dataset(), dataset(0.3)]
    extra = []  # replacement (svc, srv) pairs to tear down
    with Fleet() as fl:
        fl.router.registry.start()
        failures = []
        completed = [0]
        stop = threading.Event()

        def mix():
            while not stop.is_set():
                for b in blobs:
                    try:
                        st = fl.router.submit({"use_cache": True}, b)
                        if st.get("state") in TERMINAL_BAD:
                            failures.append(("submit", st))
                            continue
                        p = wait_done(fl.router, st["query_id"])
                        if p["state"] != "DONE":
                            failures.append(("poll", p))
                        else:
                            completed[0] += 1
                    except Exception as e:  # noqa: BLE001 - the point
                        failures.append(("raise", repr(e)))
                time.sleep(0.01)

        t = threading.Thread(target=mix, daemon=True)
        t.start()
        try:
            assert wait_for(lambda: completed[0] >= 4, timeout=60)
            for spec in list(fl.specs):
                svc = fl.by_id[spec][0]
                # SIGTERM analog: drain (in-flight finishes, new work
                # DRAINING-rejected), then LEAVE when empty
                assert svc.drain(timeout_s=60)
                host, _, port = spec.rpartition(":")
                fl.router.membership({
                    "op": "leave", "host": host, "port": int(port),
                })
                # the replacement JOINs (fresh process analog)
                nsvc = QueryService(max_concurrency=2)
                nsrv = TaskGatewayServer(service=nsvc).start()
                extra.append((nsvc, nsrv))
                fl.router.membership({
                    "op": "join", "host": nsrv.address[0],
                    "port": nsrv.address[1],
                })
                fl.by_id["%s:%d" % nsrv.address] = (nsvc, nsrv)
                base = completed[0]
                assert wait_for(
                    lambda: completed[0] >= base + 2, timeout=60
                )
            assert failures == [], failures[:5]
            assert completed[0] >= 8
            # both drained replicas are gone, both replacements alive
            stats = fl.router.stats()
            assert stats["fleet"]["departed"] == 2
            assert stats["fleet"]["alive"] >= 2
        finally:
            stop.set()
            t.join(timeout=30)
            for svc, srv in extra:
                try:
                    srv.stop()
                except OSError:
                    pass
                svc.close()


def test_inprocess_router_restart_rounds_under_live_mix(
    dataset, tmp_path
):
    """ISSUE 11 churn rounds: restart the ROUTER itself - once
    drain-style (clean close, journal fsynced) and once kill-style
    (the old router simply abandoned mid-everything) - while a
    repeated-query mix runs through the wire tier on a fixed port.
    The journal + ServiceClient's reconnect-with-backoff make both
    restarts client-invisible: zero failures in the mix."""
    blobs = [dataset(), dataset(0.3)]
    jp = str(tmp_path / "router.journal")
    with Fleet() as fl:

        def mk_router():
            return Router(
                fl.specs,
                poll_interval_s=0.1,
                heartbeat_timeout_s=1.0,
                resubmit_backoff_s=0.01,
                journal_path=jp,
                recover_timeout_s=15.0,
            )

        r = mk_router()
        srv = RouterServer(r).start()
        host, port = srv.address
        failures = []
        completed = [0]
        stop = threading.Event()

        def mix():
            with ServiceClient(host, port, timeout=60.0,
                               reconnect_attempts=8) as c:
                while not stop.is_set():
                    for b in blobs:
                        try:
                            st = c.submit(b)
                            if st.get("state") in TERMINAL_BAD:
                                failures.append(("submit", st))
                                continue
                            deadline = time.monotonic() + 60
                            while True:
                                p = c.poll(st["query_id"])
                                if p.get("state") == "DONE":
                                    completed[0] += 1
                                    break
                                if p.get("state") in TERMINAL_BAD \
                                        or "error" in p:
                                    failures.append(("poll", p))
                                    break
                                if time.monotonic() > deadline:
                                    failures.append(("stuck", p))
                                    break
                                time.sleep(0.02)
                        except Exception as e:  # noqa: BLE001
                            failures.append(("raise", repr(e)))
                    time.sleep(0.01)

        t = threading.Thread(target=mix, daemon=True)
        t.start()
        abandoned = []
        try:
            assert wait_for(lambda: completed[0] >= 2, timeout=60)
            # round 1: drain-style restart - close() fsyncs the
            # journal and stops every thread before the successor
            # binds the same port
            srv.stop()
            r.close()
            r = mk_router()
            srv = RouterServer(r, host, port).start()
            base = completed[0]
            assert wait_for(
                lambda: completed[0] >= base + 2, timeout=60
            )
            # round 2: kill-style restart - the old router is
            # ABANDONED (no close, no drain, no final fsync), exactly
            # what SIGKILL leaves behind
            srv.stop()
            abandoned.append(r)
            r = mk_router()
            srv = RouterServer(r, host, port).start()
            base = completed[0]
            assert wait_for(
                lambda: completed[0] >= base + 2, timeout=60
            )
            assert failures == [], failures[:5]
        finally:
            stop.set()
            t.join(timeout=30)
            try:
                srv.stop()
            except OSError:
                pass
            r.close()
            for old in abandoned:
                old.close()


# ---------------------------------------------------------------------------
# subprocess e2e acceptance
# ---------------------------------------------------------------------------


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _stats(client: ServiceClient) -> dict:
    try:
        return client.stats()
    except Exception:  # noqa: BLE001 - transient poll during churn
        return {}


@pytest.mark.slow
def test_e2e_rolling_restart_and_hot_kill_acceptance(
    dataset, tmp_path
):
    """ISSUE 9 acceptance, end to end: SIGTERM-drain each of 3 serve
    replicas in turn while a repeated-query mix runs through the
    route CLI - zero client-visible failures, drained replicas rejoin
    via JOIN - then SIGKILL the affinity home of a hot fingerprint
    and assert its repeat serves warm (0 dispatches) from the
    survivor holding the replicated result.

    ISSUE 14 grows the rolling leg a mid-stream round: each SIGTERM
    lands while a slow consumer is reading a multi-part stream
    through the router - the drain holds for the open stream (or the
    journal/failover resume re-places it) and the stream completes
    byte-complete, zero client-visible failures."""
    rproc, rhost, rport = _spawn(
        ["route", "--port", "0",
         "--poll-interval", "0.1", "--heartbeat-timeout", "0.8",
         "--quarantine", "60", "--breaker-threshold", "2",
         "--replicate-interval", "0.3"],
    )
    procs = [rproc]
    serves = {}

    def spawn_serve(port):
        proc, _, _ = _spawn(
            ["serve", "--port", str(port),
             "--max-concurrency", "2",
             "--router", f"{rhost}:{rport}",
             "--drain-grace", "60"],
        )
        procs.append(proc)
        serves[port] = proc
        return proc

    try:
        ports = [_free_port() for _ in range(3)]
        for p in ports:
            spawn_serve(p)
        with ServiceClient(rhost, rport, timeout=300.0) as c:
            assert wait_for(
                lambda: _stats(c).get("fleet", {}).get("alive") == 3,
                timeout=120,
            )
            blobs = [dataset(), dataset(0.3)]
            failures = []
            completed = [0]
            stop = threading.Event()

            def mix():
                with ServiceClient(rhost, rport,
                                   timeout=300.0) as mc:
                    while not stop.is_set():
                        for b in blobs:
                            try:
                                st = mc.submit(b)
                                if st.get("state") in TERMINAL_BAD:
                                    failures.append(("submit", st))
                                    continue
                                batches = mc.fetch(st["query_id"])
                                if not batches:
                                    failures.append(("empty", st))
                                else:
                                    completed[0] += 1
                            except Exception as e:  # noqa: BLE001
                                failures.append(("raise", repr(e)))
                        time.sleep(0.02)

            t = threading.Thread(target=mix, daemon=True)
            t.start()
            # warm-up: every blob executed at least twice fleet-wide
            assert wait_for(lambda: completed[0] >= 4, timeout=120)
            sblob, srows = scan_blob(tmp_path, rows=200_000)
            # --- rolling restart leg ------------------------------
            for port in ports:
                # mid-stream round: open a slow multi-part stream
                # through the router, then SIGTERM while it is live
                stream_err = []
                stream_rows = [0]
                stream_open = threading.Event()

                def slow_stream():
                    try:
                        with ServiceClient(rhost, rport,
                                           timeout=300.0,
                                           reconnect_attempts=8
                                           ) as sc:
                            sst = sc.submit(sblob)
                            for rb in sc.fetch_stream(
                                sst["query_id"]
                            ):
                                stream_rows[0] += rb.num_rows
                                stream_open.set()
                                time.sleep(0.05)
                    except Exception as e:  # noqa: BLE001 - the pin
                        stream_err.append(repr(e))

                ts = threading.Thread(target=slow_stream,
                                      daemon=True)
                ts.start()
                assert stream_open.wait(120)
                old = serves[port]
                old.terminate()  # SIGTERM -> drain -> LEAVE -> exit
                ts.join(timeout=240)
                assert not ts.is_alive()
                assert stream_err == [], stream_err
                assert stream_rows[0] == srows
                old.wait(timeout=120)
                assert wait_for(
                    lambda: _stats(c).get("fleet", {})
                    .get("alive") == 2,
                    timeout=60,
                )
                spawn_serve(port)  # rejoins via JOIN
                assert wait_for(
                    lambda: _stats(c).get("fleet", {})
                    .get("alive") == 3,
                    timeout=120,
                )
                base = completed[0]
                assert wait_for(
                    lambda: completed[0] >= base + 2, timeout=120
                )
            stop.set()
            t.join(timeout=60)
            assert failures == [], failures[:5]
            stats = _stats(c)
            assert stats["fleet"]["alive"] == 3
            # drained replicas LEFT cleanly and rejoined via JOIN:
            # each restart is one `leave` + one `rejoin` on the
            # membership counter (a rejoining replica is popped back
            # OUT of the departed ring, so the counter is the record)
            metrics = c.metrics()
            m = re.search(
                r'blaze_router_membership_events\{kind="leave"\} '
                r"(\d+)", metrics)
            assert m and int(m.group(1)) >= 3, m
            m = re.search(
                r'blaze_router_membership_events\{kind="rejoin"\} '
                r"(\d+)", metrics)
            assert m and int(m.group(1)) >= 3, m
            # --- hot-kill leg -------------------------------------
            # make blob1 unambiguously hot and learn its fingerprint
            st = c.submit(blobs[0])
            assert c.fetch(st["query_id"])
            p = c.poll(st["query_id"])
            fp, victim = p.get("fingerprint"), p["replica"]
            assert fp
            # FULL fingerprint match: content fingerprints share long
            # op-name prefixes, so a truncated check would be
            # satisfied by the OTHER blob's replication
            assert wait_for(
                lambda: fp in _stats(c).get("hot", {})
                .get("replicated_fps", []),
                timeout=60,
            )
            promoted_before = _stats(c)["hot"]["promoted"]
            victim_port = int(victim.rsplit(":", 1)[1])
            serves[victim_port].kill()  # SIGKILL the affinity home
            assert wait_for(
                lambda: _stats(c).get("fleet", {})
                .get("alive") == 2,
                timeout=60,
            )
            assert wait_for(
                lambda: _stats(c).get("hot", {}).get("promoted", 0)
                > promoted_before,
                timeout=30,
            )
            # THE acceptance pin: the FIRST repeat after the kill is
            # served warm from the survivor's replicated result
            st2 = c.submit(blobs[0])
            assert c.fetch(st2["query_id"])
            p2 = c.poll(st2["query_id"])
            assert p2["state"] == "DONE"
            assert p2["replica"] != victim
            assert p2["dispatches"] == 0, p2
            assert p2["cache_hits"] == 1
    finally:
        for proc in procs:
            _reap(proc)


@pytest.mark.slow
def test_e2e_router_sigkill_restart_recovers_with_zero_reexecutions(
    dataset, tmp_path
):
    """ISSUE 11 acceptance, end to end: SIGKILL the `route` CLI
    mid-query (the replica's detached run keeps executing), restart
    it on the SAME port with the SAME --journal, and the unchanged
    ServiceClient - reconnect-with-backoff + re-attach by query_id -
    FETCHes the full result. Zero re-executions: the replica's
    admission `submitted` counter is flat across the router's death,
    and the reconcile outcome is visible on
    `blaze_router_recovered_total{outcome}`."""
    jp = str(tmp_path / "router.journal")
    rport = _free_port()
    sport = _free_port()

    def spawn_router():
        proc, rhost_, rport_ = _spawn(
            ["route", "--port", str(rport),
             "--poll-interval", "0.1",
             "--heartbeat-timeout", "0.8",
             "--quarantine", "60",
             "--journal", jp,
             "--recover-timeout", "60"],
        )
        assert rport_ == rport
        return proc, rhost_

    rproc, rhost = spawn_router()
    procs = [rproc]
    # the replica STALLs its FIRST execution for 8s: the window the
    # router is killed and restarted inside
    sproc, shost, _ = _spawn(
        ["serve", "--port", str(sport),
         "--max-concurrency", "2",
         "--router", f"{rhost}:{rport}"],
        env_extra={"BLAZE_CHAOS": json.dumps({
            "seed": 1,
            "faults": [{"site": "task.execute", "klass": "STALL",
                        "stall_s": 8.0, "times": 1}],
        })},
    )
    procs.append(sproc)
    try:
        blob = dataset()
        with ServiceClient(rhost, rport, timeout=120.0,
                           reconnect_attempts=8) as c, \
                ServiceClient(shost, sport, timeout=60.0) as rc:
            assert wait_for(
                lambda: _stats(c).get("fleet", {}).get("alive") == 1,
                timeout=120,
            )
            st = c.submit(blob)
            qid = st["query_id"]
            assert st.get("state") not in TERMINAL_BAD
            # mid-query: placed downstream and RUNNING (stalled)
            assert wait_for(
                lambda: c.poll(qid).get("state") == "RUNNING",
                timeout=60,
            )
            submitted_before = (
                rc.stats()["admission"]["submitted"]
            )
            assert submitted_before >= 1
            rproc.kill()  # SIGKILL: no drain, no fsync, no goodbye
            rproc.wait(timeout=30)
            rproc2, _ = spawn_router()
            procs.append(rproc2)
            # the UNCHANGED client rides through: reconnect, re-attach
            # by query_id, poll to DONE (the replica re-JOINs within
            # one announcer tick; reconcile re-adopts the run)
            deadline = time.monotonic() + 120
            state = None
            while time.monotonic() < deadline:
                p = c.poll(qid)
                state = p.get("state")
                assert state not in TERMINAL_BAD, p
                assert "error" not in p, p
                if state == "DONE":
                    break
                time.sleep(0.1)
            assert state == "DONE"
            batches = c.fetch(qid)
            rows = sum(rb.num_rows for rb in batches)
            assert rows > 0
            # THE pin: zero re-executions - the replica saw exactly
            # one submit for this query across the router's death
            assert rc.stats()["admission"]["submitted"] \
                == submitted_before
            # reconcile outcome on the metrics surface
            metrics = c.metrics()
            m = re.search(
                r'blaze_router_recovered_total\{outcome='
                r'"(adopted_running|adopted_done)"\} (\d+)',
                metrics,
            )
            assert m and int(m.group(2)) >= 1, metrics[:2000]
            # integrity: a post-restart repeat (served from the
            # replica's result cache) returns the same result
            st2 = c.submit(blob)
            rows2 = sum(
                rb.num_rows for rb in c.fetch(st2["query_id"])
            )
            assert rows2 == rows
    finally:
        for proc in procs:
            _reap(proc)
