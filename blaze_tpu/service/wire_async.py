"""Async wire data plane: event-loop verb serving for both tiers.

PR 15's profiler showed the thread-per-connection wire tier is the
c16+ wall: ~60% of max-pressure stack samples blocked in
`transport:_recv_exact` and every open connection cost an OS thread
whether or not bytes were flowing. This module ports the shared verb
loop (service/wire.serve_verb_connection) onto one process-wide
asyncio event loop:

  * framed reads are non-blocking (`StreamReader.readexactly` on the
    selector loop) - an idle connection costs a parked coroutine, not
    a parked thread;
  * verb DISPATCH still runs on threads (a bounded executor pool):
    admission, cache lookups, and query bookkeeping are lock-shaped
    Python work that must not stall the IO loop;
  * streamed FETCH replies are drain-aware non-blocking writes - a
    slow client parks its writer coroutine against the stall budget
    (`asyncio.wait_for(writer.drain(), stall_s)`) instead of pinning
    a thread in `sendall`;
  * the router's windowed relay rides the same loop (proxy.py's
    `_raw_fetch_async`), so an open relayed stream no longer costs a
    reader thread.

Every wire semantic is preserved by construction: the verb skeleton,
error-handling ladder, session teardown, per-verb latency histograms,
accept-to-first-byte, connection gauges, PROFILE=9, and the chaos
seams all mirror service/wire.py line for line - the threaded loop
stays available (`--wire threaded` / BLAZE_WIRE=threaded) as the
differential oracle for the parity tests.

Loop ownership: ONE daemon loop thread per process ("blaze-wire-loop"),
shared by every AsyncWireServer (gateway and router tiers). Legacy
one-shot task connections (no _FLAG_SERVICE hello bit) are detected on
the loop and handed to a daemon thread: task execution is
thread-shaped work (jax dispatch, file IO) and keeps its existing
blocking path.
"""

from __future__ import annotations

import asyncio
import concurrent.futures as cf
import json
import os
import socket
import struct
import threading
import time
from functools import partial
from typing import Callable, List, Optional

from blaze_tpu.obs import trace as obs_trace
from blaze_tpu.testing import chaos

_U64 = struct.Struct("<Q")
_U32 = struct.Struct("<I")
_ERR = 0xFFFFFFFFFFFFFFFF

# ---------------------------------------------------------------------------
# process-wide loop + bounded dispatch pool
# ---------------------------------------------------------------------------

_LOOP_LOCK = threading.Lock()
_LOOP: Optional[asyncio.AbstractEventLoop] = None
_POOLS: dict = {}


def get_loop() -> asyncio.AbstractEventLoop:
    """The process-wide wire event loop, started lazily on a daemon
    thread. One selector thread serves every wire listener in the
    process (both tiers) - the data plane is IO-bound and the loop
    replaces the per-connection thread army."""
    global _LOOP
    with _LOOP_LOCK:
        if _LOOP is not None and not _LOOP.is_closed():
            return _LOOP
        loop = asyncio.new_event_loop()
        threading.Thread(
            target=loop.run_forever, daemon=True,
            name="blaze-wire-loop",
        ).start()
        _LOOP = loop
        return _LOOP


def dispatch_pool(tier: str = "service") -> cf.ThreadPoolExecutor:
    """Bounded verb-dispatch pool, ONE PER TIER: backend calls
    (submit/poll/cancel/stats/...) hold service or router locks and
    may block briefly - they run here so the IO loop never does. Sized
    to useful work, not to connection count: that is the whole point
    of the port.

    Per-tier isolation is a deadlock invariant, not a tuning knob: a
    router verb handler blocks its pool thread on a downstream replica
    call, and that replica's handler needs a pool thread to answer.
    One shared pool lets N parked router handlers starve the replicas
    they are waiting on (total wire deadlock when both tiers share a
    process, as the bench fleet does); separate pools keep the
    router->service call graph acyclic in thread-supply terms."""
    with _LOOP_LOCK:
        pool = _POOLS.get(tier)
        if pool is None:
            pool = _POOLS[tier] = cf.ThreadPoolExecutor(
                max_workers=max(4, min(32, 4 * (os.cpu_count() or 2))),
                thread_name_prefix=f"blaze-verb-dispatch-{tier}",
            )
        return pool


# ---------------------------------------------------------------------------
# async framing helpers (mirror service/wire.py's blocking ones)
# ---------------------------------------------------------------------------


async def _read_exact(reader: asyncio.StreamReader, n: int) -> bytes:
    """readexactly with the blocking tier's error contract: EOF
    mid-frame is a ConnectionError, so the shared error ladder
    (mid-verb disconnect -> session cleanup) stays byte-identical."""
    try:
        return await reader.readexactly(n)
    except asyncio.IncompleteReadError as e:
        raise ConnectionError("socket closed mid-frame") from e


async def _read_u32(reader) -> int:
    (v,) = _U32.unpack(await _read_exact(reader, _U32.size))
    return v


async def _read_str(reader) -> str:
    from blaze_tpu.service.wire import MAX_META_BYTES

    n = await _read_u32(reader)
    if n > MAX_META_BYTES:
        raise ValueError("string frame too large")
    return (await _read_exact(reader, n)).decode("utf-8")


async def _send_json(writer, obj: dict) -> None:
    data = json.dumps(obj).encode("utf-8")
    writer.write(_U32.pack(len(data)) + data)
    await writer.drain()


async def _send_err(writer, msg: str) -> None:
    data = msg.encode("utf-8")[:65536]
    writer.write(_U64.pack(_ERR) + _U32.pack(len(data)) + data)
    await writer.drain()


async def decode_submit_frame_async(reader):
    """Async twin of wire.decode_submit_frame: same bounds, same flag
    bits, manifest stays un-parsed for forwarding."""
    from blaze_tpu.runtime.gateway import (
        MAX_TASK_BYTES,
        _FLAG_MANIFEST,
        _FLAG_REF,
    )
    from blaze_tpu.service.wire import MAX_META_BYTES

    (meta_len,) = _U32.unpack(await _read_exact(reader, _U32.size))
    if meta_len > MAX_META_BYTES:
        raise ValueError("submit meta too large")
    meta = json.loads(await _read_exact(reader, meta_len) or b"{}")
    (header,) = _U64.unpack(await _read_exact(reader, _U64.size))
    is_ref = bool(header & _FLAG_REF)
    has_manifest = bool(header & _FLAG_MANIFEST)
    blob_len = header & ~(_FLAG_REF | _FLAG_MANIFEST)
    if blob_len > MAX_TASK_BYTES:
        raise ValueError("task too large")
    manifest_bytes = None
    if has_manifest:
        (mlen,) = _U32.unpack(await _read_exact(reader, _U32.size))
        if mlen > MAX_TASK_BYTES:
            raise ValueError("manifest too large")
        manifest_bytes = await _read_exact(reader, mlen)
    return meta, await _read_exact(reader, blob_len), is_ref, \
        manifest_bytes


# ---------------------------------------------------------------------------
# the verb loop, coroutine edition
# ---------------------------------------------------------------------------


async def serve_verb_connection_async(reader, writer, backend,
                                      t_accept: Optional[float] = None
                                      ) -> None:
    """Coroutine twin of wire.serve_verb_connection: same skeleton,
    same ladder, same observability surfaces. Socket reads/writes ride
    the loop; backend verb calls run on the bounded dispatch pool;
    FETCH goes through the backend's `fetch_async` (drain-aware part
    streaming)."""
    from blaze_tpu.obs.metrics import REGISTRY
    from blaze_tpu.service import wire

    loop = asyncio.get_running_loop()
    tier = getattr(backend, "tier", "service")
    pool = dispatch_pool(tier)
    with wire._CONN_LOCK:
        wire._CONNECTIONS[tier] = wire._CONNECTIONS.get(tier, 0) + 1
    REGISTRY.register_collector("wire_connections", wire._conn_samples)
    if t_accept is None:
        t_accept = time.perf_counter()
    first_verb = True
    session_qids: List[str] = []
    try:
        while True:
            try:
                verb = (await _read_exact(reader, 1))[0]
            except (ConnectionError, OSError):
                return  # clean EOF / client gone
            t0 = time.perf_counter()
            if first_verb:
                first_verb = False
                REGISTRY.observe("blaze_accept_first_byte_seconds",
                                 t0 - t_accept, tier=tier)
            try:
                if verb == wire.VERB_SUBMIT:
                    meta, blob, is_ref, manifest_bytes = (
                        await decode_submit_frame_async(reader)
                    )
                    t1 = time.perf_counter()
                    resp = await loop.run_in_executor(
                        pool, partial(backend.submit, meta, blob,
                                      is_ref, manifest_bytes)
                    )
                    t2 = time.perf_counter()
                    if not meta.get("detach") \
                            and "query_id" in resp:
                        session_qids.append(resp["query_id"])
                    await _send_json(writer, resp)
                elif verb == wire.VERB_FETCH:
                    qid = await _read_str(reader)
                    timeout_ms = await _read_u32(reader)
                    t1 = time.perf_counter()
                    await backend.fetch_async(writer, qid, timeout_ms)
                    t2 = time.perf_counter()
                elif verb in wire._ID_VERBS:
                    qid = await _read_str(reader)
                    flags = await _read_u32(reader)
                    t1 = time.perf_counter()
                    resp = await loop.run_in_executor(
                        pool, partial(wire._ID_VERBS[verb], backend,
                                      qid, flags)
                    )
                    t2 = time.perf_counter()
                    await _send_json(writer, resp)
                elif verb == wire.VERB_MEMBER:
                    payload = json.loads(
                        await _read_str(reader) or "{}"
                    )
                    t1 = time.perf_counter()
                    resp = await loop.run_in_executor(
                        pool, partial(backend.member_frame, payload)
                    )
                    t2 = time.perf_counter()
                    await _send_json(writer, resp)
                elif verb == wire.VERB_PROFILE:
                    payload = json.loads(
                        await _read_str(reader) or "{}"
                    )
                    t1 = time.perf_counter()
                    resp = await loop.run_in_executor(
                        pool, partial(backend.profile_frame, payload)
                    )
                    t2 = time.perf_counter()
                    await _send_json(writer, resp)
                elif verb == wire.VERB_MESH_EXCHANGE:
                    # fleet DCN plane: mirror of the blocking branch -
                    # drain the framed input parts BEFORE dispatch so
                    # a handler error leaves the connection in sync
                    payload = json.loads(
                        await _read_str(reader) or "{}"
                    )
                    parts: List[bytes] = []
                    while True:
                        (plen,) = _U64.unpack(
                            await _read_exact(reader, _U64.size)
                        )
                        if plen == 0:
                            break
                        if plen > wire.MAX_EXCHANGE_PART_BYTES:
                            raise ValueError(
                                "oversized exchange part"
                            )
                        parts.append(await _read_exact(reader, plen))
                    t1 = time.perf_counter()
                    resp, out_parts = await loop.run_in_executor(
                        pool, partial(backend.mesh_exchange_frame,
                                      payload, parts)
                    )
                    t2 = time.perf_counter()
                    await _send_json(writer, resp)
                    for p in out_parts:
                        writer.write(_U64.pack(len(p)) + p)
                        await writer.drain()
                    writer.write(_U64.pack(0))
                    await writer.drain()
                elif verb in wire._NOARG_VERBS:
                    await _read_u32(reader)
                    t1 = time.perf_counter()
                    resp = await loop.run_in_executor(
                        pool, partial(wire._NOARG_VERBS[verb], backend)
                    )
                    t2 = time.perf_counter()
                    await _send_json(writer, resp)
                else:
                    raise ValueError(f"unknown service verb {verb}")
                wire._observe_verb(tier, verb, t0, t1, t2,
                                   time.perf_counter())
            except (ConnectionError, BrokenPipeError, OSError):
                return  # mid-verb disconnect: session cleanup below
            except ValueError as e:
                try:
                    await _send_json(
                        writer,
                        {"error": f"protocol error: {e}"[:65536],
                         "fatal": True},
                    )
                except (ConnectionError, OSError):
                    pass
                return
            except KeyError as e:
                await _send_json(
                    writer, {"error": f"unknown query: {e}"}
                )
            except Exception as e:  # noqa: BLE001 - reported in-band
                await _send_json(
                    writer,
                    {"error": f"{type(e).__name__}: {e}"[:65536]},
                )
    finally:
        with wire._CONN_LOCK:
            wire._CONNECTIONS[tier] = max(
                0, wire._CONNECTIONS.get(tier, 1) - 1
            )
        if session_qids:
            # session teardown off the loop: router abandons do a
            # downstream RPC and service cancels take locks - neither
            # may stall the selector. Fire-and-forget keeps teardown
            # running even if this task is being cancelled.
            qids = list(session_qids)

            def _abandon_all():
                for qid in qids:
                    try:
                        backend.abandon(qid)
                    except Exception:  # noqa: BLE001 - best-effort
                        pass

            try:
                pool.submit(_abandon_all)
            except RuntimeError:
                pass  # interpreter shutdown


# ---------------------------------------------------------------------------
# service-tier async FETCH (twin of ServiceVerbBackend._fetch_*)
# ---------------------------------------------------------------------------


async def service_fetch_async(backend, writer, qid: str,
                              timeout_ms: int) -> None:
    from blaze_tpu.service import wire

    try:
        q = backend.service.get(qid)
    except KeyError:
        await _send_err(writer, f"UNKNOWN: no query {qid}")
        return
    # bit 31 of timeout_ms: the client accepts an arena handle
    arena_ok = bool(timeout_ms & wire._FETCH_ARENA)
    timeout_ms &= wire._FETCH_ARENA - 1
    q.note_activity()
    q.begin_fetch()
    try:
        if await _serve_arena_async(backend, writer, q, arena_ok):
            return
        sb = getattr(q, "stream", None)
        if sb is not None:
            await _fetch_incremental_async(
                backend, writer, q, sb, timeout_ms
            )
        else:
            await _fetch_materialized_async(
                backend, writer, q, timeout_ms
            )
    finally:
        q.end_fetch()
        q.note_activity()


async def _serve_arena_async(backend, writer, q,
                             arena_ok: bool) -> bool:
    """Coroutine twin of ServiceVerbBackend._serve_arena: zero-copy
    FETCH of a finalized result. Handle mode writes the arena escape
    frame; scatter-gather mode writes the segment's mmap-backed frame
    views straight into the transport (one drain at the end - the
    frames already carry the wire framing, so no re-encode and no
    per-part drain round trips). Returns False having sent NOTHING
    whenever the arena does not cover the query."""
    from blaze_tpu.service import wire
    from blaze_tpu.service.query import QueryState

    service = backend.service
    arena = getattr(service, "arena", None)
    if (
        arena is None or not q.done
        or q.state is not QueryState.DONE
        or q._fingerprint is None or not q._fingerprint_stable
        or not q.use_cache or q.degraded
    ):
        return False
    key = q._fingerprint
    loop = asyncio.get_running_loop()
    pool = dispatch_pool(getattr(backend, "tier", "service"))
    stream_start = time.monotonic()
    if arena_ok:
        # handle() reaps orphaned leases under the arena lock - keep
        # it off the selector like every other lock-shaped call
        handle = await loop.run_in_executor(
            pool, partial(arena.handle, key)
        )
        if handle is not None:
            data = json.dumps(handle).encode("utf-8")
            writer.write(
                _U64.pack(wire._ARENA) + _U32.pack(len(data)) + data
            )
            await writer.drain()
            q.fetched = True
            wire.ServiceVerbBackend._note_arena_stream(
                backend, q, stream_start, len(handle["offsets"]),
                mode="handle",
            )
            return True
    views = arena.buffers(key)
    if views is None:
        return False
    if chaos.ACTIVE:
        # same contract as the threaded path: the whole buffer list
        # goes out in one burst, so the seam fires once up front
        await loop.run_in_executor(
            pool, partial(chaos.fire, "gateway.stream",
                          query_id=q.query_id, partition=0),
        )
    # write() either sends immediately or copies into the transport
    # buffer before returning, so the views never outlive this call -
    # safe against a concurrent eviction unmapping the segment
    for v in views:
        writer.write(v)
    writer.write(_U64.pack(0))
    await writer.drain()
    q.fetched = True
    q.note_activity()
    wire.ServiceVerbBackend._note_arena_stream(
        backend, q, stream_start, len(views), mode="sg"
    )
    return True


async def _send_part(writer, q, seg: bytes,
                     stall_s: float = 0.0) -> None:
    """Write one part and wait for the transport to drain. The
    `frame_send` stage is the write, which is the loop thread's own
    work; other coroutines run inside the drain, so the wait only
    moves the span's end (wall) and is neither CPU time nor a name on
    the profiler's side."""
    sp = None
    if obs_trace.ACTIVE and getattr(q, "tracer", None) is not None:
        with obs_trace.span("frame_send", rec=q.tracer,
                            bytes=len(seg)) as sp:
            writer.write(seg)
    else:
        writer.write(seg)
    if stall_s > 0:
        await asyncio.wait_for(writer.drain(), stall_s)
    else:
        await writer.drain()
    if isinstance(sp, obs_trace.Span):
        sp.end_ns = time.monotonic_ns()


async def _fetch_incremental_async(backend, writer, q, sb,
                                   timeout_ms: int) -> None:
    """Stream-as-produced FETCH on the loop. Ready-part probes are
    non-blocking (`next_ready(i, 0.0)`); between parts the coroutine
    parks on an asyncio.Event fired by the ring's waker bridge
    (StreamBuffer.add_waker -> call_soon_threadsafe), with the
    probe-clear-reprobe-await pattern closing the lost-wakeup window.
    Slow clients park in `drain()` against the stall budget instead of
    a socket send timeout - same classified outcome, no thread."""
    from blaze_tpu.service import wire

    service = backend.service
    qid = q.query_id
    loop = asyncio.get_running_loop()
    deadline = (
        time.monotonic() + timeout_ms / 1000.0
        if timeout_ms else None
    )
    sb.attach()
    ev = asyncio.Event()

    def _waker():
        try:
            loop.call_soon_threadsafe(ev.set)
        except RuntimeError:
            pass  # loop torn down at interpreter exit

    sb.add_waker(_waker)
    t0 = time.perf_counter_ns()
    stream_start = time.monotonic()
    sent = 0
    live_parts = 0
    complete = False
    stall_s = getattr(service, "stream_stall_s", 0.0) or 0.0
    try:
        i = 0
        while True:
            if sent == 0 and deadline is not None:
                rem = deadline - time.monotonic()
                if rem <= 0:
                    await _send_err(
                        writer, f"{q.state.value}: fetch timed out"
                    )
                    return
            kind, payload = sb.next_ready(i, 0.0)
            if kind == "timeout":
                # nothing ready: clear, re-probe (a wake between the
                # probe and the clear must not be lost), then park on
                # the waker - bounded so deadline/abort checks and the
                # sync tier's 0.25s cadence are preserved
                ev.clear()
                kind, payload = sb.next_ready(i, 0.0)
                if kind == "timeout":
                    wait_s = 0.25
                    if sent == 0 and deadline is not None:
                        wait_s = min(
                            0.25, max(0.0, deadline - time.monotonic())
                        )
                    try:
                        await asyncio.wait_for(ev.wait(), wait_s)
                    except asyncio.TimeoutError:
                        pass
                    continue
            if kind == "part":
                if chaos.ACTIVE:
                    # chaos seam, same ordering as the threaded loop:
                    # fire BEFORE mark_consumed so a DROP leaves the
                    # part for the resume path. STALL sleeps must not
                    # block the loop -> executor
                    await loop.run_in_executor(
                        dispatch_pool(),
                        partial(chaos.fire, "gateway.stream",
                                query_id=qid, partition=i),
                    )
                if not q.done:
                    live_parts += 1
                sb.mark_consumed(i)
                try:
                    await _send_part(
                        writer, q, wire._encode_part(q, payload),
                        stall_s,
                    )
                except (asyncio.TimeoutError, TimeoutError) as e:
                    service._note_stream_event("stall")
                    raise ConnectionError(
                        f"fetch send stalled past {stall_s}s"
                    ) from e
                sent += 1
                i += 1
                q.note_activity()
                continue
            if kind == "finished":
                writer.write(_U64.pack(0))
                await writer.drain()
                complete = True
                q.fetched = True
                return
            # aborted: same contract as the threaded loop - abort the
            # connection after parts, else wait out the tiny
            # abort->terminal window and answer in-band
            if sent:
                raise ConnectionError(
                    f"fetch stream aborted: {payload}"
                )
            abort_deadline = time.monotonic() + 5.0
            while not q.wait(0) \
                    and time.monotonic() < abort_deadline:
                await asyncio.sleep(0.02)
            await _send_err(
                writer,
                f"{q.state.value}: {q.error or 'not completed'}",
            )
            return
    finally:
        sb.remove_waker(_waker)
        stream_s = (time.perf_counter_ns() - t0) / 1e9
        q.timings["stream_ns"] = (
            q.timings.get("stream_ns", 0)
            + (time.perf_counter_ns() - t0)
        )
        if complete and getattr(service, "_fold_phases", True):
            from blaze_tpu.obs import phases as obs_phases

            obs_phases.ROLLUP.observe(
                "stream", stream_s,
                klass=obs_phases.class_key(
                    q._fingerprint, q._fingerprint_stable
                ),
            )
        if obs_trace.ACTIVE \
                and getattr(q, "tracer", None) is not None:
            tags = {"parts": sent, "total": sb.total_parts(),
                    "live_parts": live_parts}
            if not complete:
                tags["aborted"] = True
            q.tracer.record_span(
                "result_stream", stream_start, time.monotonic(),
                **tags,
            )


async def _fetch_materialized_async(backend, writer, q,
                                    timeout_ms: int) -> None:
    """Legacy materialize-then-stream FETCH (stream_buffer_bytes <= 0)
    on the loop: the DONE wait is an adaptive poll (no thread parked),
    the part loop is drain-aware."""
    from blaze_tpu.service import wire
    from blaze_tpu.service.query import QueryState

    service = backend.service
    qid = q.query_id
    deadline = (
        time.monotonic() + timeout_ms / 1000.0
        if timeout_ms else None
    )
    loop = asyncio.get_running_loop()
    poll = 0.001
    while not q.wait(0):
        if deadline is not None and time.monotonic() >= deadline:
            await _send_err(
                writer, f"{q.state.value}: fetch timed out"
            )
            return
        await asyncio.sleep(poll)
        poll = min(0.05, poll * 2)
    if q.state is not QueryState.DONE:
        await _send_err(
            writer, f"{q.state.value}: {q.error or 'not completed'}"
        )
        return
    t0 = time.perf_counter_ns()
    stream_start = time.monotonic()
    sent = 0
    complete = False
    try:
        for i, rb in enumerate(q.result or ()):
            if chaos.ACTIVE:
                await loop.run_in_executor(
                    dispatch_pool(),
                    partial(chaos.fire, "gateway.stream",
                            query_id=qid, partition=i),
                )
            await _send_part(writer, q, wire._encode_part(q, rb))
            sent += 1
            q.note_activity()
        writer.write(_U64.pack(0))
        await writer.drain()
        complete = True
        q.fetched = True
    except Exception as e:
        raise ConnectionError(f"fetch stream aborted: {e!r}") from e
    finally:
        stream_s = (time.perf_counter_ns() - t0) / 1e9
        q.timings["stream_ns"] = (
            q.timings.get("stream_ns", 0)
            + (time.perf_counter_ns() - t0)
        )
        if complete and getattr(service, "_fold_phases", True):
            from blaze_tpu.obs import phases as obs_phases

            obs_phases.ROLLUP.observe(
                "stream", stream_s,
                klass=obs_phases.class_key(
                    q._fingerprint, q._fingerprint_stable
                ),
            )
        if obs_trace.ACTIVE \
                and getattr(q, "tracer", None) is not None:
            tags = {"parts": sent, "total": len(q.result or ())}
            if not complete:
                tags["aborted"] = True
            q.tracer.record_span(
                "result_stream", stream_start, time.monotonic(),
                **tags,
            )


# ---------------------------------------------------------------------------
# connection routing + the shared listener
# ---------------------------------------------------------------------------


async def _sock_recv_exact(loop, conn, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        b = await loop.sock_recv(conn, n - len(buf))
        if not b:
            raise ConnectionError("socket closed mid-frame")
        buf += b
    return buf


def _run_legacy(legacy, conn, header: int) -> None:
    try:
        legacy(conn, header)
    finally:
        try:
            conn.close()
        except OSError:
            pass


async def handle_wire_connection(
    conn,
    *,
    backend_factory: Optional[Callable[[], object]],
    legacy: Optional[Callable] = None,
    no_service_msg: bytes = b"no query service attached",
    no_legacy_msg: bytes = b"router speaks the service protocol only",
) -> None:
    """Read the hello u64 off an accepted socket and route it: the
    _FLAG_SERVICE bit enters the async verb loop against
    `backend_factory()`; a legacy header hands the (re-blocked) socket
    to `legacy(sock, header)` on a daemon thread - one-shot task
    execution is thread-shaped work. `None` for either side answers
    the documented error frame."""
    from blaze_tpu.runtime.gateway import _FLAG_SERVICE

    loop = asyncio.get_running_loop()
    t_accept = time.perf_counter()
    try:
        try:
            (header,) = _U64.unpack(
                await _sock_recv_exact(loop, conn, _U64.size)
            )
        except (ConnectionError, OSError):
            conn.close()
            return
        if header & _FLAG_SERVICE:
            backend = (
                backend_factory() if backend_factory is not None
                else None
            )
            if backend is None:
                try:
                    await loop.sock_sendall(
                        conn,
                        _U64.pack(_ERR)
                        + _U32.pack(len(no_service_msg))
                        + no_service_msg,
                    )
                except (ConnectionError, OSError):
                    pass
                conn.close()
                return
            reader, writer = await asyncio.open_connection(sock=conn)
            try:
                await serve_verb_connection_async(
                    reader, writer, backend, t_accept=t_accept
                )
            finally:
                # close only - no await here: this finally also runs
                # under GeneratorExit (task GC'd / cancelled at server
                # stop), where suspending again is illegal; the loop
                # outlives the connection and completes the close
                try:
                    writer.close()
                except Exception:  # noqa: BLE001 - teardown
                    pass
            return
        if legacy is None:
            try:
                await loop.sock_sendall(
                    conn,
                    _U64.pack(_ERR) + _U32.pack(len(no_legacy_msg))
                    + no_legacy_msg,
                )
            except (ConnectionError, OSError):
                pass
            conn.close()
            return
        conn.setblocking(True)
        threading.Thread(
            target=_run_legacy, args=(legacy, conn, header),
            daemon=True, name="blaze-legacy-task",
        ).start()
    except asyncio.CancelledError:
        try:
            conn.close()
        except OSError:
            pass
        raise
    except Exception:  # noqa: BLE001 - a bad connection dies alone
        try:
            conn.close()
        except OSError:
            pass


class AsyncWireServer:
    """Event-loop listener with the TaskGatewayServer surface: binds
    in __init__ (so `.address` answers before start), accepts on the
    process loop, one task per connection. `conn_handler` is an async
    callable taking the accepted (non-blocking) socket."""

    def __init__(self, host: str, port: int, conn_handler):
        self._handler = conn_handler
        lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        lsock.bind((host, port))
        lsock.listen(128)
        lsock.setblocking(False)
        self._lsock = lsock
        self._accept_task: Optional[asyncio.Task] = None
        self._tasks: set = set()
        self._stopped = threading.Event()
        self._started = False

    @property
    def address(self):
        return self._lsock.getsockname()

    def start(self) -> "AsyncWireServer":
        if self._started:
            return self
        self._started = True
        fut = asyncio.run_coroutine_threadsafe(self._arm(), get_loop())
        fut.result(timeout=10)
        return self

    async def _arm(self) -> None:
        self._accept_task = asyncio.get_running_loop().create_task(
            self._accept_loop()
        )

    async def _accept_loop(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            try:
                conn, _addr = await loop.sock_accept(self._lsock)
            except asyncio.CancelledError:
                return
            except OSError:
                return  # listener closed
            t = loop.create_task(self._handler(conn))
            self._tasks.add(t)
            t.add_done_callback(self._tasks.discard)

    def serve_blocking(self) -> None:
        """CLI shape: block the calling thread until shutdown(). The
        accept loop always lives on the wire loop, so (unlike the
        threaded server) this composes with start()."""
        self.start()
        self._stopped.wait()

    def shutdown(self) -> None:
        """Stop accepting without closing the listener (the drain
        path); live connection tasks keep serving until EOF, matching
        the threaded tier's daemon threads."""
        if self._started and self._accept_task is not None:
            async def _cancel():
                self._accept_task.cancel()
                try:
                    await self._accept_task
                except (asyncio.CancelledError, Exception):  # noqa: BLE001
                    pass

            try:
                asyncio.run_coroutine_threadsafe(
                    _cancel(), get_loop()
                ).result(timeout=5)
            except Exception:  # noqa: BLE001 - teardown best-effort
                pass
        self._stopped.set()

    def stop(self) -> None:
        self.shutdown()
        # reap live connection tasks: a clean CancelledError now beats
        # a pending task garbage-collected later (whose coroutine gets
        # closed at an arbitrary suspension point)
        tasks = list(self._tasks)
        if tasks:
            async def _reap():
                for t in tasks:
                    t.cancel()
                await asyncio.gather(*tasks, return_exceptions=True)

            try:
                asyncio.run_coroutine_threadsafe(
                    _reap(), get_loop()
                ).result(timeout=5)
            except Exception:  # noqa: BLE001 - teardown best-effort
                pass
        try:
            self._lsock.close()
        except OSError:
            pass
