"""CLI entry points: `python -m blaze_tpu <command>`.

  run-task FILE   execute a serialized TaskDefinition protobuf and print
                  the resulting Arrow batches (the embedder-facing boundary,
                  reference callNative)
  query SQL-ish   tiny demo runner: scan a parquet file with filter/limit
  info            engine / device / native-runtime status
  gateway         legacy one-shot task gateway (one task per connection)
  serve           multi-query serving tier: the gateway listener with a
                  QueryService attached (admission control, priorities,
                  deadlines, cancellation, plan-fingerprint result cache,
                  query-lifecycle tracing)
  trace QUERY_ID  export one query's span tree from a running server as
                  Chrome-trace-event JSON (load in ui.perfetto.dev or
                  chrome://tracing)
  metrics         print the server's Prometheus text exposition
                  (dispatch.*, admission, cache, query counters)
  route           replica router: front N `serve` instances behind one
                  service endpoint (fingerprint-affinity placement,
                  headroom-aware load balancing, class-aware failover,
                  elastic JOIN/LEAVE membership + hot-result
                  replication; blaze_tpu/router/, docs/ROUTER.md)
  mesh-dryrun     versioned multichip artifact generator: run the full
                  distributed query step on an n-device virtual CPU
                  mesh and emit the MULTICHIP_r*.json shape
                  ({n_devices, rc, ok, skipped, tail})
  profile         contention profiler (obs/contention.py + sampler.py):
                  drive the serving workload at increasing concurrency
                  with lock-wait accounting + the stack sampler hot,
                  and emit one JSON report attributing where the c16
                  collapse goes (top blocking locks with wait:hold
                  ratios, top sampled stacks per thread role, per-verb
                  wire latencies) - in-process by default, or against
                  a live serve/route via --host/--port and the
                  PROFILE verb
  regress         per-phase regression check (obs/phases.py): run the
                  fixed probe workload and diff its per-phase p50s
                  against a checked-in baseline (--against), emit a
                  fresh baseline (--emit-baseline), or diff the phase
                  rollups of two BENCH_r*.json rounds (--bench A B).
                  Exits nonzero on per-phase p50 creep beyond the
                  noise band - a decode regression hiding under a
                  flat e2e median fails here, not in production
"""

from __future__ import annotations

import argparse
import json
import sys


def cmd_info(args) -> int:
    import jax

    jax.config.update("jax_enable_x64", True)
    from blaze_tpu.runtime import native

    from blaze_tpu.config import get_config, resolve_core_choice

    lib = native.get_lib()
    cfg = get_config()
    info = {
        "version": __import__("blaze_tpu").__version__,
        "backend": jax.default_backend(),
        "devices": [str(d) for d in jax.devices()],
        "native_host_lib": bool(lib),
        "x64": bool(jax.config.jax_enable_x64),
        # what `auto` resolves to on this backend (config.py)
        "cores": {
            "group": resolve_core_choice(
                "BLAZE_GROUP_CORE", cfg.group_core),
            "join": resolve_core_choice("BLAZE_JOIN_CORE", cfg.join_core,
                                        chip="direct"),
            "sort": resolve_core_choice("BLAZE_SORT_CORE", cfg.sort_core),
        },
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
    }
    print(json.dumps(info, indent=2))
    return 0


def cmd_run_task(args) -> int:
    from blaze_tpu.ops.base import ExecContext, MetricNode
    from blaze_tpu.runtime.executor import decode_task, execute_partition
    from blaze_tpu.runtime.instrument import instrument, render_metrics

    with open(args.file, "rb") as f:
        blob = f.read()
    ctx = ExecContext()
    total = 0
    # ONE production decode path; --metrics only adds the mirrored
    # metric tree (the reference's Spark-UI panel, metrics.rs:32-56)
    op, partition = decode_task(blob, ctx)
    root = MetricNode("root")
    if args.metrics:
        op = instrument(op, root)
    for rb in execute_partition(op, partition, ctx):
        total += rb.num_rows
        if not args.quiet:
            print(rb.to_pandas().to_string(max_rows=20))
    if args.metrics:
        print(render_metrics(root), file=sys.stderr)
    # metrics push after stream end (reference metrics.rs:32-56)
    print(f"-- {total} rows", file=sys.stderr)
    print(json.dumps(ctx.metrics.flatten()), file=sys.stderr)
    return 0


def cmd_scan(args) -> int:
    from blaze_tpu.exprs import Col
    from blaze_tpu.ops import LimitExec
    from blaze_tpu.ops.parquet_scan import FileRange, ParquetScanExec
    from blaze_tpu.runtime.executor import run_plan

    plan = ParquetScanExec(
        [[FileRange(args.file)]],
        projection=args.columns.split(",") if args.columns else None,
    )
    op = LimitExec(plan, args.limit) if args.limit else plan
    tbl = run_plan(op)
    print(tbl.to_pandas().to_string(max_rows=args.limit or 50))
    return 0


def cmd_gateway(args) -> int:
    from blaze_tpu.runtime.gateway import serve_forever

    serve_forever(args.host, args.port)
    return 0


def cmd_serve(args) -> int:
    import signal
    import threading
    import time

    from blaze_tpu.runtime.gateway import TaskGatewayServer
    from blaze_tpu.service import QueryService, ResultCache

    cache = None
    if not args.no_cache:
        cache = ResultCache(
            max_bytes=args.cache_bytes, ttl_s=args.cache_ttl
        )
    service = QueryService(
        max_concurrency=args.max_concurrency,
        max_queue_depth=args.max_queue_depth,
        cache=cache,
        enable_cache=not args.no_cache,
        default_deadline_s=args.deadline or None,
        enable_trace=not args.no_trace,
        slow_query_s=args.slow_query_s,
        mesh_mode=("on" if args.mesh else args.mesh_mode),
        orphan_ttl_s=args.orphan_ttl,
        stream_buffer_bytes=args.stream_buffer_bytes,
        stream_stall_s=args.stream_stall_s,
        plan_cache_entries=args.plan_cache_entries,
        arena_bytes=(0 if args.no_arena else args.arena_bytes),
        arena_dir=args.arena_dir,
        tenant_config=(
            json.loads(args.tenant_config)
            if args.tenant_config else None
        ),
        fleet_peers=(args.fleet_peer or None),
        fleet_router=(args.fleet_router or args.router),
        fleet_devices=args.fleet_devices,
    )
    if args.profile_hz > 0:
        # whole-lifetime profiling: contention accounting + stack
        # sampler armed for the process (the PROFILE verb can also
        # arm a running tier without this flag)
        from blaze_tpu.obs import contention, sampler

        contention.enable()
        sampler.start(hz=args.profile_hz)
    # serve_blocking (NOT start()): the main thread is the only
    # accept loop - see TaskGatewayServer.serve_blocking
    srv = TaskGatewayServer(
        args.host, args.port, service=service, wire=args.wire
    )
    print(f"blaze_tpu gateway listening on {srv.address}", flush=True)
    announcer = None
    if args.router:
        # elastic membership (docs/ROUTER.md): JOIN the router now and
        # re-announce periodically, so a restarted router re-learns
        # this replica without anyone editing a --replica list
        from blaze_tpu.router.membership import (
            MembershipAnnouncer,
            parse_advertise,
        )

        adv_devices = args.fleet_devices
        if adv_devices is None:
            try:
                import jax

                adv_devices = jax.local_device_count()
            except Exception:  # noqa: BLE001 - advertise the floor
                adv_devices = None
        announcer = MembershipAnnouncer(
            args.router,
            parse_advertise(args.advertise, srv.address),
            devices=adv_devices,
        ).start()
    draining = threading.Event()

    def _drain_and_exit() -> None:
        # the listener stays up through the drain: in-flight queries
        # finish and their results stay FETCHable; only new SUBMITs
        # are refused (classified DRAINING rejection)
        print("SIGTERM: draining (refusing new submits)", flush=True)
        service.drain(timeout_s=args.drain_grace or None)
        # short linger: a router that saw the last query finish still
        # needs a beat to FETCH the result before the listener dies
        time.sleep(0.25)
        if announcer is not None:
            announcer.leave()
            announcer.close()
        print("drained; leaving", flush=True)
        srv.shutdown()

    def _on_sigterm(signum, frame) -> None:
        if not draining.is_set():
            draining.set()
            threading.Thread(
                target=_drain_and_exit, daemon=True,
                name="blaze-serve-drain",
            ).start()

    signal.signal(signal.SIGTERM, _on_sigterm)
    try:
        srv.serve_blocking()
    except KeyboardInterrupt:
        pass
    finally:
        try:
            srv.stop()
        except OSError:
            pass
        if announcer is not None:
            announcer.close()
        service.close()
    return 0


def cmd_trace(args) -> int:
    """Fetch one query's trace over the REPORT verb and write the
    Perfetto-loadable Chrome-trace-event JSON."""
    from blaze_tpu.obs.trace import validate_chrome
    from blaze_tpu.service.wire import ServiceClient

    with ServiceClient(args.host, args.port) as c:
        data = c.report_full(args.query_id)
    if data.get("error"):
        # in-band server error (unknown query id, protocol problem):
        # surface the real cause, not a tracing diagnosis
        print(data["error"], file=sys.stderr)
        return 1
    doc = data.get("trace")
    if not doc:
        print(
            f"no trace recorded for {args.query_id} "
            "(server tracing disabled, or query evicted)",
            file=sys.stderr,
        )
        return 1
    problems = validate_chrome(doc)
    if args.out == "-":
        json.dump(doc, sys.stdout)
        print()
    else:
        out = args.out or f"{args.query_id}.trace.json"
        with open(out, "w") as f:
            json.dump(doc, f)
        print(
            f"{out}: {len(doc['traceEvents'])} events"
            + (f" ({len(problems)} schema problems)" if problems
               else " (valid)")
            + " - load in ui.perfetto.dev or chrome://tracing",
            file=sys.stderr,
        )
    return 0 if not problems else 2


def cmd_metrics(args) -> int:
    from blaze_tpu.service.wire import ServiceClient

    with ServiceClient(args.host, args.port) as c:
        sys.stdout.write(c.metrics())
    return 0


def cmd_route(args) -> int:
    from blaze_tpu.router.proxy import route_forever

    if args.profile_hz > 0:
        from blaze_tpu.obs import contention, sampler

        contention.enable()
        sampler.start(hz=args.profile_hz)

    # --replica is only a BOOTSTRAP hint since the JOIN/LEAVE
    # protocol landed: an empty router waits for replicas to announce
    # themselves (serve --router HOST:PORT)
    if not args.replica:
        print("route: no --replica bootstrap hints; waiting for "
              "replicas to JOIN (serve --router ...)",
              file=sys.stderr)
    route_forever(
        args.host,
        args.port,
        args.replica,
        placement=args.placement,
        poll_interval_s=args.poll_interval,
        heartbeat_timeout_s=args.heartbeat_timeout,
        quarantine_s=args.quarantine,
        breaker_threshold=args.breaker_threshold,
        max_resubmits=args.max_resubmits,
        enable_trace=not args.no_trace,
        conn_pool_size=args.conn_pool,
        replicate_hot_k=args.replicate_hot,
        replicate_interval_s=args.replicate_interval,
        journal_path=args.journal,
        recover_timeout_s=args.recover_timeout,
        stream_window=args.stream_window,
        stream_stall_s=args.stream_stall_s,
        stream_total_bytes=args.stream_total_bytes,
        tenant_rate=args.tenant_rate,
        tenant_burst=args.tenant_burst,
        tenant_retry_budget=args.tenant_retry_budget,
        tenant_retry_window_s=args.tenant_retry_window,
        tenant_config=(
            json.loads(args.tenant_config)
            if args.tenant_config else None
        ),
        wire=args.wire,
    )
    return 0


def cmd_mesh_dryrun(args) -> int:
    """Versioned generator for the MULTICHIP_r*.json artifact shape:
    compile + run the full distributed query step (group-by all_to_all
    exchange, broadcast join, slack repartition + skew retry, decoded-
    TaskDefinition differential) on an n-device virtual CPU mesh in a
    FRESH subprocess (the platform choice freezes at first backend
    init), and emit {n_devices, rc, ok, skipped, tail} JSON. Skips
    cleanly (skipped=true, rc 0) when the repo-root driver entry is
    not importable."""
    import os
    import subprocess

    n = args.devices
    root = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )
    doc = {"n_devices": n, "rc": 0, "ok": False, "skipped": False,
           "tail": ""}

    def emit() -> int:
        text = json.dumps(doc, indent=2)
        if args.out and args.out != "-":
            with open(args.out, "w") as f:
                f.write(text + "\n")
            print(f"wrote {args.out}", file=sys.stderr)
        else:
            print(text)
        return 0 if (doc["ok"] or doc["skipped"]) else 1

    if not os.path.exists(os.path.join(root, "__graft_entry__.py")):
        doc.update(skipped=True,
                   tail="__graft_entry__.py not found at repo root\n")
        return emit()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    try:
        p = subprocess.run(
            [sys.executable, "-c",
             "import __graft_entry__; "
             f"__graft_entry__.dryrun_multichip({n})"],
            cwd=root, env=env, capture_output=True, text=True,
            timeout=args.timeout,
        )
        tail_lines = (
            (p.stdout or "") + (p.stderr or "")
        ).splitlines()[-20:]
        doc.update(
            rc=p.returncode, ok=p.returncode == 0,
            tail="\n".join(tail_lines) + "\n",
        )
    except subprocess.TimeoutExpired:
        doc.update(rc=124, ok=False,
                   tail=f"mesh dryrun timed out after "
                        f"{args.timeout:.0f}s\n")
    return emit()


def cmd_mesh_attr(args) -> int:
    """Mesh stage anatomy driver (ISSUE 19 / ROADMAP item 2): run the
    `mesh_groupby` shape at 1 device and at --devices in FRESH
    subprocesses (the virtual device count freezes at first backend
    init), collect each side's per-sub-phase rollup via
    obs/meshprof.run_attr_probe, and emit the versioned
    MESHATTR_r*.json artifact: per-sub-phase p50s that reconcile to
    the measured stage wall, the (dN - d1) gap attribution, and the
    written verdict (staging vs trace vs lock vs launch). `--child`
    is the in-subprocess half: probe at the CURRENT device count and
    print one JSON line."""
    import os
    import subprocess

    from blaze_tpu.obs import meshprof

    if args.child:
        if args.fleet:
            from blaze_tpu.fleet.attr import run_fleet_attr_probe

            doc = run_fleet_attr_probe(
                args.devices, rows=args.rows, iters=args.iters
            )
        else:
            doc = meshprof.run_attr_probe(
                args.devices, rows=args.rows, iters=args.iters
            )
        print(json.dumps(doc))
        return 0

    n = args.devices
    root = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )

    def emit(doc) -> int:
        text = json.dumps(doc, indent=2)
        out = args.out
        if out is None:
            out = meshprof.next_round_path(os.getcwd())
        if out != "-":
            with open(out, "w") as f:
                f.write(text + "\n")
            print(f"wrote {out}", file=sys.stderr)
        else:
            print(text)
        return 0 if (doc.get("ok", True) or doc.get("skipped")) else 1

    def child(n_dev: int) -> dict:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_dev}"
        ).strip()
        env["PYTHONPATH"] = (
            root + os.pathsep + env.get("PYTHONPATH", "")
        )
        p = subprocess.run(
            [sys.executable, "-m", "blaze_tpu", "mesh-attr",
             "--child", "--devices", str(n_dev),
             "--rows", str(args.rows), "--iters", str(args.iters)]
            + (["--fleet"] if args.fleet else []),
            cwd=root, env=env, capture_output=True, text=True,
            timeout=args.timeout,
        )
        if p.returncode != 0:
            tail = ((p.stdout or "") + (p.stderr or ""))
            raise RuntimeError(
                f"mesh-attr child (d{n_dev}) rc={p.returncode}: "
                + "\n".join(tail.splitlines()[-10:])
            )
        for line in reversed((p.stdout or "").splitlines()):
            line = line.strip()
            if line.startswith("{"):
                return json.loads(line)
        raise RuntimeError(
            f"mesh-attr child (d{n_dev}) produced no JSON line"
        )

    if args.fleet:
        # fleet anatomy: ONE measurement (2 emulated hosts inside the
        # child), mesh_dcn attributed next to the single-host phases,
        # and the attribution must cover >= 0.95 of the stage wall
        try:
            dn = child(n)
        except (RuntimeError, subprocess.TimeoutExpired) as e:
            return emit({"format": "blaze-meshattr-fleet-v1",
                         "ok": False, "skipped": False,
                         "tail": str(e)})
        dn["format"] = "blaze-meshattr-fleet-v1"
        cov = (dn.get("reconcile") or {}).get("coverage", 0.0)
        dn["ok"] = bool(dn.get("fleet_lowered")) and cov >= 0.95
        if not dn["ok"]:
            print(f"fleet attr coverage {cov} < 0.95 "
                  f"(lowered={dn.get('fleet_lowered')})",
                  file=sys.stderr)
        if args.out is None:
            args.out = "-"
        return emit(dn)

    try:
        d1 = child(1)
        dn = child(n)
    except (RuntimeError, subprocess.TimeoutExpired) as e:
        return emit({"format": "blaze-meshattr-v1", "ok": False,
                     "skipped": False, "tail": str(e)})
    doc = meshprof.build_doc(d1, dn)
    doc["ok"] = bool(dn.get("mesh_lowered"))
    if doc.get("verdict"):
        print(f"verdict: {doc['verdict']}", file=sys.stderr)
    return emit(doc)


def cmd_profile(args) -> int:
    """Contention profiler: drive the serving workload at each
    --concurrency level with lock-wait accounting + the stack sampler
    hot, and emit ONE JSON report attributing where the time goes -
    top blocking locks with wait:hold ratios, top sampled stacks per
    thread role, per-verb wire latencies. This is the artifact the
    ROADMAP item-2 wire-loop refactor is judged against."""
    import os
    import statistics
    import tempfile
    import threading
    import time

    from blaze_tpu.service.wire import ServiceClient

    levels = [max(1, int(tok)) for tok in
              str(args.concurrency).split(",") if tok.strip()]
    if not levels:
        print("profile: empty --concurrency list", file=sys.stderr)
        return 2

    def workload_blob(rows: int) -> bytes:
        # the phase probe's keyless-aggregate shape (obs/phases.py):
        # cheap kernel, so the levels measure SERVING contention,
        # not XLA compilation
        import numpy as np
        import pyarrow as pa
        import pyarrow.parquet as pq

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

        path = os.path.join(
            tempfile.gettempdir(), f"blaze_profile_{rows}.parquet"
        )
        if not os.path.exists(path):
            rng = np.random.default_rng(7)
            pq.write_table(
                pa.table({
                    "k": pa.array(
                        rng.integers(0, 64, rows), pa.int32()
                    ),
                    "v": pa.array(rng.random(rows), pa.float64()),
                }),
                path, compression="zstd",
            )
        plan = HashAggregateExec(
            FilterExec(ParquetScanExec([[FileRange(path)]]),
                       Col("v") > 0.25),
            keys=[],
            aggs=[(AggExpr(AggFn.SUM, Col("v")), "s"),
                  (AggExpr(AggFn.COUNT_STAR, None), "n")],
            mode=AggMode.COMPLETE,
        )
        return task_to_proto(plan, 0)

    blob = workload_blob(args.rows)
    per_client = max(1, args.per_client)

    def drive(host, port, conc):
        errs = []

        def client():
            try:
                with ServiceClient(host, port) as cl:
                    for _ in range(per_client):
                        cl.run(blob)
            except Exception as e:  # noqa: BLE001 - reported once
                errs.append(repr(e))

        ts = [threading.Thread(target=client,
                               name=f"blaze-profile-client-{i}")
              for i in range(conc)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        if errs:
            raise RuntimeError(errs[0])

    # target: a live tier (--port: the PROFILE verb arms and samples
    # it remotely - the workload parquet must be visible to it, i.e.
    # same host) or an in-process stack built here (default; --router
    # fronts the service with a real Router so the router-tier locks
    # and relay threads show up too)
    remote = args.port is not None
    teardown = []  # LIFO
    try:
        if remote:
            host, port = args.host, args.port
        else:
            from blaze_tpu.runtime.gateway import TaskGatewayServer
            from blaze_tpu.service import QueryService

            svc = QueryService(
                max_concurrency=args.max_concurrency,
                enable_cache=not args.no_cache,
            )
            teardown.append(svc.close)
            srv = TaskGatewayServer(service=svc).start()
            teardown.append(srv.stop)
            host, port = srv.address
            if args.router:
                from blaze_tpu.router.proxy import (
                    Router,
                    RouterServer,
                )

                router = Router([f"{host}:{port}"],
                                poll_interval_s=0.2)
                teardown.append(router.close)
                router.registry.poll_now()
                rsrv = RouterServer(router).start()
                teardown.append(rsrv.stop)
                host, port = rsrv.address

        def pctl(payload):
            with ServiceClient(host, port) as c:
                out = c.profile(payload)
            if out.get("error"):
                raise RuntimeError(f"PROFILE: {out['error']}")
            return out

        started = pctl({"op": "start", "hz": args.hz})
        teardown.append(lambda: pctl({"op": "stop"}))
        tier = started.get("tier", "service")
        drive(host, port, 1)  # warmup: kernel compile, cache prime

        report_levels = []
        last_snap = {}
        for i, conc in enumerate(levels):
            pctl({"op": "reset"})
            times = []
            for _ in range(max(1, args.rounds)):
                t0 = time.perf_counter()
                drive(host, port, conc)
                times.append(time.perf_counter() - t0)
            # collapsed stacks only for the LAST (max-pressure)
            # window: they dominate the report's size
            last = i == len(levels) - 1
            snap = pctl({"op": "snapshot", "collapsed": last,
                         "top_locks": 3})
            med = statistics.median(times)
            entry = {
                "concurrency": conc,
                "rounds": len(times),
                "median_s": round(med, 4),
                "spread": round(
                    (max(times) / med - 1.0) if med else 0.0, 3
                ),
                "qps": round(conc * per_client / med, 1)
                if med else 0.0,
                "top_locks": snap.get("top_locks", []),
                "contention": snap.get("contention", {}),
                "stacks": {
                    k: snap.get("profile", {}).get(k)
                    for k in ("samples", "distinct_stacks", "top")
                },
            }
            report_levels.append(entry)
            last_snap = snap
            locks = entry["top_locks"]
            print(
                f"profile: c{conc} qps={entry['qps']} "
                f"median={entry['median_s']}s top_lock="
                + (f"{locks[0]['lock']} "
                   f"(wait {locks[0]['wait_s']}s)" if locks
                   else "none"),
                file=sys.stderr, flush=True,
            )
        collapsed = last_snap.get("profile", {}).get("collapsed", "")
        report = {
            "format": "blaze-profile-v1",
            "tier": tier,
            "mode": "remote" if remote else "in-process",
            "router": bool(args.router) or tier == "router",
            "hz": args.hz,
            "per_client": per_client,
            "rows_per_query": args.rows,
            "result_cache": not args.no_cache,
            "levels": report_levels,
            # headline attribution: the max-concurrency window
            "top_locks": report_levels[-1]["top_locks"],
            "per_verb_seconds": last_snap.get("verbs", {}),
            "collapsed": collapsed,
            "roles": sorted({
                ln.split(";", 1)[0]
                for ln in collapsed.splitlines() if ln
            }),
        }
    finally:
        for fn in reversed(teardown):
            try:
                fn()
            except Exception:  # noqa: BLE001 - best-effort teardown
                pass
    text = json.dumps(report, indent=1, sort_keys=True)
    if args.out and args.out != "-":
        with open(args.out, "w") as f:
            f.write(text + "\n")
        print(f"wrote {args.out}", file=sys.stderr)
    else:
        print(text)
    return 0


def cmd_regress(args) -> int:
    """Per-phase regression detection (obs/phases.py): probe-vs-
    baseline or bench-round-vs-bench-round. Exit codes: 0 clean,
    1 regression(s) detected, 2 usage/input problem."""
    from blaze_tpu.obs import phases

    if args.bench:
        try:
            base = phases.phases_from_bench(args.bench[0])
            live = phases.phases_from_bench(args.bench[1])
        except (OSError, json.JSONDecodeError) as e:
            # input problems exit 2, never 1: automation must be able
            # to tell "phase regression" from "bad artifact path"
            print(f"regress: cannot read bench artifact: {e}",
                  file=sys.stderr)
            return 2
        missing = [p for p, s in zip(args.bench, (base, live))
                   if s is None]
        if missing:
            print(f"no phase rollup recorded in {missing} "
                  "(round predates phase recording?)",
                  file=sys.stderr)
            return 2
        source = f"{args.bench[1]} vs {args.bench[0]}"
        if args.emit_baseline:
            # refresh the baseline from the NEWER round's rollup
            phases.save_baseline(
                args.emit_baseline, live,
                meta={"source": args.bench[1]},
            )
            print(f"wrote {args.emit_baseline}", file=sys.stderr)
    else:
        live = phases.run_probe(rounds=args.rounds, rows=args.rows)
        source = f"probe({args.rounds}x{args.rows} rows)"
        if args.emit_baseline:
            phases.save_baseline(
                args.emit_baseline, live,
                meta={"rounds": args.rounds, "rows": args.rows},
            )
            print(f"wrote {args.emit_baseline}", file=sys.stderr)
            if not args.against:
                return 0
        if not args.against:
            print(json.dumps(live, indent=1, sort_keys=True))
            return 0
        try:
            base = phases.load_baseline(args.against)
        except (OSError, json.JSONDecodeError) as e:
            print(f"regress: cannot read baseline "
                  f"{args.against}: {e}", file=sys.stderr)
            return 2
        source += f" vs {args.against}"
    regressions = phases.compare(
        live, base,
        rel_band=args.noise,
        abs_floor_s=args.abs_floor,
        min_samples=args.min_samples,
    )
    print(json.dumps({
        "source": source,
        "noise_band": {"rel": args.noise,
                       "abs_floor_s": args.abs_floor},
        "regressions": regressions,
        "live": live if args.verbose else
        {k: v for k, v in live.items() if k == "_all"},
    }, indent=1, sort_keys=True))
    if regressions:
        worst = regressions[0]
        print(
            f"REGRESSION: {len(regressions)} phase(s) crept - worst "
            f"{worst['class']}/{worst['phase']} p50 "
            f"{worst['base_p50']}s -> {worst['live_p50']}s "
            f"({worst['ratio']}x, limit {worst['limit']}s)",
            file=sys.stderr,
        )
        return 1
    print("per-phase p50s within the noise band", file=sys.stderr)
    return 0


def _place_compile_cache() -> None:
    """The persistent compile cache is placed from outside: where
    JAX_COMPILATION_CACHE_DIR is set jax already reads it and nothing
    is set here; otherwise it lives at a fixed path in the checkout
    (the path is part of the cache key)."""
    import os

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmarks", ".jax_cache",
        ),
    )


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="blaze_tpu")
    sub = p.add_subparsers(dest="cmd", required=True)
    sub.add_parser("info")
    rt = sub.add_parser("run-task")
    rt.add_argument("file")
    rt.add_argument("--quiet", action="store_true")
    rt.add_argument("--metrics", action="store_true",
                    help="print the per-operator metric tree")
    sc = sub.add_parser("scan")
    sc.add_argument("file")
    sc.add_argument("--columns", default=None)
    sc.add_argument("--limit", type=int, default=20)
    gw = sub.add_parser("gateway")
    gw.add_argument("--host", default="127.0.0.1")
    gw.add_argument("--port", type=int, default=8484)
    sv = sub.add_parser("serve")
    sv.add_argument("--host", default="127.0.0.1")
    sv.add_argument("--port", type=int, default=8484)
    sv.add_argument("--max-concurrency", type=int, default=2)
    sv.add_argument("--max-queue-depth", type=int, default=64)
    sv.add_argument("--deadline", type=float, default=0.0,
                    help="default per-query deadline seconds (0 = none)")
    sv.add_argument("--no-cache", action="store_true",
                    help="disable the plan-fingerprint result cache")
    sv.add_argument("--cache-bytes", type=int, default=256 << 20)
    sv.add_argument("--cache-ttl", type=float, default=300.0)
    sv.add_argument("--no-trace", action="store_true",
                    help="disable query-lifecycle tracing (obs/)")
    sv.add_argument("--slow-query-s", type=float, default=None,
                    help="structured slow-query log threshold "
                         "(default 5s or BLAZE_SLOW_QUERY_S; "
                         "<= 0 disables)")
    sv.add_argument("--mesh", action="store_true",
                    help="force the mesh execution tier for every "
                         "eligible query (mesh_mode=on; docs/MESH.md)")
    sv.add_argument("--mesh-mode", default=None,
                    choices=("auto", "on", "off"),
                    help="mesh execution mode (default: defer to "
                         "BLAZE_MESH_LOWERING / auto)")
    sv.add_argument("--router", default=None, metavar="HOST:PORT",
                    help="router to JOIN (elastic membership: "
                         "announced at startup and re-announced "
                         "periodically; LEAVE is sent after a "
                         "SIGTERM drain)")
    sv.add_argument("--advertise", default=None, metavar="HOST:PORT",
                    help="address announced to the router (default: "
                         "the listener's bound address)")
    sv.add_argument("--orphan-ttl", type=float, default=900.0,
                    help="reap terminal, never-fetched queries with "
                         "no POLL activity for this many seconds - "
                         "a permanently-dead router cannot pin "
                         "replica retention forever (0 disables)")
    sv.add_argument("--drain-grace", type=float, default=30.0,
                    help="SIGTERM drain: max seconds to wait for "
                         "in-flight queries before leaving anyway "
                         "(0 = wait forever; open result streams "
                         "count as in-flight)")
    sv.add_argument("--stream-buffer-bytes", type=int,
                    default=32 << 20,
                    help="per-query bounded ring for incremental "
                         "FETCH-while-RUNNING delivery: the executor "
                         "blocks once this many produced-but-"
                         "undelivered bytes pile up (0 = legacy "
                         "materialize-then-stream)")
    sv.add_argument("--stream-stall-s", type=float, default=30.0,
                    help="slow-consumer budget: a FETCHing client "
                         "that accepts no bytes for this long while "
                         "the stream buffer sits at its cap gets the "
                         "query aborted STREAM_STALLED (CANCELLED-"
                         "class - never a breaker strike), freeing "
                         "buffer and reservation (0 disables)")
    sv.add_argument("--profile-hz", type=float, default=0.0,
                    help="arm lock-wait accounting and run the "
                         "thread-stack sampler at this Hz for the "
                         "process lifetime (0 = off; the PROFILE "
                         "verb can arm a live server without it)")
    sv.add_argument("--wire", default=None,
                    choices=("async", "threaded"),
                    help="wire data plane: event-loop verb serving "
                         "(async, the default) or the legacy thread-"
                         "per-connection tier (threaded); default "
                         "honors BLAZE_WIRE")
    sv.add_argument("--plan-cache-entries", type=int, default=256,
                    help="decoded-plan cache (zerocopy/): repeat "
                         "SUBMITs of a byte-identical blob skip the "
                         "protobuf decode entirely (0 disables)")
    sv.add_argument("--arena-bytes", type=int, default=256 << 20,
                    help="shared-memory Arrow arena budget: finalized "
                         "results are published once as mmap'd wire "
                         "frames and FETCHes are served zero-copy "
                         "(scatter-gather or a leased handle for "
                         "co-located clients)")
    sv.add_argument("--no-arena", action="store_true",
                    help="disable the arena: every FETCH re-encodes "
                         "and streams over the socket byte path")
    sv.add_argument("--arena-dir", default=None,
                    help="arena segment directory (default: a "
                         "private temp dir, removed at close)")
    sv.add_argument("--fleet-peer", action="append", default=[],
                    metavar="HOST:PORT",
                    help="peer serve host for the fleet mesh tier "
                    "(repeatable); large queries execute across this "
                    "host plus every peer over the MESH_EXCHANGE "
                    "DCN plane (docs/MESH.md, fleet tier)")
    sv.add_argument("--fleet-router", default=None,
                    metavar="HOST:PORT",
                    help="router arbitrating fleet device claims "
                    "(defaults to --router when set; omit both for "
                    "a host-local device ledger)")
    sv.add_argument("--fleet-devices", type=int, default=None,
                    help="accelerator count this host contributes to "
                    "the fleet device pool (announced on JOIN; "
                    "default: the local device count)")
    sv.add_argument("--tenant-config", default=None, metavar="JSON",
                    help="per-tenant admission budgets, e.g. "
                         '\'{"acme": {"max_queued": 8, '
                         '"max_running": 1, "weight": 2.0}, '
                         '"*": {"max_queued": 32}}\' - enables '
                         "weighted-fair (DRR) ordering across "
                         "tenants; omit for tenant-unaware admission "
                         "(docs/SERVICE.md)")
    tr = sub.add_parser("trace")
    tr.add_argument("query_id")
    tr.add_argument("--host", default="127.0.0.1")
    tr.add_argument("--port", type=int, default=8484)
    tr.add_argument("-o", "--out", default=None,
                    help="output path ('-' = stdout; default "
                         "<query_id>.trace.json)")
    mt = sub.add_parser("metrics")
    mt.add_argument("--host", default="127.0.0.1")
    mt.add_argument("--port", type=int, default=8484)
    rr = sub.add_parser("route")
    rr.add_argument("--host", default="127.0.0.1")
    rr.add_argument("--port", type=int, default=8485)
    rr.add_argument("--replica", action="append", default=[],
                    metavar="HOST:PORT",
                    help="a serve instance to front (repeatable; a "
                         "BOOTSTRAP hint only - replicas join and "
                         "leave dynamically via the MEMBER verb)")
    rr.add_argument("--placement", default="affinity",
                    choices=("affinity", "random"),
                    help="placement policy (random = baseline for "
                         "the bench comparison)")
    rr.add_argument("--poll-interval", type=float, default=0.5,
                    help="STATS heartbeat poll period seconds")
    rr.add_argument("--heartbeat-timeout", type=float, default=3.0,
                    help="no successful poll for this long = dead")
    rr.add_argument("--quarantine", type=float, default=15.0,
                    help="quarantine cool-off seconds")
    rr.add_argument("--breaker-threshold", type=int, default=3,
                    help="consecutive fatal-class failures that open "
                         "a replica's circuit breaker")
    rr.add_argument("--max-resubmits", type=int, default=2,
                    help="TRANSIENT same-replica re-submissions per "
                         "query")
    rr.add_argument("--no-trace", action="store_true",
                    help="disable router-hop tracing (obs/)")
    rr.add_argument("--conn-pool", type=int, default=4,
                    help="verb connections pooled per replica (one "
                         "slow RPC can't serialize sibling verbs)")
    rr.add_argument("--replicate-hot", type=int, default=4,
                    metavar="K",
                    help="double-place the top-K hot fingerprints on "
                         "a second replica (0 disables hot-result "
                         "replication)")
    rr.add_argument("--replicate-interval", type=float, default=2.0,
                    help="hot-replication pass period seconds")
    rr.add_argument("--journal", default=None, metavar="PATH",
                    help="durable routing journal: record every "
                         "routed query's lifecycle so a restarted "
                         "router (same --journal) replays its table "
                         "and reconciles in-flight queries against "
                         "the re-JOINing fleet instead of forgetting "
                         "them (docs/ROUTER.md 'Router recovery')")
    rr.add_argument("--recover-timeout", type=float, default=30.0,
                    help="recovery window seconds: journaled "
                         "placements whose replica has not re-JOINed "
                         "by then are re-placed on the live fleet "
                         "(or stranded when none is routable)")
    rr.add_argument("--stream-window", type=int, default=4,
                    help="streaming relay credit window: raw result "
                         "parts in flight between the downstream "
                         "reader and the client-facing writer "
                         "(1 = strictly serial relay)")
    rr.add_argument("--stream-stall-s", type=float, default=30.0,
                    help="relay slow-consumer budget: a client that "
                         "accepts no bytes for this long gets its "
                         "relay aborted (downstream keeps the parts; "
                         "a re-FETCH resumes; never a breaker "
                         "strike; 0 disables)")
    rr.add_argument("--stream-total-bytes", type=int,
                    default=256 << 20,
                    help="fleet-wide relay memory cap: total parked "
                         "(read-from-replica, not-yet-delivered) "
                         "bytes across ALL concurrent relay streams; "
                         "an over-budget stream's reader waits "
                         "(stream_total_waits counts them) until "
                         "siblings drain (0 disables)")
    rr.add_argument("--profile-hz", type=float, default=0.0,
                    help="arm lock-wait accounting and run the "
                         "thread-stack sampler at this Hz for the "
                         "router's lifetime (0 = off)")
    rr.add_argument("--wire", default=None,
                    choices=("async", "threaded"),
                    help="wire data plane: event-loop relay (async, "
                         "the default) or the legacy thread-per-"
                         "connection front (threaded); default "
                         "honors BLAZE_WIRE")
    rr.add_argument("--tenant-rate", type=float, default=0.0,
                    help="fleet-level per-tenant SUBMIT rate limit "
                         "(queries/sec, token bucket); over-rate "
                         "submits are rejected REJECTED_TENANT_BUDGET "
                         "before journaling, zero breaker strikes "
                         "(0 = off; docs/ROUTER.md)")
    rr.add_argument("--tenant-burst", type=int, default=None,
                    help="token-bucket burst size (default "
                         "2x --tenant-rate, min 1)")
    rr.add_argument("--tenant-retry-budget", type=int, default=0,
                    help="per-tenant failover/retry re-submits "
                         "allowed per trailing window; an exhausted "
                         "budget surfaces the original classified "
                         "error instead of re-submitting (0 = "
                         "unlimited)")
    rr.add_argument("--tenant-retry-window", type=float,
                    default=30.0,
                    help="trailing window seconds for "
                         "--tenant-retry-budget")
    rr.add_argument("--tenant-config", default=None, metavar="JSON",
                    help="per-tenant overrides, e.g. "
                         '\'{"acme": {"rate": 5, "burst": 10, '
                         '"retry_budget": 4}, "*": {"rate": 50}}\'')
    md = sub.add_parser("mesh-dryrun")
    md.add_argument("--devices", type=int, default=8,
                    help="virtual device count for the forced host "
                         "mesh")
    md.add_argument("-o", "--out", default=None,
                    help="output path for the MULTICHIP-shaped JSON "
                         "('-'/default = stdout)")
    md.add_argument("--timeout", type=float, default=600.0,
                    help="dryrun subprocess wall-clock bound seconds")
    ma = sub.add_parser("mesh-attr")
    ma.add_argument("--devices", type=int, default=8,
                    help="virtual device count for the dN side of "
                         "the attribution (d1 always runs too)")
    ma.add_argument("--rows", type=int, default=1 << 20,
                    help="input rows for the mesh_groupby shape")
    ma.add_argument("--iters", type=int, default=4,
                    help="warm measurement rounds per device count")
    ma.add_argument("-o", "--out", default=None,
                    help="output path for MESHATTR JSON (default: "
                         "next MESHATTR_rNN.json in cwd; '-' = "
                         "stdout)")
    ma.add_argument("--timeout", type=float, default=600.0,
                    help="per-child subprocess wall-clock bound "
                         "seconds")
    ma.add_argument("--fleet", action="store_true",
                    help="attribute the FLEET tier instead: 2 "
                    "emulated hosts in one probe process, mesh_dcn "
                    "(the DCN exchange rounds) next to the "
                    "single-host sub-phases; fails unless the "
                    "attribution covers >= 0.95 of the stage wall")
    ma.add_argument("--child", action="store_true",
                    help=argparse.SUPPRESS)
    pf = sub.add_parser("profile")
    pf.add_argument("--concurrency", default="1,4,16",
                    help="comma list of client concurrency levels "
                         "to drive and attribute (default 1,4,16)")
    pf.add_argument("--router", action="store_true",
                    help="front the in-process service with a real "
                         "Router so router-tier locks and relay "
                         "threads are attributed too")
    pf.add_argument("--host", default="127.0.0.1")
    pf.add_argument("--port", type=int, default=None,
                    help="profile a LIVE serve/route at host:port "
                         "via the PROFILE verb instead of building "
                         "an in-process stack (same host: the "
                         "workload parquet path must be visible "
                         "to it)")
    pf.add_argument("--hz", type=float, default=67.0,
                    help="stack sampler frequency")
    pf.add_argument("--rounds", type=int, default=3,
                    help="timed workload rounds per level")
    pf.add_argument("--per-client", type=int, default=4,
                    help="queries each client thread runs per round")
    pf.add_argument("--rows", type=int, default=1 << 16,
                    help="workload dataset rows (small: the levels "
                         "measure serving contention, not kernels)")
    pf.add_argument("--max-concurrency", type=int, default=16,
                    help="in-process service executor slots")
    pf.add_argument("--no-cache", action="store_true",
                    help="disable the result cache (default on: the "
                         "cached path IS the c16 collapse case)")
    pf.add_argument("-o", "--out", default=None,
                    help="report path ('-'/default = stdout)")
    rg = sub.add_parser("regress")
    rg.add_argument("--against", default=None, metavar="BASELINE",
                    help="phase baseline JSON to diff the probe "
                         "against (PHASE_BASELINE.json)")
    rg.add_argument("--emit-baseline", default=None, metavar="PATH",
                    help="write the probe's rollup as a fresh "
                         "baseline")
    rg.add_argument("--bench", nargs=2, default=None,
                    metavar=("OLD", "NEW"),
                    help="diff the phase rollups of two BENCH_r*.json "
                         "artifacts instead of probing")
    rg.add_argument("--rounds", type=int, default=6,
                    help="probe repetitions (post-warmup)")
    rg.add_argument("--rows", type=int, default=1 << 18,
                    help="probe dataset rows")
    rg.add_argument("--noise", type=float, default=0.75,
                    help="relative noise band: regress when live p50 "
                         "> base p50 * (1 + noise) + abs-floor")
    rg.add_argument("--abs-floor", type=float, default=0.05,
                    help="absolute noise floor seconds")
    rg.add_argument("--min-samples", type=int, default=3,
                    help="ignore (class, phase) cells with fewer "
                         "samples on either side")
    rg.add_argument("-v", "--verbose", action="store_true",
                    help="include every class in the report, not "
                         "just _all")
    args = p.parse_args(argv)
    _place_compile_cache()
    return {
        "info": cmd_info,
        "run-task": cmd_run_task,
        "scan": cmd_scan,
        "gateway": cmd_gateway,
        "serve": cmd_serve,
        "trace": cmd_trace,
        "metrics": cmd_metrics,
        "route": cmd_route,
        "mesh-dryrun": cmd_mesh_dryrun,
        "mesh-attr": cmd_mesh_attr,
        "profile": cmd_profile,
        "regress": cmd_regress,
    }[args.cmd](args)


if __name__ == "__main__":
    raise SystemExit(main())
