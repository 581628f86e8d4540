#!/usr/bin/env python
"""Full test suite in one command, process-sharded.

Why sharding: jaxlib's CPU client segfaults inside
`backend_compile_and_load` after enough cumulative compilation volume in
ONE process (reproduced in round 2 and bisected in round 3: it is not
thread concurrency - BLAZE_TASK_THREADS=1 crashes too - not the engine's
C++ tier - BLAZE_DISABLE_NATIVE=1 crashes too - not executable eviction
- BLAZE_KERNEL_CACHE_CAP=0 + BLAZE_NO_CACHE_CLEAR=1 crash too - and a
3000-compile minimal churn loop survives, so it is specific to large
many-output programs at volume). The reference's CI makes the same move
for different reasons: one job per TPC-DS query (tpcds.yml:105-114).

This runner executes:
  1. the core suite (everything but the TPC-DS matrices) in one process,
  2. the 99-query in-memory differential matrix in chunks of 12 queries,
  3. the exchange-tier matrix in chunks of 5 queries,
each chunk a fresh pytest subprocess, so no process crosses the
compile-volume cliff and one crash cannot take out the run. Exit code 0
iff every chunk passed.

Usage: python run_tests.py [--rows N] [--fast] [--scale]
  --rows N   BLAZE_TPCDS_ROWS for the matrices (default: env or 200000)
  --fast     20k-row matrices (quick signal, ~3x faster)
  --scale    additionally run a 6-query subset at 2M store_sales rows
             (the reference CI's 1GB-dataset class, tpcds.yml:119-121)
"""

import argparse
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

TPCDS_CHUNK = 12
# exchange queries compile far more programs per test (4-partition maps,
# spills, readers); 5 monster queries in one process crossed the
# compile-volume cliff in the first green-run attempt, and the q64+q80
# pair still did at 2 - every exchange query gets its own process
EXCHANGE_CHUNK = 1


def tpcds_query_names():
    sys.path.insert(0, REPO)
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r); "
         "from tests.tpcds_support import QUERIES; "
         "print(' '.join(sorted(QUERIES)))" % REPO],
        capture_output=True, text=True, env=_env(), check=True,
    )
    return out.stdout.split()


def exchange_query_names():
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r); "
         "from tests.test_tpcds_exchange import (EXCHANGE_QUERIES, "
         "PARQUET_QUERIES); "
         "print(' '.join(EXCHANGE_QUERIES)); "
         "print(' '.join(PARQUET_QUERIES))" % REPO],
        capture_output=True, text=True, env=_env(), check=True,
    )
    lines = out.stdout.splitlines()
    return lines[0].split(), lines[1].split()


def _env(rows=None):
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # persistent XLA compilation cache, shared across chunk processes:
    # cache hits skip backend_compile_and_load entirely, which both
    # speeds re-runs ~4x on the heavy exchange queries and removes most
    # exposure to the jaxlib compile-volume segfault (q64 died right at
    # the cliff under CPU contention even alone; warm it passes in 1/4
    # the time with a fraction of the live compilations)
    env.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(REPO, "benchmarks", ".jax_cache"),
    )
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
    # disable the aggregate ladder's small first tier in the suite:
    # its extra kernel variant per aggregate shape pushed q64's
    # exchange run over the jaxlib compile-volume cliff even in a
    # fresh process (round 5). Correctness coverage for the ladder
    # lives in tests/test_ops.py::test_group_capacity_ladder, which
    # runs with the production default.
    env.setdefault("BLAZE_AGG_TIER1", "0")
    if rows is not None:
        env["BLAZE_TPCDS_ROWS"] = str(rows)
    return env


def chunks(xs, n):
    for i in range(0, len(xs), n):
        yield xs[i:i + n]


def k_expr(names, suffixed):
    """Exact-match parametrized ids: 'q3' must not select 'q30'.
    Matrix ids look like [q3-bhj]; exchange ids like [q3]."""
    if suffixed:
        return " or ".join(f"{q}-" for q in names)
    return " or ".join(f"{q}]" for q in names)


RETRIED_CHUNKS = []  # labels that needed a fresh-process retry


def run(label, args, rows=None, extra_env=None, _retry=True):
    t0 = time.time()
    env = _env(rows)
    env.update(extra_env or {})
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "--no-header", *args],
        cwd=REPO, env=env, capture_output=True, text=True,
    )
    dt = time.time() - t0
    tail = [ln for ln in p.stdout.strip().splitlines()[-3:]]
    status = "OK " if p.returncode == 0 else "FAIL"
    print(f"[{status}] {label} ({dt:.0f}s) :: "
          f"{tail[-1] if tail else '(no output)'}", flush=True)
    if p.returncode != 0:
        print("\n".join(p.stdout.strip().splitlines()[-40:]))
        if p.returncode < 0 or "Segmentation fault" in p.stdout:
            print(f"  !! chunk died with signal/rc {p.returncode}")
            if _retry:
                # the jaxlib compile-volume segfault (see module
                # docstring / benchmarks/jaxlib_segfault_repro.py) is
                # an environmental flake that a FRESH process clears
                # (r3+r4: the killed q64 chunk passes standalone every
                # time); retry once so one flake doesn't turn a green
                # suite RED
                print("  .. retrying signal-killed chunk in a fresh "
                      "process", flush=True)
                RETRIED_CHUNKS.append(label)
                return run(label + " (retry)", args, rows=rows,
                           extra_env=extra_env, _retry=False)
    return p.returncode == 0


def bench_smoke() -> bool:
    """Commit-time bench guard (ISSUE 1 satellite; <= 60s at small
    rows): a broken bench must fail at commit time, not at round end."""
    ts = time.time()
    p = subprocess.run(
        [sys.executable, "bench.py", "--smoke"],
        cwd=REPO, env=_env(), capture_output=True, text=True,
        timeout=900,
    )
    smoke_ok = p.returncode == 0
    tail = p.stdout.strip().splitlines()
    print(f"[{'OK ' if smoke_ok else 'FAIL'}] bench smoke "
          f"({time.time() - ts:.0f}s) :: "
          f"{tail[-1][:160] if tail else '(no output)'}", flush=True)
    if not smoke_ok:
        print("\n".join(tail[-20:]))
    return smoke_ok


def service_smoke() -> bool:
    """Serving-tier smoke (ISSUE 2 satellite): the QueryService +
    gateway-service-protocol suites, including the `python -m
    blaze_tpu serve` cache-hit acceptance pin."""
    return run(
        "service smoke",
        ["tests/test_service.py", "tests/test_service_gateway.py",
         "tests/test_gateway.py", "tests/test_scheduler.py",
         "tests/test_wire_async.py"],
    )


def chaos_smoke(seed_offset: int = 0) -> bool:
    """Chaos-mode smoke (ISSUE 3 satellite): the fault-injection
    suites. By default each test runs with the FIXED chaos seed baked
    into its FaultPlan; a nonzero seed_offset shifts every
    test-installed plan's seed via BLAZE_CHAOS_SEED_OFFSET (ISSUE 5
    satellite - `--seeds N` sweeps offsets nightly-style to hunt the
    race regressions the fixed seed misses). The battery-shape test
    inside asserts that one injected transient fault per shape leaves
    results identical to the fault-free run; the cluster flavor
    injects through BLAZE_CHAOS into real worker subprocesses."""
    label = "chaos suite" if not seed_offset \
        else f"chaos suite [seed+{seed_offset}]"
    return run(
        label,
        ["tests/test_chaos.py", "tests/test_service_failures.py",
         "tests/test_cluster_chaos.py", "tests/test_router.py",
         "tests/test_membership.py", "tests/test_churn.py",
         "tests/test_journal.py", "tests/test_stream.py",
         "tests/test_contention.py", "tests/test_wire_async.py",
         "tests/test_zerocopy.py", "tests/test_tenancy.py",
         "-k", "not e2e"],
        extra_env=(
            {"BLAZE_CHAOS_SEED_OFFSET": str(seed_offset)}
            if seed_offset else None
        ),
    )


def tenancy_smoke() -> bool:
    """Multi-tenant isolation suite (ISSUE 18): TenantBudgets config
    merge + weighted-fair (DRR) admission units, the
    REJECTED_TENANT_BUDGET surfacing contract (TRANSIENT, the
    DRAINING pattern, TenantBudgetError at the client), the
    noisy-neighbor pin on both wire planes (victim p50 bounded, zero
    victim rejections), and the router-tier guards (token-bucket rate
    limit with zero breaker strikes, budget spill-through, windowed
    retry budget bounding failover amplification)."""
    return run(
        "tenancy suite",
        ["tests/test_tenancy.py"],
    )


def zerocopy_smoke() -> bool:
    """Zero-copy serve path suite (ISSUE 17): decoded-plan cache
    (digest parity with router affinity, LRU/loan semantics, the
    zero-plan_decode-spans repeat pin), the shared-memory Arrow arena
    (scatter-gather byte-identity vs the socket path on BOTH wire
    planes, handle leases + TTL orphan reap, mid-stream resume), the
    admission fast path (queued fleet still serves cached repeats),
    and the `zerocopy.map` / `zerocopy.lease` chaos degradations."""
    return run(
        "zerocopy suite",
        ["tests/test_zerocopy.py"],
    )


def stream_smoke() -> bool:
    """Streaming data-plane suite (ISSUE 14): bounded-ring
    backpressure + reservation accounting, slow-consumer stall aborts
    (STREAM_STALLED, CANCELLED-class, never a breaker strike),
    FETCH-while-RUNNING / double-FETCH / mid-stream resume semantics,
    the router's windowed zero-copy relay (credit window, mid-stream
    failover, relay stall budget), and drain-holds-open-streams."""
    return run(
        "stream suite",
        ["tests/test_stream.py"],
    )


def churn_smoke() -> bool:
    """Rolling-restart smoke (ISSUE 9 satellite + ISSUE 11
    router-restart rounds): the fleet-churn suites - JOIN/LEAVE
    membership, graceful drain, hot-result replication/promotion, the
    ROUTER restart rounds (drain-restart and kill-restart from the
    routing journal under a live query mix, zero client-visible
    failures) - plus the subprocess acceptance e2es (SIGTERM-drain 3
    replicas in turn, SIGKILL a hot fingerprint's affinity home, and
    SIGKILL the router mid-query + restart it on the same port/journal
    with zero re-executions)."""
    return run(
        "churn suite",
        ["tests/test_membership.py", "tests/test_churn.py",
         "tests/test_journal.py"],
    )


def obs_smoke() -> bool:
    """Observability smoke (ISSUE 4 satellite): trace-export schema
    validity (chaos-retried multi-partition query -> Perfetto JSON),
    METRICS/STATS wire surface, runtime-history + predicted shedding,
    the slow-query log, and the obs-off wall-overhead guard (<2% on a
    battery shape) - plus the dispatch-budget pins that obs hooks add
    zero dispatches."""
    return run(
        "obs suite",
        ["tests/test_obs.py", "tests/test_phases.py",
         "tests/test_dispatch_budget.py"],
    )


def mesh_smoke() -> bool:
    """Mesh execution tier suite (ISSUE 7): the mesh-vs-single-device
    differential battery, chaos `mesh.exchange` coverage, and the
    QueryService mesh-mode acceptance pin. Forces an 8-device virtual
    host mesh via XLA_FLAGS ITSELF (the repo conftest does the same
    for plain pytest runs, but this suite must not depend on it)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    return run(
        "mesh suite",
        ["tests/test_mesh_exec.py", "tests/test_parallel.py"],
        extra_env={"XLA_FLAGS": flags},
    )


def fleet_smoke() -> bool:
    """Fleet mesh tier suite (ISSUE 20): the 2-emulated-host
    differential battery, the `fleet.exchange` chaos degrade ladder,
    the SIGKILL-mid-stage failover, and the device-claim plane
    (tenant budgets / DRAINING-shaped capacity denials / waiter
    wake). Same 8-device forcing as the mesh suite."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    return run(
        "fleet suite",
        ["tests/test_fleet_mesh.py"],
        extra_env={"XLA_FLAGS": flags},
    )


def _bench_phase_rounds():
    """BENCH_r*.json artifacts (round order) that carry a per-phase
    rollup snapshot - the inline mirror of obs/phases.phases_from_bench
    (kept import-light: this runs before any jax-touching child)."""
    import glob
    import json

    out = []
    for path in sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json"))):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        if isinstance(doc, dict) and "tail" in doc \
                and "queries" not in doc:
            parsed = doc.get("parsed")
            if not isinstance(parsed, dict):
                parsed = None
                for line in reversed(
                    str(doc.get("tail", "")).splitlines()
                ):
                    line = line.strip()
                    if line.startswith("{"):
                        try:
                            parsed = json.loads(line)
                            break
                        except json.JSONDecodeError:
                            continue
            doc = parsed or {}
        snap = ((doc.get("queries") or {}).get("phases") or {}) \
            .get("snapshot")
        if snap:
            out.append(path)
    return out


def bench_regress_smoke() -> bool:
    """Nightly-shape regression hook (ROADMAP PR 6 follow-up): diff
    the per-phase rollups of the two most recent BENCH_r*.json rounds
    (`regress --bench OLD NEW`), so cross-round phase creep fails at
    commit time. Skips quietly while fewer than 2 artifacts carry
    `phases` snapshots."""
    rounds = _bench_phase_rounds()
    if len(rounds) < 2:
        print(f"[SKIP] bench regress ({len(rounds)} artifact(s) with "
              "phase rollups; need 2)", flush=True)
        return True
    old, new = rounds[-2], rounds[-1]
    ts = time.time()
    p = subprocess.run(
        [sys.executable, "-m", "blaze_tpu", "regress",
         "--bench", old, new,
         "--noise", "3.0", "--abs-floor", "0.25"],
        cwd=REPO, env=_env(), capture_output=True, text=True,
        timeout=300,
    )
    ok = p.returncode == 0
    tail = (p.stderr or p.stdout).strip().splitlines()
    print(f"[{'OK ' if ok else 'FAIL'}] bench regress "
          f"{os.path.basename(old)} -> {os.path.basename(new)} "
          f"({time.time() - ts:.0f}s) :: "
          f"{tail[-1][:160] if tail else '(no output)'}", flush=True)
    if not ok:
        print("\n".join((p.stdout or "").splitlines()[-30:]))
    return ok


def meshattr_regress_smoke() -> bool:
    """Mesh-attribution regression hook (ISSUE 19 satellite): diff the
    per-sub-phase rollups of the two most recent MESHATTR_r*.json
    rounds through the same `regress --bench` path bench artifacts use
    (meshattr docs carry a `phases.snapshot` section shaped for it).
    A sub-phase whose p50 creeps across rounds - staging ballooning,
    re-trace returning, sync growing - fails at commit time instead of
    surfacing as a slower round-end attribution run. Skips quietly
    while fewer than 2 rounds exist."""
    import glob

    rounds = sorted(glob.glob(os.path.join(REPO, "MESHATTR_r*.json")))
    if len(rounds) < 2:
        print(f"[SKIP] meshattr regress ({len(rounds)} round(s); "
              "need 2)", flush=True)
        return True
    old, new = rounds[-2], rounds[-1]
    ts = time.time()
    p = subprocess.run(
        [sys.executable, "-m", "blaze_tpu", "regress",
         "--bench", old, new,
         "--noise", "3.0", "--abs-floor", "0.25"],
        cwd=REPO, env=_env(), capture_output=True, text=True,
        timeout=300,
    )
    ok = p.returncode == 0
    tail = (p.stderr or p.stdout).strip().splitlines()
    print(f"[{'OK ' if ok else 'FAIL'}] meshattr regress "
          f"{os.path.basename(old)} -> {os.path.basename(new)} "
          f"({time.time() - ts:.0f}s) :: "
          f"{tail[-1][:160] if tail else '(no output)'}", flush=True)
    if not ok:
        print("\n".join((p.stdout or "").splitlines()[-30:]))
    return ok


def regress_smoke() -> bool:
    """Per-phase regression guard (ISSUE 6): run the fixed phase
    probe and diff its per-phase p50s against the checked-in
    PHASE_BASELINE.json. The noise band is deliberately generous
    (hosts and CI load differ; the baseline pins ORDER-of-magnitude
    phase cost, not exact timing) - a real decode or queue-wait
    regression is a multiple, not a percent. Skips quietly when no
    baseline is checked in (fresh clone before the first bench
    round)."""
    baseline = os.path.join(REPO, "PHASE_BASELINE.json")
    if not os.path.exists(baseline):
        print("[SKIP] regress smoke (no PHASE_BASELINE.json)",
              flush=True)
        return True
    ts = time.time()
    # noise band tightened 3.0 -> 1.5 (ISSUE 9 satellite / ROADMAP
    # follow-up): per-host phase baselines held stable across
    # BENCH_r07/r08, so a 2.5x p50 blowup is now a failure, not noise
    p = subprocess.run(
        [sys.executable, "-m", "blaze_tpu", "regress",
         "--against", baseline,
         "--noise", "1.5", "--abs-floor", "0.25"],
        cwd=REPO, env=_env(), capture_output=True, text=True,
        timeout=600,
    )
    ok = p.returncode == 0
    tail = (p.stderr or p.stdout).strip().splitlines()
    print(f"[{'OK ' if ok else 'FAIL'}] regress smoke "
          f"({time.time() - ts:.0f}s) :: "
          f"{tail[-1][:160] if tail else '(no output)'}", flush=True)
    if not ok:
        print("\n".join((p.stdout or "").splitlines()[-30:]))
    return ok


def trace_smoke() -> bool:
    """Trace-export smoke (ISSUE 4 satellite, `--trace`): ONE
    multi-partition query with a chaos-injected transient retry,
    exported and validated against the minimal Chrome-trace-event
    schema (matched B/E pairs, monotonic ts, attempt spans tagged with
    error_class) plus the export/stitching unit tests."""
    return run(
        "trace smoke",
        ["tests/test_obs.py", "-k", "trace or chrome or stitch"],
    )


def profile_smoke() -> bool:
    """Profiler smoke (ISSUE 15 satellite, `--profile`): runs the
    `python -m blaze_tpu profile` CLI at c1/c4 against an in-process
    service and asserts the blaze-profile-v1 report schema - every
    concurrency level carries qps + contention accounting, the
    collapsed-stack section sampled at least one frame, and the
    top-lock table names real locks with wait:hold ratios."""
    import json
    import tempfile

    ts = time.time()
    out = os.path.join(tempfile.gettempdir(),
                       f"blaze_profile_smoke_{os.getpid()}.json")
    p = subprocess.run(
        [sys.executable, "-m", "blaze_tpu", "profile",
         "--concurrency", "1,4", "--rounds", "1", "--per-client", "2",
         "--rows", "4096", "-o", out],
        cwd=REPO, env=_env(), capture_output=True, text=True,
        timeout=300,
    )
    ok = p.returncode == 0
    why = f"exit {p.returncode}"
    if ok:
        try:
            with open(out) as f:
                rep = json.load(f)
            assert rep["format"] == "blaze-profile-v1", rep.get("format")
            assert len(rep["levels"]) == 2, len(rep["levels"])
            for lvl in rep["levels"]:
                assert lvl["qps"] > 0, lvl
                assert lvl["contention"], "empty contention section"
            assert rep["top_locks"], "empty top_locks"
            for row in rep["top_locks"]:
                assert "lock" in row and "wait_hold_ratio" in row, row
            stacks = rep["levels"][-1]["stacks"]
            assert stacks["samples"] > 0, stacks
            assert any(ln for ln in rep["collapsed"].splitlines()), \
                "empty collapsed section"
            why = (f"c4 {rep['levels'][-1]['qps']:.0f} qps, "
                   f"top lock {rep['top_locks'][0]['lock']}, "
                   f"{stacks['samples']} stack samples")
        except (OSError, KeyError, AssertionError,
                json.JSONDecodeError) as e:
            ok = False
            why = f"report invalid: {e!r}"
    print(f"[{'OK ' if ok else 'FAIL'}] profile smoke "
          f"({time.time() - ts:.0f}s) :: {why}", flush=True)
    if not ok:
        print("\n".join((p.stderr or "").splitlines()[-20:]))
    try:
        os.remove(out)
    except OSError:
        pass
    return ok


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int,
                    default=int(os.environ.get("BLAZE_TPCDS_ROWS",
                                               200_000)))
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--scale", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="bench + serving-tier + chaos smoke only "
                         "(commit-time guard, no TPC-DS matrices)")
    ap.add_argument("--chaos", action="store_true",
                    help="chaos suite only: fixed-seed fault injection "
                         "across the serving stack (retry / degrade / "
                         "reconnect / quarantine / failover semantics)")
    ap.add_argument("--seeds", type=int, default=1, metavar="N",
                    help="with --chaos: sweep N FaultPlan seed offsets "
                         "(nightly-style race hunting) instead of the "
                         "single fixed seed baked into each test")
    ap.add_argument("--trace", action="store_true",
                    help="trace-export smoke only: chaos-retried "
                         "multi-partition query -> Perfetto JSON, "
                         "validated against the Chrome-trace-event "
                         "schema")
    ap.add_argument("--mesh", action="store_true",
                    help="mesh execution tier suite only: forces an "
                         "8-device virtual host mesh itself")
    ap.add_argument("--stream", action="store_true",
                    help="streaming suite only: bounded-ring "
                         "backpressure, slow-consumer stall aborts, "
                         "mid-stream resume, and the router's "
                         "windowed zero-copy relay")
    ap.add_argument("--zerocopy", action="store_true",
                    help="zero-copy serve path suite only: decoded-"
                         "plan cache, shm Arrow arena (sg/handle "
                         "byte-identity, lease reap), admission fast "
                         "path, chaos degradations")
    ap.add_argument("--profile", action="store_true",
                    help="profiler smoke only: the `python -m "
                         "blaze_tpu profile` CLI at c1/c4 against an "
                         "in-process service, report schema + "
                         "non-empty lock and stack sections asserted")
    ap.add_argument("--churn", action="store_true",
                    help="fleet-churn suite only: JOIN/LEAVE "
                         "membership, graceful drain, hot-result "
                         "replication, and the rolling-restart "
                         "subprocess e2e")
    ap.add_argument("--fleet", action="store_true",
                    help="fleet mesh tier suite only: 2-emulated-"
                         "host differentials, fleet.exchange chaos "
                         "ladder, SIGKILL failover, and the device-"
                         "claim plane")
    ap.add_argument("--tenancy", action="store_true",
                    help="multi-tenant isolation suite only: "
                         "weighted-fair admission, tenant budgets, "
                         "the noisy-neighbor pin on both wire "
                         "planes, and the router rate-limit / "
                         "retry-budget guards")
    args = ap.parse_args()
    rows = 20_000 if args.fast else args.rows

    ok = True
    t0 = time.time()

    if args.mesh:
        ok &= mesh_smoke()
        print(f"\n{'PASS' if ok else 'FAIL'} (mesh) "
              f"in {time.time() - t0:.0f}s", flush=True)
        return 0 if ok else 1

    if args.trace:
        ok &= trace_smoke()
        print(f"\n{'PASS' if ok else 'FAIL'} (trace) "
              f"in {time.time() - t0:.0f}s", flush=True)
        return 0 if ok else 1

    if args.stream:
        ok &= stream_smoke()
        print(f"\n{'PASS' if ok else 'FAIL'} (stream) "
              f"in {time.time() - t0:.0f}s", flush=True)
        return 0 if ok else 1

    if args.zerocopy:
        ok &= zerocopy_smoke()
        print(f"\n{'PASS' if ok else 'FAIL'} (zerocopy) "
              f"in {time.time() - t0:.0f}s", flush=True)
        return 0 if ok else 1

    if args.profile:
        ok &= profile_smoke()
        print(f"\n{'PASS' if ok else 'FAIL'} (profile) "
              f"in {time.time() - t0:.0f}s", flush=True)
        return 0 if ok else 1

    if args.churn:
        ok &= churn_smoke()
        print(f"\n{'PASS' if ok else 'FAIL'} (churn) "
              f"in {time.time() - t0:.0f}s", flush=True)
        return 0 if ok else 1

    if args.fleet:
        ok &= fleet_smoke()
        print(f"\n{'PASS' if ok else 'FAIL'} (fleet) "
              f"in {time.time() - t0:.0f}s", flush=True)
        return 0 if ok else 1

    if args.tenancy:
        ok &= tenancy_smoke()
        print(f"\n{'PASS' if ok else 'FAIL'} (tenancy) "
              f"in {time.time() - t0:.0f}s", flush=True)
        return 0 if ok else 1

    if args.chaos:
        for off in range(max(1, args.seeds)):
            ok &= chaos_smoke(seed_offset=off)
        print(f"\n{'PASS' if ok else 'FAIL'} (chaos x"
              f"{max(1, args.seeds)} seeds) "
              f"in {time.time() - t0:.0f}s", flush=True)
        return 0 if ok else 1

    if args.smoke:
        ok &= bench_smoke()
        ok &= service_smoke()
        # small seed sweep (ISSUE 5 satellite): the fixed-seed run plus
        # one shifted offset, so commit-time smoke already exercises a
        # second probabilistic firing sequence
        ok &= chaos_smoke()
        ok &= chaos_smoke(seed_offset=1)
        ok &= stream_smoke()
        ok &= zerocopy_smoke()
        ok &= tenancy_smoke()
        ok &= churn_smoke()
        ok &= obs_smoke()
        ok &= profile_smoke()
        ok &= mesh_smoke()
        ok &= fleet_smoke()
        ok &= regress_smoke()
        ok &= bench_regress_smoke()
        ok &= meshattr_regress_smoke()
        print(f"\n{'PASS' if ok else 'FAIL'} (smoke) "
              f"in {time.time() - t0:.0f}s", flush=True)
        return 0 if ok else 1

    ok &= bench_smoke()

    ok &= run(
        "core suite",
        ["tests/",
         "--ignore=tests/test_tpcds_queries.py",
         "--ignore=tests/test_tpcds_exchange.py"],
    )

    qnames = tpcds_query_names()
    for i, group in enumerate(chunks(qnames, TPCDS_CHUNK)):
        ok &= run(
            f"tpcds matrix {group[0]}..{group[-1]}",
            ["tests/test_tpcds_queries.py", "-k",
             k_expr(group, suffixed=True)],
            rows=rows,
        )

    # exchange flavor: correctness of the shuffle tier, not scale - 20k
    # rows keeps each chunk's 4-partition spill/merge cycle quick
    # (scale coverage comes from the in-memory matrix + test_shuffle)
    enames, pq_names = exchange_query_names()
    shuffle_fn = ("tests/test_tpcds_exchange.py::"
                  "test_query_through_shuffle_exchanges")
    parquet_fn = ("tests/test_tpcds_exchange.py::"
                  "test_query_through_parquet_and_exchanges")
    for group in chunks(enames, EXCHANGE_CHUNK):
        ok &= run(
            f"exchange matrix {group[0]}..{group[-1]}",
            [shuffle_fn, "-k", k_expr(group, suffixed=False)],
            rows=min(rows, 20_000),
        )
    # parquet-scan flavor: own process per query (the monsters sit
    # near the compile-volume cliff even alone; two flavors in one
    # process pushed q64 over it)
    for group in chunks(pq_names, EXCHANGE_CHUNK):
        ok &= run(
            f"exchange parquet {group[0]}..{group[-1]}",
            [parquet_fn, "-k", k_expr(group, suffixed=False)],
            rows=min(rows, 20_000),
        )

    if args.scale:
        # 2M store_sales rows (returns/web/catalog proportional) - the
        # reference CI's 1GB-dataset tier; monsters included
        scale_qs = ["q3", "q7", "q23", "q64", "q80", "q94"]
        for group in chunks(scale_qs, 2):
            ok &= run(
                f"scale 2M {group[0]}..{group[-1]}",
                ["tests/test_tpcds_queries.py", "-k",
                 k_expr(group, suffixed=True)],
                rows=2_000_000,
            )

    total = time.time() - t0
    print(f"\n{'GREEN' if ok else 'RED'} in {total:.0f}s")
    # cross-round observability (VERDICT r3 weak #6): one CSV row per
    # full-suite run so the wall-clock trend (and the effect of the
    # persistent compile cache) is a diff, not archaeology
    try:
        import csv
        import datetime

        commit = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=REPO,
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
        path = os.path.join(REPO, "benchmark-results",
                            "suite-times.csv")
        new = not os.path.exists(path)
        with open(path, "a", newline="") as f:
            w = csv.writer(f)
            if new:
                w.writerow(["date", "commit", "status", "total_s",
                            "args"])
            status = "GREEN" if ok else "RED"
            if RETRIED_CHUNKS:
                # flake archaeology across rounds is the point of this
                # file: record which chunks needed a fresh process
                status += (
                    " (segv-retried: " + ",".join(RETRIED_CHUNKS) + ")"
                )
            w.writerow(
                [datetime.date.today().isoformat(), commit, status,
                 round(total), " ".join(sys.argv[1:])]
            )
    except Exception as e:  # noqa: BLE001 - reporting must not fail CI
        print(f"(suite-times append failed: {e})")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
