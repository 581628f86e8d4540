"""Engine benchmark: a TPC-DS-shaped query battery, end-to-end + staged.

What is measured (and why this shape): the reference's published numbers
are whole-workload TPC-DS costs vs vanilla Spark (BASELINE.md,
benchmark-results/20220522.md) - a battery of join/aggregate/window
queries over shared tables, not one scan. This bench mirrors that at
micro scale with five representative query shapes:

  e2e_scan_agg   cold path: parquet -> decode -> H2D -> filter/project/
                 aggregate through the PRODUCTION entry (a serialized
                 TaskDefinition via runtime/executor.execute_task),
                 chunk-streamed so host decode overlaps device compute.
  join_agg       item dimension join + per-brand revenue rollup
                 (q3/q55 shape) over device-resident tables.
  grouped_agg    4096-group multi-aggregate (sum/min/max/avg x 2 cols).
  window         per-partition rank + running sum (q47/q51/q67 shape).
  expr_chain     heavy scalar math (log/exp/sqrt chains) + reduction -
                 the VPU/MXU-friendly shape XLA fuses into one pass.

The battery queries run over HBM-resident tables ("staged", the warm
path every query after the first enjoys - the reference equivalently
re-reads OS-page-cached parquet through DataFusion each query) while the
CPU baselines run over RAM-resident pandas/numpy/pyarrow tables - the
same warm-vs-warm comparison. The CPU number per query is the FASTEST of
a numpy, a pandas, and a pyarrow/Acero implementation on this host (all
single-core: the host exposes one core, matching per-task parallelism of
the reference's executor model). Every engine result is asserted equal
to the CPU result before any timing is reported.

Headline: vs_baseline = geometric mean of per-query (cpu_time /
engine_time) across all five shapes; value = total engine rows/s over
the battery.

Launch: the parent starts ONE measurement child (`--child ROWS`) on
whatever backend the environment gives and passes its exit code on.
The child's last stdout line is the result JSON; it names the device
it ran on (`device`: platform, kind, count). There is no probe, no
retry and no fallback: asked for a chip (JAX_PLATFORMS=tpu) and given
none, jax fails at start-up and so does this. The parent never touches
a jax backend (a chip belongs to one process).
"""

import json
import math
import os
import subprocess
import sys
import time

ROWS = int(os.environ.get("BLAZE_BENCH_ROWS", 8 << 20))


def _repo_env(platform=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        os.path.dirname(os.path.abspath(__file__))
        + os.pathsep
        + env.get("PYTHONPATH", "")
    )
    # persistent XLA compilation cache, placed from outside where
    # JAX_COMPILATION_CACHE_DIR is set and at the checkout's fixed path
    # otherwise (the path `python -m blaze_tpu` and run_tests.py use)
    env.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "benchmarks", ".jax_cache",
        ),
    )
    if platform is not None:
        env["JAX_PLATFORMS"] = platform
    return env


def main():
    """One child, on the backend the environment gives; its last stdout
    line is the result and its exit code is ours."""
    return subprocess.run(
        [sys.executable, "-u", os.path.abspath(__file__), "--child",
         str(ROWS)],
        env=_repo_env(),
    ).returncode


# ---------------------------------------------------------------------------
# measurement child
# ---------------------------------------------------------------------------

def _device_hbm_bandwidth():
    """Peak HBM bandwidth (bytes/s) of the default device. Sources:
    public TPU spec sheets (v4 1228 GB/s, v5e 819, v5p 2765, v6e
    1640). A device kind that is not in the table is an error, not a
    default."""
    import jax

    kind = jax.devices()[0].device_kind.lower()
    for pat, bw in (
        ("v6", 1640e9), ("v5p", 2765e9), ("v5 lite", 819e9),
        ("v5litepod", 819e9), ("v5e", 819e9), ("v4", 1228e9),
        ("v3", 900e9), ("v2", 700e9),
    ):
        if pat in kind:
            return bw
    raise ValueError(f"no HBM bandwidth on record for {kind!r}")


def _tpu_core_probe(n=1 << 20):
    """On a real chip, time the scatter vs sort grouping cores and the
    packed vs ladder argsort at 1M rows - the measurement that decides
    next round's `auto` defaults (they currently guess sort on TPU).

    Each knob's two modes are also VALIDATED against each other
    (`<knob>_valid`): config.resolve_core_choice only trusts a probe
    whose results agreed on this chip, so a mis-compiling core can
    never be selected on timing alone. The artifact also records
    `device_kind` so a measurement from one chip generation cannot
    steer another. Returns a dict, or {} on any failure."""
    import numpy as np

    import jax

    out = {}
    try:
        out["device_kind"] = jax.devices()[0].device_kind
    except Exception:  # noqa: BLE001
        pass
    try:
        rng = np.random.default_rng(7)
        g = np.asarray(rng.integers(0, 4096, n), dtype=np.int32)
        v = (rng.random(n) * 100).astype(np.float32)
        for knob, env, modes in (
            ("group", "BLAZE_GROUP_CORE", ("scatter", "sort")),
            ("sort", "BLAZE_SORT_CORE", ("scatter", "sort")),
        ):
            results = {}
            for mode in modes:
                os.environ[env] = mode
                try:
                    if knob == "group":
                        from blaze_tpu.ops import hash_table as ht
                        import jax.numpy as jnp

                        gg = jnp.asarray(g)
                        vv = jnp.asarray(v)
                        live = jnp.ones(n, bool)
                        if mode == "scatter":
                            def fn(gg=gg, vv=vv):
                                slot, tab, _ = ht.group_slots(
                                    [(gg, None)], live, n, 1 << 17,
                                    max_rounds=16,
                                )
                                gid, ngr, _ = ht.dense_group_ids(
                                    slot, tab, live, n, 65536
                                )
                                return jax.ops.segment_sum(
                                    vv, gid, num_segments=65536
                                )
                        else:
                            def fn(gg=gg, vv=vv):
                                import jax.numpy as jnp

                                order = jnp.argsort(gg, stable=True)
                                sg = jnp.take(gg, order)
                                sv = jnp.take(vv, order)
                                b = jnp.concatenate(
                                    [jnp.ones(1, bool),
                                     sg[1:] != sg[:-1]]
                                )
                                gid = jnp.cumsum(
                                    b.astype(jnp.int32)) - 1
                                return jax.ops.segment_sum(
                                    sv, gid, num_segments=65536
                                )
                    else:
                        from blaze_tpu.ops.util import sort_indices
                        import jax.numpy as jnp

                        gg = jnp.asarray(g)

                        def fn(gg=gg):
                            return sort_indices(
                                [(gg, None, True, True)], n, n
                            )
                    f = jax.jit(fn)
                    r = jax.block_until_ready(f())
                    results[mode] = np.asarray(r)
                    t0 = time.perf_counter()
                    jax.block_until_ready(f())
                    out[f"{knob}_{mode}_s"] = round(
                        time.perf_counter() - t0, 4
                    )
                except Exception as e:  # noqa: BLE001
                    out[f"{knob}_{mode}_s"] = f"error: {e}"[:120]
                finally:
                    os.environ.pop(env, None)
            # cross-validate: both cores must agree on this chip
            # (group sums within float tolerance; sort permutations
            # exactly - stable sorts over identical keys are unique)
            if len(results) == 2:
                a, b = results["scatter"], results["sort"]
                try:
                    out[f"{knob}_valid"] = bool(
                        np.allclose(a, b, rtol=1e-5, atol=1e-3)
                        if a.dtype.kind == "f"
                        else np.array_equal(a, b)
                    )
                except Exception:  # noqa: BLE001
                    out[f"{knob}_valid"] = False
    except Exception:  # noqa: BLE001
        return out
    return out


def timed(fn, iters=None, warmup=1):
    """median-of-k with warm-up separated from steady state: a shared
    host core is noisy; the median reflects the steady state and the
    relative spread (max-min)/median makes each number's noise band
    part of the artifact (a 0.66x-vs-1.13x swing on one shape must be
    explainable from the JSON alone).

    Returns (median_s, rel_spread, k, out)."""
    k = iters or int(os.environ.get("BLAZE_BENCH_ITERS", 5))
    for _ in range(warmup):
        out = fn()  # warm-up: compile + cache fill, excluded from stats
    ts = []
    for _ in range(k):
        t0 = time.perf_counter()
        out = fn()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    median = ts[len(ts) // 2]
    spread = (ts[-1] - ts[0]) / median if median > 0 else 0.0
    return median, spread, k, out


def mesh_child(n_dev: int, n_rows: int) -> int:
    """One mesh_groupby_d{n} measurement (ISSUE 7): the SAME global
    grouped aggregate - a FINAL/exchange/PARTIAL sandwich over an
    8-partition in-memory table - run at the forced host device count
    the parent set via XLA_FLAGS. With 1 device the mesh pass is a
    no-op and the sandwich runs the file-shuffle exchange tier; with 8
    the planner lowers it to one pjit program exchanging partial
    states over the virtual ICI all_to_all. Results are asserted equal
    to a pandas oracle before timing; the steady state re-executes the
    warm plan (mesh: program compiled once, fresh execution per round
    - the battery's warm-kernel convention). Prints one JSON line."""
    import tempfile

    import numpy as np

    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)

    import pandas as pd
    import pyarrow as pa

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

    from blaze_tpu.batch import ColumnBatch
    from blaze_tpu.exprs import AggExpr, AggFn, Col
    from blaze_tpu.ops import AggMode, HashAggregateExec, MemoryScanExec
    from blaze_tpu.planner.distribute import (
        insert_exchanges,
        lower_plan_to_mesh,
    )
    from blaze_tpu.runtime.executor import run_plan

    assert len(jax.devices()) == n_dev, (
        f"expected {n_dev} forced host devices, saw "
        f"{len(jax.devices())}"
    )
    n_parts = 8
    per = max(1, n_rows // n_parts)
    rng = np.random.default_rng(17)
    parts, schema, frames = [], None, []
    for _ in range(n_parts):
        k = rng.integers(0, 4096, per).astype(np.int64)
        v = rng.integers(0, 1000, per).astype(np.int64)
        frames.append(pd.DataFrame({"k": k, "v": v}))
        cb = ColumnBatch.from_arrow(
            pa.record_batch({"k": k, "v": v})
        )
        schema = cb.schema
        parts.append([cb])
    shuffle_dir = tempfile.mkdtemp(prefix="blaze_mesh_bench_")

    def sandwich():
        return insert_exchanges(
            HashAggregateExec(
                MemoryScanExec(parts, schema),
                keys=[(Col("k"), "k")],
                aggs=[(AggExpr(AggFn.SUM, Col("v")), "s"),
                      (AggExpr(AggFn.COUNT_STAR, None), "n")],
                mode=AggMode.COMPLETE,
            ),
            n_parts, shuffle_dir=shuffle_dir,
        )

    lowered = lower_plan_to_mesh(sandwich(), mode="on")
    mesh_lowered = type(lowered).__name__ == "MeshGroupByExec"

    def run_once():
        if mesh_lowered:
            lowered._result = None  # fresh execution, warm program
            return run_plan(lowered)
        return run_plan(sandwich())

    got = (
        run_once().to_pandas().sort_values("k")
        .reset_index(drop=True)
    )
    want = (
        pd.concat(frames).groupby("k")
        .agg(s=("v", "sum"), n=("v", "size"))
        .reset_index().sort_values("k").reset_index(drop=True)
    )
    assert np.array_equal(got["k"], want["k"]), "mesh bench keys drift"
    assert np.array_equal(got["s"], want["s"]), "mesh bench sums drift"
    assert np.array_equal(got["n"], want["n"]), "mesh bench counts drift"
    # sub-phase attribution rollup (ISSUE 19 satellite): the timed
    # window runs against a private meshprof rollup, so each
    # mesh_groupby_d{n} measurement carries WHERE its wall went
    # (stage_in / trace / launch / sync / gather p50s) alongside the
    # wall itself, with a reconcile smoke check that the named
    # sub-phases actually cover the stage
    from blaze_tpu.obs import meshprof

    with meshprof.capture() as rol:
        med, spread, k_iters, _ = timed(run_once)
    attr = None
    if mesh_lowered:
        snap = next(iter(rol.snapshot().values()), None)
        if snap:
            subs = snap.get("subphases") or {}
            wall_p50 = (snap.get("stage_wall") or {}).get("p50", 0.0)
            sub_sum = sum(
                subs.get(n, {}).get("p50", 0.0)
                for n in meshprof.STAGE_SUBPHASES
            )
            attr = {
                "subphase_p50_s": {
                    n: subs[n]["p50"] for n in meshprof.SUBPHASES
                    if n in subs
                },
                "wall_p50": round(wall_p50, 6),
                "subphase_sum": round(sub_sum, 6),
                "coverage": round(sub_sum / wall_p50, 4)
                if wall_p50 > 0 else 0.0,
                "bytes_staged": snap.get("bytes_staged", 0),
            }
            # the rollup is pure host control flow; if the named
            # sub-phases stop covering the stage wall, a new
            # unattributed segment crept into the dispatch path
            cov = attr["coverage"]
            assert 0.6 <= cov <= 1.15, (
                f"mesh sub-phases no longer reconcile to the stage "
                f"wall: coverage {cov} (want 0.6..1.15)"
            )
    print(json.dumps({
        "median": round(med, 4),
        "spread": round(spread, 3),
        "k": k_iters,
        "n_devices": n_dev,
        "rows": per * n_parts,
        "groups": int(len(got)),
        "mesh_lowered": mesh_lowered,
        **({"attr": attr} if attr else {}),
    }), flush=True)
    return 0


def fleet_child(n_rows: int) -> int:
    """The mesh_fleet_h2 measurement (ISSUE 20): the SAME global
    grouped aggregate executed FLEET-WIDE across 2 emulated hosts -
    a second QueryService behind a real wire listener in this process
    stands in for the remote host, stage boundaries crossing the
    MESH_EXCHANGE DCN plane as framed Arrow-IPC segments, each host's
    stage running its own ICI mesh tier. Result asserted equal to the
    pandas oracle BEFORE timing; warm rounds re-execute the lowered
    plan ({median, spread, k}); the meshprof rollup attributes the
    stage wall with mesh_dcn next to the single-host sub-phases.
    Prints one JSON line."""
    import tempfile

    import numpy as np

    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)

    import pandas as pd
    import pyarrow as pa

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

    from blaze_tpu.batch import ColumnBatch
    from blaze_tpu.exprs import AggExpr, AggFn, Col
    from blaze_tpu.fleet.exec import FleetContext, FleetMeshExec
    from blaze_tpu.obs import meshprof
    from blaze_tpu.ops import AggMode, HashAggregateExec, MemoryScanExec
    from blaze_tpu.planner.distribute import (
        insert_exchanges,
        lower_plan_to_fleet,
    )
    from blaze_tpu.runtime.executor import run_plan
    from blaze_tpu.runtime.gateway import TaskGatewayServer
    from blaze_tpu.service import QueryService

    n_parts = 8
    per = max(1, n_rows // n_parts)
    rng = np.random.default_rng(17)
    parts, schema, frames = [], None, []
    for _ in range(n_parts):
        k = rng.integers(0, 4096, per).astype(np.int64)
        v = rng.integers(0, 1000, per).astype(np.int64)
        frames.append(pd.DataFrame({"k": k, "v": v}))
        cb = ColumnBatch.from_arrow(
            pa.record_batch({"k": k, "v": v})
        )
        schema = cb.schema
        parts.append([cb])
    shuffle_dir = tempfile.mkdtemp(prefix="blaze_fleet_bench_")

    def sandwich():
        return insert_exchanges(
            HashAggregateExec(
                MemoryScanExec(parts, schema),
                keys=[(Col("k"), "k")],
                aggs=[(AggExpr(AggFn.SUM, Col("v")), "s"),
                      (AggExpr(AggFn.COUNT_STAR, None), "n")],
                mode=AggMode.COMPLETE,
            ),
            n_parts, shuffle_dir=shuffle_dir,
        )

    peer = QueryService(enable_cache=False, enable_trace=False,
                        mesh_mode="on")
    srv = TaskGatewayServer(service=peer)
    srv.__enter__()
    try:
        host, port = srv.address
        fleet = FleetContext([f"{host}:{port}"])
        lowered = lower_plan_to_fleet(sandwich(), fleet, mode="on")
        fleet_lowered = isinstance(lowered, FleetMeshExec)

        def run_once():
            if fleet_lowered:
                lowered._result = None  # fresh execution, warm programs
                return run_plan(lowered)
            return run_plan(sandwich())

        got = (
            run_once().to_pandas().sort_values("k")
            .reset_index(drop=True)
        )
        want = (
            pd.concat(frames).groupby("k")
            .agg(s=("v", "sum"), n=("v", "size"))
            .reset_index().sort_values("k").reset_index(drop=True)
        )
        assert np.array_equal(got["k"], want["k"]), \
            "fleet bench keys drift"
        assert np.array_equal(got["s"], want["s"]), \
            "fleet bench sums drift"
        assert np.array_equal(got["n"], want["n"]), \
            "fleet bench counts drift"
        if fleet_lowered:
            assert not lowered._use_fallback, \
                "fleet bench degraded before timing"

        with meshprof.capture() as rol:
            med, spread, k_iters, _ = timed(run_once)
        if fleet_lowered:
            assert not lowered._use_fallback, \
                "fleet bench degraded mid-timing"
    finally:
        srv.__exit__(None, None, None)
        peer.close()

    attr = None
    snapshot = None
    if fleet_lowered:
        snap = rol.snapshot().get("fleet.groupby")
        if snap:
            subs = snap.get("subphases") or {}
            wall_p50 = (snap.get("stage_wall") or {}).get("p50", 0.0)
            sub_sum = sum(
                subs.get(n, {}).get("p50", 0.0)
                for n in meshprof.STAGE_SUBPHASES
            )
            attr = {
                "subphase_p50_s": {
                    n: subs[n]["p50"] for n in meshprof.SUBPHASES
                    if n in subs
                },
                "wall_p50": round(wall_p50, 6),
                "subphase_sum": round(sub_sum, 6),
                "coverage": round(sub_sum / wall_p50, 4)
                if wall_p50 > 0 else 0.0,
                "bytes_staged": snap.get("bytes_staged", 0),
            }
            cov = attr["coverage"]
            # DCN rounds overlap the coordinator's local launch
            # (peers are driven from threads), so the p50 sum can
            # legitimately exceed the stage wall - the upper bound
            # only guards against double-counted phases
            assert 0.6 <= cov <= 1.75, (
                f"fleet sub-phases no longer reconcile to the stage "
                f"wall: coverage {cov} (want 0.6..1.75)"
            )
            # regress-diffable per-phase rollup ({class: {phase:
            # {n,p50,p95,mean}}} - obs/phases.compare's input shape)
            snapshot = {"_all": {
                n: dict(subs[n]) for n in meshprof.SUBPHASES
                if n in subs
            }}
    print(json.dumps({
        "median": round(med, 4),
        "spread": round(spread, 3),
        "k": k_iters,
        "n_devices": int(jax.local_device_count()),
        "hosts": 2,
        "rows": per * n_parts,
        "groups": int(len(got)),
        "fleet_lowered": fleet_lowered,
        **({"attr": attr} if attr else {}),
        **({"phases": {"snapshot": snapshot}} if snapshot else {}),
    }), flush=True)
    return 0


def child(n_rows):
    import numpy as np

    import jax

    jax.config.update("jax_enable_x64", True)

    import pandas as pd
    import pyarrow as pa
    import pyarrow.compute as pc
    import pyarrow.parquet as pq

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

    from blaze_tpu.config import EngineConfig, set_config

    chunk = min(n_rows, 1 << 20)
    set_config(
        EngineConfig(
            batch_size=chunk,
            # intermediate buckets between 64k and 1M: the cold-scan
            # path's host filter pushdown compacts ~40%-selective
            # chunks to ~390k rows, which would otherwise pad straight
            # back to the 1M bucket and forfeit the compaction
            # sorted set: bucket_for picks the FIRST bucket >= n, so a
            # small dev-mode n_rows must not hide behind a larger
            # intermediate bucket
            shape_buckets=tuple(sorted(
                {4096, 65536, 262144, 524288, 1 << 20, chunk, n_rows}
            )),
        )
    )

    from blaze_tpu.exprs import AggExpr, AggFn, Col
    from blaze_tpu.exprs.ir import Literal
    from blaze_tpu.ops import (
        AggMode,
        FilterExec,
        HashAggregateExec,
        MemoryScanExec,
        ProjectExec,
    )
    from blaze_tpu.ops.joins import HashJoinExec, JoinType
    from blaze_tpu.ops.parquet_scan import FileRange, ParquetScanExec
    from blaze_tpu.ops.fused import fuse_pipelines
    from blaze_tpu.ops.window import WindowExec, WindowFn
    from blaze_tpu.ops.sort import SortKey
    from blaze_tpu.plan.serde import task_to_proto
    from blaze_tpu.runtime import dispatch
    from blaze_tpu.runtime.executor import execute_task, run_plan
    from blaze_tpu.batch import ColumnBatch
    from blaze_tpu.types import DataType

    rng = np.random.default_rng(42)
    n_items = 1 << 17
    n_part = 1 << 10  # window partitions
    item_sk = rng.integers(0, n_items, n_rows).astype(np.int32)
    qty = rng.integers(1, 10, n_rows).astype(np.int32)
    price = (rng.random(n_rows) * 100).astype(np.float32)
    part_sk = rng.integers(0, n_part, n_rows).astype(np.int32)
    i_item_sk = np.arange(n_items, dtype=np.int32)
    i_brand = rng.integers(0, 4096, n_items).astype(np.int32)

    queries = {}   # name -> dict(engine=..., cpu=..., rows=N)

    # ---- 1. cold end-to-end: parquet -> execute_task (q6 shape) ----
    path = "/tmp/blaze_bench_store_sales.parquet"
    pq.write_table(
        pa.table({"item": item_sk, "qty": qty, "price": price}), path,
        compression="zstd", row_group_size=1 << 20,
    )

    def q6_plan(scan):
        return HashAggregateExec(
            ProjectExec(
                FilterExec(
                    scan, (Col("price") > 50.0) & (Col("qty") < 8)
                ),
                [(Col("price") * Col("qty").cast(DataType.float32()),
                  "rev")],
            ),
            keys=[],
            aggs=[(AggExpr(AggFn.SUM, Col("rev")), "t"),
                  (AggExpr(AggFn.COUNT_STAR, None), "n")],
            mode=AggMode.COMPLETE,
        )

    blob = task_to_proto(
        q6_plan(ParquetScanExec([[FileRange(path)]])), 0
    )

    def e2e():
        rows = list(execute_task(blob))
        return (float(rows[0].column(0)[0].as_py()),
                int(rows[0].column(1)[0].as_py()))

    def e2e_cpu_numpy():
        tbl = pq.read_table(path, columns=["qty", "price"])
        p = tbl.column("price").to_numpy()
        q = tbl.column("qty").to_numpy()
        live = (p > 50.0) & (q < 8)
        rev = np.where(live, p * q.astype(np.float32), np.float32(0))
        return float(rev.sum(dtype=np.float64)), int(live.sum())

    def e2e_cpu_arrow():
        tbl = pq.read_table(path, columns=["qty", "price"])
        live = pc.and_(
            pc.greater(tbl.column("price"), 50.0),
            pc.less(tbl.column("qty"), 8),
        )
        f = tbl.filter(live)
        rev = pc.multiply(
            f.column("price"), pc.cast(f.column("qty"), pa.float32())
        )
        return float(pc.sum(rev).as_py() or 0.0), f.num_rows

    queries["e2e_scan_agg"] = {
        "engine": e2e, "cpu": [e2e_cpu_numpy, e2e_cpu_arrow],
        "rows": n_rows,
        "close": lambda a, b: (a[1] == b[1]
                               and abs(a[0] - b[0])
                               / max(abs(b[0]), 1) < 1e-3),
    }

    # ---- staged tables (one H2D each; the warm tier every later query
    # shares - symmetric with the CPU side's RAM-resident frames) ----
    fact_rb = pa.record_batch(
        {"item": item_sk, "qty": qty, "price": price, "part": part_sk}
    )
    fact_cb = ColumnBatch.from_arrow(fact_rb)
    item_rb = pa.record_batch({"i_item": i_item_sk, "i_brand": i_brand})
    item_cb = ColumnBatch.from_arrow(item_rb)
    fact_df = fact_rb.to_pandas()
    item_df = item_rb.to_pandas()
    fact_pa = pa.table(fact_rb)
    item_pa = pa.table(item_rb)

    def fact_scan():
        return MemoryScanExec([[fact_cb]], fact_cb.schema)

    def item_scan():
        return MemoryScanExec([[item_cb]], item_cb.schema)

    # ---- 2. dimension join + per-brand rollup (q3/q55 shape) ----
    join_plan = fuse_pipelines(HashAggregateExec(
        ProjectExec(
            HashJoinExec(
                item_scan(),
                ProjectExec(fact_scan(),
                            [(Col("item"), "item"),
                             (Col("price"), "price")]),
                [Col("i_item")], [Col("item")], JoinType.INNER,
            ),
            [(Col("i_brand"), "brand"), (Col("price"), "price")],
        ),
        keys=[(Col("brand"), "brand")],
        aggs=[(AggExpr(AggFn.SUM, Col("price")), "rev"),
              (AggExpr(AggFn.COUNT_STAR, None), "cnt")],
        mode=AggMode.COMPLETE,
    ))

    def join_engine():
        t = run_plan(join_plan)
        idx = np.asarray(t.column("brand"))
        rev = np.zeros(4096)
        cnt = np.zeros(4096, dtype=np.int64)
        rev[idx] = t.column("rev").to_numpy()
        cnt[idx] = t.column("cnt").to_numpy()
        return rev, cnt

    def join_cpu_pandas():
        m = fact_df.merge(item_df, left_on="item", right_on="i_item")
        g = m.groupby("i_brand")["price"].agg(["sum", "size"])
        rev = np.zeros(4096)
        cnt = np.zeros(4096, dtype=np.int64)
        rev[g.index.to_numpy()] = g["sum"].to_numpy()
        cnt[g.index.to_numpy()] = g["size"].to_numpy()
        return rev, cnt

    def join_cpu_arrow():
        j = fact_pa.join(item_pa, keys="item", right_keys="i_item",
                         join_type="inner")
        g = j.group_by("i_brand").aggregate(
            [("price", "sum"), ("price", "count")]
        )
        rev = np.zeros(4096)
        cnt = np.zeros(4096, dtype=np.int64)
        idx = g.column("i_brand").to_numpy()
        rev[idx] = g.column("price_sum").to_numpy()
        cnt[idx] = g.column("price_count").to_numpy()
        return rev, cnt

    queries["join_agg"] = {
        "engine": join_engine, "cpu": [join_cpu_pandas, join_cpu_arrow],
        "rows": n_rows,
        "close": lambda a, b: (np.allclose(a[0], b[0], rtol=1e-6)
                               and (a[1] == b[1]).all()),
    }

    # ---- 3. many-group multi-aggregate ----
    grp_expr = (Col("item") % Literal(4096, DataType.int32()))
    grouped_plan = fuse_pipelines(HashAggregateExec(
        ProjectExec(fact_scan(),
                    [(grp_expr, "g"), (Col("price"), "price"),
                     (Col("qty"), "qty")]),
        keys=[(Col("g"), "g")],
        aggs=[(AggExpr(AggFn.SUM, Col("price")), "s"),
              (AggExpr(AggFn.MIN, Col("price")), "lo"),
              (AggExpr(AggFn.MAX, Col("price")), "hi"),
              (AggExpr(AggFn.AVG, Col("qty")), "aq")],
        mode=AggMode.COMPLETE,
    ))

    def grouped_engine():
        t = run_plan(grouped_plan)
        idx = np.asarray(t.column("g"))
        out = np.zeros((4096, 4))
        out[idx, 0] = t.column("s").to_numpy()
        out[idx, 1] = t.column("lo").to_numpy()
        out[idx, 2] = t.column("hi").to_numpy()
        out[idx, 3] = t.column("aq").to_numpy()
        return out

    def grouped_cpu_pandas():
        g = fact_df.assign(g=fact_df["item"] % 4096).groupby("g").agg(
            s=("price", "sum"), lo=("price", "min"),
            hi=("price", "max"), aq=("qty", "mean"),
        )
        out = np.zeros((4096, 4))
        out[g.index.to_numpy()] = g.to_numpy()
        return out

    def grouped_cpu_numpy():
        g = item_sk.astype(np.int64) % 4096
        s = np.bincount(g, weights=price.astype(np.float64),
                        minlength=4096)
        cnt = np.bincount(g, minlength=4096)
        qs = np.bincount(g, weights=qty.astype(np.float64),
                         minlength=4096)
        order = np.argsort(g, kind="stable")
        gs = g[order]
        ps = price[order]
        bounds = np.searchsorted(gs, np.arange(4097))
        lo = np.full(4096, np.inf)
        hi = np.full(4096, -np.inf)
        mins = np.minimum.reduceat(
            ps, np.minimum(bounds[:-1], len(ps) - 1))
        maxs = np.maximum.reduceat(
            ps, np.minimum(bounds[:-1], len(ps) - 1))
        nz = bounds[:-1] < bounds[1:]
        lo[nz] = mins[nz]
        hi[nz] = maxs[nz]
        out = np.zeros((4096, 4))
        out[:, 0] = s
        out[:, 1] = np.where(nz, lo, 0.0)
        out[:, 2] = np.where(nz, hi, 0.0)
        with np.errstate(invalid="ignore"):
            out[:, 3] = np.where(cnt > 0, qs / np.maximum(cnt, 1), 0.0)
        return out

    queries["grouped_agg"] = {
        "engine": grouped_engine,
        "cpu": [grouped_cpu_pandas, grouped_cpu_numpy],
        "rows": n_rows,
        "close": lambda a, b: np.allclose(a, b, rtol=1e-5, atol=1e-8),
    }

    # ---- 4. window: per-partition rank + running revenue ----
    window_plan = fuse_pipelines(HashAggregateExec(
        WindowExec(
            ProjectExec(fact_scan(),
                        [(Col("part"), "part"), (Col("price"), "price")]),
            partition_by=[Col("part")],
            order_by=[SortKey(Col("price"), ascending=False)],
            functions=[WindowFn("row_number", None, "rk"),
                       WindowFn("sum", Col("price"), "run",
                                frame=("rows", None, 0))],
        ),
        keys=[],
        # checksum the window outputs so the whole N-row result need not
        # cross the wire: sum of ranks + sum of running sums
        aggs=[(AggExpr(AggFn.SUM, Col("rk").cast(DataType.float64())),
               "rksum"),
              (AggExpr(AggFn.SUM, Col("run")), "runsum")],
        mode=AggMode.COMPLETE,
    ))

    def window_engine():
        t = run_plan(window_plan)
        return (float(t.column("rksum")[0].as_py()),
                float(t.column("runsum")[0].as_py()))

    def window_cpu_pandas():
        df = fact_df[["part", "price"]]
        g = df.sort_values(["part", "price"],
                           ascending=[True, False]).groupby(
            "part", sort=False)["price"]
        rk = g.cumcount() + 1
        run = g.cumsum()
        return (float(rk.sum()), float(run.sum()))

    queries["window"] = {
        "engine": window_engine, "cpu": [window_cpu_pandas],
        "rows": n_rows,
        # rank sum is exact; the running f32 sum differs by
        # accumulation order between engine and pandas
        "close": lambda a, b: (abs(a[0] - b[0]) / max(abs(b[0]), 1)
                               < 1e-9
                               and abs(a[1] - b[1])
                               / max(abs(b[1]), 1) < 5e-5),
    }

    # ---- 5. heavy scalar expression chain + reduction ----
    from blaze_tpu.exprs.ir import ScalarFn

    rev = Col("price") * Col("qty").cast(DataType.float32())
    score = ScalarFn(
        "ln", (rev + Literal(1.0, DataType.float32()),)
    ) * ScalarFn(
        "sqrt",
        (ScalarFn(
            "abs", (Col("price") - Literal(50.0, DataType.float32()),)
        ),),
    )
    expr_plan = fuse_pipelines(HashAggregateExec(
        ProjectExec(fact_scan(), [(score.cast(DataType.float64()), "sc")]),
        keys=[],
        aggs=[(AggExpr(AggFn.SUM, Col("sc")), "s"),
              (AggExpr(AggFn.MAX, Col("sc")), "m")],
        mode=AggMode.COMPLETE,
    ))

    def expr_engine():
        t = run_plan(expr_plan)
        return (float(t.column("s")[0].as_py()),
                float(t.column("m")[0].as_py()))

    def expr_cpu_numpy():
        r = price * qty.astype(np.float32)
        sc = (np.log(r + np.float32(1.0))
              * np.sqrt(np.abs(price - np.float32(50.0)))).astype(
            np.float64)
        return float(sc.sum()), float(sc.max())

    queries["expr_chain"] = {
        "engine": expr_engine, "cpu": [expr_cpu_numpy],
        "rows": n_rows,
        "close": lambda a, b: (abs(a[0] - b[0]) / max(abs(b[0]), 1)
                               < 1e-4
                               and abs(a[1] - b[1])
                               / max(abs(b[1]), 1) < 1e-4),
    }

    # single-pass lower bound on bytes the device must touch per row
    # (input columns read once) - the numerator of the HBM-utilization
    # estimate below
    bytes_per_row = {
        "e2e_scan_agg": 8,     # qty i32 + price f32
        "join_agg": 16,        # item+price read, brand+match traffic
        "grouped_agg": 12,     # item+price+qty
        "window": 24,          # part+price through sort + scan passes
        "expr_chain": 8,       # qty+price
    }
    backend = jax.default_backend()
    # the CPU backend has no HBM: its shapes carry no utilization estimate
    hbm_bw = _device_hbm_bandwidth() if backend != "cpu" else None

    # ---- run the battery (one query's failure must not void the rest:
    # failed queries are reported by name and excluded from the
    # geomean, which the JSON flags). Each shape emits a PARTIAL line
    # as it completes, so a long run shows where it is. ----
    detail = {}
    ratios = []
    failed = []
    total_engine_s = 0.0
    battery_rows = 0
    for name, q in queries.items():
        try:
            t_eng, eng_spread, k, engine_out = timed(q["engine"])
            cpu_best = None
            cpu_spread = 0.0
            cpu_out = None
            for impl in q["cpu"]:
                t_c, s_c, _, out_c = timed(impl)
                if cpu_best is None or t_c < cpu_best:
                    cpu_best, cpu_spread, cpu_out = t_c, s_c, out_c
            if not q["close"](engine_out, cpu_out):
                raise AssertionError(
                    f"result mismatch: {engine_out!r} != {cpu_out!r}"
                )
        except Exception as e:  # noqa: BLE001 - reported, not fatal
            failed.append(name)
            detail[name] = {"error": f"{type(e).__name__}: {e}"[:300]}
            print(
                "PARTIAL " + json.dumps(
                    {"query": name, "backend": backend,
                     **detail[name]}
                ),
                flush=True,
            )
            continue
        ratio = cpu_best / t_eng
        ratios.append(ratio)
        total_engine_s += t_eng
        battery_rows += q["rows"]
        detail[name] = {
            "engine_s": round(t_eng, 4),
            "cpu_s": round(cpu_best, 4),
            "vs": round(ratio, 3),
            "median": round(t_eng, 4),
            "spread": round(max(eng_spread, cpu_spread), 3),
            "k": k,
        }
        # per-shape dispatch counts (ISSUE 13 satellite): the warm
        # dispatch/H2D/fetch profile recorded next to the timing, so a
        # fusion regression is a visible count diff between rounds,
        # not timing archaeology (counts are exact on a warmed query;
        # tests/test_dispatch_budget.py pins the same numbers)
        try:
            with dispatch.counting() as c:
                q["engine"]()
            detail[name]["dispatch_counts"] = dict(c.counts)
        except Exception:  # noqa: BLE001 - counts are advisory here
            pass
        # a shape whose run-to-run noise exceeds its margin over 1x
        # cannot support a "beats/loses to CPU" claim - flag it in the
        # artifact instead of leaving the discrepancy to archaeology
        if max(eng_spread, cpu_spread) > abs(ratio - 1.0):
            detail[name]["noisy"] = True
        if hbm_bw:
            detail[name]["hbm_util_est"] = round(
                q["rows"] * bytes_per_row.get(name, 8)
                / t_eng / hbm_bw,
                4,
            )
        print(
            "PARTIAL " + json.dumps(
                {"query": name, "backend": backend, **detail[name]}
            ),
            flush=True,
        )

    try:
        with dispatch.counting() as c:
            e2e()
        e2e_counts = c.counts
    except Exception:  # noqa: BLE001
        e2e_counts = {}

    # ---- observability overhead (ISSUE 4 satellite): the same
    # battery shape measured obs-off and obs-ON, so the perf
    # trajectory records what the obs layer costs. Obs-on now means
    # the FULL stack: tracing + the terminal-hook phase fold +
    # lock-wait accounting + the stack sampler running at its
    # serving default (ISSUE 15) - the <3% smoke pin prices all of
    # it. `median` is the obs-on number; overhead_pct the delta. ----
    try:
        from blaze_tpu.obs import contention as obs_contention
        from blaze_tpu.obs import phases as obs_phases
        from blaze_tpu.obs import sampler as obs_sampler
        from blaze_tpu.obs import trace as obs_trace

        g = queries["grouped_agg"]["engine"]
        off_med, off_spread, k_obs, _ = timed(g)
        # the terminal-hook phase fold rides the measurement (ISSUE
        # 11 satellite): the serving tier folds EVERY finished query,
        # so the shape must price it in - against a private rollup,
        # like the regress probe, to keep synthetic samples out of
        # the process-global STATS view
        fold_rollup = obs_phases.PhaseRollup()

        def traced():
            rec = obs_trace.begin_trace("bench-obs")
            with obs_trace.span("battery", rec=rec):
                out = g()
            rec.finish(state="DONE")
            fold_rollup.fold_phases(
                rec.phase_totals(obs_phases.SPAN_PHASE)
            )
            return out

        obs_trace.enable()
        obs_contention.enable()
        obs_sampler.start(hz=67.0)
        try:
            on_med, on_spread, _, _ = timed(traced)
        finally:
            obs_sampler.stop()
            obs_contention.disable()
            obs_trace.disable()
        detail["obs_overhead"] = {
            "median": round(on_med, 4),
            "median_off": round(off_med, 4),
            "spread": round(max(off_spread, on_spread), 3),
            "k": k_obs,
            "overhead_pct": (
                round((on_med / off_med - 1.0) * 100.0, 2)
                if off_med else 0.0
            ),
        }
        print(
            "PARTIAL " + json.dumps(
                {"query": "obs_overhead", "backend": backend,
                 **detail["obs_overhead"]}
            ),
            flush=True,
        )
    except Exception as e:  # noqa: BLE001 - the battery must survive
        detail["obs_overhead"] = {
            "error": f"{type(e).__name__}: {e}"[:300]
        }

    # ---- per-phase rollup (ISSUE 6): the phase probe's per-phase
    # p50s recorded in the artifact, so `python -m blaze_tpu regress
    # --bench OLD NEW` can diff two rounds PHASE BY PHASE - queue-wait
    # creep and decode regressions are invisible to the e2e medians
    # every other shape tracks. `median` is the probe's e2e p50 (the
    # {median, spread, k} contract the smoke asserts); `snapshot` is
    # the full per-class rollup regress consumes. ----
    try:
        from blaze_tpu.obs import phases as obs_phases

        ph_rounds = 5
        snap = obs_phases.run_probe(
            rounds=ph_rounds, rows=min(n_rows, 1 << 18)
        )
        e2e_ph = snap.get("_all", {}).get("e2e", {})
        p50 = float(e2e_ph.get("p50", 0.0))
        p95 = float(e2e_ph.get("p95", 0.0))
        detail["phases"] = {
            "median": round(p50, 4),
            "spread": round((p95 / p50 - 1.0) if p50 else 0.0, 3),
            "k": ph_rounds,
            "per_phase_p50": {
                ph: v.get("p50")
                for ph, v in snap.get("_all", {}).items()
            },
            "snapshot": snap,
        }
        print(
            "PARTIAL " + json.dumps(
                {"query": "phases", "backend": backend,
                 **{k: v for k, v in detail["phases"].items()
                    if k != "snapshot"}}
            ),
            flush=True,
        )
    except Exception as e:  # noqa: BLE001 - the battery must survive
        detail["phases"] = {
            "error": f"{type(e).__name__}: {e}"[:300]
        }

    # ---- mesh execution tier (ISSUE 7): the SAME global grouped
    # aggregate timed at 1 forced host device (single-device path -
    # the FINAL/exchange/PARTIAL file-shuffle sandwich) and at 8 (the
    # planner lowers the sandwich onto the mesh: one pjit program,
    # partial states exchanged over the virtual ICI all_to_all).
    # Each runs in its OWN subprocess because the device count
    # freezes at first backend init. Results are asserted equal
    # before timing, battery-style. ----
    for n_dev in (1, 8):
        name = f"mesh_groupby_d{n_dev}"
        try:
            mesh_rows = min(n_rows, 1 << 20)
            env = _repo_env(platform="cpu")
            flags = env.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                env["XLA_FLAGS"] = (
                    flags
                    + f" --xla_force_host_platform_device_count"
                      f"={n_dev}"
                ).strip()
            env.setdefault("BLAZE_BENCH_ITERS",
                           os.environ.get("BLAZE_BENCH_ITERS", "3"))
            # per-shape bound well inside smoke()'s 420s outer budget:
            # a hung compile lands as THIS shape's error, it must not
            # starve the rest of the battery (or the smoke parent)
            p = subprocess.run(
                [sys.executable, "-u", os.path.abspath(__file__),
                 "--mesh-child", str(n_dev), str(mesh_rows)],
                capture_output=True, text=True, timeout=150, env=env,
            )
            parsed = None
            for line in reversed(p.stdout.splitlines()):
                line = line.strip()
                if line.startswith("{"):
                    try:
                        parsed = json.loads(line)
                        break
                    except json.JSONDecodeError:
                        continue
            if p.returncode != 0 or parsed is None:
                tail = (p.stderr or "").strip().splitlines()
                raise RuntimeError(
                    f"mesh child rc={p.returncode} "
                    f"({tail[-1][:160] if tail else 'no stderr'})"
                )
            detail[name] = parsed
        except Exception as e:  # noqa: BLE001 - battery survives
            detail[name] = {"error": f"{type(e).__name__}: {e}"[:300]}
        print(
            "PARTIAL " + json.dumps(
                {"query": name, "backend": backend, **detail[name]}
            ),
            flush=True,
        )

    # ---- fleet mesh tier (ISSUE 20): the SAME grouped aggregate
    # executed across 2 EMULATED HOSTS - the second host a real
    # QueryService behind a wire listener inside the child process,
    # stage boundaries crossing the MESH_EXCHANGE DCN plane. Own
    # subprocess (8 forced devices), oracle-asserted before timing. ----
    name = "mesh_fleet_h2"
    try:
        fleet_rows = min(n_rows, 1 << 20)
        env = _repo_env(platform="cpu")
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        env.setdefault("BLAZE_BENCH_ITERS",
                       os.environ.get("BLAZE_BENCH_ITERS", "3"))
        p = subprocess.run(
            [sys.executable, "-u", os.path.abspath(__file__),
             "--fleet-child", str(fleet_rows)],
            capture_output=True, text=True, timeout=150, env=env,
        )
        parsed = None
        for line in reversed(p.stdout.splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    parsed = json.loads(line)
                    break
                except json.JSONDecodeError:
                    continue
        if p.returncode != 0 or parsed is None:
            tail = (p.stderr or "").strip().splitlines()
            raise RuntimeError(
                f"fleet child rc={p.returncode} "
                f"({tail[-1][:160] if tail else 'no stderr'})"
            )
        detail[name] = parsed
    except Exception as e:  # noqa: BLE001 - battery survives
        detail[name] = {"error": f"{type(e).__name__}: {e}"[:300]}
    print(
        "PARTIAL " + json.dumps(
            {"query": name, "backend": backend, **detail[name]}
        ),
        flush=True,
    )

    # ---- serving tier: queries/sec through the gateway service at
    # concurrency 1/4/16, with and without the plan-fingerprint result
    # cache (ISSUE 2 satellite). Same {median, spread, k} form as the
    # battery; qps derives from the median round time. A small
    # dedicated table keeps a single query cheap so the shape measures
    # SERVING overhead (admission, wire, cache), not kernel time. ----
    try:
        import threading

        from blaze_tpu.runtime.gateway import TaskGatewayServer
        from blaze_tpu.service import QueryService, ServiceClient

        n_svc = min(n_rows, 1 << 16)
        svc_path = "/tmp/blaze_bench_service.parquet"
        pq.write_table(
            pa.table({"item": item_sk[:n_svc], "qty": qty[:n_svc],
                      "price": price[:n_svc]}),
            svc_path, compression="zstd",
        )
        svc_blob = task_to_proto(
            q6_plan(ParquetScanExec([[FileRange(svc_path)]])), 0
        )
        per_client = 4

        def service_round(host, port, conc):
            errs = []

            def client():
                try:
                    with ServiceClient(host, port) as cl:
                        for _ in range(per_client):
                            cl.run(svc_blob)
                except Exception as e:  # noqa: BLE001
                    errs.append(repr(e))

            ts = [threading.Thread(target=client)
                  for _ in range(conc)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            if errs:
                raise RuntimeError(errs[0])

        from blaze_tpu.obs import contention as svc_contention

        for cache_on in (True, False):
            # the cached pass rides the full zero-copy serve path
            # (ISSUE 17): decoded-plan cache is on by default, and the
            # arena serves every repeat FETCH scatter-gather - the
            # c64 >= c16 smoke pin below is the "with arena" bar
            svc = QueryService(
                max_concurrency=16, enable_cache=cache_on,
                arena_bytes=(256 << 20) if cache_on else 0,
            )
            # lock-wait accounting rides the CACHED pass (the c16
            # collapse case, ISSUE 15): each concurrency entry
            # carries its own window's top blocking locks, so the
            # artifact attributes the qps curve, not just plots it
            if cache_on:
                svc_contention.enable()
            try:
                with TaskGatewayServer(service=svc) as srv:
                    host, port = srv.address
                    # c64 rides the async wire plane (event-loop verb
                    # serving): 64 blocked reader threads would thrash
                    # the threaded tier - the monotone-in-concurrency
                    # smoke pin guards exactly that collapse
                    for conc in (1, 4, 16, 64):
                        name = (
                            f"service_qps_c{conc}_"
                            f"{'cache' if cache_on else 'nocache'}"
                        )
                        try:
                            if cache_on:
                                svc_contention.reset_stats()
                            med, spread, k, _ = timed(
                                lambda: service_round(
                                    host, port, conc
                                ),
                                iters=3,
                            )
                            detail[name] = {
                                "median": round(med, 4),
                                "spread": round(spread, 3),
                                "k": k,
                                "qps": round(
                                    conc * per_client / med, 1
                                ),
                                "concurrency": conc,
                                "result_cache": cache_on,
                                "arena": cache_on,
                                "rows_per_query": n_svc,
                            }
                            if cache_on:
                                detail[name]["contention"] = (
                                    svc_contention.top_locks(3)
                                )
                        except Exception as e:  # noqa: BLE001
                            detail[name] = {
                                "error":
                                f"{type(e).__name__}: {e}"[:300]
                            }
                        print(
                            "PARTIAL " + json.dumps(
                                {"query": name,
                                 "backend": backend,
                                 **detail[name]}
                            ),
                            flush=True,
                        )
            finally:
                if cache_on:
                    svc_contention.disable()
                svc.close()
    except Exception as e:  # noqa: BLE001 - the battery must survive
        detail["service_qps"] = {
            "error": f"{type(e).__name__}: {e}"[:300]
        }

    # ---- multi-tenant fairness (ISSUE 18): two tenants through one
    # gateway, one flooding far past its budget. `median` is the
    # VICTIM tenant's per-query p50 while the flood runs; solo_median
    # is the same client alone on the same service. degradation =
    # median / solo_median is the smoke's <= 2x isolation bar: the
    # flooder's over-budget submits must be rejected at admission
    # (REJECTED_TENANT_BUDGET - the budget WORKING, not a failure),
    # never queued ahead of the victim. Victim rejections must be 0. ----
    try:
        import threading as _tf_threading

        from blaze_tpu.errors import (
            TenantBudgetError as _TfBudgetError,
        )
        from blaze_tpu.runtime.gateway import (
            TaskGatewayServer as _TfGateway,
        )
        from blaze_tpu.service import (
            QueryService as _TfService,
            ServiceClient as _TfClient,
        )

        tf_svc = _TfService(
            max_concurrency=4, enable_cache=False,
            tenant_config={
                "flood": {"max_queued": 4, "max_running": 1},
            },
        )
        tf_name = "tenant_fairness_qps"
        try:
            with _TfGateway(service=tf_svc) as tf_srv:
                tf_host, tf_port = tf_srv.address
                k_tf = int(os.environ.get("BLAZE_BENCH_ITERS", 3))
                n_victim = max(3, k_tf)

                def victim_p50():
                    ts = []
                    with _TfClient(tf_host, tf_port,
                                   tenant="victim") as cl:
                        for _ in range(n_victim):
                            t0 = time.perf_counter()
                            cl.run(svc_blob, use_cache=False)
                            ts.append(time.perf_counter() - t0)
                    ts.sort()
                    return ts

                victim_p50()  # warm-up: compile, excluded
                solo = victim_p50()
                solo_p50 = solo[len(solo) // 2]

                stop = _tf_threading.Event()
                flood_sent = [0]

                def flooder():
                    with _TfClient(tf_host, tf_port,
                                   tenant="flood",
                                   reconnect_attempts=1) as cl:
                        while not stop.is_set():
                            try:
                                cl.submit(svc_blob,
                                          use_cache=False)
                                flood_sent[0] += 1
                            except _TfBudgetError:
                                continue  # budget doing its job
                            except Exception:  # noqa: BLE001
                                time.sleep(0.01)

                floods = [
                    _tf_threading.Thread(target=flooder,
                                         daemon=True)
                    for _ in range(4)
                ]
                for t in floods:
                    t.start()
                time.sleep(0.2)  # let the flood saturate its budget
                try:
                    flooded = victim_p50()
                finally:
                    stop.set()
                    for t in floods:
                        t.join(timeout=5)
                fl_p50 = flooded[len(flooded) // 2]
                tstats = (tf_svc.stats().get("tenants") or {})
                detail[tf_name] = {
                    "median": round(fl_p50, 4),
                    "spread": round(
                        (flooded[-1] - flooded[0]) / fl_p50
                        if fl_p50 else 0.0, 3
                    ),
                    "k": n_victim,
                    "qps": round(1.0 / fl_p50, 1) if fl_p50 else 0,
                    "solo_median": round(solo_p50, 4),
                    "degradation": round(
                        fl_p50 / solo_p50 if solo_p50 else 0.0, 3
                    ),
                    "victim_rejections": int(
                        (tstats.get("victim") or {})
                        .get("rejected_budget", 0)
                    ),
                    "flood_rejections": int(
                        (tstats.get("flood") or {})
                        .get("rejected_budget", 0)
                    ),
                    "flood_submitted": int(
                        (tstats.get("flood") or {})
                        .get("submitted", 0)
                    ),
                }
        finally:
            tf_svc.close()
        print(
            "PARTIAL " + json.dumps(
                {"query": tf_name, "backend": backend,
                 **detail[tf_name]}
            ),
            flush=True,
        )
    except Exception as e:  # noqa: BLE001 - the battery must survive
        detail["tenant_fairness_qps"] = {
            "error": f"{type(e).__name__}: {e}"[:300]
        }

    # ---- streaming data plane (ISSUE 14): time-to-first-part vs
    # time-to-last-part through the gateway FETCH stream. A filter-
    # only plan over an 8-row-group parquet file keeps parts flowing
    # as execution produces them (an aggregate would collapse the
    # stream to one terminal part), so TTFP measures when the FIRST
    # batch crosses the wire while the query is still RUNNING - the
    # incremental-delivery win the materialized path cannot have
    # (there TTFP == TTLP by construction). Cache off: a ResultCache
    # hit feeds the ring all at once and would fake a perfect TTFP.
    # `median` is TTLP (the e2e cost, comparable across rounds);
    # ttfp_over_ttlp < 0.5 is the smoke's incremental-delivery bar. ----
    try:
        from blaze_tpu.config import get_config as _get_cfg
        from blaze_tpu.runtime.gateway import (
            TaskGatewayServer as _StGateway,
        )
        from blaze_tpu.service import (
            QueryService as _StService,
            ServiceClient as _StClient,
        )

        n_stream = n_rows
        stream_parts = 8
        stream_bs = max(4096, n_stream // stream_parts)
        st_path = "/tmp/blaze_bench_stream.parquet"
        pq.write_table(
            pa.table({"item": item_sk[:n_stream], "qty": qty[:n_stream],
                      "price": price[:n_stream]}),
            st_path, compression="zstd", row_group_size=stream_bs,
        )
        st_blob = task_to_proto(
            FilterExec(
                ParquetScanExec([[FileRange(st_path)]]),
                Col("price") > 1.0,
            ),
            0,
        )
        prev_cfg = _get_cfg()
        set_config(EngineConfig(batch_size=stream_bs))
        st_svc = _StService(max_concurrency=4)
        try:
            with _StGateway(service=st_svc) as st_srv:
                st_host, st_port = st_srv.address

                def stream_once():
                    with _StClient(st_host, st_port) as cl:
                        st = cl.submit(st_blob, use_cache=False)
                        t0 = time.perf_counter()
                        first = last = None
                        nparts = rows_seen = 0
                        for rb in cl.fetch_stream(st["query_id"]):
                            now = time.perf_counter()
                            if first is None:
                                first = now - t0
                            last = now - t0
                            nparts += 1
                            rows_seen += rb.num_rows
                    return first, last, nparts, rows_seen

                k_st = int(os.environ.get("BLAZE_BENCH_ITERS", 3))
                stream_once()  # warm-up: compile at the stream bucket
                samples = [stream_once() for _ in range(k_st)]
                samples.sort(key=lambda s: s[1])
                ttfp, ttlp, nparts, rows_seen = (
                    samples[len(samples) // 2]
                )
                lps = [s[1] for s in samples]
                spread = (
                    (lps[-1] - lps[0]) / ttlp if ttlp else 0.0
                )
                detail["stream_first_byte_8m"] = {
                    "median": round(ttlp, 4),
                    "spread": round(spread, 3),
                    "k": k_st,
                    "first_part_s": round(ttfp, 4),
                    "last_part_s": round(ttlp, 4),
                    "ttfp_over_ttlp": (
                        round(ttfp / ttlp, 3) if ttlp else 0.0
                    ),
                    "parts": nparts,
                    "rows": rows_seen,
                }
        finally:
            st_svc.close()
            set_config(prev_cfg)
        print(
            "PARTIAL " + json.dumps(
                {"query": "stream_first_byte_8m", "backend": backend,
                 **detail["stream_first_byte_8m"]}
            ),
            flush=True,
        )
    except Exception as e:  # noqa: BLE001 - the battery must survive
        detail["stream_first_byte_8m"] = {
            "error": f"{type(e).__name__}: {e}"[:300]
        }

    # ---- streaming under fan-in: 16 concurrent FETCH streams against
    # one gateway. The async wire plane serves every stream from the
    # loop (no reader/writer thread pairs), so first-part latency must
    # hold up under fan-in instead of queueing behind 15 blocked
    # threads. `median` is the worst client's TTLP (the e2e bar);
    # first_part_s is the median client's TTFP. ----
    try:
        import threading as _st_threading

        from blaze_tpu.config import get_config as _get_cfg16
        from blaze_tpu.runtime.gateway import (
            TaskGatewayServer as _St16Gateway,
        )
        from blaze_tpu.service import (
            QueryService as _St16Service,
            ServiceClient as _St16Client,
        )

        st16_conc = 16
        prev_cfg16 = _get_cfg16()
        set_config(EngineConfig(batch_size=stream_bs))
        st16_svc = _St16Service(max_concurrency=16)
        try:
            with _St16Gateway(service=st16_svc) as st16_srv:
                h16, p16 = st16_srv.address

                def stream_client(out, i):
                    try:
                        with _St16Client(h16, p16) as cl:
                            st = cl.submit(st_blob, use_cache=False)
                            t0 = time.perf_counter()
                            first = last = None
                            for _rb in cl.fetch_stream(
                                st["query_id"]
                            ):
                                now = time.perf_counter()
                                if first is None:
                                    first = now - t0
                                last = now - t0
                        out[i] = (first, last)
                    except Exception as e:  # noqa: BLE001
                        out[i] = e

                def fanin_round():
                    out = [None] * st16_conc
                    ts = [
                        _st_threading.Thread(
                            target=stream_client, args=(out, i)
                        )
                        for i in range(st16_conc)
                    ]
                    for t in ts:
                        t.start()
                    for t in ts:
                        t.join()
                    for o in out:
                        if isinstance(o, Exception):
                            raise o
                    firsts = sorted(o[0] for o in out)
                    lasts = sorted(o[1] for o in out)
                    return firsts[len(firsts) // 2], lasts[-1]

                k16 = int(os.environ.get("BLAZE_BENCH_ITERS", 3))
                fanin_round()  # warm-up
                rounds = sorted(
                    (fanin_round() for _ in range(k16)),
                    key=lambda r: r[1],
                )
                ttfp16, ttlp16 = rounds[len(rounds) // 2]
                worst = [r[1] for r in rounds]
                detail["stream_first_byte_c16"] = {
                    "median": round(ttlp16, 4),
                    "spread": round(
                        (worst[-1] - worst[0]) / ttlp16
                        if ttlp16 else 0.0, 3,
                    ),
                    "k": k16,
                    "first_part_s": round(ttfp16, 4),
                    "ttfp_over_ttlp": (
                        round(ttfp16 / ttlp16, 3) if ttlp16 else 0.0
                    ),
                    "concurrency": st16_conc,
                }
        finally:
            st16_svc.close()
            set_config(prev_cfg16)
        print(
            "PARTIAL " + json.dumps(
                {"query": "stream_first_byte_c16", "backend": backend,
                 **detail["stream_first_byte_c16"]}
            ),
            flush=True,
        )
    except Exception as e:  # noqa: BLE001 - the battery must survive
        detail["stream_first_byte_c16"] = {
            "error": f"{type(e).__name__}: {e}"[:300]
        }

    # ---- zero-copy serve path (ISSUE 17). Three repeat-plan shapes:
    # repeat_plan_qps hammers ONE warm plan through the wire (result
    # cache + decoded-plan cache + arena all hot: nothing decodes,
    # nothing executes, FETCH serves mmap frames scatter-gather);
    # decode_p50_repeat isolates the submit path (p50 submit_task wall
    # time on repeats, plan cache on vs off - the >= 10x decode-skip
    # acceptance bar); stream_first_byte_repeat re-FETCHes one DONE
    # result with the arena on vs off (same connection, same bytes:
    # the delta is pure re-encode cost the sg path skips). ----
    try:
        import threading as _zc_threading

        from blaze_tpu.runtime.gateway import (
            TaskGatewayServer as _ZcGateway,
        )
        from blaze_tpu.service import (
            QueryService as _ZcService,
            ServiceClient as _ZcClient,
        )

        zc_conc = 8
        zc_per_client = 8
        zc_svc = _ZcService(max_concurrency=16,
                            arena_bytes=256 << 20)
        try:
            with _ZcGateway(service=zc_svc) as zc_srv:
                zh, zp = zc_srv.address

                def zc_round():
                    errs = []

                    def client():
                        try:
                            with _ZcClient(zh, zp) as cl:
                                for _ in range(zc_per_client):
                                    cl.run(svc_blob)
                        except Exception as e:  # noqa: BLE001
                            errs.append(repr(e))

                    ts = [
                        _zc_threading.Thread(target=client)
                        for _ in range(zc_conc)
                    ]
                    for t in ts:
                        t.start()
                    for t in ts:
                        t.join()
                    if errs:
                        raise RuntimeError(errs[0])

                zc_round()  # warm: decode once, cache + publish
                med, spread, k, _ = timed(zc_round, iters=3)
                zc_pc = zc_svc.stats().get("plan_cache") or {}
                zc_ar = zc_svc.arena.stats() if zc_svc.arena else {}
                detail["repeat_plan_qps"] = {
                    "median": round(med, 4),
                    "spread": round(spread, 3),
                    "k": k,
                    "qps": round(zc_conc * zc_per_client / med, 1),
                    "concurrency": zc_conc,
                    "rows_per_query": n_svc,
                    "plan_cache_hits": zc_pc.get("hits", 0),
                    "plan_cache_misses": zc_pc.get("misses", 0),
                    "arena_sg_serves": zc_ar.get("sg_serves", 0),
                    "fast_path_serves": zc_svc.obs_counters[
                        "fast_path_serves"
                    ],
                }
        finally:
            zc_svc.close()
        print(
            "PARTIAL " + json.dumps(
                {"query": "repeat_plan_qps", "backend": backend,
                 **detail["repeat_plan_qps"]}
            ),
            flush=True,
        )
    except Exception as e:  # noqa: BLE001 - the battery must survive
        detail["repeat_plan_qps"] = {
            "error": f"{type(e).__name__}: {e}"[:300]
        }

    try:
        from blaze_tpu.service import (
            QueryService as _ZdService,
        )

        zd_reps = 20
        zd_p50 = {}       # plan_decode phase p50 per repeat
        zd_submit50 = {}  # submit_task wall p50 per repeat
        for zd_label, zd_entries in (("cache", 256), ("nocache", 0)):
            zd_svc = _ZdService(max_concurrency=2,
                                plan_cache_entries=zd_entries,
                                enable_trace=True)
            try:
                q = zd_svc.submit_task(svc_blob)
                if not q.wait(120.0):
                    raise RuntimeError("decode-shape warm timed out")
                zd_times = []
                zd_decode = []
                for _ in range(zd_reps):
                    zd_t0 = time.perf_counter()
                    q = zd_svc.submit_task(svc_blob)
                    zd_times.append(time.perf_counter() - zd_t0)
                    if not q.wait(120.0):
                        raise RuntimeError(
                            "decode-shape repeat timed out"
                        )
                    # the phase the plan cache exists to kill: sum of
                    # this repeat's plan_decode spans (0.0 on a hit -
                    # no protobuf walk happens at all)
                    zd_decode.append(sum(
                        (s["end_ns"] - s["start_ns"]) / 1e9
                        for s in q.tracer.to_dicts()
                        if s["name"] == "plan_decode"
                    ) if q.tracer is not None else 0.0)
                zd_times.sort()
                zd_decode.sort()
                zd_submit50[zd_label] = zd_times[len(zd_times) // 2]
                zd_p50[zd_label] = zd_decode[len(zd_decode) // 2]
            finally:
                zd_svc.close()
        detail["decode_p50_repeat"] = {
            # median = the CACHED repeat's plan_decode p50 (0.0 when
            # every repeat hits: the decode phase is GONE, which is
            # the acceptance bar - not merely faster)
            "median": round(zd_p50["cache"], 6),
            "spread": 0.0,
            "k": zd_reps,
            "plan_decode_p50_cache_s": round(zd_p50["cache"], 6),
            "plan_decode_p50_nocache_s": round(
                zd_p50["nocache"], 6
            ),
            "submit_p50_cache_s": round(zd_submit50["cache"], 6),
            "submit_p50_nocache_s": round(
                zd_submit50["nocache"], 6
            ),
            "decode_skip_speedup": round(
                zd_p50["nocache"] / max(zd_p50["cache"], 1e-9), 1
            ),
        }
        print(
            "PARTIAL " + json.dumps(
                {"query": "decode_p50_repeat", "backend": backend,
                 **detail["decode_p50_repeat"]}
            ),
            flush=True,
        )
    except Exception as e:  # noqa: BLE001 - the battery must survive
        detail["decode_p50_repeat"] = {
            "error": f"{type(e).__name__}: {e}"[:300]
        }

    try:
        from blaze_tpu.runtime.gateway import (
            TaskGatewayServer as _ZsGateway,
        )
        from blaze_tpu.service import (
            QueryService as _ZsService,
            ServiceClient as _ZsClient,
        )

        zs_svc = _ZsService(max_concurrency=4,
                            arena_bytes=256 << 20)
        zs_saved_arena = zs_svc.arena
        try:
            with _ZsGateway(service=zs_svc) as zs_srv:
                zs_h, zs_p = zs_srv.address
                with _ZsClient(zs_h, zs_p) as zs_cl:
                    zs_qid = zs_cl.submit(st_blob)["query_id"]
                    for _rb in zs_cl.fetch_stream(zs_qid):
                        pass
                    zs_deadline = time.monotonic() + 10.0
                    while (zs_svc.arena.stats()["segments"] == 0
                           and time.monotonic() < zs_deadline):
                        time.sleep(0.01)

                    def zs_refetch():
                        t0 = time.perf_counter()
                        first = last = None
                        for _rb in zs_cl.fetch_stream(zs_qid):
                            now = time.perf_counter()
                            if first is None:
                                first = now - t0
                            last = now - t0
                        return first, last

                    zs_k = int(
                        os.environ.get("BLAZE_BENCH_ITERS", 3)
                    )
                    zs_out = {}
                    for zs_mode in ("arena", "noarena"):
                        zs_svc.arena = (
                            zs_saved_arena if zs_mode == "arena"
                            else None
                        )
                        zs_refetch()  # warm
                        zs_samples = sorted(
                            (zs_refetch() for _ in range(zs_k)),
                            key=lambda s: s[1],
                        )
                        zs_out[zs_mode] = zs_samples[len(zs_samples)
                                                     // 2]
                on_first, on_last = zs_out["arena"]
                off_first, off_last = zs_out["noarena"]
                detail["stream_first_byte_repeat"] = {
                    "median": round(on_last, 4),
                    "spread": round(
                        abs(off_last - on_last)
                        / max(on_last, 1e-9), 3,
                    ),
                    "k": zs_k,
                    "first_part_arena_s": round(on_first, 5),
                    "first_part_noarena_s": round(off_first, 5),
                    "last_part_arena_s": round(on_last, 5),
                    "last_part_noarena_s": round(off_last, 5),
                    "arena_sg_serves": (
                        zs_saved_arena.stats()["sg_serves"]
                    ),
                }
        finally:
            zs_svc.arena = zs_saved_arena
            zs_svc.close()
        print(
            "PARTIAL " + json.dumps(
                {"query": "stream_first_byte_repeat",
                 "backend": backend,
                 **detail["stream_first_byte_repeat"]}
            ),
            flush=True,
        )
    except Exception as e:  # noqa: BLE001 - the battery must survive
        detail["stream_first_byte_repeat"] = {
            "error": f"{type(e).__name__}: {e}"[:300]
        }

    # ---- replica router: a repeated-query mix through TWO replicas,
    # affinity vs random placement (ISSUE 5 satellite). Every round
    # submits `rt_conc` repeats of `rt_distinct` fresh plans (fresh
    # literals per round, so each round is cache-cold fleet-wide).
    # Affinity placement sends every repeat of a plan to the replica
    # that ran it first - one execution per plan FLEET-wide, the rest
    # ResultCache hits; random placement splits repeats across both
    # replicas - one execution per plan PER REPLICA. The delta is pure
    # placement quality: same wire, same replicas, same plans. ----
    try:
        import threading as _rt_threading

        from blaze_tpu.router import Router, RouterServer
        from blaze_tpu.runtime.gateway import (
            TaskGatewayServer as _RtGateway,
        )
        from blaze_tpu.service import (
            QueryService as _RtService,
            ServiceClient as _RtClient,
        )

        rt_path = "/tmp/blaze_bench_router.parquet"
        n_rt = min(n_rows, 1 << 16)
        pq.write_table(
            pa.table({"item": item_sk[:n_rt], "qty": qty[:n_rt],
                      "price": price[:n_rt]}),
            rt_path, compression="zstd",
        )
        rt_distinct = 4   # distinct plans per round
        rt_conc = 4       # client threads = repeats of each plan
        rt_round_no = {"n": 0}

        def rt_blobs():
            """rt_distinct plans with round-unique filter literals:
            distinct content fingerprints every round, so each round
            measures a COLD fleet and the affinity-vs-random execution
            count difference, not steady-state cache hits."""
            rt_round_no["n"] += 1
            base = 20.0 + 0.001 * rt_round_no["n"]
            return [
                task_to_proto(
                    HashAggregateExec(
                        ProjectExec(
                            FilterExec(
                                ParquetScanExec(
                                    [[FileRange(rt_path)]]
                                ),
                                (Col("price") > base + 10.0 * j)
                                & (Col("qty") < 8),
                            ),
                            [(Col("price")
                              * Col("qty").cast(DataType.float32()),
                              "rev")],
                        ),
                        keys=[],
                        aggs=[(AggExpr(AggFn.SUM, Col("rev")), "t"),
                              (AggExpr(AggFn.COUNT_STAR, None), "n")],
                        mode=AggMode.COMPLETE,
                    ),
                    0,
                )
                for j in range(rt_distinct)
            ]

        def rt_round(host, port):
            blobs_i = rt_blobs()
            errs = []

            def client():
                try:
                    with _RtClient(host, port) as cl:
                        for b in blobs_i:
                            cl.run(b)
                except Exception as e:  # noqa: BLE001
                    errs.append(repr(e))

            ts = [_rt_threading.Thread(target=client)
                  for _ in range(rt_conc)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            if errs:
                raise RuntimeError(errs[0])

        for rt_mode in ("affinity", "random"):
            name = f"router_qps_r2_{rt_mode}"
            svcs = [_RtService(max_concurrency=8) for _ in range(2)]
            srvs = [_RtGateway(service=s).start() for s in svcs]
            router = Router(
                ["%s:%d" % s.address for s in srvs],
                placement=rt_mode,
                poll_interval_s=0.2,
                # no hot-result replication: it would warm the second
                # replica mid-round and blur the affinity-vs-random
                # comparison this shape exists to measure
                replicate_hot_k=0,
                start=True,
            )
            rs = RouterServer(router).start()
            try:
                router.registry.poll_now()
                med, spread, k, _ = timed(
                    lambda: rt_round(*rs.address), iters=3,
                )
                detail[name] = {
                    "median": round(med, 4),
                    "spread": round(spread, 3),
                    "k": k,
                    "qps": round(rt_distinct * rt_conc / med, 1),
                    "replicas": 2,
                    "distinct_plans": rt_distinct,
                    "repeats_per_plan": rt_conc,
                    "placement": rt_mode,
                    "rows_per_query": n_rt,
                }
            except Exception as e:  # noqa: BLE001
                detail[name] = {
                    "error": f"{type(e).__name__}: {e}"[:300]
                }
            finally:
                rs.stop()
                router.close()
                for s in srvs:
                    s.stop()
                for s in svcs:
                    s.close()
            print(
                "PARTIAL " + json.dumps(
                    {"query": name, "backend": backend,
                     **detail[name]}
                ),
                flush=True,
            )
    except Exception as e:  # noqa: BLE001 - the battery must survive
        detail["router_qps"] = {
            "error": f"{type(e).__name__}: {e}"[:300]
        }

    # ---- router-fronted c64 (the tentpole's fan-in bar at the relay
    # tier): 64 clients hammering ONE warm cached plan through the
    # router front. Both hops (client->router, router->replica) ride
    # the event-loop wire plane; the shape measures pure serving +
    # relay overhead at a concurrency the thread-per-connection front
    # could not hold without 64 parked reader threads. ----
    try:
        import threading as _rt64_threading

        from blaze_tpu.router import (
            Router as _Rt64Router,
            RouterServer as _Rt64Server,
        )
        from blaze_tpu.runtime.gateway import (
            TaskGatewayServer as _Rt64Gateway,
        )
        from blaze_tpu.service import (
            QueryService as _Rt64Service,
            ServiceClient as _Rt64Client,
        )

        rt64_conc = 64
        rt64_per_client = 2
        svcs64 = [
            _Rt64Service(max_concurrency=16) for _ in range(2)
        ]
        srvs64 = [
            _Rt64Gateway(service=s).start() for s in svcs64
        ]
        router64 = _Rt64Router(
            ["%s:%d" % s.address for s in srvs64],
            poll_interval_s=0.2,
            start=True,
        )
        rs64 = _Rt64Server(router64).start()
        try:
            router64.registry.poll_now()
            h64, p64 = rs64.address

            def rt64_round():
                errs = []

                def client():
                    try:
                        with _Rt64Client(h64, p64) as cl:
                            for _ in range(rt64_per_client):
                                cl.run(svc_blob)
                    except Exception as e:  # noqa: BLE001
                        errs.append(repr(e))

                ts = [
                    _rt64_threading.Thread(target=client)
                    for _ in range(rt64_conc)
                ]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                if errs:
                    raise RuntimeError(errs[0])

            rt64_round()  # warm-up: cache the plan fleet-wide
            med, spread, k, _ = timed(rt64_round, iters=3)
            detail["router_qps_c64"] = {
                "median": round(med, 4),
                "spread": round(spread, 3),
                "k": k,
                "qps": round(
                    rt64_conc * rt64_per_client / med, 1
                ),
                "concurrency": rt64_conc,
                "replicas": 2,
                "rows_per_query": n_svc,
            }
        finally:
            rs64.stop()
            router64.close()
            for s in srvs64:
                s.stop()
            for s in svcs64:
                s.close()
        print(
            "PARTIAL " + json.dumps(
                {"query": "router_qps_c64", "backend": backend,
                 **detail["router_qps_c64"]}
            ),
            flush=True,
        )
    except Exception as e:  # noqa: BLE001 - the battery must survive
        detail["router_qps_c64"] = {
            "error": f"{type(e).__name__}: {e}"[:300]
        }

    geomean = (
        math.exp(sum(math.log(r) for r in ratios) / len(ratios))
        if ratios else 0.0
    )
    devices = jax.devices()
    out = {
        "metric": "tpcds_shape_battery_rows_per_sec_chip",
        "value": (round(battery_rows / total_engine_s)
                  if total_engine_s else 0),
        "unit": "rows/s",
        "vs_baseline": round(geomean, 3),
        "backend": backend,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
        "rows_per_query": n_rows,
        "queries": detail,
        "e2e_dispatch_counts": e2e_counts,
        "tpu_core_probe": {},
        "hbm_bw_model": hbm_bw,
        "baseline": (
            "fastest of single-core numpy/pandas/pyarrow-Acero "
            "per query on this host; every engine result "
            "asserted equal before timing"
        ),
    }
    if failed:
        out["failed_queries"] = failed
        out["error"] = (
            f"{len(failed)}/{len(queries)} battery queries failed; "
            "geomean covers the rest"
        )
    # battery result is safe on the wire BEFORE the (minutes-long on a
    # cold chip) core probe - a kill mid-probe can't lose the battery
    print(json.dumps(out), flush=True)
    if backend != "cpu":
        probe = _tpu_core_probe()
        out["tpu_core_probe"] = probe
        if probe:
            # record the measurement so config.resolve_core_choice's
            # `auto` derives future core defaults from data, not the
            # guess (the driver commits round-end working-tree changes)
            try:
                bdir = os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "benchmarks",
                )
                os.makedirs(bdir, exist_ok=True)
                with open(
                    os.path.join(bdir, "tpu_core_probe.json"), "w"
                ) as f:
                    json.dump(probe, f, indent=1)
            except OSError:
                pass
        print(json.dumps(out), flush=True)


def fleet_multichip(out_path=None) -> int:
    """Versioned MULTICHIP_r*.json generator for the FLEET tier: run
    the mesh_fleet_h2 shape (2 emulated hosts, 8 forced devices, own
    subprocess) and write the artifact with the `queries.phases.
    snapshot` per-sub-phase rollup `regress --bench` diffs across
    rounds - mesh_dcn creep fails at commit time like every other
    phase."""
    import glob
    import re

    root = os.path.dirname(os.path.abspath(__file__))
    if out_path is None:
        n = 0
        for p in glob.glob(os.path.join(root, "MULTICHIP_r*.json")):
            m = re.search(r"MULTICHIP_r(\d+)\.json$", p)
            if m:
                n = max(n, int(m.group(1)))
        out_path = os.path.join(root, f"MULTICHIP_r{n + 1:02d}.json")
    rows = int(os.environ.get("BLAZE_BENCH_SMOKE_ROWS", 1 << 18))
    env = _repo_env(platform="cpu")
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    env.setdefault("BLAZE_BENCH_ITERS", "3")
    p = subprocess.run(
        [sys.executable, "-u", os.path.abspath(__file__),
         "--fleet-child", str(rows)],
        capture_output=True, text=True, timeout=300, env=env,
    )
    parsed = None
    for line in reversed(p.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                parsed = json.loads(line)
                break
            except json.JSONDecodeError:
                continue
    ok = (p.returncode == 0 and parsed is not None
          and parsed.get("fleet_lowered", False))
    doc = {
        "format": "blaze-multichip-fleet-v1",
        "n_devices": 8,
        "hosts": 2,
        "rc": p.returncode,
        "ok": bool(ok),
        "skipped": False,
        "tail": "\n".join(
            ((p.stdout or "") + (p.stderr or "")).splitlines()[-10:]
        ) + "\n",
        "queries": {
            "mesh_fleet_h2": parsed or {},
            # phases.snapshot at the regress --bench consumption path
            "phases": (parsed or {}).get("phases") or {},
        },
    }
    if out_path == "-":
        print(json.dumps(doc, indent=2))
    else:
        with open(out_path, "w") as f:
            f.write(json.dumps(doc, indent=2) + "\n")
        print(f"wrote {out_path}", file=sys.stderr)
    return 0 if ok else 1


def smoke():
    """Commit-time bench guard (<= 60s): run the CPU battery at small
    rows and assert (a) a parseable JSON result line, (b) every shape
    succeeded with its oracle check, (c) the e2e dispatch budget holds.
    Wired into run_tests.py so bench breakage fails at commit time, not
    at round end. Exit code 0 iff all assertions hold."""
    rows = int(os.environ.get("BLAZE_BENCH_SMOKE_ROWS", 1 << 18))
    env = _repo_env(platform="cpu")
    env["BLAZE_BENCH_ITERS"] = env.get("BLAZE_BENCH_ITERS", "3")
    t0 = time.monotonic()
    try:
        out = subprocess.run(
            [sys.executable, "-u", os.path.abspath(__file__),
             "--child", str(rows)],
            # the battery + the two mesh_groupby_d{1,8} subprocesses
            # + the c64 / fan-in serving shapes
            capture_output=True, text=True, timeout=540, env=env,
        )
    except subprocess.TimeoutExpired as e:
        # a wedged child must fail the smoke as a PROBLEM with
        # whatever partial output streamed, not as a traceback
        print(json.dumps({
            "smoke": "FAIL",
            "elapsed_s": round(time.monotonic() - t0, 1),
            "rows": rows,
            "problems": [f"child timed out after {e.timeout:.0f}s"],
            "result": None,
        }), flush=True)
        return 1
    result = None
    for line in reversed(out.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                result = json.loads(line)
                break
            except json.JSONDecodeError:
                continue
    problems = []
    if out.returncode != 0:
        tail = (out.stderr or "").strip().splitlines()
        problems.append(
            f"child rc={out.returncode} "
            f"({tail[-1][:200] if tail else 'no stderr'})"
        )
    if result is None:
        problems.append("no parseable JSON line on stdout")
    else:
        if result.get("failed_queries"):
            problems.append(
                f"failed queries: {result['failed_queries']}"
            )
        for name, d in (result.get("queries") or {}).items():
            for field in ("median", "spread", "k"):
                if "error" not in d and field not in d:
                    problems.append(f"{name}: missing {field!r}")
        counts = result.get("e2e_dispatch_counts") or {}
        if not counts:
            problems.append("no e2e_dispatch_counts in artifact")
        elif counts.get("dispatches", 99) > 8:
            problems.append(
                f"e2e dispatch budget blown: {counts} (want <= 8)"
            )
        # per-shape counts (ISSUE 13): every battery shape records its
        # warm dispatch profile; the relational-core shapes must hold
        # the fused 1-dispatch budget the tests pin
        for name in ("e2e_scan_agg", "join_agg", "grouped_agg",
                     "window", "expr_chain"):
            d = (result.get("queries") or {}).get(name) or {}
            if "error" in d:
                continue
            dc = d.get("dispatch_counts")
            if not dc:
                problems.append(f"{name}: missing dispatch_counts")
            elif name in ("join_agg", "grouped_agg") \
                    and dc.get("dispatches", 99) > 1:
                problems.append(
                    f"{name}: fused dispatch budget blown: {dc} "
                    "(want 1 warm dispatch)"
                )
        # mesh attribution rollup (ISSUE 19): a lowered mesh shape
        # must carry its sub-phase split, and the named sub-phases
        # must reconcile to the stage wall (the child asserts the
        # tight band; this guards the field going missing entirely)
        mq = (result.get("queries") or {}).get("mesh_groupby_d8") or {}
        if mq and "error" not in mq and mq.get("mesh_lowered"):
            mattr = mq.get("attr") or {}
            if not mattr.get("subphase_p50_s"):
                problems.append(
                    "mesh_groupby_d8: lowered but no attr rollup"
                )
            elif not 0.6 <= float(mattr.get("coverage", 0.0)) <= 1.15:
                problems.append(
                    f"mesh_groupby_d8: sub-phase coverage "
                    f"{mattr.get('coverage')} outside 0.6..1.15"
                )
        # fleet tier (ISSUE 20): the 2-emulated-host shape must run
        # the DCN path (not silently fall back) and attribute its
        # stage wall with mesh_dcn present
        fq = (result.get("queries") or {}).get("mesh_fleet_h2") or {}
        if fq and "error" not in fq:
            if not fq.get("fleet_lowered"):
                problems.append(
                    "mesh_fleet_h2: fleet pass did not lower"
                )
            else:
                fattr = fq.get("attr") or {}
                if "mesh_dcn" not in (
                    fattr.get("subphase_p50_s") or {}
                ):
                    problems.append(
                        "mesh_fleet_h2: no mesh_dcn attribution"
                    )
                elif not 0.6 <= float(
                    fattr.get("coverage", 0.0)
                ) <= 1.75:
                    # upper bound is looser than the single-host
                    # shape: DCN rounds overlap the local launch
                    problems.append(
                        f"mesh_fleet_h2: sub-phase coverage "
                        f"{fattr.get('coverage')} outside 0.6..1.75"
                    )
        elif fq:
            problems.append(
                f"mesh_fleet_h2 failed: {fq.get('error')}"
            )
        stq = (result.get("queries") or {}).get(
            "stream_first_byte_8m") or {}
        if stq and "error" not in stq:
            # incremental-delivery bar (ISSUE 14): the first part must
            # cross the wire well before the stream finishes - under
            # materialized delivery TTFP == TTLP by construction, so
            # a ratio creeping toward 1.0 means streaming regressed
            # back to buffer-then-send
            st_ratio = float(stq.get("ttfp_over_ttlp", 1.0))
            if st_ratio >= 0.5:
                problems.append(
                    f"stream TTFP/TTLP {st_ratio} >= 0.5 "
                    f"(first part no longer beats the full stream; "
                    f"parts={stq.get('parts')})"
                )
        elif stq:
            problems.append(
                f"stream_first_byte_8m failed: {stq.get('error')}"
            )
        # zero-copy serve path (ISSUE 17): the decode-skip acceptance
        # bar - the plan_decode phase p50 on repeat submits must drop
        # >= 10x with the decoded-plan cache (in practice to 0.0: a
        # hit never walks the protobuf at all, so the phase vanishes)
        zdq = (result.get("queries") or {}).get(
            "decode_p50_repeat") or {}
        if zdq and "error" not in zdq:
            zd_cache = float(zdq.get("plan_decode_p50_cache_s", 1.0))
            zd_nocache = float(
                zdq.get("plan_decode_p50_nocache_s", 0.0)
            )
            if zd_cache > zd_nocache / 10.0:
                problems.append(
                    f"plan-cache decode skip insufficient: repeat "
                    f"plan_decode p50 {zd_cache}s with cache vs "
                    f"{zd_nocache}s without (want >= 10x drop)"
                )
        elif zdq:
            problems.append(
                f"decode_p50_repeat failed: {zdq.get('error')}"
            )
        zrq = (result.get("queries") or {}).get(
            "repeat_plan_qps") or {}
        if zrq and "error" in zrq:
            problems.append(
                f"repeat_plan_qps failed: {zrq['error']}"
            )
        zsq = (result.get("queries") or {}).get(
            "stream_first_byte_repeat") or {}
        if zsq and "error" in zsq:
            problems.append(
                f"stream_first_byte_repeat failed: {zsq['error']}"
            )
        # monotone-in-concurrency pin (async wire plane): cached qps
        # must not DROP as clients pile on - c1 -> c4 -> c16
        # non-decreasing, and c64 holds >= 0.8x of c16. Each step is
        # spread-guarded: on a noisy host the qps drop must also
        # exceed the two rounds' own noise band before it reddens the
        # smoke. A violation here is the thread-per-connection
        # collapse shape (parked readers starving the accept loop).
        qshapes = {
            c: (result.get("queries") or {}).get(
                f"service_qps_c{c}_cache"
            ) or {}
            for c in (1, 4, 16, 64)
        }
        if all(q and "error" not in q for q in qshapes.values()):
            def _qps(c):
                return float(qshapes[c].get("qps", 0.0))

            def _noise(a, b):
                # qps noise band: spread is on round TIME; qps scales
                # inversely, so the band is qps * spread of each side
                return (
                    _qps(a) * float(qshapes[a].get("spread", 0.0))
                    + _qps(b) * float(qshapes[b].get("spread", 0.0))
                )

            for lo, hi in ((1, 4), (4, 16)):
                if _qps(hi) < _qps(lo) \
                        and (_qps(lo) - _qps(hi)) > _noise(lo, hi):
                    problems.append(
                        f"cached qps not monotone: c{hi} "
                        f"{_qps(hi)} < c{lo} {_qps(lo)} beyond "
                        "noise (concurrency collapse)"
                    )
            floor64 = 0.8 * _qps(16)
            if _qps(64) < floor64 \
                    and (floor64 - _qps(64)) > _noise(16, 64):
                problems.append(
                    f"c64 qps {_qps(64)} < 0.8x c16 "
                    f"({round(floor64, 1)}) beyond noise "
                    "(fan-in collapse at 64 connections)"
                )
        else:
            for c, q in qshapes.items():
                if q and "error" in q:
                    problems.append(
                        f"service_qps_c{c}_cache failed: "
                        f"{q['error']}"
                    )
        # router-fronted fan-in (the tentpole's relay-tier bar): the
        # shape records {"error": ...} instead of raising, so an
        # erroring c64 relay (e.g. the cross-tier dispatch-pool
        # deadlock) must be surfaced here, not silently skipped
        rq64 = (result.get("queries") or {}).get("router_qps_c64") or {}
        if not rq64:
            problems.append("router_qps_c64 missing from artifact")
        elif "error" in rq64:
            problems.append(
                f"router_qps_c64 failed: {rq64['error']}"
            )
        # multi-tenant isolation bar (ISSUE 18): a tenant flooding
        # past its admission budget must not degrade the victim
        # tenant's p50 beyond 2x its solo baseline, and the victim
        # must see ZERO budget rejections - its traffic never
        # competes with the flooder's over-budget backlog. Spread-
        # guarded like the qps pins: the degradation must exceed the
        # run's own noise band before it reddens the smoke.
        tfq = (result.get("queries") or {}).get(
            "tenant_fairness_qps") or {}
        if tfq and "error" not in tfq:
            deg = float(tfq.get("degradation", 0.0))
            tf_noise = float(tfq.get("spread", 0.0))
            if deg > 2.0 and (deg - 2.0) > tf_noise:
                problems.append(
                    f"tenant isolation broken: victim p50 degraded "
                    f"{deg}x under flood (want <= 2x solo; "
                    f"solo {tfq.get('solo_median')}s vs "
                    f"flooded {tfq.get('median')}s)"
                )
            if int(tfq.get("victim_rejections", 0)) != 0:
                problems.append(
                    f"victim tenant saw "
                    f"{tfq['victim_rejections']} budget rejections "
                    "(flooder's backlog leaked into the victim's "
                    "budget)"
                )
        elif tfq:
            problems.append(
                f"tenant_fairness_qps failed: {tfq.get('error')}"
            )
        obs = (result.get("queries") or {}).get("obs_overhead") or {}
        if obs and "error" not in obs:
            # obs-overhead pin (ISSUE 11 satellite, re-pinned from
            # the BENCH_r08 8.3% creep): tracing + the terminal-hook
            # fold must stay within 3% of obs-off on the battery
            # shape. Spread-guarded - on a noisy host the on/off
            # delta must also exceed the run's own noise band before
            # it can redden the smoke
            pct = float(obs.get("overhead_pct", 0.0))
            on = float(obs.get("median", 0.0))
            off = float(obs.get("median_off", 0.0))
            noise = float(obs.get("spread", 0.0)) * max(off, 1e-9)
            if pct > 3.0 and (on - off) > noise:
                problems.append(
                    f"obs overhead {pct}% > 3% bar "
                    f"(on {on}s vs off {off}s, noise {noise:.4f}s)"
                )
    status = "OK" if not problems else "FAIL"
    print(json.dumps({
        "smoke": status,
        "elapsed_s": round(time.monotonic() - t0, 1),
        "rows": rows,
        "problems": problems,
        "result": result,
    }), flush=True)
    return 0 if not problems else 1


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        child(int(sys.argv[2]))
    elif len(sys.argv) > 1 and sys.argv[1] == "--mesh-child":
        sys.exit(mesh_child(int(sys.argv[2]), int(sys.argv[3])))
    elif len(sys.argv) > 1 and sys.argv[1] == "--fleet-child":
        sys.exit(fleet_child(int(sys.argv[2])))
    elif len(sys.argv) > 1 and sys.argv[1] == "--fleet-multichip":
        sys.exit(fleet_multichip(
            sys.argv[2] if len(sys.argv) > 2 else None
        ))
    elif len(sys.argv) > 1 and sys.argv[1] == "--smoke":
        sys.exit(smoke())
    else:
        sys.exit(main())
