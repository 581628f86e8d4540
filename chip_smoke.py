#!/usr/bin/env python3
"""Chip smoke: the served query path, once, on the chip.

    python chip_smoke.py [--rows N] [--seed S] [--mesh]

Starts `python -m blaze_tpu serve --port 0` as a child (the child owns
the chip; this parent is pinned to the CPU before `blaze_tpu` is
imported), writes a store_sales-shaped fact table plus date_dim / item
dimensions as parquet, and sends four BASELINE.json configs as task
blobs over `ServiceClient`: q6 (scan -> filter -> project -> keyless
aggregate), q1 (grouped aggregate), q3 (join + group + order + limit)
and the 200-way murmur3 repartition on the int64 customer key. Each is
sent three times with a different literal (the repartition: a different
output path), so every send is a device run and never a result-cache
serve. Every result is compared with a pandas / host-murmur3 oracle and
every send must show device work (`dispatches > 0`, `cache_hits == 0`,
not degraded, no retry).

The last line of stdout is one JSON object,
`{"ok": ..., "device": {"platform", "kind", "count"}}`, with the device
as the SERVING process reports it. `ok` is true only when every send
matched, every device check held, the server drained with rc 0 and its
platform is "tpu"; anything else exits 1. No option waives that.

`--mesh` (four chips; the driver never gives it) runs instead the
grouped aggregate through `serve --mesh` - one process driving all four
chips, `all_to_all` over ICI - and the same blob through
`--mesh-mode off` on one device, and no other phase; `count` is then 4.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
N_PARTS = 200
N_DATES = 366 * 5
N_ITEMS = 2000
N_CUSTOMERS = 5000
SENDS = 3
# sums of float32 columns accumulate in float64 on both sides (the
# engine's SUM(float) is float64; on the chip f64 is a double-single
# pair, ~49 mantissa bits): agreement is far inside 1e-6
RTOL = 1e-6


def gen_tables(n_rows: int, seed: int):
    """store_sales / date_dim / item in TPC-DS column types: int32
    keys, one int64 customer key, float32 money."""
    import numpy as np
    import pandas as pd

    rng = np.random.default_rng(seed)
    i32, f32 = np.int32, np.float32
    ss = pd.DataFrame({
        "ss_sold_date_sk": rng.integers(0, N_DATES, n_rows).astype(i32),
        "ss_item_sk": rng.integers(0, N_ITEMS, n_rows).astype(i32),
        "ss_customer_sk": rng.integers(
            0, N_CUSTOMERS, n_rows).astype(np.int64),
        "ss_quantity": rng.integers(1, 100, n_rows).astype(i32),
        "ss_sales_price": (rng.random(n_rows) * 200).astype(f32),
        "ss_ext_sales_price": (rng.random(n_rows) * 2000).astype(f32),
    })
    sk = np.arange(N_DATES)
    dd = pd.DataFrame({
        "d_date_sk": sk.astype(i32),
        "d_year": (1998 + sk // 366).astype(i32),
        "d_moy": ((sk % 366) // 31 + 1).astype(i32),
    })
    it = pd.DataFrame({
        "i_item_sk": np.arange(N_ITEMS, dtype=i32),
        "i_brand_id": rng.integers(1, 500, N_ITEMS).astype(i32),
        "i_manufact_id": rng.integers(0, 20, N_ITEMS).astype(i32),
    })
    return ss, dd, it


def write_parquet(df, path: str) -> str:
    import pyarrow as pa
    import pyarrow.parquet as pq

    pq.write_table(pa.Table.from_pandas(df, preserve_index=False), path)
    return path


# ---- the four configs: (blob, oracle check) per send ------------------

def _scan(path, columns):
    from blaze_tpu.ops.parquet_scan import FileRange, ParquetScanExec

    return ParquetScanExec([[FileRange(path)]], projection=columns)


def _blob(op) -> bytes:
    from blaze_tpu.plan.serde import task_to_proto

    return task_to_proto(op, 0)


def _frame(batches):
    import pyarrow as pa

    return pa.Table.from_batches(batches).to_pandas()


def q6(paths, tables, k):
    import numpy as np

    from blaze_tpu.exprs import AggExpr, AggFn, Col
    from blaze_tpu.ops import (
        AggMode, FilterExec, HashAggregateExec, ProjectExec,
    )
    from blaze_tpu.types import DataType

    price = (100.0, 110.0, 120.0)[k]
    op = HashAggregateExec(
        ProjectExec(
            FilterExec(
                _scan(paths["ss"], ["ss_quantity", "ss_sales_price"]),
                (Col("ss_sales_price") > price)
                & (Col("ss_quantity") < 50),
            ),
            [(Col("ss_sales_price")
              * Col("ss_quantity").cast(DataType.float32()), "rev")],
        ),
        keys=[],
        aggs=[(AggExpr(AggFn.SUM, Col("rev")), "t")],
        mode=AggMode.COMPLETE,
    )

    def check(batches):
        ss = tables["ss"]
        p, q = ss.ss_sales_price.values, ss.ss_quantity.values
        m = (p > np.float32(price)) & (q < 50)
        want = float(
            (p[m] * q[m].astype(np.float32)).astype(np.float64).sum()
        )
        got = _frame(batches)
        return (
            got.shape == (1, 1)
            and bool(np.isclose(got["t"][0], want, rtol=RTOL)),
            1,
        )

    return f"q6 price>{price:g}", _blob(op), check


def q1(paths, tables, k):
    import numpy as np

    from blaze_tpu.exprs import AggExpr, AggFn, Col
    from blaze_tpu.ops import AggMode, FilterExec, HashAggregateExec

    # close together on purpose: the final merge kernel's shape is the
    # bucket of sum(groups per batch), and on the chip a NEW shape is a
    # 100-280 s XLA sort compile (my chip run, PR 23). These three land
    # in one bucket at the default --rows/--seed, so the later sends
    # find the first one's program in the compile cache
    qty = (88, 84, 80)[k]
    op = HashAggregateExec(
        FilterExec(
            _scan(paths["ss"], ["ss_customer_sk", "ss_quantity",
                                "ss_ext_sales_price"]),
            Col("ss_quantity") < qty,
        ),
        keys=[(Col("ss_customer_sk"), "c")],
        aggs=[(AggExpr(AggFn.SUM, Col("ss_ext_sales_price")), "s")],
        mode=AggMode.COMPLETE,
    )

    def check(batches):
        ss = tables["ss"]
        f = ss[ss.ss_quantity < qty]
        want = (
            f.ss_ext_sales_price.astype(np.float64)
            .groupby(f.ss_customer_sk).sum().sort_index()
        )
        got = _frame(batches).sort_values("c").reset_index(drop=True)
        return (
            len(got) == len(want)
            and np.array_equal(got["c"].values, want.index.values)
            and bool(np.allclose(got["s"].values, want.values,
                                 rtol=RTOL)),
            len(got),
        )

    return f"q1 qty<{qty}", _blob(op), check


def q3(paths, tables, k):
    import numpy as np

    from blaze_tpu.exprs import AggExpr, AggFn, Col
    from blaze_tpu.ops import (
        AggMode, FilterExec, HashAggregateExec, HashJoinExec, JoinType,
        SortExec, SortKey, SortMergeJoinExec,
    )

    moy, manufact, limit = (11, 12, 10)[k], 7, 100
    dates = FilterExec(
        _scan(paths["dd"], ["d_date_sk", "d_year", "d_moy"]),
        Col("d_moy") == moy,
    )
    items = FilterExec(
        _scan(paths["it"], ["i_item_sk", "i_brand_id", "i_manufact_id"]),
        Col("i_manufact_id") == manufact,
    )
    sales = _scan(paths["ss"], ["ss_sold_date_sk", "ss_item_sk",
                                "ss_ext_sales_price"])
    # the BASELINE q3 config: sort-merge join with date_dim, then the
    # item dimension as a broadcast hash join (build side first)
    j = SortMergeJoinExec(
        sales, dates, ["ss_sold_date_sk"], ["d_date_sk"], JoinType.INNER,
    )
    j = HashJoinExec(
        items, j, ["i_item_sk"], ["ss_item_sk"], JoinType.INNER,
    )
    op = SortExec(
        HashAggregateExec(
            j,
            keys=[(Col("d_year"), "d_year"),
                  (Col("i_brand_id"), "brand_id")],
            aggs=[(AggExpr(AggFn.SUM, Col("ss_ext_sales_price")),
                   "sum_agg")],
            mode=AggMode.COMPLETE,
        ),
        [SortKey(Col("d_year")), SortKey(Col("sum_agg"), ascending=False),
         SortKey(Col("brand_id"))],
        fetch=limit,
    )

    def check(batches):
        ss, dd, it = tables["ss"], tables["dd"], tables["it"]
        mer = ss.merge(
            dd[dd.d_moy == moy], left_on="ss_sold_date_sk",
            right_on="d_date_sk",
        ).merge(
            it[it.i_manufact_id == manufact], left_on="ss_item_sk",
            right_on="i_item_sk",
        )
        want = (
            mer.assign(v=mer.ss_ext_sales_price.astype(np.float64))
            .groupby(["d_year", "i_brand_id"])["v"].sum().reset_index()
            .sort_values(["d_year", "v", "i_brand_id"],
                         ascending=[True, False, True])
            .head(limit).reset_index(drop=True)
        )
        got = _frame(batches)
        return (
            len(got) == len(want)
            and np.array_equal(got["d_year"].values, want.d_year.values)
            and np.array_equal(got["brand_id"].values,
                               want.i_brand_id.values)
            and bool(np.allclose(got["sum_agg"].values, want.v.values,
                                 rtol=RTOL)),
            len(got),
        )

    return f"q3 moy={moy}", _blob(op), check


def repartition(paths, tables, k):
    import numpy as np
    import pyarrow as pa

    from blaze_tpu.exprs import Col
    from blaze_tpu.io.ipc import partition_ranges, read_file_segment
    from blaze_tpu.ops import ShuffleWriterExec
    from blaze_tpu.ops.shuffle_writer import _chain_fixed
    from blaze_tpu.types import DataType

    data = os.path.join(paths["dir"], f"shuffle{k}.data")
    index = os.path.join(paths["dir"], f"shuffle{k}.index")
    op = ShuffleWriterExec(
        _scan(paths["ss"], None), [Col("ss_customer_sk")], N_PARTS,
        data, index,
    )

    def check(batches):
        ss = tables["ss"]
        # the host oracle: Spark murmur3 (seed 42) chained over the
        # int64 key, then pmod
        h = _chain_fixed(
            ss.ss_customer_sk.values, None, DataType.int64(),
            np.full(len(ss), 42, dtype=np.uint32),
        )
        pid = h.view(np.int32) % N_PARTS  # numpy % is already pmod
        want_counts = np.bincount(pid, minlength=N_PARTS)
        ranges = partition_ranges(index)
        ok = len(ranges) == N_PARTS and not batches
        cols = list(ss.columns)
        for p, (off, length) in enumerate(ranges):
            rbs = list(read_file_segment(data, off, length))
            ok = ok and sum(rb.num_rows for rb in rbs) == want_counts[p]
            if p in (0, N_PARTS // 2, N_PARTS - 1) and rbs:
                # whole rows of a few partitions: the scatter moved the
                # payload with its key
                got = pa.Table.from_batches(rbs).to_pandas()
                got = got.sort_values(cols).reset_index(drop=True)
                want = ss[pid == p].sort_values(cols).reset_index(
                    drop=True)
                ok = ok and got.equals(want)
        return bool(ok), int(want_counts.sum())

    return f"repartition {N_PARTS}-way #{k}", _blob(op), check


CONFIGS = (q6, q1, q3, repartition)


# ---- the serving child ------------------------------------------------

class Server:
    """`python -m blaze_tpu serve --port 0` as a child; its output goes
    to a log file (a pipe nobody drains would block it)."""

    def __init__(self, env: dict, workdir: str, extra=()):
        self.log_path = os.path.join(workdir, "serve.log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-u", "-m", "blaze_tpu", "serve",
             "--port", "0", *extra],
            stdout=self._log, stderr=subprocess.STDOUT, env=env, cwd=REPO,
        )

    def log_tail(self, n=4000) -> str:
        with open(self.log_path, "rb") as f:
            return f.read()[-n:].decode("utf-8", "replace")

    def address(self, timeout_s=300.0):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            m = re.search(r"listening on \('([^']+)', (\d+)\)",
                          self.log_tail(1 << 20))
            if m:
                return m.group(1), int(m.group(2))
            if self.proc.poll() is not None:
                break
            time.sleep(0.1)
        raise RuntimeError(
            f"serve did not start (rc={self.proc.poll()}):\n"
            + self.log_tail()
        )

    def stop(self) -> int:
        """SIGTERM drain; the exit code is part of the result."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()
        return self.proc.returncode


def send(client, name, blob, check) -> bool:
    """One send: run, compare with the oracle (outside the timing),
    then require device work. Prints the send's line."""
    t0 = time.perf_counter()
    st = client.submit(blob)
    batches = client.fetch(st["query_id"])
    wall = time.perf_counter() - t0
    poll = client.poll(st["query_id"])
    matched, rows = check(batches)
    device_ok = (
        poll["state"] == "DONE"
        and poll["dispatches"] > 0
        and poll.get("cache_hits", 0) == 0
        and not poll.get("degraded")
        and not poll.get("retries")
        and not poll.get("attempts")
    )
    print(
        f"send {name}: rows={rows} "
        f"{'matched' if matched else 'MISMATCH'} wall_s={wall:.3f} "
        f"dispatches={poll['dispatches']} "
        f"cache_hits={poll.get('cache_hits', 0)} "
        f"degraded={bool(poll.get('degraded'))} "
        f"retries={poll.get('retries', 0)} "
        f"device_ok={device_ok}",
        flush=True,
    )
    if not device_ok:
        print(client.report(st["query_id"]), flush=True)
    return matched and device_ok


def mesh_agg(paths, tables, seen):
    """The grouped-aggregate blob `serve --mesh` lowers onto the mesh
    (tests/test_mesh_exec.py's shape: int64 key, SUM + COUNT(*)), exact
    in integers. `seen` collects each run's canonical frame."""
    import numpy as np

    from blaze_tpu.exprs import AggExpr, AggFn, Col
    from blaze_tpu.ops import AggMode, HashAggregateExec

    op = HashAggregateExec(
        _scan(paths["ss"], ["ss_customer_sk", "ss_quantity"]),
        keys=[(Col("ss_customer_sk"), "k")],
        aggs=[(AggExpr(AggFn.SUM, Col("ss_quantity")), "s"),
              (AggExpr(AggFn.COUNT_STAR, None), "n")],
        mode=AggMode.COMPLETE,
    )

    def check(batches):
        ss = tables["ss"]
        g = ss.groupby("ss_customer_sk")["ss_quantity"]
        want = g.agg(["sum", "count"]).sort_index()
        got = _frame(batches).sort_values("k").reset_index(drop=True)
        seen.append(got)
        return (
            len(got) == len(want)
            and np.array_equal(got["k"].values, want.index.values)
            and np.array_equal(got["s"].values, want["sum"].values)
            and np.array_equal(got["n"].values, want["count"].values),
            len(got),
        )

    return "grouped aggregate", _blob(op), check


def serve_and_send(env, workdir, extra, sends):
    """One `serve` child for its whole life: start, run `sends`
    [(label, blob, check)], SIGTERM, check the exit code. Returns
    (ok, device, metrics exposition)."""
    from blaze_tpu.service.wire import ServiceClient

    ok, device, metrics = True, None, ""
    server = Server(env, workdir, extra)
    try:
        host, port = server.address()
        with ServiceClient(host, port, timeout=1100.0) as client:
            device = client.stats()["service"]["device"]
            print(f"device: {json.dumps(device)}", flush=True)
            for label, blob, check in sends:
                try:
                    ok = send(client, label, blob, check) and ok
                except Exception:  # noqa: BLE001 - report, go on, fail
                    traceback.print_exc(file=sys.stdout)
                    print(f"send {label}: FAILED", flush=True)
                    ok = False
            metrics = client.metrics()
    finally:
        rc = server.stop()
        print(f"server: SIGTERM drain rc={rc}", flush=True)
        if rc != 0 or not ok:
            print(server.log_tail(), flush=True)
    return ok and rc == 0, device, metrics


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    # 8,388,608 rows run right on a v5e but
    # take ~1,400 s cold, nearly all of it XLA sort compiles; the smoke
    # has 1,200 s, so the default is the size that was SEEN to finish
    # inside it: 1,048,576 rows, ~460 s cold (my chip run, PR 23)
    ap.add_argument("--rows", type=int, default=1 << 20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--mesh", action="store_true",
        help="four chips: the grouped aggregate through `serve --mesh` "
             "and, as what it is compared with, `--mesh-mode off` on "
             "one device - those two and no other phase",
    )
    args = ap.parse_args()

    # one process per chip: the child keeps the environment's backend,
    # this parent is pinned to the CPU before blaze_tpu imports jax
    child_env = dict(os.environ)
    child_env["PYTHONPATH"] = (
        REPO + os.pathsep + child_env.get("PYTHONPATH", "")
    )
    os.environ["JAX_PLATFORMS"] = "cpu"

    workdir = tempfile.mkdtemp(prefix="chip_smoke-")
    try:
        # `info` first, alone on the chip and gone before the server
        # starts: the native host library or its Python tier, what the
        # `auto` cores resolve to on this backend, the compile cache
        info = subprocess.run(
            [sys.executable, "-m", "blaze_tpu", "info"], env=child_env,
            cwd=REPO, capture_output=True, text=True, timeout=300,
        )
        if info.returncode != 0:
            raise RuntimeError(f"info failed:\n{info.stderr[-4000:]}")
        print(f"info: {json.dumps(json.loads(info.stdout))}", flush=True)
        t0 = time.perf_counter()
        ss, dd, it = gen_tables(args.rows, args.seed)
        tables = {"ss": ss, "dd": dd, "it": it}
        paths = {
            "dir": workdir,
            "ss": write_parquet(ss, os.path.join(workdir, "ss.parquet")),
            "dd": write_parquet(dd, os.path.join(workdir, "dd.parquet")),
            "it": write_parquet(it, os.path.join(workdir, "it.parquet")),
        }
        print(f"data: rows={args.rows} seed={args.seed} "
              f"gen+write_s={time.perf_counter() - t0:.1f}", flush=True)
        if args.mesh:
            ok, device = run_mesh(child_env, workdir, paths, tables)
        else:
            sends = []
            for config in CONFIGS:
                for k in range(SENDS):
                    name, blob, check = config(paths, tables, k)
                    sends.append((
                        f"{name} [{'first' if k == 0 else 'later'}]",
                        blob, check,
                    ))
            ok, device, _ = serve_and_send(child_env, workdir, (), sends)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    ok = ok and device["platform"] == "tpu"
    print(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


def run_mesh(env, workdir, paths, tables):
    """`serve --mesh` on every chip of the host (one process drives all
    four), then the same blob through `--mesh-mode off` on one device.
    The mesh op's failure ladder (parallel/mesh_exec.py) would finish a
    faulted query on one device and still answer right, so the run
    must also SHOW the exchange: all_to_all counted, nothing degraded."""
    seen = []
    name, blob, check = mesh_agg(paths, tables, seen)
    ok, device, metrics = serve_and_send(
        env, workdir, ("--mesh",), [(f"{name} [mesh]", blob, check)])
    exchanges = _metric(metrics, "blaze_mesh_exchange_total",
                        'kind="all_to_all"')
    mesh_degraded = _metric(metrics, "blaze_mesh_degraded_total")
    print(f"mesh: all_to_all exchanges={exchanges:g} "
          f"mesh_degraded={mesh_degraded:g} devices={device['count']}",
          flush=True)
    ok = (ok and exchanges > 0 and mesh_degraded == 0
          and device["count"] == 4)
    ok1, _, _ = serve_and_send(
        env, workdir, ("--mesh-mode", "off"),
        [(f"{name} [mesh off]", blob, check)])
    same = len(seen) == 2 and seen[0].equals(seen[1])
    print(f"mesh vs single device: {'equal' if same else 'DIFFERENT'}",
          flush=True)
    return ok and ok1 and same, device


def _metric(exposition: str, name: str, label: str = "") -> float:
    """Sum of one metric's series (those carrying `label`) in a
    Prometheus text exposition; 0 when absent."""
    samples = re.findall(
        rf"^{name}(\{{[^}}]*\}})?\s+(\S+)$", exposition, re.M)
    return sum(float(v) for labels, v in samples if label in labels)


if __name__ == "__main__":
    sys.exit(main())
