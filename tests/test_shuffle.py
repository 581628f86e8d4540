"""Shuffle + segmented IPC format tests: round trips, the on-disk contract,
spill merge, partition placement vs Spark's hash semantics."""

import os
import struct
import sys

import numpy as np
import pyarrow as pa
import pytest

from blaze_tpu import ColumnBatch
from blaze_tpu.config import EngineConfig, get_config, set_config
from blaze_tpu.exprs import Col
from blaze_tpu.io.ipc import (
    decode_ipc_parts,
    encode_ipc_segment,
    partition_ranges,
    read_file_segment,
    read_index_file,
)
from blaze_tpu.ops import (
    ExecContext,
    FileSegment,
    IpcReaderExec,
    IpcReadMode,
    IpcWriterExec,
    MemoryScanExec,
    ShuffleWriterExec,
    collect_ipc,
)
from blaze_tpu.exprs.hashing import hash_int_host, hash_long_host


def scan_of(data, **kw):
    return MemoryScanExec.from_batches([ColumnBatch.from_pydict(data, **kw)])


def drain(op, partition, ctx):
    return list(op.execute(partition, ctx))


def test_ipc_part_roundtrip():
    rb = pa.RecordBatch.from_pydict(
        {"a": [1, 2, 3], "s": ["x", None, "zz"]}
    )
    part = encode_ipc_segment(rb)
    # contract: 8-byte LE length prefix + zstd frame
    (length,) = struct.unpack_from("<Q", part, 0)
    assert length == len(part) - 8
    out = list(decode_ipc_parts(part))
    assert len(out) == 1
    assert out[0].to_pydict() == rb.to_pydict()
    # empty batch writes nothing (write_ipc_compressed returns 0)
    assert encode_ipc_segment(rb.slice(0, 0)) == b""


def test_shuffle_write_read_roundtrip(tmp_path):
    data = {"k": list(range(100)), "v": [i * 10 for i in range(100)]}
    op = ShuffleWriterExec(
        scan_of(data), [Col("k")], 4,
        str(tmp_path / "s.data"), str(tmp_path / "s.index"),
    )
    ctx = ExecContext()
    assert drain(op, 0, ctx) == []
    offs = read_index_file(str(tmp_path / "s.index"))
    assert len(offs) == 5 and offs[0] == 0
    # read all partitions back; every row lands exactly once, in the
    # partition Spark murmur3 dictates
    seen = {}
    for p, (off, length) in enumerate(
        partition_ranges(str(tmp_path / "s.index"))
    ):
        for rb in read_file_segment(str(tmp_path / "s.data"), off, length):
            for k, v in zip(*[rb.column(i).to_pylist() for i in range(2)]):
                seen[k] = (p, v)
                h = hash_long_host(k)
                exp_p = np.int32(np.uint32(h & 0xFFFFFFFF)) % 4
                if exp_p < 0:
                    exp_p += 4
                assert p == exp_p, (k, p, exp_p)
    assert len(seen) == 100
    assert all(seen[k][1] == k * 10 for k in seen)


def test_shuffle_string_keys(tmp_path):
    data = {"k": [f"key-{i % 7}" for i in range(50)], "v": list(range(50))}
    op = ShuffleWriterExec(
        scan_of(data), [Col("k")], 8,
        str(tmp_path / "s.data"), str(tmp_path / "s.index"),
    )
    drain(op, 0, ExecContext())
    total = 0
    groups = {}
    for p, (off, length) in enumerate(
        partition_ranges(str(tmp_path / "s.index"))
    ):
        for rb in read_file_segment(str(tmp_path / "s.data"), off, length):
            total += rb.num_rows
            for k in rb.column(0).to_pylist():
                groups.setdefault(k, set()).add(p)
    assert total == 50
    # all rows of one key land in one partition
    assert all(len(ps) == 1 for ps in groups.values())


def parts_of(tmp_path, stem):
    """Per partition, the parts of its segment as lists of row tuples."""
    out = []
    for off, length in partition_ranges(str(tmp_path / f"{stem}.index")):
        out.append([
            list(zip(*[c.to_pylist() for c in rb.columns]))
            for rb in read_file_segment(
                str(tmp_path / f"{stem}.data"), off, length
            )
        ] if length else [])
    return out


def spark_pmod(k, n):
    p = int(np.int32(np.uint32(hash_long_host(k) & 0xFFFFFFFF))) % n
    return p + n if p < 0 else p


@pytest.fixture
def tiny_pool():
    """A pool so small that whatever grows it spills."""
    from blaze_tpu.runtime import memory

    old_pool = memory._POOL
    memory._POOL = memory.MemoryPool(budget=64)
    try:
        yield memory._POOL
    finally:
        memory._POOL = old_pool


def test_shuffle_spill_merge(tmp_path, tiny_pool):
    """Force spills with a tiny budget: staged rows are frozen before
    each spill, and the merged file holds every row, each partition's
    in the order they came (batch order, across the spill boundaries)."""
    batches = [
        ColumnBatch.from_pydict(
            {"k": list(range(i * 20, (i + 1) * 20))}
        )
        for i in range(5)
    ]
    scan = MemoryScanExec([batches], batches[0].schema)
    op = ShuffleWriterExec(
        scan, [Col("k")], 3,
        str(tmp_path / "s.data"), str(tmp_path / "s.index"),
    )
    ctx = ExecContext()
    drain(op, 0, ctx)
    assert tiny_pool.spill_count > 0
    assert tiny_pool.spilled_bytes > 0  # rows went out, frozen
    assert tiny_pool.total_used() == 0
    parts = parts_of(tmp_path, "s")
    got = [[k for part in parts[p] for (k,) in part] for p in range(3)]
    # a batch's rows spilled when the next batch grew the pool: one
    # part a batch and partition, the keys ascending as they came
    assert got == [
        [k for k in range(100) if spark_pmod(k, 3) == p]
        for p in range(3)
    ]
    assert ctx.metrics.counters["shuffle_segments_written"] == sum(
        len(ps) for ps in parts
    ) > 3


def expected_slices(mode, batches, n, bounds):
    """What the per-slice writer wrote: for each partition, one list
    of rows a batch (empty ones left out), computed from the input."""
    out = [[] for _ in range(n)]
    rr_next = 0
    for b in batches:
        rows = list(zip(b["k"], b["v"]))
        if mode == "hash":
            pids = [spark_pmod(k, n) for k, _ in rows]
        elif mode == "round_robin":
            pids = [(i + rr_next) % n for i in range(len(rows))]
            rr_next = (rr_next + len(rows)) % n
        else:
            pids = [sum(k > bound for (bound,) in bounds)
                    for k, _ in rows]
        for p in range(n):
            mine = [r for r, pid in zip(rows, pids) if pid == p]
            if mine:
                out[p].append(mine)
    return out


@pytest.mark.parametrize("mode", ["hash", "round_robin", "range"])
def test_staged_parts_fill_a_batch(mode, tmp_path):
    """Many small batches into few partitions: rows are staged and a
    partition is frozen just before a slice would take it past
    batch_size, so parts are as large as a batch allows and no larger,
    and a partition's rows stay in the per-slice writer's order."""
    batch_size, n = 64, 3
    rng = np.random.default_rng(26)
    batches, v = [], 0
    for _ in range(40):
        rows = int(rng.integers(5, 50))
        batches.append({
            "k": rng.integers(0, 1000, rows).tolist(),
            "v": list(range(v, v + rows)),
        })
        v += rows
    bounds = [(300,), (650,)]
    cbs = [ColumnBatch.from_pydict(b) for b in batches]
    op = ShuffleWriterExec(
        MemoryScanExec([cbs], cbs[0].schema),
        [] if mode == "round_robin" else [Col("k")], n,
        str(tmp_path / "s.data"), str(tmp_path / "s.index"),
        mode=mode, range_bounds=bounds if mode == "range" else None,
    )
    ctx = ExecContext(config=EngineConfig(batch_size=batch_size))
    drain(op, 0, ctx)
    parts = parts_of(tmp_path, "s")
    slices = expected_slices(mode, batches, n, bounds)
    for p in range(n):
        sizes = [len(part) for part in parts[p]]
        assert len(sizes) > 2 and max(sizes) <= batch_size, (p, sizes)
        # no two neighbours would have fitted in one part
        assert all(a + b > batch_size
                   for a, b in zip(sizes, sizes[1:])), (p, sizes)
        assert [r for part in parts[p] for r in part] == \
            [r for sl in slices[p] for r in sl], p
        # and fewer parts than slices by far
        assert len(sizes) < len(slices[p]) / 2
    assert ctx.metrics.counters["shuffle_segments_written"] == sum(
        len(ps) for ps in parts
    )
    assert ctx.metrics.counters["shuffle_rows_written"] == v


@pytest.mark.parametrize("rows, want_parts", [
    # one batch: the parts the per-slice writer wrote, one a partition
    ([90], [[30], [30], [30]]),
    # a slice that alone passes batch_size is a part of its own, and
    # the staged rows before it are frozen first
    ([30, 240, 30], [[10, 80, 10], [10, 80, 10], [10, 80, 10]]),
    # 30 + 30 fit in 64, the third 30 does not
    ([90, 90, 90], [[60, 30], [60, 30], [60, 30]]),
])
def test_part_sizes_follow_the_batch_rule(rows, want_parts, tmp_path):
    v, cbs = 0, []
    for r in rows:
        cbs.append(ColumnBatch.from_pydict({"v": list(range(v, v + r))}))
        v += r
    op = ShuffleWriterExec(
        MemoryScanExec([cbs], cbs[0].schema), [], 3,
        str(tmp_path / "s.data"), str(tmp_path / "s.index"),
        mode="round_robin",
    )
    drain(op, 0, ExecContext(config=EngineConfig(batch_size=64)))
    parts = parts_of(tmp_path, "s")
    assert [[len(part) for part in ps] for ps in parts] == want_parts
    for p in range(3):
        # round robin from 0, every batch a multiple of 3 rows long
        assert [x for part in parts[p] for (x,) in part] == \
            list(range(p, v, 3))


def test_two_writers_spill_each_other(tmp_path, tiny_pool):
    """Two PartitionBuffers on two threads under a pool that spills
    whoever holds bytes whenever either grows: the pool runs a
    victim's spill on the growing thread, which may be the other
    writer's, while the victim stages. No row is lost or doubled and
    each partition keeps its order."""
    import threading

    from blaze_tpu.ops.shuffle_writer import PartitionBuffers

    n, steps, rows = 8, 40, 400  # 50-row slices: a freeze a stage
    barrier = threading.Barrier(2)
    foreign = []
    errors = []

    def writer(name, base):
        try:
            bufs = PartitionBuffers(n, str(tmp_path), 64, 1)
            spill, me = bufs.spill, threading.get_ident()

            def watched_spill():
                released = spill()
                if released and threading.get_ident() != me:
                    foreign.append(name)
                return released

            tiny_pool.register(id(bufs), watched_spill)
            for i in range(steps):
                vals = np.arange(rows) + base + i * rows
                pids = vals % n
                order = np.argsort(pids, kind="stable")
                rb = pa.RecordBatch.from_pydict({"v": vals[order]})
                # the first two steps in turn, so that each writer is
                # surely spilled from the other's thread once; the rest
                # side by side
                first = i < 2 and name == "ab"[i]
                if not first:
                    barrier.wait(timeout=60)
                bufs.stage(rb, np.bincount(pids, minlength=n))
                if first or i >= 2:
                    barrier.wait(timeout=60)
            bufs.finalize(str(tmp_path / f"{name}.data"),
                          str(tmp_path / f"{name}.index"))
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append((name, e))
            barrier.abort()

    threads = [threading.Thread(target=writer, args=(name, base))
               for name, base in (("a", 0), ("b", 10 ** 6))]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # threads change places often
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    for name, base in (("a", 0), ("b", 10 ** 6)):
        parts = parts_of(tmp_path, name)
        for p in range(n):
            assert [x for part in parts[p] for (x,) in part] == [
                x for x in range(base, base + steps * rows)
                if x % n == p
            ], (name, p)
    # either thread's grow spills both writers
    assert set(foreign) == {"a", "b"}
    assert tiny_pool.total_used() == 0
    assert [f for f in os.listdir(tmp_path) if f.endswith(".tmp")] == []


def test_ipc_reader_modes(tmp_path):
    cb = ColumnBatch.from_pydict({"a": [1, 2, 3]})
    parts = collect_ipc(MemoryScanExec.from_batches([cb]), ExecContext())
    assert len(parts) == 1

    ctx = ExecContext()
    ctx.resources["r"] = [parts]
    rd = IpcReaderExec("r", cb.schema, 1, IpcReadMode.CHANNEL)
    got = [b.to_pydict() for b in rd.execute(0, ctx)]
    assert got == [{"a": [1, 2, 3]}]

    # file segment mode through a shuffle file
    op = ShuffleWriterExec(
        MemoryScanExec.from_batches([cb]), [Col("a")], 2,
        str(tmp_path / "x.data"), str(tmp_path / "x.index"),
    )
    drain(op, 0, ctx)
    segs = [
        [FileSegment(str(tmp_path / "x.data"), off, length)]
        for off, length in partition_ranges(str(tmp_path / "x.index"))
    ]
    rd2 = IpcReaderExec(
        "r2", cb.schema, 2, IpcReadMode.CHANNEL_AND_FILE_SEGMENT
    )
    ctx.resources["r2"] = segs
    rows = []
    for p in range(2):
        for b in rd2.execute(p, ctx):
            rows += b.to_pydict()["a"]
    assert sorted(rows) == [1, 2, 3]


def test_single_partition_mode(tmp_path):
    op = ShuffleWriterExec(
        scan_of({"a": [5, 6]}), [], 1,
        str(tmp_path / "p.data"), str(tmp_path / "p.index"),
        mode="single",
    )
    drain(op, 0, ExecContext())
    (rng,) = partition_ranges(str(tmp_path / "p.index"))
    rows = []
    for rb in read_file_segment(str(tmp_path / "p.data"), *rng):
        rows += rb.column(0).to_pylist()
    assert rows == [5, 6]


def test_round_robin_mode(tmp_path):
    op = ShuffleWriterExec(
        scan_of({"a": list(range(10))}), [], 3,
        str(tmp_path / "rr.data"), str(tmp_path / "rr.index"),
        mode="round_robin",
    )
    drain(op, 0, ExecContext())
    sizes = [
        sum(
            rb.num_rows
            for rb in read_file_segment(str(tmp_path / "rr.data"), o, l)
        )
        for o, l in partition_ranges(str(tmp_path / "rr.index"))
    ]
    assert sum(sizes) == 10
    assert max(sizes) - min(sizes) <= 1  # balanced


def test_ipc_reader_uncompressed_recordbatch():
    """CHANNEL_UNCOMPRESSED: pre-decoded RecordBatches pass straight
    through (the ConvertToNative input path, ipc_reader_exec.rs mode
    CHANNEL_UNCOMPRESSED)."""
    import pyarrow as pa

    rb = pa.RecordBatch.from_pydict({"a": [1, 2], "s": ["x", None]})
    cb = ColumnBatch.from_pydict({"a": [0]})
    ctx = ExecContext()
    ctx.resources["u"] = [[rb]]
    from blaze_tpu.types import from_arrow_schema

    rd = IpcReaderExec(
        "u", from_arrow_schema(rb.schema), 1,
        IpcReadMode.CHANNEL_UNCOMPRESSED,
    )
    out = [b.to_arrow().to_pydict() for b in rd.execute(0, ctx)]
    assert out == [rb.to_pydict()]
    assert ctx.metrics.counters["ipc_rows_read"] == 2


def test_metrics_counters_flow(tmp_path):
    ctx = ExecContext()
    op = ShuffleWriterExec(
        scan_of({"k": list(range(40))}), [Col("k")], 4,
        str(tmp_path / "m.data"), str(tmp_path / "m.index"),
    )
    drain(op, 0, ctx)
    flat = ctx.metrics.flatten()["root"]
    assert flat["shuffle_rows_written"] == 40
    assert flat["shuffle_bytes_written"] > 0


def test_ipc_stream_channel_source(tmp_path):
    """Remote-stream mode: a file-like object of concatenated parts
    decodes incrementally (reference ReadableByteChannel path)."""
    import io

    rbs = [
        pa.RecordBatch.from_pydict({"a": [1, 2]}),
        pa.RecordBatch.from_pydict({"a": [3]}),
    ]
    blob = b"".join(encode_ipc_segment(rb) for rb in rbs)
    ctx = ExecContext()
    ctx.resources["st"] = [[io.BytesIO(blob)]]
    from blaze_tpu.types import from_arrow_schema

    rd = IpcReaderExec(
        "st", from_arrow_schema(rbs[0].schema), 1,
        IpcReadMode.CHANNEL_AND_FILE_SEGMENT,
    )
    rows = [x for b in rd.execute(0, ctx) for x in b.to_pydict()["a"]]
    assert rows == [1, 2, 3]


# ---------------------------------------------------------------------------
# range partitioning (reference ArrowShuffleExchangeExec301.scala:317-357)
# ---------------------------------------------------------------------------

def test_range_partition_ids_bounds_ties_nulls_desc():
    import numpy as np

    from blaze_tpu.ops.shuffle_writer import range_partition_ids

    keys = [np.array([None, 1, 5, 10, 10, 25], dtype=object)]
    bounds = [(5,), (10,)]
    pids = range_partition_ids(keys, bounds, [True])
    # NULL first -> 0; 1 -> 0; 5 (== bound) -> lower partition 0;
    # 10 -> 1 (== second bound); 25 -> 2
    assert pids.tolist() == [0, 0, 0, 1, 1, 2]

    # descending: order reverses (25 sorts first -> partition 0; 1
    # sorts past both bounds -> partition 2); NULL still ranks first
    pids_d = range_partition_ids(keys, [(10,), (5,)], [False])
    assert pids_d.tolist() == [0, 2, 1, 0, 0, 0]

    # two keys, lexicographic
    k2 = [
        np.array([1, 1, 2, 2], dtype=object),
        np.array(["a", "z", "a", "z"], dtype=object),
    ]
    pids2 = range_partition_ids(k2, [(1, "m"), (2, "m")], [True, True])
    assert pids2.tolist() == [0, 1, 1, 2]


def test_compute_range_bounds_quantiles():
    import numpy as np
    import pandas as pd

    from blaze_tpu.ops.shuffle_writer import compute_range_bounds

    df = pd.DataFrame({"k0": np.arange(100)})
    bounds = compute_range_bounds(df, 4, [True])
    assert bounds == [(25,), (50,), (75,)]
    assert compute_range_bounds(df, 1, [True]) == []
    assert compute_range_bounds(df.iloc[:0], 4, [True]) == []


def test_range_exchange_global_sort():
    """Distributed global sort: range exchange + per-partition sort =>
    concatenated output is totally ordered."""
    import numpy as np

    from blaze_tpu.exprs import Col
    from blaze_tpu.ops import SortExec, SortKey
    from blaze_tpu.parallel import ShuffleExchangeExec

    rng = np.random.default_rng(11)
    parts = [
        {"k": rng.integers(0, 1000, 500).tolist(),
         "v": list(range(500))}
        for _ in range(3)
    ]
    batches = [[ColumnBatch.from_pydict(p)] for p in parts]
    scan = MemoryScanExec(batches, ColumnBatch.from_pydict(parts[0]).schema)
    ex = ShuffleExchangeExec(scan, [Col("k")], 4, mode="range")
    ctx = ExecContext()
    all_keys = []
    for p in range(4):
        part_keys = []
        srt = SortExec(ex, [SortKey(Col("k"), True, True)])
        # sort executes per partition; collect partition p
        for cb in srt.execute(p, ctx):
            part_keys += cb.to_arrow().column("k").to_pylist()
        assert part_keys == sorted(part_keys)
        all_keys.append(part_keys)
    flat = [k for part in all_keys for k in part]
    assert flat == sorted(flat)  # global order across partitions
    expect = sorted(k for p in parts for k in p["k"])
    assert flat == expect  # no rows lost or duplicated


def test_range_writer_serde_roundtrip(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    from blaze_tpu.exprs import Col
    from blaze_tpu.ops.parquet_scan import FileRange, ParquetScanExec
    from blaze_tpu.ops.shuffle_writer import ShuffleWriterExec
    from blaze_tpu.plan.serde import plan_from_proto, plan_to_proto

    src = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"k": [3, 1, 2], "v": [1.0, 2.0, 3.0]}), src)
    op = ShuffleWriterExec(
        ParquetScanExec([[FileRange(src)]]), [Col("k")], 3,
        str(tmp_path / "o.data"), str(tmp_path / "o.index"),
        mode="range", range_bounds=[(1,), (2,)],
        sort_ascending=[True],
    )
    back = plan_from_proto(plan_to_proto(op))
    assert back.mode == "range"
    assert back.range_bounds == [(1,), (2,)]
    assert back.sort_ascending == [True]
    # and it runs: write + verify partition ordering via the index
    ctx = ExecContext()
    for _ in back.execute(0, ctx):
        pass
    from blaze_tpu.io.ipc import partition_ranges, read_file_segment

    ranges = partition_ranges(str(tmp_path / "o.index"))
    seen = []
    for off, length in ranges:
        if length:
            for rb in read_file_segment(
                str(tmp_path / "o.data"), off, length
            ):
                seen.append(rb.column("k").to_pylist())
    assert seen == [[1], [2], [3]]


def test_host_writer_interchangeable_with_native(tmp_path):
    """The host-tier writer (ops/host_shuffle, the JVM row-shuffle
    analog) and the native writer must produce interchangeable shuffle
    outputs: identical partition assignment (bit-exact murmur3) and
    identical per-partition row sets under the same reader - the
    reference's both-producers-one-format property
    (ArrowShuffleExternalSorter301.java:141-260)."""
    import pandas as pd
    import pyarrow as pa

    from blaze_tpu.ops.host_shuffle import host_shuffle_write

    rng = np.random.default_rng(5)
    n = 4000
    df = pd.DataFrame({
        "k": rng.integers(-50, 50, n).astype(np.int64),
        "name": pd.array(
            [f"user_{i % 37}" if i % 11 else None for i in range(n)]
        ),
        "v": rng.random(n),
    })
    rb = pa.RecordBatch.from_pandas(df, preserve_index=False)

    # native writer (device hash tier) over the same rows
    cb = ColumnBatch.from_arrow(rb)
    op = ShuffleWriterExec(
        MemoryScanExec([[cb]], cb.schema), [Col("k"), Col("name")], 4,
        str(tmp_path / "n.data"), str(tmp_path / "n.index"),
    )
    assert drain(op, 0, ExecContext()) == []

    # host writer: pyarrow in, no device involvement
    lengths = host_shuffle_write(
        [rb], ["k", "name"], 4,
        str(tmp_path / "h.data"), str(tmp_path / "h.index"),
        spill_dir=str(tmp_path),
    )
    assert len(lengths) == 4 and sum(lengths) > 0

    def rows_by_partition(stem):
        out = []
        for off, length in partition_ranges(
            str(tmp_path / f"{stem}.index")
        ):
            parts = []
            for rb_ in read_file_segment(
                str(tmp_path / f"{stem}.data"), off, length
            ):
                t = pa.Table.from_batches([rb_])
                parts.append(t.to_pandas())
            out.append(
                pd.concat(parts, ignore_index=True)
                if parts else pd.DataFrame(columns=df.columns)
            )
        return out

    native_parts = rows_by_partition("n")
    host_parts = rows_by_partition("h")
    total = 0
    for p, (a, b) in enumerate(zip(native_parts, host_parts)):
        a = a.sort_values(["k", "v"]).reset_index(drop=True)
        b = b.sort_values(["k", "v"]).reset_index(drop=True)
        b = b[a.columns]
        assert len(a) == len(b), p
        total += len(a)
        pd.testing.assert_frame_equal(
            a.astype({"name": "string"}), b.astype({"name": "string"}),
            check_dtype=False,
        )
    assert total == n
