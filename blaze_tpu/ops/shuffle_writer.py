"""Shuffle write: hash-repartition batches into a .data/.index file pair.

Reference counterpart: the native ShuffleWriterExec (shuffle_writer_exec.rs,
780 LoC): spark-murmur3 pmod bucketing, per-partition buffers with
spill-to-disk under memory pressure, final merge into one data file + LE
i64 offsets index, committed by Spark (ArrowShuffleExchangeExec301.scala:
531-602). Single-partition (no-key) and round-robin variants cover the
JVM fallback paths' semantics.

Staging, freeze, spill (PartitionBuffers, as the reference's
PartitionBuffer): a partition-sorted batch is kept as zero-copy slices,
one a non-empty partition, with no IPC and no zstd. A partition is
frozen - its slices concatenated and encoded as ONE part - before a slice
that would take its staged rows past `batch_size`, and whatever is still
staged is frozen by `finalize` and, under memory pressure, by `spill`
before the spill file is written. So a 64-batch task into 200 partitions
writes some 200 parts, not 12,800, and a reader meets no part larger than
a batch unless one slice alone was. Staged Arrow bytes count against the
MemoryPool like encoded bytes. A partition's rows keep batch order, and
the stable sort's order within a batch, across freezes and spills; the
file format (io/ipc.py) does not change.

TPU-first layout (SURVEY 7 step 5): partition ids are computed on-device
(bit-exact Spark murmur3 over the key columns, one program) and stay
there: a second program (`sort_by_partition`) does the row scatter as ONE
stable device sort by partition id - the counting-sort scatter of the
reference (rs:349-371) becomes an XLA sort - gathers every buffer and
counts the rows of each partition, followed by a single D2H transfer of
the already-partition-contiguous batch. Nothing waits for the device
between the first launch and that transfer. String/f64 keys hash through
the C++ host runtime instead (TPU has no string compute; its f64 is not
bit-exact - exprs/hashing.device_hash_supported) and enter the sort as a
host array.
"""

from __future__ import annotations

import functools
import os
import threading
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa

import jax
import jax.numpy as jnp

from blaze_tpu.config import get_config
from blaze_tpu.types import Schema
from blaze_tpu.batch import ColumnBatch
from blaze_tpu.exprs import ir
from blaze_tpu.exprs.optimize import bind_opt
from blaze_tpu.exprs.eval import DeviceEvaluator
from blaze_tpu.exprs.hashing import (
    device_hash_supported,
    hash_columns_device,
    pmod,
)
from blaze_tpu.exprs.typing import infer_dtype
from blaze_tpu.io.ipc import encode_ipc_segment
from blaze_tpu.obs import trace as obs_trace
from blaze_tpu.ops.base import ExecContext, PhysicalOp
from blaze_tpu.ops.host_lower import lower_strings_host
from blaze_tpu.ops.project import _unflatten_cvs
from blaze_tpu.ops.util import ensure_compacted, take_batch
from blaze_tpu.runtime.dispatch import cached_kernel, current_task, launch
from blaze_tpu.runtime import native
from blaze_tpu.runtime.memory import get_pool


class PartitionBuffers:
    """Per-partition staging and part buffers with the reference's
    stage->freeze->spill->merge ladder (PartitionBuffer/spill_into,
    shuffle_writer_exec.rs:66-194, :522-556).

    `stage` keeps a partition-sorted batch's zero-copy slices, one list
    a partition. A partition is frozen (its slices concatenated, encoded
    as ONE part and appended to its byte buffer) before a slice that
    would take its staged rows past `batch_size`, so a reader meets no
    part larger than a batch unless one slice alone was; `spill` and
    `finalize` freeze whatever is still staged. Staged Arrow bytes and
    encoded bytes are both accounted to the MemoryPool. The pool calls
    `spill` on whichever thread ran short, so stage, freeze, spill and
    finalize hold `_lock`; the pool is only ever grown outside it (a
    grow may spill a neighbour, which takes the neighbour's lock)."""

    def __init__(self, num_partitions: int, spill_dir: str,
                 batch_size: int, compression_level: int):
        self.num_partitions = num_partitions
        self.batch_size = batch_size
        self.compression_level = compression_level
        self.buffers: List[bytearray] = [
            bytearray() for _ in range(num_partitions)
        ]
        self._staged: List[List[pa.RecordBatch]] = [
            [] for _ in range(num_partitions)
        ]
        self._staged_rows = [0] * num_partitions
        self._staged_bytes = [0] * num_partitions
        self.spills: List[Tuple[str, List[int]]] = []
        self.spill_dir = spill_dir
        self.mem_used = 0
        self.segments = 0  # parts encoded so far
        self.segment_bytes = 0
        self._lock = threading.Lock()
        self._pool = get_pool()
        self._pool.register(id(self), self.spill)

    def append(self, partition: int, part: bytes) -> None:
        """An already encoded part (the single-partition writer)."""
        # accounted before it is held: a spill between the two lines
        # releases what the pool has been told of, no more
        self._pool.grow(id(self), len(part))
        with self._lock:
            self.buffers[partition] += part
            self.mem_used += len(part)
            self.segments += 1
            self.segment_bytes += len(part)

    def stage(self, rb_sorted: pa.RecordBatch, counts) -> None:
        """Keep `rb_sorted`'s rows, which lie partition by partition
        (`counts[p]` of them for partition p, in order), as one slice a
        non-empty partition; no IPC and no zstd but for the partitions
        a slice fills past `batch_size`, which are frozen first."""
        n = rb_sorted.num_rows
        if n == 0:
            return
        nbytes = rb_sorted.nbytes
        # obs seam: one span a batch, not one a part
        with (obs_trace.span("shuffle_encode")
              if obs_trace.ACTIVE else obs_trace.NULL) as sp:
            self._pool.grow(id(self), nbytes)  # as in `append`
            with self._lock:
                seg0, bytes0 = self.segments, self.segment_bytes
                self.mem_used += nbytes
                owed = start = 0
                for p, c in enumerate(np.asarray(counts).tolist()):
                    if c == 0:
                        continue
                    if (self._staged_rows[p]
                            and self._staged_rows[p] + c > self.batch_size):
                        owed += self._freeze(p)
                    self._staged[p].append(rb_sorted.slice(start, c))
                    self._staged_rows[p] += c
                    # shares of the batch's bytes that add up to them
                    self._staged_bytes[p] += (
                        (start + c) * nbytes // n - start * nbytes // n
                    )
                    start += c
                sp.tag(segments=self.segments - seg0,
                       bytes=self.segment_bytes - bytes0)
            if owed > 0:  # parts larger than their rows: tiny slices
                self._pool.grow(id(self), owed)
            else:
                self._pool.shrink(id(self), -owed)

    def _freeze(self, p: int) -> int:
        """Partition p's staged slices become one part at the end of
        its buffer (caller holds the lock). Returns the change in bytes
        held, which the caller owes the pool."""
        slices = self._staged[p]
        part = encode_ipc_segment(
            slices[0] if len(slices) == 1 else pa.concat_batches(slices),
            self.compression_level,
        )
        self.buffers[p] += part
        delta = len(part) - self._staged_bytes[p]
        self._staged[p] = []
        self._staged_rows[p] = self._staged_bytes[p] = 0
        self.mem_used += delta
        self.segments += 1
        self.segment_bytes += len(part)
        return delta

    def _freeze_all(self, **tags) -> None:
        """Every partition still staged (caller holds the lock, and
        settles `mem_used` with the pool itself)."""
        staged = [p for p in range(self.num_partitions)
                  if self._staged_rows[p]]
        if not staged:
            return
        # obs seam: the same stage as `stage`'s, nested in the
        # `shuffle_finalize` around it, which the fold takes it out of
        with (obs_trace.span("shuffle_encode", **tags)
              if obs_trace.ACTIVE else obs_trace.NULL) as sp:
            seg0, bytes0 = self.segments, self.segment_bytes
            for p in staged:
                self._freeze(p)
            sp.tag(segments=self.segments - seg0,
                   bytes=self.segment_bytes - bytes0)

    def spill(self) -> int:
        """Freeze what is staged and write every buffer to a spill
        file; returns bytes released."""
        with self._lock:
            released = self.mem_used
            if released == 0:
                return 0
            path = os.path.join(
                self.spill_dir,
                f"blz-spill-{id(self):x}-{len(self.spills)}.tmp",
            )
            offsets = [0] * (self.num_partitions + 1)
            pos = 0
            # obs seam: a spill is file writing too (the thread that ran
            # short of memory pays, whichever task's buffers these are)
            with (obs_trace.span("shuffle_finalize", spill=True)
                  if obs_trace.ACTIVE else obs_trace.NULL):
                self._freeze_all(spill=True)
                with open(path, "wb") as f:
                    for p in range(self.num_partitions):
                        offsets[p] = pos
                        f.write(self.buffers[p])
                        pos += len(self.buffers[p])
                        self.buffers[p] = bytearray()
            offsets[self.num_partitions] = pos
            self.spills.append((path, offsets))
            self.mem_used = 0
            return released

    def finalize(self, data_path: str, index_path: str) -> List[int]:
        """Freeze what is staged and assemble .data/.index (native C++
        fast path); returns partition lengths. Cleans up spill files."""
        with (obs_trace.span("shuffle_finalize")
              if obs_trace.ACTIVE else obs_trace.NULL), self._lock:
            self._freeze_all()
            native.shuffle_assemble(
                data_path, index_path, self.buffers,
                self.num_partitions, self.spills,
            )
            self.mem_used = 0
        self._pool.unregister(id(self))
        for path, _ in self.spills:
            try:
                os.remove(path)
            except OSError:
                pass
        from blaze_tpu.io.ipc import partition_ranges

        return [length for _, length in partition_ranges(index_path)]


def _pallas_murmur3():
    """The Pallas murmur3 module where its programs run: a real TPU
    (Mosaic compiles for nothing else)."""
    if jax.default_backend() != "tpu":
        return None
    from blaze_tpu.ops.kernels import murmur3_pallas

    return murmur3_pallas


def _build_partition_ids(schema: Schema, layout: Tuple, exprs, dtypes,
                         num_partitions: int):
    """Key expressions, the murmur3 chain with validity and pmod traced
    together. Its own name, so a device trace tells its program from the
    fused `kernel`s and from the Pallas programs."""
    cap = layout[0]

    def shuffle_partition_ids(bufs):
        ev = DeviceEvaluator(schema, _unflatten_cvs(layout, bufs), cap)
        cols = [ev.evaluate(e) + (dt,) for e, dt in zip(exprs, dtypes)]
        return pmod(hash_columns_device(cols, cap), num_partitions)

    return shuffle_partition_ids


def spark_partition_ids(cb: ColumnBatch, key_exprs: Sequence[ir.Expr],
                        num_partitions: int):
    """Spark-murmur3 pmod partition id per row (batch must be
    compacted). Where all key dtypes hash bit-exactly on the device: a
    device array of the batch's capacity, left there (whatever lies at
    and past `num_rows` is padding's hash; `sort_by_partition` masks
    it). Otherwise the C++/numpy host path: numpy, `num_rows` long."""
    schema = cb.schema
    dtypes = [infer_dtype(e, schema) for e in key_exprs]
    # pallas fast path: one bound int key whose batch holds no NULL (a
    # batch carries a validity buffer only where it does, so the data
    # picks the path a batch at a time), where the program can run
    # (SURVEY 7: murmur3 partition hash as a Pallas kernel)
    mp = _pallas_murmur3()
    if (
        mp is not None
        and len(key_exprs) == 1
        and isinstance(key_exprs[0], ir.BoundCol)
        and cb.columns[key_exprs[0].index].validity is None
    ):
        col = cb.columns[key_exprs[0].index]
        tid = dtypes[0].id.value
        if mp.supports(tid, cb.capacity):
            fn = (
                mp.partition_ids_int32
                if tid in ("int32", "date32")
                else mp.partition_ids_int64
            )
            pids = launch(fn, col.values, num_partitions)
            task = current_task()
            if task is not None:
                # POLL's `shuffle_pallas_batches`
                task.metrics.add("shuffle_pallas_batches", 1)
            return pids
    if all(device_hash_supported(dt) for dt in dtypes):
        exprs, layout = tuple(key_exprs), cb.layout()
        fn = cached_kernel(
            ("shuffle_ids", exprs, schema, layout, num_partitions),
            lambda: _build_partition_ids(
                schema, layout, exprs, dtypes, num_partitions
            ),
        )
        return fn(cb.device_buffers())
    # host path: exact Spark chain incl. utf8 bytes via the C++ runtime
    n = cb.num_rows
    h = np.full(n, 42, dtype=np.uint32)
    ev = DeviceEvaluator(
        schema, [(c.values, c.validity) for c in cb.columns], cb.capacity
    )
    for e, dt in zip(key_exprs, dtypes):
        if dt.is_dictionary_encoded:
            # string keys are plain columns after host lowering
            assert isinstance(e, ir.BoundCol), "string key must be a column"
            col = cb.columns[e.index]
            validity = (
                np.asarray(col.validity)[:n]
                if col.validity is not None
                else None
            )
            h = native.murmur3_dict_strings_chain(
                col.dictionary,
                np.ascontiguousarray(np.asarray(col.values)[:n],
                                     dtype=np.int32),
                validity, h,
            )
        else:
            v, m = ev.evaluate(e)
            validity = np.asarray(m)[:n] if m is not None else None
            h = _chain_fixed(np.asarray(v)[:n], validity, dt, h)
    return native.pmod_np(h, num_partitions)


def _build_sort_by_partition(num_partitions: int):
    def shuffle_sort_gather(pids, num_rows, bufs):
        cap = pids.shape[0]
        iota = jnp.arange(cap, dtype=jnp.int32)
        # dead rows sort behind the last partition
        keys = jnp.where(
            iota < num_rows, pids.astype(jnp.int32),
            jnp.int32(num_partitions),
        )
        keys, order = jax.lax.sort_key_val(keys, iota, is_stable=True)
        bounds = jnp.searchsorted(
            keys, jnp.arange(num_partitions + 1, dtype=jnp.int32)
        )
        return (
            [jnp.take(b, order, axis=0) for b in bufs],
            (bounds[1:] - bounds[:-1]).astype(jnp.int32),
        )

    return shuffle_sort_gather


@functools.lru_cache(maxsize=64)
def _device_rows(num_rows: int) -> jax.Array:
    """A row count as the device scalar the sort takes, kept: nearly
    every batch of a task is full, and a fresh scalar is one more
    transfer a batch (0.16 ms of the launching thread on a v5e)."""
    return jax.device_put(np.int32(num_rows))


def sort_by_partition(cb: ColumnBatch, pids, num_partitions: int
                      ) -> Tuple[ColumnBatch, jax.Array]:
    """`cb`'s live rows in partition order (stable within a partition)
    and the rows of each partition, by ONE device program: the scatter is
    a stable sort by partition id, then the gather of every buffer and
    the counts. `pids` is what `spark_partition_ids` returns, a device
    array or numpy; `num_rows` is traced, so a short last batch compiles
    nothing. Launch only: neither result is waited for here (the counts
    follow the batch's own read-back to the host)."""
    if isinstance(pids, jax.Array):
        task = current_task()
        if task is not None:
            # POLL's `shuffle_device_ids_batches`: no read-back between
            # the hash and the sort
            task.metrics.add("shuffle_device_ids_batches", 1)
    else:
        # the host path's are `num_rows` long
        pids = np.pad(
            np.asarray(pids, dtype=np.int32), (0, cb.capacity - len(pids)),
            constant_values=num_partitions,
        )
    fn = cached_kernel(
        ("shuffle_sort_gather", num_partitions),
        lambda: _build_sort_by_partition(num_partitions),
    )
    taken, counts = fn(pids, _device_rows(cb.num_rows), cb.device_buffers())
    counts.copy_to_host_async()
    return ColumnBatch.from_device_buffers(
        cb.schema, cb.layout(), taken, cb.num_rows, cb.dictionaries()
    ), counts


def _chain_fixed(values, validity, dt, h):
    """Chain one fixed-width column into running hashes (numpy)."""
    from blaze_tpu.exprs import hashing as H
    from blaze_tpu.types import TypeId

    tid = dt.id
    if tid in (TypeId.INT8, TypeId.INT16, TypeId.INT32, TypeId.DATE32,
               TypeId.BOOL):
        link = H._np_hash_int(values.astype(np.int32).view(np.uint32)
                              if tid is not TypeId.BOOL
                              else values.astype(np.uint32), h)
    elif tid in (TypeId.INT64, TypeId.TIMESTAMP_US) or (
        tid is TypeId.DECIMAL and dt.precision <= 18
    ):
        u = values.astype(np.int64).view(np.uint64)
        low = (u & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        high = (u >> np.uint64(32)).astype(np.uint32)
        h1 = H._np_mix_h1(h, H._np_mix_k1(low))
        h1 = H._np_mix_h1(h1, H._np_mix_k1(high))
        link = H._np_fmix(h1, 8)
    elif tid is TypeId.FLOAT32:
        v = values.astype(np.float32)
        v = np.where(v == 0.0, np.float32(0.0), v)
        link = H._np_hash_int(v.view(np.uint32), h)
    elif tid is TypeId.FLOAT64:
        v = values.astype(np.float64)
        v = np.where(v == 0.0, 0.0, v)
        u = v.view(np.uint64)
        low = (u & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        high = (u >> np.uint64(32)).astype(np.uint32)
        h1 = H._np_mix_h1(h, H._np_mix_k1(low))
        h1 = H._np_mix_h1(h1, H._np_mix_k1(high))
        link = H._np_fmix(h1, 8)
    else:
        raise NotImplementedError(f"hash of {dt}")
    if validity is not None:
        link = np.where(validity, link, h)
    return link


def _key_array_for_range(rb, cb: ColumnBatch, e: ir.Expr) -> np.ndarray:
    """Materialized host key values for range partitioning, as object
    arrays with None for NULL. Non-string keys use the engine's PHYSICAL
    representation (date32 day ints, timestamp micros, decimal unscaled
    i64) - physical order == logical order, and the values round-trip
    through the plan proto as plain int/float literals. Strings use real
    values (dictionary codes don't order). Float NaN maps to +inf so it
    ranks greatest like Spark's total order (inf ties break to the same
    or adjacent partition; the in-partition sort finishes the job)."""
    if isinstance(e, ir.BoundCol):
        idx = e.index
    elif isinstance(e, ir.Col):
        idx = cb.schema.index_of(e.name)
    else:
        raise NotImplementedError(
            "range partitioning keys must be plain columns"
        )
    field = cb.schema.fields[idx]
    n = cb.num_rows
    if field.dtype.is_string_like:
        out = np.asarray(rb.column(idx).to_pandas(), dtype=object)
        return out[:n]
    col = cb.columns[idx]
    vals = np.asarray(col.values)[:n]
    if np.issubdtype(vals.dtype, np.floating):
        vals = np.where(np.isnan(vals), np.inf, vals)
    out = vals.astype(object)
    if col.validity is not None:
        valid = np.asarray(col.validity)[:n]
        out[~valid] = None
    return out


def range_partition_ids(key_arrays: Sequence[np.ndarray],
                        bounds: Sequence[Tuple],
                        ascending: Sequence[bool]) -> np.ndarray:
    """Partition id per row for RANGE partitioning: the count of
    boundary tuples the row's key tuple exceeds lexicographically (rows
    equal to a bound land in the lower partition, like Spark's
    RangePartitioner binary search). NULL ranks first in the sort
    order regardless of direction."""
    import pandas as pd

    n = len(key_arrays[0]) if key_arrays else 0
    pid = np.zeros(n, dtype=np.int32)
    for bound in bounds:
        gt = np.zeros(n, dtype=bool)
        eq = np.ones(n, dtype=bool)
        for arr, bv, asc in zip(key_arrays, bound, ascending):
            isn = pd.isna(arr)
            if bv is None or (isinstance(bv, float) and np.isnan(bv)):
                col_gt = ~isn  # any value outranks a NULL bound
                col_eq = isn
            else:
                # NULL slots can't be compared (object arrays raise);
                # substitute the bound itself, then mask them out
                safe = np.where(isn, bv, arr)
                with np.errstate(invalid="ignore"):
                    raw_gt = np.asarray(safe > bv, dtype=bool)
                    raw_lt = np.asarray(safe < bv, dtype=bool)
                if not asc:
                    raw_gt, raw_lt = raw_lt, raw_gt
                col_gt = raw_gt & ~isn
                col_eq = np.asarray(safe == bv, dtype=bool) & ~isn
            gt = gt | (eq & col_gt)
            eq = eq & col_eq
        pid += gt.astype(np.int32)
    return pid


def compute_range_bounds(sample_df, num_partitions: int,
                         ascending: Sequence[bool]) -> List[Tuple]:
    """num_partitions-1 boundary tuples from a sample of key rows
    (driver-side sampling, reference RangePartitioner role in
    ArrowShuffleExchangeExec301.scala:317-357)."""
    if len(sample_df) == 0 or num_partitions <= 1:
        return []
    s = sample_df.sort_values(
        list(sample_df.columns),
        ascending=list(ascending),
        na_position="first",
        kind="stable",
    ).reset_index(drop=True)
    n = len(s)
    bounds = []
    for k in range(1, num_partitions):
        idx = min(n - 1, (k * n) // num_partitions)
        row = tuple(
            None if (v is None or (isinstance(v, float) and np.isnan(v)))
            else v
            for v in s.iloc[idx]
        )
        bounds.append(row)
    return bounds


class ShuffleWriterExec(PhysicalOp):
    """Writes one map task's shuffle output; the output stream is empty
    (lengths land in the index file), matching the reference
    (external_shuffle, shuffle_writer_exec.rs:753-780)."""

    def __init__(self, child: PhysicalOp, key_exprs: Sequence[ir.Expr],
                 num_partitions: int, data_file: str, index_file: str,
                 mode: str = "hash",
                 range_bounds: Optional[Sequence[Tuple]] = None,
                 sort_ascending: Optional[Sequence[bool]] = None):
        self.children = [child]
        self.key_exprs = [bind_opt(e, child.schema) for e in key_exprs]
        self.num_partitions = num_partitions
        self.data_file = data_file
        self.index_file = index_file
        assert mode in ("hash", "single", "round_robin", "range")
        self.mode = mode
        if mode == "hash" and not key_exprs:
            raise ValueError("hash partitioning requires keys")
        if mode == "range":
            if not key_exprs:
                raise ValueError("range partitioning requires sort keys")
            # bounds are plan constants (driver-sampled) so every map
            # task splits identically
            self.range_bounds = list(range_bounds or [])
            self.sort_ascending = list(
                sort_ascending
                if sort_ascending is not None
                else [True] * len(key_exprs)
            )
        else:
            self.range_bounds = []
            self.sort_ascending = []

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def execute(self, partition: int, ctx: ExecContext
                ) -> Iterator[ColumnBatch]:
        cfg = ctx.config
        bufs = PartitionBuffers(
            self.num_partitions, cfg.spill_dir(), cfg.batch_size,
            cfg.ipc_compression_level,
        )
        rr_next = partition  # round-robin start varies by map partition
        for cb in self.children[0].execute(partition, ctx):
            cb = ensure_compacted(cb)
            if cb.num_rows == 0:
                continue
            exprs, _, aug = lower_strings_host(self.key_exprs, cb) \
                if self.mode == "hash" else (self.key_exprs, 0, cb)
            if self.mode == "single" or self.num_partitions == 1:
                rb = cb.to_arrow()
                bufs.append(
                    0, encode_ipc_segment(rb, cfg.ipc_compression_level)
                )
                continue
            if self.mode == "round_robin":
                pids = (
                    (np.arange(cb.num_rows) + rr_next)
                    % self.num_partitions
                ).astype(np.int32)
                rr_next = int(
                    (rr_next + cb.num_rows) % self.num_partitions
                )
                order = np.argsort(pids, kind="stable")
                rb_sorted = take_batch(
                    cb, jnp.asarray(np.concatenate(
                        [order,
                         np.arange(cb.num_rows, cb.capacity)])),
                    cb.num_rows,
                ).to_arrow()
                counts = np.bincount(pids, minlength=self.num_partitions)
            elif self.mode == "range":
                # host path: key ordering incl. strings/NULLs needs real
                # values (ordering on dictionary codes would be wrong);
                # the D2H below is the same transfer the IPC encode
                # needs anyway
                rb = cb.to_arrow()
                key_arrays = [
                    _key_array_for_range(rb, cb, e)
                    for e in self.key_exprs
                ]
                pids = range_partition_ids(
                    key_arrays, self.range_bounds, self.sort_ascending
                )
                order = np.argsort(pids, kind="stable")
                rb_sorted = rb.take(order)
                counts = np.bincount(pids, minlength=self.num_partitions)
            else:
                # obs seam: the ids' program and the sort-and-gather
                # program, launched and not waited for, up to the
                # read-back (`d2h`, inside to_arrow)
                with (obs_trace.span("shuffle_partition")
                      if obs_trace.ACTIVE else obs_trace.NULL):
                    cb_sorted, counts = sort_by_partition(
                        cb,
                        spark_partition_ids(
                            aug, exprs, self.num_partitions
                        ),
                        self.num_partitions,
                    )
                rb_sorted = cb_sorted.to_arrow()
            bufs.stage(rb_sorted, counts)
            ctx.metrics.add("shuffle_rows_written", cb.num_rows)
        lengths = bufs.finalize(self.data_file, self.index_file)
        ctx.metrics.add("shuffle_segments_written", bufs.segments)
        ctx.metrics.add("shuffle_bytes_written", sum(lengths))
        return iter(())
