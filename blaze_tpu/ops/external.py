"""External (grace) execution: hash-bucket oversized inputs through the
engine's own shuffle format, then process bucket-by-bucket.

The reference handles oversized state with the DataFusion MemoryConsumer
spill ladder (shuffle_writer_exec.rs:570-623) and streaming operators; our
sort-based aggregate and vectorized join instead materialize a partition,
which caps input size at device-buffer capacity. This module restores
unbounded inputs the TPU-first way (SURVEY 7 "spill & memory ladder"):

    too-big stream -> murmur3 hash-bucket on the op's keys ->
    segmented-IPC bucket file (same writer/format as the shuffle tier) ->
    per-bucket processing (each bucket now fits)

Because bucketing uses the same key hash on both join sides, equal keys
co-locate and every join type remains correct bucket-wise; for aggregation
every group lands wholly in one bucket.
"""

from __future__ import annotations

import os
import tempfile
from typing import Callable, Iterator, List, Optional, Sequence

from blaze_tpu.types import Schema
from blaze_tpu.batch import ColumnBatch
from blaze_tpu.exprs import ir
from blaze_tpu.io.ipc import partition_ranges, read_file_segment
from blaze_tpu.ops.base import ExecContext
from blaze_tpu.ops.shuffle_writer import (
    PartitionBuffers,
    sort_by_partition,
    spark_partition_ids,
)
from blaze_tpu.ops.util import ensure_compacted


class BucketedInput:
    """A stream hash-bucketed into an on-disk .data/.index pair."""

    def __init__(self, data_path: str, index_path: str, schema: Schema,
                 n_buckets: int):
        self.data_path = data_path
        self.index_path = index_path
        self.schema = schema
        self.n_buckets = n_buckets

    def bucket(self, i: int) -> Iterator[ColumnBatch]:
        off, length = partition_ranges(self.index_path)[i]
        if length == 0:
            return
        for rb in read_file_segment(self.data_path, off, length):
            yield ColumnBatch.from_arrow(rb)

    def cleanup(self) -> None:
        for p in (self.data_path, self.index_path):
            try:
                os.remove(p)
            except OSError:
                pass


def subdivide_pid_fn(key_exprs: Sequence[ir.Expr], parent_modulus: int,
                     fanout: int = 4) -> Callable:
    """pid function splitting one parent hash bucket into `fanout`
    children using the NEXT hash bits: rows of a parent bucket share
    h % parent_modulus, so pmod(h, parent_modulus * fanout) //
    parent_modulus spreads them over 0..fanout-1. Grace recursion uses
    this so each level allocates `fanout` buckets, not parent * fanout
    (of which all but `fanout` would stay empty)."""

    def pid(cb: ColumnBatch):
        # a device array or numpy, as `spark_partition_ids` hands it
        wide = spark_partition_ids(
            cb, list(key_exprs), parent_modulus * fanout
        )
        return wide // parent_modulus

    return pid


def bucket_stream(
    batches: Iterator[ColumnBatch],
    key_exprs: Sequence[ir.Expr],
    n_buckets: int,
    ctx: ExecContext,
    schema: Schema,
    head: Sequence[ColumnBatch] = (),
    pid_fn: Optional[Callable] = None,
) -> BucketedInput:
    """Write (head + remaining stream) into n_buckets hash buckets using
    the shuffle writer's scatter + segmented-IPC machinery. `pid_fn`
    overrides the partition-id computation (grace recursion)."""
    d = ctx.config.spill_dir()
    fd, data_path = tempfile.mkstemp(prefix="blz-ext-", suffix=".data",
                                     dir=d)
    os.close(fd)
    index_path = data_path[:-5] + ".index"
    bufs = PartitionBuffers(
        n_buckets, d, ctx.config.batch_size,
        ctx.config.ipc_compression_level,
    )

    def feed(cb: ColumnBatch) -> None:
        cb = ensure_compacted(cb)
        if cb.num_rows == 0:
            return
        pids = (
            pid_fn(cb) if pid_fn is not None
            else spark_partition_ids(cb, list(key_exprs), n_buckets)
        )
        cb_sorted, counts = sort_by_partition(cb, pids, n_buckets)
        bufs.stage(cb_sorted.to_arrow(), counts)

    for cb in head:
        feed(cb)
    for cb in batches:
        feed(cb)
    bufs.finalize(data_path, index_path)
    return BucketedInput(data_path, index_path, schema, n_buckets)


def collect_until(
    it: Iterator[ColumnBatch], row_limit: int
) -> tuple[List[ColumnBatch], bool]:
    """Pull batches until the stream ends or row_limit is crossed.
    Returns (collected, exceeded)."""
    out: List[ColumnBatch] = []
    total = 0
    for cb in it:
        out.append(cb)
        total += cb.num_rows
        if total > row_limit:
            return out, True
    return out, False
