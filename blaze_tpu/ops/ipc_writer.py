"""IPC writer: collect a partition's batches as compressed IPC parts.

Reference counterpart: IpcWriterExec (ipc_writer_exec.rs, 196 LoC) -
coalesces to batch_size rows and hands length-prefixed zstd IPC parts to a
consumer (there a JVM lambda via direct ByteBuffer; here the context
resource registry). Feeds broadcast exchange collection (SURVEY 3.4)."""

from __future__ import annotations

from typing import Iterator, List

from blaze_tpu.types import Schema
from blaze_tpu.batch import ColumnBatch
from blaze_tpu.io.ipc import encode_ipc_segment
from blaze_tpu.ops.base import ExecContext, PhysicalOp
from blaze_tpu.ops.util import sink_arrow


class IpcWriterExec(PhysicalOp):
    def __init__(self, child: PhysicalOp, resource_id: str):
        self.children = [child]
        self.resource_id = resource_id

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def execute(self, partition: int, ctx: ExecContext
                ) -> Iterator[ColumnBatch]:
        sink = ctx.resources.setdefault(self.resource_id, [])
        nbytes = 0
        for cb in self.children[0].execute(partition, ctx):
            rb = sink_arrow(cb, ctx)
            if rb is None:
                continue
            part = encode_ipc_segment(
                rb, ctx.config.ipc_compression_level
            )
            nbytes += len(part)
            sink.append(part)
        ctx.metrics.add("ipc_bytes_written", nbytes)
        return iter(())


def collect_ipc(child: PhysicalOp, ctx: ExecContext) -> List[bytes]:
    """Run all partitions through an IpcWriter and return the parts - the
    engine-side analog of the reference's broadcast collect
    (ArrowBroadcastExchangeExec.scala:178-222)."""
    rid = f"collect-{id(child):x}"
    op = IpcWriterExec(child, rid)
    ctx.resources[rid] = []
    for p in range(child.partition_count):
        for _ in op.execute(p, ctx):
            pass
    return ctx.resources.pop(rid)
