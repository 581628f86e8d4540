"""Task executor: the engine's entry point.

Reference counterpart: the JNI entry `callNative` (exec.rs:118-328) -
decode a TaskDefinition, build the operator tree, execute one partition,
stream Arrow batches back, then push collected metrics. Here the embedding
is in-process Python instead of JNI, and the batch handshake is a plain
iterator instead of the SynchronousQueue rendezvous (NativeSupports.scala:
237-323) - XLA's async dispatch already overlaps host and device work.

Failure semantics follow the reference (SURVEY 5.3): operator errors are
wrapped with task context into TaskExecutionError and propagate cleanly to
the embedder; partial output is never silently dropped.
"""

from __future__ import annotations

import logging
from typing import Iterator, List, Optional

import pyarrow as pa

from blaze_tpu.batch import ColumnBatch
from blaze_tpu.errors import ErrorClass, classify
from blaze_tpu.obs import trace as obs_trace
from blaze_tpu.ops.base import ExecContext, MetricNode, PhysicalOp
from blaze_tpu.ops.util import sink_arrow
from blaze_tpu.testing import chaos

log = logging.getLogger("blaze_tpu.executor")


def _process_count() -> int:
    """jax.process_count without forcing backend init side effects
    beyond what execution needs anyway."""
    import jax

    try:
        return jax.process_count()
    except Exception:  # noqa: BLE001 - uninitialized distributed
        return 1


class TaskExecutionError(RuntimeError):
    def __init__(self, task_id: str, partition: int, cause: BaseException):
        super().__init__(
            f"task {task_id} partition {partition} failed: {cause!r}"
        )
        self.task_id = task_id
        self.partition = partition
        self.__cause__ = cause

    @property
    def error_class(self) -> ErrorClass:
        """Failure taxonomy class of the wrapped cause (the raise-site
        classification the scheduler's retry policy keys on)."""
        return classify(self)


def prepare_decoded_task(decoded, ctx: ExecContext):
    """Shared decode tail for every wire format (engine-native and
    reference-compat): fuse the tree exactly like driver-built plans
    (decoded tasks are the production entry, so they must hit the same
    one-dispatch pipeline programs; reference: the decoded plan IS the
    executed plan, exec.rs:137-165), attach scan hints, and install the
    task's resources into the context."""
    from blaze_tpu.ops.fused import fuse_pipelines
    from blaze_tpu.planner.colprune import install as install_scan_hints

    op, partition, task_id, resources = decoded
    # Mesh lowering first (it matches raw aggregate shapes the fusion
    # rewrite would consume): with >1 visible device, eligible root
    # shapes become one pjit program over the ICI mesh - the
    # cost-guarded pass in planner/distribute.lower_plan_to_mesh.
    # ONLY single-partition plans qualify at this boundary: a
    # TaskDefinition carries ONE partition of its stage, and the SPMD
    # operators consume the WHOLE child - lowering a multi-partition
    # task would double-count its siblings' data. The lowered tree is
    # coalesced so the task's one partition carries every group (the
    # mesh ops' output is per-device disjoint). Mode resolution:
    # ctx.mesh_mode (the serving tier's knob) > BLAZE_MESH_LOWERING
    # env > "auto". "auto" lowers only in a single-controller process
    # (in a multi-process group, ranks decode DIFFERENT tasks - the
    # task-per-partition cluster model - and a one-sided collective
    # would deadlock the group); "on" forces (asserts the caller
    # decodes rank-symmetric tasks - the launcher's SPMD workload);
    # "off" disables. Root-only: a mid-tree rewrite would change the
    # partitioning under Sort/Limit/Window parents.
    from blaze_tpu.planner.distribute import (
        lower_plan_to_mesh,
        resolve_mesh_mode,
    )

    mode = resolve_mesh_mode(ctx)
    lower_ok = mode == "on" or (
        mode == "auto" and _process_count() == 1
    )
    if lower_ok and op.partition_count == 1:
        from blaze_tpu.ops.union import CoalescePartitionsExec

        lowered = lower_plan_to_mesh(op, mode=mode)
        op = (
            CoalescePartitionsExec(lowered)
            if lowered.partition_count != 1
            else lowered
        )
    op = fuse_pipelines(op)
    # freshly-decoded tree: scans are private to this task, so filter
    # pushdown (not just column pruning) is safe to attach
    install_scan_hints(op, with_filters=True)
    ctx.partition_id = partition
    ctx.task_id = task_id
    for rid, provider in resources.items():
        ctx.resources.setdefault(rid, provider)
    return op, partition


def decode_task(task_bytes: bytes, ctx: ExecContext):
    """Decode engine-native TaskDefinition bytes into a runnable
    (op, partition) pair.

    Mesh lowering happens inside prepare_decoded_task (before fusion),
    so every wire format shares it."""
    from blaze_tpu.plan.serde import task_from_proto

    return prepare_decoded_task(task_from_proto(task_bytes), ctx)


def execute_task(task_bytes: bytes,
                 ctx: Optional[ExecContext] = None
                 ) -> Iterator[pa.RecordBatch]:
    """Decode and run one serialized TaskDefinition; yields Arrow batches
    (the FFI-equivalent boundary, exec.rs:205-255)."""
    ctx = ctx or ExecContext()
    op, partition = decode_task(task_bytes, ctx)
    yield from execute_partition(op, partition, ctx)


def execute_partition(op: PhysicalOp, partition: int, ctx: ExecContext
                      ) -> Iterator[pa.RecordBatch]:
    from blaze_tpu.planner.colprune import install as install_scan_hints

    # column pruning for driver-built plans too (required sets only
    # union-grow, so scans shared across plans stay correct; filters are
    # reserved for the fresh-tree decode path)
    install_scan_hints(op)
    if log.isEnabledFor(logging.DEBUG):
        log.debug(
            "executing task %s partition %d:\n%s",
            ctx.task_id, partition, op.display(),
        )
    from blaze_tpu.runtime import dispatch

    counter = dispatch.counting()
    counter.__enter__()
    # obs seam: one span per partition drain (child spans - parquet
    # decode, H2D, kernel dispatch - attach under it via the
    # thread-current stack; the off path is one attribute check)
    span_cm = (
        obs_trace.span(
            "execute_partition", rec=ctx.tracer,
            partition=partition, task=ctx.task_id,
        )
        if obs_trace.ACTIVE else obs_trace.NULL
    )
    try:
        with span_cm:
            if chaos.ACTIVE:
                # the generic per-partition fault seam (chaos harness);
                # inside the try so an injected fault is classified and
                # wrapped exactly like a real operator failure
                chaos.fire(
                    "task.execute", partition=partition,
                    task_id=ctx.task_id,
                )
            # every launch inside a pull is this task's
            # (`task_dispatches`); the consumer runs between pulls
            mine = dispatch.task_scope(ctx)
            with mine:  # an operator may do all its work right here
                stream = iter(op.execute(partition, ctx))
            while True:
                with mine:
                    cb = next(stream, None)
                    if cb is None:
                        break
                    rb = sink_arrow(cb, ctx)
                    if rb is None:
                        continue
                ctx.metrics.add("output_rows", rb.num_rows)
                ctx.metrics.add("output_batches", 1)
                yield rb
    except (KeyboardInterrupt, GeneratorExit):
        # task cancellation must not poison the engine (the reference
        # swallows JVM-interrupts the same way, exec.rs:330-343)
        log.info("task %s partition %d cancelled", ctx.task_id, partition)
        raise
    except Exception as e:
        raise TaskExecutionError(ctx.task_id, partition, e) from e
    finally:
        # per-task dispatch/transfer/kernel-cache accounting in the
        # metric tree (delta of the process-global counters, so
        # concurrent tasks in other threads land here too - same
        # caveat as dispatch.counting itself)
        counter.__exit__(None, None, None)
        for k, v in counter.counts.items():
            ctx.metrics.add("dispatch." + k, v)


def run_plan(op: PhysicalOp, ctx: Optional[ExecContext] = None
             ) -> pa.Table:
    """Run every partition and collect one Arrow table (driver-side
    convenience; partitions share the context/resource registry)."""
    ctx = ctx or ExecContext()
    batches: List[pa.RecordBatch] = []
    schema = None
    for p in range(op.partition_count):
        for rb in execute_partition(op, p, ctx):
            if schema is None:
                schema = rb.schema
            batches.append(rb)
    if schema is None:
        from blaze_tpu.types import to_arrow_schema

        return pa.Table.from_batches([], to_arrow_schema(op.schema))
    aligned = []
    for rb in batches:
        if rb.schema != schema:
            rb = rb.cast(schema)
        aligned.append(rb)
    return pa.Table.from_batches(aligned, schema)
