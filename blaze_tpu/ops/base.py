"""Operator base: execution context, metrics, the PhysicalOp protocol."""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, Iterator, List, Optional

from blaze_tpu.config import EngineConfig, get_config
from blaze_tpu.types import Schema
from blaze_tpu.batch import ColumnBatch


class MetricNode:
    """Per-operator metric tree mirroring the plan, like the reference's
    MetricNode mirrored into Spark SQLMetrics (NativeSupports.scala:215-228,
    native side metrics.rs:32-56). Collected after a partition's stream is
    drained."""

    def __init__(self, name: str, children: Optional[List["MetricNode"]] = None):
        self.name = name
        self.children = children or []
        self.counters: Dict[str, int] = {}

    def add(self, key: str, value: int) -> None:
        self.counters[key] = self.counters.get(key, 0) + int(value)

    def child(self, i: int) -> "MetricNode":
        while len(self.children) <= i:
            self.children.append(MetricNode(f"{self.name}.{len(self.children)}"))
        return self.children[i]

    def flatten(self) -> Dict[str, Dict[str, int]]:
        out = {self.name: dict(self.counters)}
        for c in self.children:
            out.update(c.flatten())
        return out


@dataclasses.dataclass
class ExecContext:
    """Per-task execution context (the reference's TaskDefinition partition
    context + SessionContext config, exec.rs:137-165)."""

    partition_id: int = 0
    num_partitions: int = 1
    task_id: str = "task-0"
    config: EngineConfig = dataclasses.field(default_factory=get_config)
    metrics: MetricNode = dataclasses.field(
        default_factory=lambda: MetricNode("root")
    )
    # resource registry: shuffle readers/writers, broadcast values, etc.
    # (the reference's JniBridge.resourcesMap, JniBridge.java:31)
    resources: Dict[str, object] = dataclasses.field(default_factory=dict)
    # per-query TraceRecorder (obs/trace.py) when tracing is on; the
    # executor/scheduler seams check `trace.ACTIVE` before touching it
    tracer: Optional[object] = None
    # mesh execution mode for this task ("auto"|"on"|"off"); None
    # defers to the BLAZE_MESH_LOWERING env
    # (planner/distribute.resolve_mesh_mode) - the serving tier's
    # mesh_mode knob threads through here
    mesh_mode: Optional[str] = None
    # kernel launches made on behalf of this task alone
    # (runtime/dispatch.py counts them; POLL's `task_dispatches`)
    task_dispatches: int = 0
    # every program launch made on behalf of this task (cached kernels
    # and the plain jits runtime/dispatch.py's `launch` wraps), the
    # launching threads' wall time inside the calls, and the arrays
    # the calls handed back (POLL's `launches`, `launch_s`,
    # `launch_buffers`)
    launches: int = 0
    launch_ns: int = 0
    launch_buffers: int = 0


class PhysicalOp:
    """A node in the physical plan.

    `execute(partition, ctx)` yields ColumnBatches for one partition -
    the host-side analog of DataFusion's ExecutionPlan::execute returning a
    RecordBatch stream (reference from_proto.rs:162-560 builds these).
    """

    children: List["PhysicalOp"] = []

    @property
    def schema(self) -> Schema:
        raise NotImplementedError

    @property
    def partition_count(self) -> int:
        if self.children:
            return self.children[0].partition_count
        return 1

    def execute(self, partition: int, ctx: ExecContext
                ) -> Iterator[ColumnBatch]:
        raise NotImplementedError

    # ------------------------------------------------------------------
    def describe(self) -> str:
        """One-line operator description for plan display."""
        return type(self).__name__

    def display(self, indent: int = 0) -> str:
        """Indented plan tree (the reference logs the same shape at task
        start: displayable(...).indent(), exec.rs:154-158)."""
        lines = ["  " * indent + self.describe()]
        for c in self.children:
            lines.append(c.display(indent + 1))
        return "\n".join(lines)

    # ------------------------------------------------------------------
    def fingerprint(self) -> str:
        """Content-addressed plan identity: operator name + parameter
        digest + children, recursively. Two independently-built (or
        independently-decoded) plans that compute the same thing get the
        SAME fingerprint, which is what keys the serving tier's result
        cache (service/cache.py) and jit-cache lookups.

        Ops that cannot prove stable identity (in-memory scans over
        arbitrary buffers, resource-registry readers) keep the default
        `@id` param digest, valid only for THIS plan object; stability
        is reported out-of-band by `fingerprint_is_stable` (a class
        flag, not a content inspection - parameter digests may contain
        any characters), so result reuse across submissions is refused
        rather than silently wrong."""
        me = f"{type(self).__name__}({self._fingerprint_params()})"
        if not self.children:
            return me
        kids = ",".join(c.fingerprint() for c in self.children)
        return f"{me}[{kids}]"

    # set True by subclasses whose _fingerprint_params covers EVERY
    # execution-relevant parameter (content identity, not object
    # identity)
    _FINGERPRINT_STABLE = False

    def _fingerprint_params(self) -> str:
        """Parameter digest for fingerprint(). Subclasses with full
        parameter coverage return a deterministic content string and
        set _FINGERPRINT_STABLE; the default is object identity."""
        return f"@{id(self):x}"

    def fingerprint_is_stable(self) -> bool:
        """True iff the fingerprint survives re-building the plan:
        every op in the tree declares content-complete parameter
        coverage. Only stable fingerprints may key results shared
        across query submissions (the serving tier's result cache)."""
        return self._FINGERPRINT_STABLE and all(
            c.fingerprint_is_stable() for c in self.children
        )

    def timed(self, metrics: MetricNode, it: Iterator[ColumnBatch]
              ) -> Iterator[ColumnBatch]:
        """Wrap a batch stream with elapsed_compute / row metrics (the
        reference's BaselineMetrics, SURVEY 5.1)."""
        while True:
            t0 = time.perf_counter_ns()
            try:
                b = next(it)
            except StopIteration:
                return
            metrics.add("elapsed_compute", time.perf_counter_ns() - t0)
            metrics.add("output_rows", b.num_rows)
            metrics.add("output_batches", 1)
            yield b
