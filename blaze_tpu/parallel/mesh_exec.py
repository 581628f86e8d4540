"""Mesh execution tier: partition-parallel operators over the device mesh.

PR 1 made one partition cheap (fused single-dispatch pipelines); this tier
makes N partitions simultaneous: every eligible operator executes ALL of
its partitions inside ONE pjit program over `get_mesh()`, one partition
per device on the 'data' axis, with exchange state HBM-resident end to end
(the host touches data only at the mesh boundary - staging in, fetching
out). The reference's exchange operators (shuffle repartition + broadcast,
SURVEY 2/5) map onto the mesh's native collectives: group-by partial
states repartition by key hash over ICI `all_to_all`
(parallel/sharded.DistributedGroupBy), broadcast joins replicate the build
side with one `all_gather` and reduce matches locally.

Operators here (plus MeshGroupByExec in parallel/mesh_ops.py, which
predates this module and shares its helpers):

  MeshPipelineExec       a scan->filter->project chain executed for every
                         source partition at once: N partitions = ONE
                         dispatch instead of N (no collective - purely
                         partition-parallel)
  MeshBroadcastJoinExec  broadcast hash join: small build side replicated
                         over ICI all_gather, probes local per shard,
                         matches reduced locally (unique-build-key inner
                         join, the dimension-table case)

Failure ladder (blaze_tpu/errors.py taxonomy, PR 3): a TRANSIENT mesh
failure propagates so the task-retry tier re-runs the whole mesh program;
anything else degrades to the op's single-device `fallback` plan
(`mesh.degraded` in the metric tree) - and if that in turn exhausts
resources, the existing service path degrades it to the host engine.
Chaos seam: `mesh.exchange` fires before every mesh program launch.

Observability: every mesh run lands a `mesh_execute` span with one
`mesh_device` child span per device (rows in / rows out tags) and a
`mesh.exchange.*` metric family in the query metric tree; the program
launch is counted as a dispatch (`mesh_dispatches` alongside
`dispatches`), so the dispatch-count perf model covers mesh plans too.
Stage anatomy (obs/meshprof.py): every stage additionally splits into
named sub-phases - mesh_trace (AOT lower+compile, pulled AHEAD of the
launch so trace cost is its own phase), mesh_stage_in, mesh_launch,
mesh_sync, mesh_gather - child spans under `mesh_execute` plus an
always-on rollup; the single-flight locks are named `TimedLock`s so
wait:hold lands in the contention report. The chaos seam fires at the
top of mesh_launch: after the program exists, modeling exchange-fabric
faults rather than compile faults (an injected STALL lands in
mesh_launch, not mesh_trace).
"""

from __future__ import annotations

import logging
import time
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map

from blaze_tpu.batch import Column, ColumnBatch
from blaze_tpu.errors import ErrorClass, classify
from blaze_tpu.exprs import ir
from blaze_tpu.obs import contention as obs_contention
from blaze_tpu.obs import meshprof
from blaze_tpu.obs import trace as obs_trace
from blaze_tpu.obs.metrics import REGISTRY
from blaze_tpu.ops.base import ExecContext, PhysicalOp
from blaze_tpu.ops.util import concat_batches, ensure_compacted
from blaze_tpu.parallel.mesh import get_mesh
from blaze_tpu.runtime import dispatch
from blaze_tpu.testing import chaos

log = logging.getLogger("blaze_tpu.mesh")

# per-device span tracks in the exported trace: small synthetic tids so
# each device renders as its own row under the query's process
_DEVICE_TID_BASE = 1000
_MESH_TID = 999


# ---------------------------------------------------------------------------
# staging: host partitions -> HBM-resident [n_dev, cap] stacks
# ---------------------------------------------------------------------------


def to_mesh(global_np: np.ndarray, mesh, axis: str = "data"):
    """Place one host array on the mesh, sharded on its leading axis.

    Single-controller: an explicit device_put with the mesh sharding (the
    HBM-residency contract - the pjit consumes shards in place, no
    implicit re-layout). Multi-process SPMD: every rank holds the full
    logical value (callers decode rank-symmetrically), so build the
    global array from each rank's addressable shards."""
    spec = P(axis, *([None] * (global_np.ndim - 1)))
    sharding = NamedSharding(mesh, spec)
    if jax.process_count() > 1:
        return jax.make_array_from_callback(
            global_np.shape, sharding, lambda idx: global_np[idx]
        )
    return jax.device_put(global_np, sharding)


def _partitions(child: PhysicalOp, ctx: ExecContext,
                n_dev: int) -> Iterator[ColumnBatch]:
    """Every child partition in turn as one compacted batch. Raises
    NotImplementedError for what no mesh operator stages (string
    columns, more partitions than devices)."""
    if child.partition_count > n_dev:
        raise NotImplementedError(
            "more partitions than devices; use the exchange tier"
        )
    for f in child.schema.fields:
        if f.dtype.is_string_like or f.dtype.is_dictionary_encoded:
            raise NotImplementedError(
                "string columns use the file-shuffle tier"
            )
    for p in range(child.partition_count):
        yield ensure_compacted(concat_batches(
            list(child.execute(p, ctx)), schema=child.schema
        ))


def stack_nullable(child: PhysicalOp, ctx: ExecContext, mesh,
                   axis: str = "data"):
    """`stack_partitions` for the group-by, whose program takes
    validity: every column comes with a [n_dev, cap] validity stack
    (all true where the child's batches had none), and a child of ONE
    partition is dealt over the devices, a run of its rows each,
    instead of landing whole on device 0.

    Returns (stacked_cols, stacked_validity, num_rows_arr, the rows a
    device as a host array, staged_bytes)."""
    from blaze_tpu.runtime.pack import deal_cuts

    n_dev = int(mesh.shape[axis])
    per_part = list(_partitions(child, ctx, n_dev))
    if len(per_part) == 1 and n_dev > 1:
        cuts = [(0, lo, hi)
                for lo, hi in deal_cuts(per_part[0].num_rows, n_dev)]
        cap = ctx.config.bucket_for(max(cuts[0][2], 1))
    else:
        cuts = [(p, 0, b.num_rows) for p, b in enumerate(per_part)]
        cap = max(max((b.capacity for b in per_part), default=1), 1)
    stacked, valids, nbytes = [], [], 0
    for ci, f in enumerate(child.schema.fields):
        host = np.zeros((n_dev, cap), dtype=f.dtype.physical_dtype())
        ok = None  # a column that holds no NULL stages no validity
        # one read-back a partition's column, whatever its cuts
        vals = [np.asarray(b.columns[ci].values) for b in per_part]
        oks = [None if b.columns[ci].validity is None
               else np.asarray(b.columns[ci].validity) for b in per_part]
        for d, (p, lo, hi) in enumerate(cuts):
            host[d, :hi - lo] = vals[p][lo:hi]
            if oks[p] is not None:
                if ok is None:
                    ok = np.ones((n_dev, cap), dtype=np.bool_)
                ok[d, :hi - lo] = oks[p][lo:hi]
        nbytes += host.nbytes + (0 if ok is None else ok.nbytes)
        stacked.append(to_mesh(host, mesh, axis))
        valids.append(None if ok is None else to_mesh(ok, mesh, axis))
    rows = np.zeros(n_dev, dtype=np.int32)
    rows[:len(cuts)] = [hi - lo for _, lo, hi in cuts]
    # staging accounting: one logical H2D per staged stack (+1 for the
    # row counts) - the mesh analog of the packed-batch H2D
    dispatch.record(
        "h2d_batches",
        len(stacked) + sum(v is not None for v in valids) + 1)
    return stacked, valids, to_mesh(rows, mesh, axis), rows, nbytes


def stack_partitions(child: PhysicalOp, ctx: ExecContext, mesh,
                     axis: str = "data"):
    """Materialize every child partition and stage the columns as
    HBM-resident [n_dev, cap] stacks (one device per partition, zero-
    padded tail devices for children narrower than the mesh).

    Returns (stacked_cols, num_rows_arr, cap, total_rows, host_cols);
    `host_cols` is the pre-device_put [n_dev, cap] numpy stack per
    column, so a consumer that needs input columns BACK on the host
    (the broadcast join's probe output) reuses them instead of paying
    a second boundary crossing. Raises NotImplementedError for data
    the mesh tier does not handle (string columns, materialized
    validity masks) - callers treat that as ineligibility and fall
    back."""
    n_dev = int(mesh.shape[axis])
    per_part = []
    for b in _partitions(child, ctx, n_dev):
        # fail fast BEFORE materializing the remaining partitions: a
        # nullable input detected here falls back to the original plan,
        # and everything collected so far is sunk cost
        for c in b.columns:
            if c.validity is not None:
                raise NotImplementedError(
                    "mesh tier handles non-nullable columns; nullable "
                    "inputs use the exchange tier"
                )
        per_part.append(b)
    cap = max(max((b.capacity for b in per_part), default=1), 1)
    stacked, host_cols = [], []
    for ci, f in enumerate(child.schema.fields):
        phys = f.dtype.physical_dtype()
        rows = []
        for b in per_part:
            v = np.asarray(b.columns[ci].values)
            if len(v) < cap:
                v = np.pad(v, (0, cap - len(v)))
            rows.append(v)
        for _ in range(n_dev - len(per_part)):
            rows.append(np.zeros(cap, dtype=phys))
        host = np.stack(rows)
        host_cols.append(host)
        stacked.append(to_mesh(host, mesh, axis))
    num_rows = to_mesh(
        np.array(
            [b.num_rows for b in per_part]
            + [0] * (n_dev - len(per_part)),
            dtype=np.int32,
        ),
        mesh, axis,
    )
    # staging accounting: one logical H2D per staged column stack (+1
    # for the row counts) - the mesh analog of the packed-batch H2D
    dispatch.record("h2d_batches", len(stacked) + 1)
    total = sum(b.num_rows for b in per_part)
    return stacked, num_rows, cap, total, host_cols


# ---------------------------------------------------------------------------
# shared observe / chaos / degrade machinery
# ---------------------------------------------------------------------------


def mesh_chaos(op_name: str, n_dev: int, ctx: ExecContext) -> None:
    """The `mesh.exchange` chaos seam: fires before every mesh program
    launch (docs/ROBUSTNESS.md) - one module-attribute check off."""
    if chaos.ACTIVE:
        chaos.fire(
            "mesh.exchange", op=op_name, devices=n_dev,
            task_id=ctx.task_id,
        )


def record_exchange(ctx: ExecContext, kind: str, rows: int,
                    nbytes: int) -> None:
    """One ICI collective in the `mesh.exchange.*` metric family (the
    per-query metric tree) + the process registry."""
    ctx.metrics.add(f"mesh.exchange.{kind}", 1)
    ctx.metrics.add("mesh.exchange.rows", rows)
    ctx.metrics.add("mesh.exchange.bytes", nbytes)
    REGISTRY.inc("blaze_mesh_exchange_total", kind=kind)
    REGISTRY.inc("blaze_mesh_exchange_rows_total", n=rows)


def record_mesh_run(ctx: ExecContext, op_name: str, n_dev: int,
                    t0: float, t1: float,
                    per_device: Sequence[dict],
                    stage: Optional["meshprof.MeshStage"] = None
                    ) -> None:
    """Fold one mesh program execution into the metric tree and (when
    tracing) land a `mesh_execute` span with one `mesh_device` child
    per device - the per-device view of a single SPMD program. With a
    finished meshprof stage, the `mesh_execute` span widens to the full
    stage wall and the named sub-phases land as child spans on their
    own synthetic track (sequential, so the per-track nesting sweep
    stays chrome-clean; the mesh_lower phase may predate the stage -
    the recorder's root-widening invariant absorbs it)."""
    ctx.metrics.add("mesh.runs", 1)
    ctx.metrics.add("mesh.devices", n_dev)
    REGISTRY.inc("blaze_mesh_runs_total", op=op_name)
    if not (obs_trace.ACTIVE and ctx.tracer is not None):
        return
    rec = ctx.tracer
    span_t0 = stage.t0 if stage is not None else t0
    span_t1 = stage.t1 if stage is not None and stage.t1 else t1
    parent = rec.record_span(
        "mesh_execute", span_t0, span_t1,
        parent=obs_trace.current_span(), tid=_MESH_TID,
        op=op_name, devices=n_dev,
    )
    if parent is None:  # span cap
        return
    if stage is not None:
        for name, p0, p1 in stage.phases:
            rec.record_span(
                name, p0, p1, parent=parent,
                tid=meshprof.MESH_SUB_TID, op=op_name,
            )
    for d, tags in enumerate(per_device):
        rec.record_span(
            "mesh_device", t0, t1, parent=parent,
            tid=_DEVICE_TID_BASE + d, device=d, **tags,
        )


def degrade_or_raise(op: PhysicalOp, ctx: ExecContext,
                     e: BaseException) -> None:
    """The mesh failure ladder: TRANSIENT (and cancellation) propagate
    so the task-retry tier re-runs the mesh program; everything else -
    ineligibility discovered at execution, injected faults, resource
    exhaustion inside the mesh program - degrades THIS op to its
    single-device fallback plan. (A fallback that itself exhausts
    resources still reaches the host engine through the service's
    existing degradation path - mesh -> single-device -> host.)"""
    if getattr(op, "fallback", None) is None:
        raise e
    if not isinstance(e, (NotImplementedError, AssertionError)):
        ec = classify(e)
        if ec in (ErrorClass.TRANSIENT, ErrorClass.CANCELLED):
            raise e
    op._use_fallback = True
    op._result = None
    ctx.metrics.add("mesh.degraded", 1)
    REGISTRY.inc("blaze_mesh_degraded_total")
    if obs_trace.ACTIVE:
        obs_trace.event(
            "mesh.degraded", op=type(op).__name__,
            error=str(e)[:200],
        )
    log.warning(
        "%s degrading to single-device fallback: %s",
        type(op).__name__, e,
    )


# ---------------------------------------------------------------------------
# MeshPipelineExec: sharded scan -> filter -> project chains
# ---------------------------------------------------------------------------


class _TracedProgram:
    """Signature-keyed trace state for a mesh program that jits one
    callable: the cacheable holder shape (fleet/program_cache) shared
    by the pipeline and sort ops. `prepare()` returns True only when a
    trace actually ran, so a cache hit re-lowered onto a fresh op
    instance skips the trace AND the retrace accounting."""

    def __init__(self, compile_fn):
        self._compile = compile_fn
        self._fn = None
        self._exec = None  # AOT-compiled executable (mesh_trace phase)
        self._exec_sig = None
        self._traced_sigs = set()

    def prepare(self, *args) -> bool:
        if self._fn is None:
            self._fn = self._compile(len(args))
        sig = meshprof.arg_signature(*args)
        if sig in self._traced_sigs:
            return False
        self._traced_sigs.add(sig)
        try:
            self._exec = self._fn.lower(*args).compile()
            self._exec_sig = sig
        except Exception:  # noqa: BLE001 - no AOT: trace at launch
            self._exec = None
            self._exec_sig = None
        return True

    def __call__(self, *args):
        sig = meshprof.arg_signature(*args)
        if self._exec is not None and self._exec_sig == sig:
            return dispatch.launch(self._exec, *args)
        return dispatch.launch(self._fn, *args)


class MeshPipelineExec(PhysicalOp):
    """A filter/project chain over a multi-partition source, executed
    for ALL source partitions in one shard_map program (one partition
    per device). No collective - purely partition-parallel - but the
    N-partitions-for-one-dispatch shape is the mesh tier's raw-speed
    lever for the pipeline stages under an exchange.

    `chain` is the list of Filter/Project nodes from the ROOT down to
    (excluding) the source; each node's bound expressions are evaluated
    per shard against its own input schema. Output: one partition per
    device, live rows compacted host-side at the mesh boundary.
    """

    def __init__(self, root: PhysicalOp, chain: List[PhysicalOp],
                 source: PhysicalOp, mesh=None,
                 fallback: Optional[PhysicalOp] = None):
        from blaze_tpu.ops.filter import FilterExec
        from blaze_tpu.ops.project import ProjectExec

        self.fallback = fallback
        self._use_fallback = False
        self.children = [source]
        self.mesh = mesh or get_mesh()
        self._axis = "data"
        self._schema = root.schema
        for f in self._schema.fields:
            if f.dtype.is_string_like or f.dtype.is_dictionary_encoded:
                raise NotImplementedError(
                    "string outputs use the per-partition tier"
                )
        # bottom-up stage list; every stage is (kind, payload, schema)
        self._stages: List[Tuple[str, object, object]] = []
        for node in reversed(chain):
            if isinstance(node, FilterExec):
                self._stages.append(("filter", node.predicate,
                                     node.schema))
            elif isinstance(node, ProjectExec):
                self._stages.append(("project", list(node.exprs),
                                     node.schema))
            else:
                raise NotImplementedError(
                    f"mesh pipeline cannot shard {type(node).__name__}"
                )
        # structurally-keyed program holder: a fresh lowering of the
        # same chain on the same mesh reuses the traced program
        from blaze_tpu.fleet.program_cache import (
            PROGRAM_CACHE, mesh_cache_key,
        )

        src_schema = source.schema
        cache_key = (
            "mesh.pipeline",
            tuple((f.name, repr(f.dtype), f.nullable)
                  for f in src_schema.fields),
            tuple((kind, repr(payload))
                  for kind, payload, _ in self._stages),
            self._axis,
            mesh_cache_key(self.mesh),
        )
        self._prog = PROGRAM_CACHE.get_or_build(
            cache_key,
            lambda: _TracedProgram(
                lambda nargs: self._compile(nargs - 1)
            ),
        )
        self._result = None
        # single-flight, named so wait:hold lands in the contention
        # report (obs/contention) when the collector is armed
        self._lock = obs_contention.TimedLock("mesh_pipeline")

    @property
    def schema(self):
        return self._schema

    @property
    def partition_count(self) -> int:
        return int(self.mesh.shape[self._axis])

    def describe(self) -> str:
        return (f"MeshPipelineExec[{len(self._stages)} stages, "
                f"{self.partition_count} devices]")

    def _trace_key(self, sig) -> tuple:
        """Logical program identity for re-trace accounting: op kind +
        structural stage expressions + argument signature (repr of the
        IR dataclasses prints structurally)."""
        return (
            "mesh.pipeline",
            tuple(
                (kind, repr(payload)) for kind, payload, _ in self._stages
            ),
            sig,
        )

    # -- program ---------------------------------------------------------
    def _compile(self, ncols: int):
        from blaze_tpu.exprs.eval import DeviceEvaluator

        mesh, axis = self.mesh, self._axis
        src_schema = self.children[0].schema
        stages = self._stages

        def per_shard(num_rows_s, *cols_s):
            cols = [c[0] for c in cols_s]
            nr = num_rows_s[0]
            cap = cols[0].shape[0]
            live = jnp.arange(cap, dtype=jnp.int32) < nr
            cur_schema, cur_cols = src_schema, cols
            for kind, payload, out_schema in stages:
                ev = DeviceEvaluator(
                    cur_schema, [(c, None) for c in cur_cols], cap
                )
                if kind == "filter":
                    live = live & ev.evaluate_predicate(payload)
                else:
                    outs = []
                    for e, _ in payload:
                        v, mm = ev.evaluate(e)
                        if mm is not None:
                            # a masked (nullable) projection output
                            # has no mesh representation yet: trace-
                            # time ineligibility -> fallback
                            raise NotImplementedError(
                                "nullable projection output on the "
                                "mesh tier"
                            )
                        outs.append(v)
                    cur_schema, cur_cols = out_schema, outs
            return tuple(c[None] for c in cur_cols) + (live[None],)

        n_out = len(self._schema) + 1
        fn = shard_map(
            per_shard, mesh=mesh,
            in_specs=(P(axis),) + tuple(P(axis) for _ in range(ncols)),
            out_specs=tuple([P(axis)] * n_out),
        )
        return jax.jit(fn)

    def _run(self, ctx: ExecContext):
        with self._lock:
            if self._result is not None:
                return self._result
            n_dev = self.partition_count
            st = meshprof.stage(
                "mesh.pipeline", n_dev,
                lower_window=getattr(self, "_mesh_lower", None),
            )
            with st.phase("mesh_stage_in"):
                stacked, num_rows, cap, total, host_cols = (
                    stack_partitions(
                        self.children[0], ctx, self.mesh, self._axis
                    )
                )
                st.add_bytes(sum(h.nbytes for h in host_cols))
            with st.phase("mesh_trace"):
                if self._prog.prepare(num_rows, *stacked):
                    meshprof.note_trace(
                        "mesh.pipeline",
                        self._trace_key(meshprof.arg_signature(
                            num_rows, *stacked
                        )),
                    )
            t0 = time.monotonic()
            with st.phase("mesh_launch"):
                mesh_chaos("mesh.pipeline", n_dev, ctx)
                dispatch.record("dispatches")
                dispatch.record("mesh_dispatches")
                outs = self._prog(num_rows, *stacked)
            with st.phase("mesh_sync"):
                outs = jax.block_until_ready(outs)
            with st.phase("mesh_gather"):
                outs = dispatch.device_get(outs)
            t1 = st.finish()
            out_cols, live = outs[:-1], np.asarray(outs[-1])
            nr_host = np.asarray(num_rows)
            record_mesh_run(
                ctx, "mesh.pipeline", n_dev, t0, t1,
                [{"rows_in": int(nr_host[d]),
                  "rows_out": int(live[d].sum())}
                 for d in range(n_dev)],
                stage=st,
            )
            ctx.metrics.add("mesh.pipeline_rows", total)
            self._result = (out_cols, live)
            return self._result

    def execute(self, partition: int, ctx: ExecContext
                ) -> Iterator[ColumnBatch]:
        if self.fallback is not None and not self._use_fallback:
            try:
                self._run(ctx)
            except Exception as e:  # noqa: BLE001 - ladder below
                degrade_or_raise(self, ctx, e)
        if self._use_fallback:
            if partition < self.fallback.partition_count:
                yield from self.fallback.execute(partition, ctx)
            return
        out_cols, live = self._run(ctx)
        idx = np.nonzero(live[partition])[0]
        if len(idx) == 0:
            return
        cols: List[Column] = []
        for arr, f in zip(out_cols, self._schema.fields):
            v = np.asarray(arr[partition])[idx].astype(
                f.dtype.physical_dtype()
            )
            cols.append(Column(f.dtype, v, None, None))
        yield ColumnBatch(self._schema, cols, len(idx))


# ---------------------------------------------------------------------------
# MeshBroadcastJoinExec: ICI-broadcast build side, local probe
# ---------------------------------------------------------------------------


class MeshBroadcastJoinExec(PhysicalOp):
    """Broadcast hash join over the mesh: the (small) build relation is
    replicated to every device with ONE all_gather over ICI, each probe
    partition matches locally, and matches are reduced locally - the
    reference's ArrowBroadcastExchangeExec + CollectLeft probe as a
    single SPMD program (parallel/sharded.DistributedBroadcastJoin).

    Gates (fall back otherwise): INNER equi-join on ONE integer key
    pair, unique build keys (checked at execution - the dimension-table
    contract that keeps output shapes static), fixed-width non-nullable
    columns, probe partitions <= mesh size. Output: one partition per
    device, schema = build fields + probe fields (HashJoinExec INNER
    layout).
    """

    def __init__(self, build: PhysicalOp, probe: PhysicalOp,
                 build_key: int, probe_key: int,
                 mesh=None, fallback: Optional[PhysicalOp] = None):
        self.fallback = fallback
        self._use_fallback = False
        self.children = [build, probe]
        self.mesh = mesh or get_mesh()
        self._axis = "data"
        self.build_key = build_key
        self.probe_key = probe_key
        for side, key in ((build, build_key), (probe, probe_key)):
            dt = side.schema.fields[key].dtype
            if not dt.is_integer:
                raise NotImplementedError(
                    "mesh broadcast join requires integer keys"
                )
        from blaze_tpu.types import Field, Schema

        self._schema = Schema(
            [Field(f.name, f.dtype, f.nullable)
             for f in build.schema.fields]
            + [Field(f.name, f.dtype, f.nullable)
               for f in probe.schema.fields]
        )
        self._join = None
        self._result = None
        # single-flight, named for the contention report
        self._lock = obs_contention.TimedLock("mesh_bcast_join")

    @property
    def schema(self):
        return self._schema

    @property
    def partition_count(self) -> int:
        return int(self.mesh.shape[self._axis])

    def describe(self) -> str:
        return (f"MeshBroadcastJoinExec[{self.partition_count} "
                f"devices]")

    def _shard_build(self, ctx: ExecContext):
        """Collect the build relation and shard it row-wise over the
        mesh [n_dev, b_cap] (the all_gather inside the program re-
        assembles the full relation on every device)."""
        build = self.children[0]
        n_dev = self.partition_count
        batches = [
            b for p in range(build.partition_count)
            for b in build.execute(p, ctx)
        ]
        whole = ensure_compacted(
            concat_batches(batches, schema=build.schema)
        )
        for c in whole.columns:
            if c.validity is not None:
                raise NotImplementedError(
                    "nullable build side uses the per-partition tier"
                )
        n_build = whole.num_rows
        keys = np.asarray(whole.columns[self.build_key].values)[:n_build]
        if len(np.unique(keys)) != n_build:
            raise NotImplementedError(
                "duplicate build keys use the per-partition join"
            )
        b_cap = max(1, -(-max(n_build, 1) // n_dev))
        stacked = []
        for ci, f in enumerate(build.schema.fields):
            v = np.asarray(whole.columns[ci].values)[:n_build]
            pad = n_dev * b_cap - n_build
            v = np.pad(v, (0, pad)).reshape(n_dev, b_cap)
            stacked.append(to_mesh(
                v.astype(f.dtype.physical_dtype()), self.mesh,
                self._axis,
            ))
        rows = np.full(n_dev, b_cap, dtype=np.int32)
        used = n_build
        for d in range(n_dev):
            rows[d] = max(0, min(b_cap, used))
            used -= rows[d]
        dispatch.record("h2d_batches", len(stacked) + 1)
        return stacked, to_mesh(rows, self.mesh, self._axis), n_build

    def _run(self, ctx: ExecContext):
        with self._lock:
            if self._result is not None:
                return self._result
            from blaze_tpu.parallel.sharded import (
                DistributedBroadcastJoin,
            )

            build, probe = self.children
            n_dev = self.partition_count
            st = meshprof.stage(
                "mesh.broadcast_join", n_dev,
                lower_window=getattr(self, "_mesh_lower", None),
            )
            with st.phase("mesh_stage_in"):
                b_cols, b_rows, n_build = self._shard_build(ctx)
                p_cols, p_rows, p_cap, p_total, p_host = (
                    stack_partitions(
                        probe, ctx, self.mesh, self._axis
                    )
                )
                # probe stacks dominate staging; the build side is the
                # small (dimension-table) relation
                st.add_bytes(sum(h.nbytes for h in p_host))
            with st.phase("mesh_trace"):
                if self._join is None:
                    from blaze_tpu.fleet.program_cache import (
                        PROGRAM_CACHE, mesh_cache_key,
                    )

                    cache_key = (
                        "mesh.broadcast_join",
                        tuple((f.name, repr(f.dtype), f.nullable)
                              for f in probe.schema.fields),
                        tuple((f.name, repr(f.dtype), f.nullable)
                              for f in build.schema.fields),
                        self.probe_key, self.build_key, self._axis,
                        mesh_cache_key(self.mesh),
                    )
                    self._join = PROGRAM_CACHE.get_or_build(
                        cache_key,
                        lambda: DistributedBroadcastJoin(
                            self.mesh, probe.schema, build.schema,
                            probe_key=ir.BoundCol(
                                self.probe_key,
                                probe.schema.fields[
                                    self.probe_key
                                ].dtype,
                            ),
                            build_key=ir.BoundCol(
                                self.build_key,
                                build.schema.fields[
                                    self.build_key
                                ].dtype,
                            ),
                            axis=self._axis,
                        ),
                    )
                if self._join.prepare(p_cols, p_rows, b_cols, b_rows):
                    meshprof.note_trace(
                        "mesh.broadcast_join",
                        ("mesh.broadcast_join",
                         repr(self._join.probe_key),
                         repr(self._join.build_key),
                         meshprof.arg_signature(
                             p_cols, p_rows, b_cols, b_rows
                         )),
                    )
            t0 = time.monotonic()
            with st.phase("mesh_launch"):
                mesh_chaos("mesh.broadcast_join", n_dev, ctx)
                dispatch.record("dispatches")
                dispatch.record("mesh_dispatches")
                hit, build_out = self._join(
                    p_cols, p_rows, b_cols, b_rows
                )
            with st.phase("mesh_sync"):
                hit, build_out = jax.block_until_ready(
                    (hit, build_out)
                )
            # ONE batched fetch of the small outputs (hit mask +
            # gathered build values); the probe columns come back from
            # stack_partitions' host-side stacks - staging them in is
            # the only boundary crossing they pay
            with st.phase("mesh_gather"):
                hit, build_out = dispatch.device_get((hit, build_out))
            t1 = st.finish()
            hit = np.asarray(hit)
            nbytes = sum(
                int(np.asarray(c).nbytes) for c in build_out
            )
            record_exchange(ctx, "all_gather", n_build, nbytes)
            nr_host = np.asarray(p_rows)
            record_mesh_run(
                ctx, "mesh.broadcast_join", n_dev, t0, t1,
                [{"rows_in": int(nr_host[d]),
                  "matches": int(hit[d].sum())}
                 for d in range(n_dev)],
                stage=st,
            )
            ctx.metrics.add(
                "mesh_join_matches", int(hit.sum())
            )
            self._result = (
                hit,
                [np.asarray(c) for c in build_out],
                p_host,
            )
            return self._result

    def execute(self, partition: int, ctx: ExecContext
                ) -> Iterator[ColumnBatch]:
        if self.fallback is not None and not self._use_fallback:
            try:
                self._run(ctx)
            except Exception as e:  # noqa: BLE001 - ladder below
                degrade_or_raise(self, ctx, e)
        if self._use_fallback:
            if partition < self.fallback.partition_count:
                yield from self.fallback.execute(partition, ctx)
            return
        hit, build_out, probe_out = self._run(ctx)
        idx = np.nonzero(hit[partition])[0]
        if len(idx) == 0:
            return
        build, probe = self.children
        cols: List[Column] = []
        for arr, f in zip(build_out, build.schema.fields):
            cols.append(Column(
                f.dtype,
                arr[partition][idx].astype(f.dtype.physical_dtype()),
                None, None,
            ))
        for arr, f in zip(probe_out, probe.schema.fields):
            cols.append(Column(
                f.dtype,
                arr[partition][idx].astype(f.dtype.physical_dtype()),
                None, None,
            ))
        yield ColumnBatch(self._schema, cols, len(idx))


# ---------------------------------------------------------------------------
# MeshSortExec: per-shard device sort, host run-merge (ISSUE 20)
# ---------------------------------------------------------------------------


class MeshSortExec(PhysicalOp):
    """A global sort executed as N simultaneous per-shard device sorts
    (one stable lexsort per device inside ONE shard_map program)
    followed by a host k-way merge of the sorted runs - the expensive
    O(n log n) comparisons happen on all devices at once, the host pays
    only the linear merge. Single output partition (a sort is a global
    ordering).

    Gates (fall back otherwise): exactly one ascending key, a
    non-nullable integer bound column, fixed-width non-nullable input
    columns (stack_partitions' contract). Stability matches the
    single-device oracle: ties keep earlier partitions first, and the
    per-shard lexsort is stable within a partition.
    """

    def __init__(self, source: PhysicalOp, keys, fetch=None,
                 mesh=None, fallback: Optional[PhysicalOp] = None):
        self.fallback = fallback
        self._use_fallback = False
        self.children = [source]
        self.mesh = mesh or get_mesh()
        self._axis = "data"
        self._schema = source.schema
        self.fetch = fetch
        if len(keys) != 1:
            raise NotImplementedError(
                "mesh sort takes exactly one key"
            )
        k = keys[0]
        if not k.ascending or not isinstance(k.expr, ir.BoundCol):
            raise NotImplementedError(
                "mesh sort: single ascending bound column only"
            )
        f = source.schema.fields[k.expr.index]
        if not f.dtype.is_integer:
            raise NotImplementedError(
                "mesh sort requires an integer key"
            )
        self.key_index = k.expr.index
        from blaze_tpu.fleet.program_cache import (
            PROGRAM_CACHE, mesh_cache_key,
        )

        cache_key = (
            "mesh.sort",
            tuple((fld.name, repr(fld.dtype), fld.nullable)
                  for fld in self._schema.fields),
            self.key_index, self._axis,
            mesh_cache_key(self.mesh),
        )
        self._prog = PROGRAM_CACHE.get_or_build(
            cache_key,
            lambda: _TracedProgram(
                lambda nargs: self._compile(nargs - 1)
            ),
        )
        self._result = None
        self._lock = obs_contention.TimedLock("mesh_sort")

    @property
    def schema(self):
        return self._schema

    @property
    def partition_count(self) -> int:
        return 1

    def describe(self) -> str:
        return (f"MeshSortExec[key={self.key_index}, "
                f"{int(self.mesh.shape[self._axis])} devices]")

    def _trace_key(self, sig) -> tuple:
        return ("mesh.sort", self.key_index,
                tuple(repr(f.dtype) for f in self._schema.fields), sig)

    def _compile(self, ncols: int):
        mesh, axis = self.mesh, self._axis
        ki = self.key_index

        def per_shard(num_rows_s, *cols_s):
            cols = [c[0] for c in cols_s]
            nr = num_rows_s[0]
            cap = cols[0].shape[0]
            dead = (jnp.arange(cap, dtype=jnp.int32) >= nr)
            # stable: primary = liveness (dead rows sink), secondary =
            # the key; ties keep input order within the shard
            order = jnp.lexsort((cols[ki], dead))
            return tuple(
                jnp.take(c, order)[None] for c in cols
            )

        fn = shard_map(
            per_shard, mesh=mesh,
            in_specs=(P(axis),) + tuple(P(axis) for _ in range(ncols)),
            out_specs=tuple([P(axis)] * ncols),
        )
        return jax.jit(fn)

    @staticmethod
    def _merge_runs(runs, key_index):
        """Stable pairwise merge of per-shard sorted runs (earlier
        shards win ties), vectorized with searchsorted."""
        merged = None
        for cols in runs:
            if merged is None:
                merged = [np.asarray(c) for c in cols]
                continue
            a_keys = merged[key_index]
            b_keys = np.asarray(cols[key_index])
            na, nb = len(a_keys), len(b_keys)
            pos_a = np.arange(na) + np.searchsorted(
                b_keys, a_keys, side="left"
            )
            pos_b = np.arange(nb) + np.searchsorted(
                a_keys, b_keys, side="right"
            )
            out = []
            for ac, bc in zip(merged, cols):
                bc = np.asarray(bc)
                m = np.empty(na + nb, dtype=ac.dtype)
                m[pos_a] = ac
                m[pos_b] = bc
                out.append(m)
            merged = out
        return merged

    def _run(self, ctx: ExecContext):
        with self._lock:
            if self._result is not None:
                return self._result
            source = self.children[0]
            n_dev = int(self.mesh.shape[self._axis])
            st = meshprof.stage(
                "mesh.sort", n_dev,
                lower_window=getattr(self, "_mesh_lower", None),
            )
            with st.phase("mesh_stage_in"):
                stacked, num_rows, cap, total, host_cols = (
                    stack_partitions(
                        source, ctx, self.mesh, self._axis
                    )
                )
                st.add_bytes(sum(h.nbytes for h in host_cols))
            with st.phase("mesh_trace"):
                if self._prog.prepare(num_rows, *stacked):
                    meshprof.note_trace(
                        "mesh.sort",
                        self._trace_key(meshprof.arg_signature(
                            num_rows, *stacked
                        )),
                    )
            t0 = time.monotonic()
            with st.phase("mesh_launch"):
                mesh_chaos("mesh.sort", n_dev, ctx)
                dispatch.record("dispatches")
                dispatch.record("mesh_dispatches")
                outs = self._prog(num_rows, *stacked)
            with st.phase("mesh_sync"):
                outs = jax.block_until_ready(outs)
            with st.phase("mesh_gather"):
                outs = dispatch.device_get(outs)
                nr_host = np.asarray(num_rows)
                runs = [
                    [np.asarray(c)[d][: int(nr_host[d])]
                     for c in outs]
                    for d in range(n_dev)
                    if int(nr_host[d]) > 0
                ]
                merged = (
                    self._merge_runs(runs, self.key_index)
                    if runs else None
                )
            t1 = st.finish()
            record_mesh_run(
                ctx, "mesh.sort", n_dev, t0, t1,
                [{"rows_in": int(nr_host[d]),
                  "rows_out": int(nr_host[d])}
                 for d in range(n_dev)],
                stage=st,
            )
            ctx.metrics.add("mesh.sort_rows", total)
            self._result = (merged,)
            return self._result

    def execute(self, partition: int, ctx: ExecContext
                ) -> Iterator[ColumnBatch]:
        if self.fallback is not None and not self._use_fallback:
            try:
                self._run(ctx)
            except Exception as e:  # noqa: BLE001 - ladder below
                degrade_or_raise(self, ctx, e)
        if self._use_fallback:
            if partition < self.fallback.partition_count:
                yield from self.fallback.execute(partition, ctx)
            return
        (merged,) = self._run(ctx)
        if merged is None:
            return
        n = len(merged[0])
        if self.fetch is not None:
            n = min(n, int(self.fetch))
        if n == 0:
            return
        cols: List[Column] = []
        for arr, f in zip(merged, self._schema.fields):
            cols.append(Column(
                f.dtype, arr[:n].astype(f.dtype.physical_dtype()),
                None, None,
            ))
        yield ColumnBatch(self._schema, cols, n)


# ---------------------------------------------------------------------------
# MeshRepartitionExec: hash repartition over ICI all_to_all (ISSUE 20)
# ---------------------------------------------------------------------------


class MeshRepartitionExec(PhysicalOp):
    """The hash ShuffleExchange as one mesh program: every input
    partition lands on a device, rows move to their key-hash owner with
    one `lax.all_to_all` per column (parallel/sharded.
    DistributedRepartition), and the mesh boundary yields one output
    partition per device - key-disjoint, exactly the contract a
    WindowExec's PARTITION BY needs. Schema passes through unchanged.
    """

    def __init__(self, child: PhysicalOp, keys, mesh=None,
                 fallback: Optional[PhysicalOp] = None):
        self.fallback = fallback
        self._use_fallback = False
        self.children = [child]
        self.mesh = mesh or get_mesh()
        self._axis = "data"
        self._schema = child.schema
        self.keys = list(keys)
        from blaze_tpu.fleet.program_cache import (
            PROGRAM_CACHE, mesh_cache_key,
        )
        from blaze_tpu.parallel.sharded import DistributedRepartition

        cache_key = (
            "mesh.repartition",
            tuple((f.name, repr(f.dtype), f.nullable)
                  for f in self._schema.fields),
            tuple(repr(k) for k in self.keys),
            self._axis,
            mesh_cache_key(self.mesh),
        )
        self._rp = PROGRAM_CACHE.get_or_build(
            cache_key,
            lambda: DistributedRepartition(
                self.mesh, self._schema, self.keys, axis=self._axis
            ),
        )
        self._result = None
        self._lock = obs_contention.TimedLock("mesh_repartition")

    @property
    def schema(self):
        return self._schema

    @property
    def partition_count(self) -> int:
        return int(self.mesh.shape[self._axis])

    def describe(self) -> str:
        return (f"MeshRepartitionExec[{len(self.keys)} keys, "
                f"{self.partition_count} devices]")

    def _trace_key(self, sig) -> tuple:
        return ("mesh.repartition",
                tuple(repr(k) for k in self._rp.keys), sig)

    def _run(self, ctx: ExecContext):
        with self._lock:
            if self._result is not None:
                return self._result
            child = self.children[0]
            n_dev = self.partition_count
            st = meshprof.stage(
                "mesh.repartition", n_dev,
                lower_window=getattr(self, "_mesh_lower", None),
            )
            with st.phase("mesh_stage_in"):
                stacked, num_rows, cap, total, host_cols = (
                    stack_partitions(
                        child, ctx, self.mesh, self._axis
                    )
                )
                st.add_bytes(sum(h.nbytes for h in host_cols))
            with st.phase("mesh_trace"):
                if self._rp.prepare(stacked, num_rows):
                    meshprof.note_trace(
                        "mesh.repartition",
                        self._trace_key(meshprof.arg_signature(
                            *stacked, num_rows
                        )),
                    )
            t0 = time.monotonic()
            with st.phase("mesh_launch"):
                mesh_chaos("mesh.repartition", n_dev, ctx)
                dispatch.record("dispatches")
                dispatch.record("mesh_dispatches")
                out_cols, live = self._rp(stacked, num_rows)
            with st.phase("mesh_sync"):
                out_cols, live = jax.block_until_ready(
                    (out_cols, live)
                )
            with st.phase("mesh_gather"):
                out_cols, live = dispatch.device_get((out_cols, live))
            t1 = st.finish()
            live = np.asarray(live)
            nbytes = total * sum(
                np.dtype(f.dtype.physical_dtype()).itemsize
                for f in self._schema.fields
            )
            record_exchange(ctx, "all_to_all", total, nbytes)
            nr_host = np.asarray(num_rows)
            record_mesh_run(
                ctx, "mesh.repartition", n_dev, t0, t1,
                [{"rows_in": int(nr_host[d]),
                  "rows_out": int(live[d].sum())}
                 for d in range(n_dev)],
                stage=st,
            )
            ctx.metrics.add("mesh.repartition_rows", total)
            self._result = (
                [np.asarray(c) for c in out_cols], live
            )
            return self._result

    def execute(self, partition: int, ctx: ExecContext
                ) -> Iterator[ColumnBatch]:
        if self.fallback is not None and not self._use_fallback:
            try:
                self._run(ctx)
            except Exception as e:  # noqa: BLE001 - ladder below
                degrade_or_raise(self, ctx, e)
        if self._use_fallback:
            if partition < self.fallback.partition_count:
                yield from self.fallback.execute(partition, ctx)
            return
        out_cols, live = self._run(ctx)
        idx = np.nonzero(live[partition])[0]
        if len(idx) == 0:
            return
        cols: List[Column] = []
        for arr, f in zip(out_cols, self._schema.fields):
            cols.append(Column(
                f.dtype,
                arr[partition][idx].astype(f.dtype.physical_dtype()),
                None, None,
            ))
        yield ColumnBatch(self._schema, cols, len(idx))
