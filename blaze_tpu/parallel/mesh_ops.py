"""Mesh-integrated operators: the ICI fast path as a PhysicalOp.

`MeshGroupByExec` executes an entire two-phase GROUP BY across the device
mesh in one pjit program (parallel/sharded.DistributedGroupBy): each child
partition lands on one device (a child of ONE partition, a task's split,
is dealt over all of them), partial-aggregates locally, exchanges partial
states by key hash over ICI (all_to_all), and final-merges on the owner -
replacing a ShuffleExchange(partial->final) pair with zero host round
trips for slice-resident data. Validity goes through the program. The
file-fabric path remains the fallback for string keys / decimal(>18) /
more partitions than devices / multi-host.
"""

from __future__ import annotations

import time
from typing import Iterator, List, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from blaze_tpu.types import DataType, Field, Schema, TypeId
from blaze_tpu.batch import Column, ColumnBatch
from blaze_tpu.exprs import ir
from blaze_tpu.exprs.ir import AggExpr, AggFn
from blaze_tpu.exprs.typing import infer_dtype
from blaze_tpu.obs import contention as obs_contention
from blaze_tpu.obs import meshprof
from blaze_tpu.obs import trace as obs_trace
from blaze_tpu.ops.base import ExecContext, PhysicalOp
from blaze_tpu.parallel.mesh import get_mesh
from blaze_tpu.parallel.mesh_exec import (
    degrade_or_raise,
    mesh_chaos,
    record_exchange,
    record_mesh_run,
    stack_nullable,
)
from blaze_tpu.parallel.sharded import DistAgg, DistributedGroupBy
from blaze_tpu.runtime import dispatch


# the stage's phases that are stage spans too (obs/trace.py
# STAGE_SPANS): POLL's `stages` folds them and the profiler names the
# devices' idle gaps by them
_STAGE_PHASES = ("mesh_stage_in", "mesh_sync", "mesh_gather")


def _traced(ctx: ExecContext) -> bool:
    return obs_trace.ACTIVE and ctx.tracer is not None


def _stage_span(ctx: ExecContext, name: str):
    """A stage span of this thread while the task is traced."""
    if _traced(ctx):
        return obs_trace.span(name, rec=ctx.tracer)
    return obs_trace.NULL


def _dealt_scan(child: PhysicalOp):
    """(scan, predicate or None) where `child` is one split read by a
    parquet scan, bare or under a filter: the shape whose rows the
    scan's prefetch thread deals over the mesh itself. None for any
    other child."""
    from blaze_tpu.ops.filter import FilterExec
    from blaze_tpu.ops.parquet_scan import ParquetScanExec

    pred = None
    if isinstance(child, FilterExec):
        pred, child = child.predicate, child.children[0]
    if not isinstance(child, ParquetScanExec) or child.partition_count != 1:
        return None
    return child, pred


class MeshGroupByExec(PhysicalOp):
    """GROUP BY over the whole mesh in one dispatch.

    Constraints (fall back to exchange+aggregate otherwise): fixed-width
    key/agg exprs (no strings, no decimal(>18), a decimal SUM within 18
    digits), child partition count <= mesh size. Output: one partition
    per device (group-disjoint).
    """

    def __init__(self, child: PhysicalOp,
                 keys: Sequence[Tuple[ir.Expr, str]],
                 aggs: Sequence[Tuple[AggExpr, str]],
                 filter_pred: ir.Expr = None,
                 mesh=None,
                 fallback: PhysicalOp = None):
        # ineligibility that only shows at execution (an exchange
        # bucket too small for a skewed key, more batches than the
        # footers promised) runs `fallback`, the ORIGINAL aggregate
        # plan, instead - the runtime half of tryConvert semantics
        self.fallback = fallback
        self._use_fallback = False
        self.children = [child]
        self.mesh = mesh or get_mesh()
        in_schema = child.schema
        self.keys = list(keys)
        self.aggs = list(aggs)
        for f in in_schema.fields:
            if (f.dtype.is_string_like or f.dtype.is_dictionary_encoded
                    or f.dtype.is_wide_decimal):
                raise NotImplementedError(
                    "string and decimal(>18) columns use the "
                    "file-shuffle tier"
                )
        key_fields = [
            Field(n, infer_dtype(ir.bind(e, in_schema), in_schema), True)
            for e, n in keys
        ]
        agg_fields = []
        for a, n in aggs:
            if a.fn in (AggFn.COUNT, AggFn.COUNT_STAR):
                agg_fields.append(Field(n, DataType.int64(), False))
                continue
            ct = infer_dtype(ir.bind(a.child, in_schema), in_schema)
            if ct.id is TypeId.DECIMAL and a.fn is AggFn.AVG:
                raise NotImplementedError(
                    "a decimal AVG divides on the host tier"
                )
            if a.fn is AggFn.AVG:
                ct = DataType.float64()
            elif a.fn is AggFn.SUM and ct.id is TypeId.DECIMAL:
                # Spark's rule, decimal(p + 10, s): exact in the i64
                # the program sums in while that is within 18 digits
                if ct.precision + 10 > 18:
                    raise NotImplementedError(
                        "a decimal SUM past 18 digits needs limbs"
                    )
                ct = DataType.decimal(ct.precision + 10, ct.scale)
            elif a.fn is AggFn.SUM:
                # SUM widens (exprs/typing.py): the mesh result
                # carries the single-device aggregate's type
                ct = (DataType.int64() if ct.is_integer
                      else DataType.float64())
            agg_fields.append(Field(n, ct, True))
        self._schema = Schema(key_fields + agg_fields)
        # a task's split under a parquet scan is dealt over the mesh by
        # the scan itself and its filter runs inside the mesh program
        self._deal = _dealt_scan(child)
        if self._deal is not None and self._deal[1] is not None:
            filter_pred = (self._deal[1] if filter_pred is None
                           else ir.BinaryOp(ir.Op.AND, self._deal[1],
                                            filter_pred))
        self.filter_pred = filter_pred
        # program identity is structural (fleet/program_cache): a fresh
        # lowering of the same plan shape on the same mesh reuses the
        # already-traced DistributedGroupBy instead of re-paying the
        # trace (prepare() sees a known signature -> no retrace)
        from blaze_tpu.fleet.program_cache import (
            PROGRAM_CACHE, mesh_cache_key,
        )

        self._mesh_key = mesh_cache_key(self.mesh)
        cache_key = (
            "mesh.groupby",
            tuple((f.name, repr(f.dtype), f.nullable)
                  for f in in_schema.fields),
            tuple(repr(e) for e, _ in keys),
            tuple((a.fn, repr(a.child)) for a, _ in aggs),
            repr(filter_pred),
            self._mesh_key,
        )
        self._gb = PROGRAM_CACHE.get_or_build(
            cache_key,
            lambda: DistributedGroupBy(
                self.mesh, in_schema,
                keys=[e for e, _ in keys],
                aggs=[DistAgg(a.fn, a.child) for a, _ in aggs],
                filter_pred=filter_pred,
                slack=_BUCKET_SLACK,
            ),
        )
        self._result = None
        # single-flight: concurrent partition pulls (the parallel
        # scheduler) must compile/launch the mesh program once; named
        # so wait:hold lands in the contention report when armed
        self._lock = obs_contention.TimedLock("mesh_groupby")

    @property
    def schema(self) -> Schema:
        return self._schema

    @property
    def partition_count(self) -> int:
        return int(self.mesh.shape["data"])

    def _trace_key(self, sig) -> tuple:
        """Logical program identity for re-trace accounting: op kind +
        structural key/agg/filter expressions + argument signature
        (the bound IR dataclasses repr structurally)."""
        return (
            "mesh.groupby",
            tuple(repr(k) for k in self._gb.keys),
            tuple((a.fn, repr(a.expr)) for a in self._gb.aggs),
            repr(self._gb.filter_pred),
            sig,
        )

    # -- staging: a split dealt by its scan ------------------------------
    def _stage_dealt(self, ctx: ExecContext):
        """The scan's prefetch thread cuts every batch into a run of
        rows a device and puts the runs (`DealtBatch`); this thread
        appends each into the devices' column buffers with one program
        a batch, the buffers donated, so nothing comes back to the host
        between the scan and the mesh program. The buffers hold as many
        batches as the split's footers allow (to a power of two), full
        or not: every split of one size shares one mesh program."""
        scan = self._deal[0]
        n_dev = self.partition_count
        cfg = ctx.config
        slots = max(1, scan.batch_bound(0, cfg.batch_size))
        slots = 1 << (slots - 1).bit_length()
        run_cap = -(-cfg.bucket_for(cfg.batch_size) // n_dev)
        dtypes = tuple(str(f.dtype.physical_dtype())
                       for f in scan.schema.fields)
        mesh_key = self._mesh_key
        with _stage_span(ctx, "mesh_stage_in"):
            acc = dispatch.cached_kernel(
                ("mesh_deal_init", slots * run_cap, dtypes, mesh_key),
                lambda: _build_deal_init(n_dev, slots * run_cap, dtypes),
                out_shardings=NamedSharding(self.mesh, P("data")),
            )()
        dealt = np.zeros(n_dev, dtype=np.int64)
        nbytes = taken = 0
        for db in scan.execute(0, ctx, deal=NamedSharding(
                self.mesh, P("data", None))):
            if taken == slots or db.run_cap != run_cap:
                raise NotImplementedError(
                    "the scan's batches outgrew the buffers its "
                    "footers sized"
                )
            # the span holds this thread's part alone: the batch's
            # decode and its transfer are the prefetch thread's
            with _stage_span(ctx, "mesh_stage_in"):
                append = dispatch.cached_kernel(
                    ("mesh_deal_append", db.layout(), dtypes, mesh_key),
                    lambda: _build_deal_append(self.mesh, db),
                    donate_argnums=(0,),
                )
                acc = append(acc, db.buf)
            taken += 1
            dealt += db.runs
            nbytes += db.buf.nbytes
        _, live, cols, valids = acc
        return cols, valids, live, dealt, nbytes

    def _run(self, ctx: ExecContext):
        with self._lock:
            if self._result is not None:
                return self._result
            n_dev = self.partition_count
            st = meshprof.stage(
                "mesh.groupby", n_dev,
                lower_window=getattr(self, "_mesh_lower", None),
            )
            # HBM-resident staging: rows land sharded over the mesh and
            # stay device-side through the whole program - the host
            # sees data again only at the mesh boundary (the
            # grouped-result fetch below)
            with st.phase("mesh_stage_in"):
                if self._deal is not None:
                    cols, valids, rows, dealt, nbytes = (
                        self._stage_dealt(ctx))
                else:
                    with _stage_span(ctx, "mesh_stage_in"):
                        cols, valids, rows, dealt, nbytes = (
                            stack_nullable(
                                self.children[0], ctx, self.mesh))
                st.add_bytes(nbytes)
            total = int(dealt.sum())
            multi = jax.process_count() > 1
            with st.phase("mesh_trace"):
                if self._gb.prepare(cols, rows, valids):
                    meshprof.note_trace(
                        "mesh.groupby",
                        self._trace_key(
                            self._gb.signature(rows, cols, valids)),
                    )
            t0 = time.monotonic()
            with st.phase("mesh_launch"):
                mesh_chaos("mesh.groupby", n_dev, ctx)
                dispatch.record("dispatches")
                dispatch.record("mesh_dispatches")
                out = self._gb.run(cols, rows, valids)
            if multi:
                # every rank needs every device's output slice
                # (execute() may be asked for any partition):
                # allgather the small grouped results - the whole
                # collect lands in mesh_gather (no separate sync)
                from blaze_tpu.parallel.mesh import allgather_rows

                with st.phase("mesh_gather"):
                    out = jax.tree.map(
                        lambda x: allgather_rows(
                            x, n_dev, trailing=x.ndim > 1), out)
            else:
                with st.phase("mesh_sync"), _stage_span(ctx, "mesh_sync"):
                    out = jax.block_until_ready(out)
                with st.phase("mesh_gather"), _stage_span(
                        ctx, "mesh_gather"):
                    out = dispatch.device_get(out)
            t1 = st.finish()
            if np.asarray(out.overflow).any():
                raise NotImplementedError(
                    "a device's groups for one owner outgrew an "
                    "exchange bucket"
                )
            counts = np.asarray(out.counts)
            # the partial-state repartition inside the program is the
            # exchange: every live input row's partial group crosses
            # ICI at most once (conservatively counted as the input
            # rows - the partial states are bounded by them)
            nbytes = total * sum(
                np.dtype(f.dtype.physical_dtype()).itemsize
                for f in self.schema.fields
            )
            record_exchange(ctx, "all_to_all", total, nbytes)
            if _traced(ctx):
                # the stage spans above are in the trace already
                st.phases = [p for p in st.phases
                             if p[0] not in _STAGE_PHASES]
            record_mesh_run(
                ctx, "mesh.groupby", n_dev, t0, t1,
                [{"rows_in": int(dealt[d]),
                  "groups_out": int(counts[d])}
                 for d in range(n_dev)],
                stage=st,
            )
            ctx.metrics.add("mesh_group_runs", 1)
            ctx.metrics.add("mesh_rows_in", total)
            ctx.metrics.add("mesh_groupby_groups", int(counts.sum()))
            self._result = (
                [(np.asarray(v), None if m is None else np.asarray(m))
                 for v, m in list(out.keys) + list(out.aggs)],
                counts,
            )
            return self._result

    def execute(self, partition: int, ctx: ExecContext
                ) -> Iterator[ColumnBatch]:
        if self.fallback is not None and not self._use_fallback:
            try:
                self._run(ctx)
            except Exception as e:  # noqa: BLE001 - failure ladder:
                # TRANSIENT propagates (task retry re-runs the mesh),
                # everything else degrades to the single-device plan
                degrade_or_raise(self, ctx, e)
                # POLL's `mesh_group_runs` 0: no mesh program answered
                ctx.metrics.add("mesh_group_runs", 0)
        if self._use_fallback:
            if partition < self.fallback.partition_count:
                yield from self.fallback.execute(partition, ctx)
            return
        outs, counts = self._run(ctx)
        n = int(counts[partition])
        if n == 0:
            return
        cols: List[Column] = []
        for (v, m), f in zip(outs, self._schema.fields):
            v = v[partition, :n].astype(f.dtype.physical_dtype())
            cols.append(Column(
                f.dtype, v, None if m is None else m[partition, :n]))
        yield ColumnBatch(self._schema, cols, n)


# the exchange's buckets hold the expected share of a shard's groups
# times this (parallel/repartition.py's slack): hashed keys spread to
# within a percent, and a shard holds a key once, so only a hash that
# sends half a shard's distinct keys to one owner can overflow one
_BUCKET_SLACK = 1.5


def _build_deal_init(n_dev: int, cap: int, dtypes):
    """The devices' empty column buffers: (slots taken, live, values a
    column, validity a column), every array [n_dev, cap] but the
    first."""

    def mesh_deal_init():
        return (
            jnp.zeros(n_dev, jnp.int32),
            jnp.zeros((n_dev, cap), jnp.bool_),
            [jnp.zeros((n_dev, cap), dt) for dt in dtypes],
            [jnp.ones((n_dev, cap), jnp.bool_) for _ in dtypes],
        )

    return mesh_deal_init


def _build_deal_append(mesh, db):
    """One dealt batch into the buffers: every device unpacks its run
    and writes it at its next slot; a run's live rows are its first."""
    from blaze_tpu.runtime.pack import build_unpack_at

    unpack = build_unpack_at(db.metas, db.pairs)
    run_cap = db.run_cap
    col_meta = [(has_validity, packed)
                for _, has_validity, _, packed in db.col_meta]

    def per_shard(acc, buf):
        taken, live, cols, valids = acc
        parts = iter(unpack(buf[0]))
        rows = next(parts)[0]
        at = (taken[0] * run_cap,)
        live = lax.dynamic_update_slice(
            live[0], jnp.arange(run_cap, dtype=jnp.int32) < rows, at)
        cols, valids = list(cols), list(valids)
        for i, (has_validity, packed) in enumerate(col_meta):
            if not packed:
                continue  # a column no ancestor reads: zeros
            cols[i] = lax.dynamic_update_slice(
                cols[i][0], next(parts), at)[None]
            ok = (next(parts) if has_validity
                  else jnp.ones(run_cap, jnp.bool_))
            valids[i] = lax.dynamic_update_slice(
                valids[i][0], ok, at)[None]
        return taken + 1, live[None], cols, valids

    def mesh_deal_append(acc, buf):
        return shard_map(per_shard, mesh=mesh, in_specs=P("data"),
                         out_specs=P("data"))(acc, buf)

    return mesh_deal_append
