"""Mesh-integrated operators: the ICI fast path as a PhysicalOp.

`MeshGroupByExec` executes an entire two-phase GROUP BY across the device
mesh in one pjit program (parallel/sharded.DistributedGroupBy): each child
partition lands on one device, partial-aggregates locally, exchanges
partial states by key hash over ICI (all_to_all), and final-merges on the
owner - replacing a ShuffleExchange(partial->final) pair with zero host
round trips for slice-resident data. The file-fabric path remains the
fallback for string keys / more partitions than devices / multi-host.
"""

from __future__ import annotations

import time
from typing import Iterator, List, Sequence, Tuple

import numpy as np

import jax

from blaze_tpu.types import DataType, Field, Schema, TypeId
from blaze_tpu.batch import Column, ColumnBatch
from blaze_tpu.exprs import ir
from blaze_tpu.exprs.ir import AggExpr, AggFn
from blaze_tpu.exprs.typing import infer_dtype
from blaze_tpu.obs import contention as obs_contention
from blaze_tpu.obs import meshprof
from blaze_tpu.ops.base import ExecContext, PhysicalOp
from blaze_tpu.parallel.mesh import get_mesh
from blaze_tpu.parallel.mesh_exec import (
    degrade_or_raise,
    mesh_chaos,
    record_exchange,
    record_mesh_run,
    stack_partitions,
)
from blaze_tpu.parallel.sharded import DistAgg, DistributedGroupBy
from blaze_tpu.runtime import dispatch


class MeshGroupByExec(PhysicalOp):
    """GROUP BY over the whole mesh in one dispatch.

    Constraints (fall back to exchange+aggregate otherwise): fixed-width
    non-null-sensitive key/agg exprs (no strings), child partition count
    <= mesh size. Output: one partition per device (group-disjoint).
    """

    def __init__(self, child: PhysicalOp,
                 keys: Sequence[Tuple[ir.Expr, str]],
                 aggs: Sequence[Tuple[AggExpr, str]],
                 filter_pred: ir.Expr = None,
                 mesh=None,
                 fallback: PhysicalOp = None):
        # data-dependent ineligibility (nullable inputs materializing
        # actual validity masks) only surfaces at execution: `fallback`
        # is the ORIGINAL aggregate plan to run instead - the runtime
        # half of tryConvert semantics
        self.fallback = fallback
        self._use_fallback = False
        self.children = [child]
        self.mesh = mesh or get_mesh()
        in_schema = child.schema
        self.keys = list(keys)
        self.aggs = list(aggs)
        self.filter_pred = filter_pred
        for e, _ in keys:
            if infer_dtype(ir.bind(e, in_schema),
                           in_schema).is_string_like:
                raise NotImplementedError(
                    "string keys use the file-shuffle tier"
                )
        key_fields = [
            Field(n, infer_dtype(ir.bind(e, in_schema), in_schema), True)
            for e, n in keys
        ]
        agg_fields = []
        for a, n in aggs:
            if a.fn in (AggFn.COUNT, AggFn.COUNT_STAR):
                agg_fields.append(Field(n, DataType.int64(), False))
            elif a.fn is AggFn.AVG:
                agg_fields.append(Field(n, DataType.float64(), True))
            else:
                ct = infer_dtype(ir.bind(a.child, in_schema), in_schema)
                if a.fn is AggFn.SUM and (ct.is_integer
                                          or ct.is_floating):
                    # SUM widens (exprs/typing.py): the mesh result
                    # carries the single-device aggregate's type
                    ct = (DataType.int64() if ct.is_integer
                          else DataType.float64())
                agg_fields.append(Field(n, ct, True))
        self._schema = Schema(key_fields + agg_fields)
        # program identity is structural (fleet/program_cache): a fresh
        # lowering of the same plan shape on the same mesh reuses the
        # already-traced DistributedGroupBy instead of re-paying the
        # trace (prepare() sees a known signature -> no retrace)
        from blaze_tpu.fleet.program_cache import (
            PROGRAM_CACHE, mesh_cache_key,
        )

        cache_key = (
            "mesh.groupby",
            tuple((f.name, repr(f.dtype), f.nullable)
                  for f in in_schema.fields),
            tuple(repr(e) for e, _ in keys),
            tuple((a.fn, repr(a.child)) for a, _ in aggs),
            repr(filter_pred),
            mesh_cache_key(self.mesh),
        )
        self._gb = PROGRAM_CACHE.get_or_build(
            cache_key,
            lambda: DistributedGroupBy(
                self.mesh, in_schema,
                keys=[e for e, _ in keys],
                aggs=[DistAgg(a.fn, a.child) for a, _ in aggs],
                filter_pred=filter_pred,
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

    def _run(self, ctx: ExecContext):
        with self._lock:
            if self._result is not None:
                return self._result
            child = self.children[0]
            n_dev = self.partition_count
            st = meshprof.stage(
                "mesh.groupby", n_dev,
                lower_window=getattr(self, "_mesh_lower", None),
            )
            # HBM-resident staging: partitions land sharded over the
            # mesh and stay device-side through the whole program -
            # host spill happens only at the mesh boundary (the
            # grouped-result fetch below)
            with st.phase("mesh_stage_in"):
                stacked, num_rows, cap, total, host_cols = (
                    stack_partitions(child, ctx, self.mesh)
                )
                st.add_bytes(sum(h.nbytes for h in host_cols))
            multi = jax.process_count() > 1
            with st.phase("mesh_trace"):
                if self._gb.prepare(stacked, num_rows):
                    meshprof.note_trace(
                        "mesh.groupby",
                        self._trace_key(meshprof.arg_signature(
                            *stacked, num_rows
                        )),
                    )
            t0 = time.monotonic()
            with st.phase("mesh_launch"):
                mesh_chaos("mesh.groupby", n_dev, ctx)
                dispatch.record("dispatches")
                dispatch.record("mesh_dispatches")
                key_out, agg_out, counts = self._gb(stacked, num_rows)
            if multi:
                # every rank needs every device's output slice
                # (execute() may be asked for any partition):
                # allgather the small grouped results - the whole
                # collect lands in mesh_gather (no separate sync)
                from blaze_tpu.parallel.mesh import allgather_rows

                with st.phase("mesh_gather"):
                    key_out = [
                        allgather_rows(k, n_dev) for k in key_out
                    ]
                    agg_out = [
                        allgather_rows(a, n_dev) for a in agg_out
                    ]
                    counts = allgather_rows(
                        counts, n_dev, trailing=False
                    )
            else:
                with st.phase("mesh_sync"):
                    key_out, agg_out, counts = jax.block_until_ready(
                        (key_out, agg_out, counts)
                    )
                with st.phase("mesh_gather"):
                    key_out, agg_out, counts = dispatch.device_get(
                        (key_out, agg_out, counts)
                    )
            t1 = st.finish()
            counts = np.asarray(counts)
            # the partial-state repartition inside the program is the
            # exchange: every live input row's partial group crosses
            # ICI at most once (conservatively counted as the input
            # rows - the partial states are bounded by them)
            nbytes = total * sum(
                np.dtype(f.dtype.physical_dtype()).itemsize
                for f in self.schema.fields
            )
            record_exchange(ctx, "all_to_all", total, nbytes)
            nr_host = np.asarray(num_rows)
            record_mesh_run(
                ctx, "mesh.groupby", n_dev, t0, t1,
                [{"rows_in": int(nr_host[d]),
                  "groups_out": int(counts[d])}
                 for d in range(n_dev)],
                stage=st,
            )
            self._result = (
                [np.asarray(k) for k in key_out],
                [np.asarray(a) for a in agg_out],
                counts,
            )
            ctx.metrics.add(
                "mesh_groupby_groups", int(self._result[2].sum())
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
        if self._use_fallback:
            if partition < self.fallback.partition_count:
                yield from self.fallback.execute(partition, ctx)
            return
        key_out, agg_out, counts = self._run(ctx)
        n = int(counts[partition])
        if n == 0:
            return
        cols: List[Column] = []
        for arr, f in zip(
            list(key_out) + list(agg_out), self._schema.fields
        ):
            v = arr[partition].astype(f.dtype.physical_dtype())
            cols.append(Column(f.dtype, v, None, None))
        yield ColumnBatch(self._schema, cols, n)
