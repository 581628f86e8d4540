"""Pipeline fusion: collapse operator chains into ONE device program.

SURVEY 7 design stance: "operators are pure functions composed and jit'd
per (plan-fingerprint, batch-shape-bucket)". Unfused, each operator in a
scan->filter->project chain dispatches its own device program per batch:
every dispatch pays a host round trip, and the chain forfeits XLA's
cross-op fusion. The
`fuse_pipelines` pass rewrites maximal stateless chains into a
FusedPipelineExec whose whole chain traces into a single program; the
deferred selection vector (batch.ColumnBatch.selection) carries filter
results through without any host sync. A result sink takes the batch
with that selection and trims on the host after its one read-back
(ops/util.py: sink_arrow); only an operator that goes on computing on
the device with the rows packs them there first (ops/util.py: compact,
one sync for the row count).

Aggregate folding goes further (the reference's one-native-call-per-task
model, exec.rs:196-255): a PARTIAL aggregate fuses into the producing
chain (FusedAggregateExec - one dispatch per input batch), and a COMPLETE
aggregate is rewritten as device-PARTIAL + host-FINAL: the per-batch heavy
reduction happens on device inside the fused program, its tiny
grouped-state output comes back in ONE batched D2H, and finalization
(AVG division, variance, multi-batch merge) runs in numpy on the host -
zero additional device round trips. Per single-batch aggregation query the
device cost is exactly 1 dispatch + 1 fetch.

Stages whose expressions need the host string tier are left unfused (the
per-op path handles their per-batch host lowering).
"""

from __future__ import annotations

import itertools
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from blaze_tpu.types import DataType, Schema, TypeId
from blaze_tpu.batch import Column, ColumnBatch, packed_view
from blaze_tpu.exprs import ir
from blaze_tpu.exprs.ir import AggExpr, AggFn
from blaze_tpu.exprs.eval import DeviceEvaluator
from blaze_tpu.exprs.typing import infer_dtype
from blaze_tpu.ops.base import ExecContext, PhysicalOp
from blaze_tpu.ops.filter import FilterExec
from blaze_tpu.ops.project import ProjectExec, _unflatten_cvs
from blaze_tpu.obs import trace as obs_trace
from blaze_tpu.runtime.dispatch import cached_kernel
from blaze_tpu.runtime.dispatch import count as _count


def _expr_needs_host(e: ir.Expr, schema: Schema) -> bool:
    """True when any non-passthrough node has a direct string input (the
    host_lower hoisting condition)."""
    if isinstance(e, (ir.BoundCol, ir.Col, ir.Literal)):
        return False
    for c in ir.children(e):
        if _expr_needs_host(c, schema):
            return True
        try:
            if infer_dtype(c, schema).is_string_like:
                return True
        except Exception:
            return True
    return False


def _stage_key(st: PhysicalOp) -> Tuple:
    """Structural descriptor of one fused stage (global kernel-cache key
    component; two plans with equal descriptors trace identically)."""
    if isinstance(st, FilterExec):
        return ("F", st.predicate)
    if isinstance(st, ProjectExec):
        return ("P", tuple(e for e, _ in st.exprs))
    return ("R",)


class FusedPipelineExec(PhysicalOp):
    """A chain of stateless stages compiled as one device program.

    An empty stage list is allowed (identity pipeline) - used when an
    aggregate fuses directly over a non-chain child such as a join."""

    def __init__(self, leaf: PhysicalOp, stages: Sequence[PhysicalOp]):
        self.children = [leaf]
        self.stages = list(stages)  # bottom-up; stage i's child is i-1
        self._schema = (
            self.stages[-1].schema if self.stages else leaf.schema
        )

    @property
    def schema(self) -> Schema:
        return self._schema

    def describe(self) -> str:
        inner = " -> ".join(type(s).__name__ for s in self.stages)
        return f"FusedPipelineExec[{inner}]"

    def structure_key(self) -> Tuple:
        return tuple(_stage_key(s) for s in self.stages)

    def execute(self, partition: int, ctx: ExecContext):
        for cb in self.children[0].execute(partition, ctx):
            yield self._run(cb)

    def _run(self, cb: ColumnBatch) -> ColumnBatch:
        pv = packed_view(cb)
        if pv is not None:
            # still-packed scan batch: the H2D wire-buffer split traces
            # INTO this kernel - transfer-unpack + the whole stage chain
            # is one dispatch (and never materializes pruned columns)
            fn = cached_kernel(
                ("fusedpipe_packed", self.structure_key(), pv.key),
                lambda: self._build_kernel_packed(pv),
            )
            out_bufs, sel = fn(pv.buf, cb.selection)
        else:
            layout = cb.layout()
            fn = cached_kernel(
                ("fusedpipe", self.structure_key(), layout),
                lambda: self._build_kernel(layout),
            )
            out_bufs, sel = fn(cb.device_buffers(), cb.selection)
        # dictionaries for passthrough string columns
        dicts = self._out_dictionaries(cb)
        cols: List[Column] = []
        it = iter(out_bufs)
        for field, d in zip(self._schema, dicts):
            v = next(it)
            m = next(it)
            cols.append(Column(field.dtype, v, m, d))
        return ColumnBatch(self._schema, cols, cb.num_rows, sel)

    def _build_kernel(self, layout):
        stages = self.stages
        leaf_schema = self.children[0].schema

        def kernel(bufs, selection):
            cols = _unflatten_cvs(layout, bufs)
            schema = leaf_schema
            cap = layout[0]
            sel = selection
            for st in stages:
                ev = DeviceEvaluator(schema, cols, cap)
                if isinstance(st, FilterExec):
                    keep = ev.evaluate_predicate(st.predicate)
                    sel = keep if sel is None else (sel & keep)
                elif isinstance(st, ProjectExec):
                    cols = [ev.evaluate(e) for e, _ in st.exprs]
                    schema = st.schema
                else:  # Rename
                    schema = st.schema
            out = []
            for v, m in cols:
                out.append(v)
                out.append(
                    m if m is not None
                    else jnp.ones(cap, dtype=jnp.bool_)
                )
            return out, sel

        return kernel

    def _build_kernel_packed(self, pv):
        unflatten = pv.build_unflatten()
        inner = self._build_kernel(pv.layout)

        def kernel(buf, selection):
            return inner(unflatten(buf), selection)

        return kernel

    def _out_dictionaries(self, cb: ColumnBatch):
        """Track dictionaries of string columns through the stage chain
        (only passthrough BoundCol survives fusion for strings)."""
        dicts = cb.dictionaries()
        for st in self.stages:
            if isinstance(st, ProjectExec):
                new = []
                for e, _ in st.exprs:
                    if isinstance(e, ir.BoundCol) and \
                            e.dtype.is_dictionary_encoded:
                        new.append(dicts[e.index])
                    else:
                        new.append(None)
                dicts = new
        return dicts


class FusedAggregateExec(PhysicalOp):
    """A stateless chain + a streaming PARTIAL aggregate in ONE program.

    Each input batch flows scan -> filter/project stages -> sort-based
    partial aggregation without leaving the device or re-dispatching:
    stage evaluation and the aggregate kernel trace into a single jit.
    With fetch_host=True (the COMPLETE/host-finalize rewrite) the
    grouped state of the first non-empty batch returns in ONE batched
    D2H together with the group count; otherwise (standalone PARTIAL
    feeding a device consumer) states stay device-resident and only the
    group-count scalar syncs."""

    def __init__(self, pipeline: FusedPipelineExec, agg,
                 fetch_host: bool = False):
        self.children = [pipeline.children[0]]
        self.pipeline = pipeline
        self.agg = agg
        # fetch_host: the consumer finalizes on the host (COMPLETE
        # rewrite) - fold the state fetch into one batched D2H. A
        # standalone PARTIAL (feeding a device shuffle writer) keeps
        # states device-resident and pays only the scalar sync.
        self.fetch_host = fetch_host
        self._schema = agg.schema

    @property
    def schema(self) -> Schema:
        return self._schema

    def describe(self) -> str:
        return f"FusedAggregateExec[{self.pipeline.describe()} -> partial]"

    def execute(self, partition: int, ctx: ExecContext):
        from blaze_tpu.ops.joins import HashJoinExec, JoinType

        leaf = self.children[0]
        if (
            isinstance(leaf, HashJoinExec)
            and leaf.join_type is JoinType.INNER
        ):
            # INNER join below the fused aggregate: probe per batch and
            # gather the build side INSIDE the fused kernel, so the
            # joined batch never materializes and XLA dead-codes build
            # columns no stage/aggregate references
            yield from self._execute_join_fused(leaf, partition, ctx)
            return
        if self.fetch_host and not self.agg.keys:
            plan = _keyless_merge_plan(
                self.agg.aggs, self.agg.schema.fields
            )
            if plan is not None:
                yield from self._execute_keyless_carry(
                    leaf, partition, ctx, plan
                )
                return
        plan = self._grouped_carry_plan()
        if plan is not None:
            yield from self._execute_grouped_carry(
                (self._batch_spec(cb)
                 for cb in leaf.execute(partition, ctx)),
                plan,
            )
            return
        first = True
        for cb in leaf.execute(partition, ctx):
            out, first = self._run_agg(*self._batch_spec(cb), first)
            if out is not None:
                yield out

    def _batch_spec(self, cb: ColumnBatch):
        """(key_suffix, build_fn, args, capacity) for one input batch:
        the packed wire-buffer kernel variant when the batch still
        carries its H2D buffer, else the plain-layout variant."""
        pv = packed_view(cb)
        if pv is not None:
            return (
                ("fusedagg_packed", pv.key),
                lambda fl, gc, pv=pv: self._build_kernel_packed(
                    pv, force_lexsort=fl, group_cap=gc
                ),
                (pv.buf, cb.selection,
                 None if cb.num_rows == cb.capacity else cb.num_rows),
                cb.capacity,
            )
        layout = cb.layout()
        return (
            ("fusedagg", layout),
            lambda fl, gc, layout=layout: self._build_kernel(
                layout, force_lexsort=fl, group_cap=gc
            ),
            (cb.device_buffers(), cb.selection,
             None if cb.num_rows == cb.capacity else cb.num_rows),
            cb.capacity,
        )

    def _execute_keyless_carry(self, leaf, partition: int,
                               ctx: ExecContext, plan):
        """Keyless COMPLETE rewrite, streamed: ONE dispatch per input
        batch and ZERO extra dispatches at end of stream.

        The per-batch kernel computes the batch's partial state, merges
        it with the device-resident carry from the previous batch
        (SUM/COUNT add, MIN/MAX combine - masked-out states hold the
        reduction's neutral element so the merge needs no validity
        branching), AND packs the merged state into a tiny uint8 buffer.
        Only the LAST batch's packed buffer ever crosses the wire: one
        plain host fetch, no d2h pack dispatch, no per-batch sync -
        exactly the reference's one-native-call-per-task dispatch shape
        (exec.rs:196-255) with the final merge folded into the stream."""
        agg_sig = tuple((a.fn, a.child) for a, _ in self.agg.aggs)
        carry = None
        packed = None
        batches = 0
        for cb in leaf.execute(partition, ctx):
            batches += 1
            pv = packed_view(cb)
            if pv is not None:
                shape_key = ("packed", pv.key)
                build_inner = (
                    lambda pv=pv: self._build_kernel_packed(
                        pv, group_cap=1
                    )
                )
                bufs = pv.buf
            else:
                layout = cb.layout()
                shape_key = ("plain", layout)
                build_inner = (
                    lambda layout=layout: self._build_kernel(
                        layout, group_cap=1
                    )
                )
                bufs = cb.device_buffers()
            with_carry = carry is not None
            fn = cached_kernel(
                ("fusedagg_carry", shape_key,
                 self.pipeline.structure_key(), agg_sig, tuple(plan),
                 with_carry),
                lambda: _build_carry_kernel(
                    build_inner(), plan, with_carry
                ),
            )
            num_rows = (
                None if cb.num_rows == cb.capacity else cb.num_rows
            )
            if with_carry:
                carry, packed = fn(bufs, cb.selection, num_rows, carry)
            else:
                carry, packed = fn(bufs, cb.selection, num_rows)
        if carry is None:
            return  # empty stream: HostFinalAggExec emits the global row
        # batches merged into the device's carry with no read-back
        ctx.metrics.add("agg_carry_batches", batches)
        yield _PackedStateBatch(carry, packed, self._schema)

    # ------------------------------------------------------------------
    # keyed streaming device carry (the grouped twin of the keyless form)
    def _grouped_carry_plan(self):
        """Merge plan for the KEYED streaming device carry, or None when
        the shape must keep the batch-at-a-time path.

        Eligible: host-finalized (COMPLETE rewrite) keyed aggregates
        whose partial states merge by pure add/min/max (FIRST/LAST are
        order-sensitive) running on the SCATTER grouping core - the
        scatter core's exact-equality probing has no hash-collision
        sentinel, so the only per-batch retry condition left is group
        overflow, which the carry driver demotes on instead of
        re-laddering inside the composed kernel."""
        if not (self.fetch_host and self.agg.keys):
            return None
        if not self.agg._scatter_core_hint(
            self.agg.children[0].schema,
            [e for e, _ in self.agg.keys],
        ):
            return None
        n_keys = len(self.agg.keys)
        plan = _keyless_merge_plan(
            self.agg.aggs, self._schema.fields[n_keys:]
        )
        if plan is None:
            return None
        # the merge kernel's MIN/MAX lanes have no bool encoding (the
        # batch kernel widens bool to int8, which would break the
        # carry's dtype fixed point)
        for op, f in zip(plan, self._schema.fields[n_keys:]):
            if op in ("min", "max") and f.dtype.id is TypeId.BOOL:
                return None
        return tuple(plan)

    def _execute_grouped_carry(self, specs, plan):
        """Stream a KEYED aggregate through a persistent device carry:
        ONE dispatch per input batch, the grouped state re-merged
        in-kernel into a fixed set of carry slots instead of being
        re-fetched (or re-merged by a separate device FINAL pass) per
        batch, and ONE plain end-of-stream fetch of the in-kernel-packed
        (count, states) buffer.

        Single-batch partitions (the hot path) skip even the scalar
        sync: the group count rides inside the packed buffer. Multi-
        batch streams pay one scalar sync per batch - the group-overflow
        guard: when the merged group count outgrows the carry slots the
        driver DEMOTES, yielding the accumulated carry as one device-
        resident partial batch and running the rest of the stream
        through the standard per-batch ladder (HostFinalAggExec's device
        FINAL merges, external/grace behavior unchanged)."""
        from blaze_tpu.config import get_config
        from blaze_tpu.runtime.dispatch import host_int

        agg_cap = get_config().agg_group_capacity
        base = (
            "fusedagg_gcarry", self.pipeline.structure_key(),
            tuple((e, n) for e, n in self.agg.keys),
            tuple((a.fn, a.child) for a, _ in self.agg.aggs),
            plan,
        )
        it = iter(specs)
        spec = next(it, None)
        carry = None        # (n_groups device scalar, [(v, m)...])
        carry_n = 0         # host copy of the carry's group count
        slots = None        # carry slot capacity (first batch's out_cap)
        packed = None
        demote = None
        while spec is not None:
            key_suffix, build_fn, args, cap = spec
            s_b = min(cap, agg_cap)
            nxt = next(it, None)
            if carry is None:
                slots = s_b
                fn = cached_kernel(
                    base + (key_suffix, s_b, False),
                    lambda b=build_fn, s=s_b, c=cap:
                        self._build_grouped_carry_kernel(
                            b, plan, s, c, None, None
                        ),
                    scatter_class=True,
                )
                (n_dev, outs), packed = fn(args)
                if nxt is None:
                    # single-batch hot path: one dispatch + one fetch,
                    # group count inside the packed buffer (no sync)
                    n, out = self._fetch_carry(outs, packed, n_dev)
                    if n > s_b:
                        # overflow: rare re-dispatch under the ladder
                        out, _ = self._run_agg(
                            key_suffix, build_fn, args, cap, True
                        )
                    if out is not None:
                        yield out
                    return
            else:
                struct = tuple(
                    (str(np.dtype(v.dtype)), m is not None)
                    for v, m in carry[1]
                )
                fn = cached_kernel(
                    base + (key_suffix, slots, s_b, struct, True),
                    lambda b=build_fn, s=s_b, c=cap, st=struct:
                        self._build_grouped_carry_kernel(
                            b, plan, s, c, slots, st
                        ),
                    scatter_class=True,
                )
                (n_dev, outs), packed = fn(args, carry)
            # batch-level overflow already rides in n (the kernel
            # substitutes slots+1), so one slot check covers both
            n = host_int(n_dev)
            if n < 0 or n > slots:
                demote = spec
                if nxt is not None:
                    # the lookahead batch is already off the iterator -
                    # put it back for the demotion loop
                    it = itertools.chain([nxt], it)
                break
            carry = (n_dev, outs)
            carry_n = n
            spec = nxt
        if demote is None:
            if carry is not None and carry_n > 0:
                _n, out = self._fetch_carry(carry[1], packed, carry[0])
                if out is not None:
                    yield out
            return
        # ---- demotion: carry -> one device partial batch; the
        # offending batch and the rest of the stream take the standard
        # per-batch ladder (device FINAL merges downstream) ----
        first = True
        if carry is not None and carry_n > 0:
            cols = [
                Column(f.dtype, v, m, None)
                for f, (v, m) in zip(self._schema.fields, carry[1])
            ]
            yield ColumnBatch(self._schema, cols, carry_n)
            first = False
        out, first = self._run_agg(*demote, first)
        if out is not None:
            yield out
        for spec in it:
            out, first = self._run_agg(*spec, first)
            if out is not None:
                yield out

    def _fetch_carry(self, outs, packed, n_dev):
        """ONE plain fetch of an in-kernel-packed (group count, states)
        buffer -> (n, ColumnBatch | None). No pack dispatch, no scalar
        sync: the count travels inside the buffer. Returns (n, None)
        for an empty result or a count that overflowed the state slots
        (the caller re-runs the ladder)."""
        from blaze_tpu.runtime.dispatch import record
        from blaze_tpu.runtime.pack import unpack_host

        specs = [(str(np.dtype(n_dev.dtype)), tuple(n_dev.shape))]
        for v, m in outs:
            specs.append((str(np.dtype(v.dtype)), tuple(v.shape)))
            if m is not None:
                specs.append((str(np.dtype(m.dtype)), tuple(m.shape)))
        record("d2h_fetches")
        host = iter(unpack_host(np.asarray(packed), specs))
        n = int(next(host))
        if n <= 0 or n > len(outs[0][0]):
            return n, None
        cols = []
        for (v, m), f in zip(outs, self._schema.fields):
            hv = next(host)
            hm = next(host) if m is not None else None
            cols.append(Column(f.dtype, hv, hm, None))
        return n, ColumnBatch(self._schema, cols, n)

    def _build_grouped_carry_kernel(self, build_inner, plan, s_b, cap_b,
                                    s_carry, carry_struct):
        """Compose one fused-aggregate batch kernel with the keyed
        device carry: batch partial -> (with a carry) concatenate the
        carry rows with the batch's grouped state and regroup them back
        into the carry slots via a state-preserving PARTIAL merge
        aggregate -> pack (count, states) in-kernel. Returns
        ((n, states), packed_u8); n carries the overflow sentinel
        (slots + 1) when either the batch or the merged result outgrew
        its static slot count."""
        from blaze_tpu.runtime.pack import pack_in_kernel

        inner = build_inner(False, s_b if s_b < cap_b else None)
        merge_inner = None
        if s_carry is not None:
            merge_inner = self._build_carry_merge_kernel(
                plan, s_carry, s_b, carry_struct
            )

        def kernel(args, carry=None):
            outs, n_b = inner(*args)
            over_b = n_b > jnp.int32(s_b)
            if carry is None:
                m_outs = outs
                n_out = jnp.where(
                    over_b, jnp.int32(s_b + 1), n_b
                ).astype(jnp.int32)
            else:
                n_c, c_cols = carry
                live = jnp.concatenate([
                    jnp.arange(s_carry, dtype=jnp.int32) < n_c,
                    jnp.arange(s_b, dtype=jnp.int32)
                    < jnp.minimum(n_b, jnp.int32(s_b)),
                ])
                merged = []
                for (cv, cm), (bv, bm) in zip(c_cols, outs):
                    merged.append(jnp.concatenate([cv, bv]))
                    if cm is not None:
                        merged.append(jnp.concatenate([cm, bm]))
                mo, n_m = merge_inner(tuple(merged), live, None)
                # restore the canonical state-mask structure: the merge
                # lanes always emit a validity, the inner states may not
                m_outs = [
                    (v, m if om is not None else None)
                    for (v, m), (_ov, om) in zip(mo, outs)
                ]
                n_out = jnp.where(
                    over_b, jnp.int32(s_carry + 1), n_m
                ).astype(jnp.int32)
            flat = [n_out.reshape(())]
            for v, m in m_outs:
                flat.append(v)
                if m is not None:
                    flat.append(m)
            return (n_out, m_outs), pack_in_kernel(flat)

        return kernel

    def _build_carry_merge_kernel(self, plan, s_carry, s_b, struct):
        """State-preserving grouped merge: a PARTIAL aggregate over the
        (carry + batch) state rows whose lanes are SUM for additive
        state columns and MIN/MAX for extrema - unlike a FINAL kernel it
        emits mergeable partial state again, keeping the carry a fixed
        point. Groups resolve through the same scatter core as the
        batch kernel; output capacity is the carry slot count."""
        from blaze_tpu.ops.hash_aggregate import (
            AggMode,
            HashAggregateExec,
            _SchemaStub,
        )

        pschema = self._schema
        n_keys = len(self.agg.keys)
        fn_map = {
            "add": AggFn.SUM, "min": AggFn.MIN, "max": AggFn.MAX
        }
        merge_agg = HashAggregateExec(
            _SchemaStub(pschema),
            keys=[
                (ir.BoundCol(i, pschema.fields[i].dtype),
                 pschema.fields[i].name)
                for i in range(n_keys)
            ],
            aggs=[
                (AggExpr(
                    fn_map[op],
                    ir.BoundCol(
                        n_keys + j, pschema.fields[n_keys + j].dtype
                    ),
                ), f"m{j}")
                for j, op in enumerate(plan)
            ],
            mode=AggMode.PARTIAL,
        )
        cap = s_carry + s_b
        layout = (cap, tuple(
            (f.dtype.id.value, f.dtype.precision, f.dtype.scale, has_m)
            for f, (_dt, has_m) in zip(pschema.fields, struct)
        ))
        return merge_agg._build_kernel(
            pschema, cap,
            [e for e, _ in merge_agg.keys],
            {j: a.child for j, (a, _) in enumerate(merge_agg.aggs)},
            False, layout, group_cap=s_carry,
        )

    def _execute_join_fused(self, join, partition: int,
                            ctx: ExecContext):
        from blaze_tpu.ops.joins import _eq_layout, _flatten_cols

        # the build INDEX is as probe-invariant as the build relation
        # itself: share one core across partitions/executions (the
        # reference equivalently caches broadcast build relations) so
        # repeated probes don't re-pay the insert + blocking dup sync
        build, core = join.build_side(ctx, shared=True)
        first = True
        fused_probe = getattr(join, "_fused_probe", None)
        folded = None
        if fused_probe is not None:
            # planner-recorded probe chain (_fuse_join_under_agg): try
            # the fully folded form - raw probe leaf batch -> stages ->
            # key extraction -> table walk -> build gather -> aggregate
            # as ONE kernel. Ineligible shapes (dictionary keys, the
            # sorted core) fall through to the materialized loop below,
            # where children[1] - the same pipeline object - still runs
            # the whole probe chain as one dispatch per batch.
            folded = core.table_state_static(
                join.right_keys, fused_probe[1].schema
            )
        if folded is not None:
            mode, tab = folded
            pleaf, ppipe = fused_probe
            b_layout = build.layout()
            build_key_cols = [build.columns[i] for i in join.left_keys]
            b_eq_layout = _eq_layout(build_key_cols)
            b_eq_bufs = _flatten_cols(build_key_cols)
            pkey_idx = tuple(join.right_keys)

            def probe_spec(raw):
                _count("join_probe_batches", 1)
                if mode == "table_direct":
                    _count("join_direct_batches", 1)
                pv = packed_view(raw)
                if pv is not None:
                    # still-packed wire batch: the H2D buffer split
                    # traces into the folded kernel too (scan unpack ->
                    # stages -> probe -> aggregate, one program; packed
                    # columns nothing references never materialize)
                    key = ("fusedagg_join_probe_packed", mode, pv.key,
                           ppipe.structure_key(), b_layout,
                           b_eq_layout, pkey_idx)
                    build_fn = (
                        lambda fl, gc, pv=pv:
                            self._build_join_probe_kernel_packed(
                                pv, mode, b_layout, b_eq_layout,
                                pkey_idx, ppipe, force_lexsort=fl,
                                group_cap=gc,
                            )
                    )
                    p_bufs = pv.buf
                    pcap = pv.layout[0]
                else:
                    p_layout = raw.layout()
                    key = ("fusedagg_join_probe", mode, p_layout,
                           ppipe.structure_key(), b_layout,
                           b_eq_layout, pkey_idx)
                    build_fn = (
                        lambda fl, gc, p_layout=p_layout:
                            self._build_join_probe_kernel(
                                mode, p_layout, b_layout, b_eq_layout,
                                pkey_idx, ppipe, force_lexsort=fl,
                                group_cap=gc,
                            )
                    )
                    p_bufs = raw.device_buffers()
                    pcap = p_layout[0]
                return (
                    key, build_fn,
                    (build.device_buffers(), p_bufs, b_eq_bufs, tab,
                     raw.selection,
                     None if raw.num_rows == pcap else raw.num_rows),
                    pcap,
                )

            specs = (
                probe_spec(raw)
                for raw in pleaf.execute(partition, ctx)
            )
            plan = self._grouped_carry_plan()
            if plan is not None:
                yield from self._execute_grouped_carry(specs, plan)
                return
            for spec in specs:
                out, first = self._run_agg(*spec, first)
                if out is not None:
                    yield out
            return
        for pb in join.children[1].execute(partition, ctx):
            out, first = self._join_batch(core, join, build, pb, first)
            if out is not None:
                yield out

    def _join_batch(self, core, join, build, pb, first):
        """Fused-join step over one MATERIALIZED probe batch: table-core
        state + the lookup-inclusive fused kernel, or the sorted-core
        pair-emission fallback. Returns (ColumnBatch | None, first)."""
        from blaze_tpu.ops.joins import _eq_layout, _flatten_cols

        _count("join_probe_batches", 1)
        tstate, pb = core.table_state(pb, join.right_keys)
        if tstate is None:
            # duplicate build keys / sort core: fall back to the
            # materialized pair emission + the standard fused kernel
            state = core.probe(pb, join.right_keys)
            pb = state[1]
            out_cols, valid, pair_cap, _mp = core.emit_pairs(
                state, list(build.columns), list(pb.columns),
                build_first=True,
            )
            cb = ColumnBatch(join.schema, out_cols, pair_cap, valid)
            return self._run_agg(
                ("fusedagg", cb.layout()),
                lambda fl, gc, layout=cb.layout():
                    self._build_kernel(
                        layout, force_lexsort=fl, group_cap=gc
                    ),
                (cb.device_buffers(), cb.selection,
                 None if cb.num_rows == cb.capacity
                 else cb.num_rows),
                cb.layout()[0],
                first,
            )
        _pb, unified_b, unified_p, tab, mode = tstate
        p_layout = pb.layout()
        b_layout = build.layout()
        b_eq_layout = _eq_layout(unified_b)
        p_eq_layout = _eq_layout(unified_p)
        return self._run_agg(
            ("fusedagg_join", mode, p_layout, b_layout,
             b_eq_layout, p_eq_layout),
            lambda fl, gc: self._build_join_kernel(
                mode, p_layout, b_layout, b_eq_layout,
                p_eq_layout, force_lexsort=fl, group_cap=gc,
            ),
            (build.device_buffers(), pb.device_buffers(),
             _flatten_cols(unified_b),
             _flatten_cols(unified_p),
             tab,
             None if pb.num_rows == p_layout[0]
             else pb.num_rows),
            p_layout[0],
            first,
        )

    def _run_agg(self, key_suffix, build_kernel, args, cap: int,
                 first: bool):
        """Shared per-batch aggregate dispatch: run under the retry
        ladder, fetch per the host-finalize policy, wrap the output.
        Returns (ColumnBatch | None, first)."""
        from blaze_tpu.config import get_config
        from blaze_tpu.ops.hash_aggregate import (
            _group_core_choice,
            _group_count,
            run_grouped_kernel,
        )
        from blaze_tpu.runtime.pack import get_packed

        base_key = (
            key_suffix, self.pipeline.structure_key(),
            tuple((e, n) for e, n in self.agg.keys),
            tuple((a.fn, a.child) for a, _ in self.agg.aggs),
            _group_core_choice(),
        )
        # the fused kernel's dominant cost is the grouping core's
        # scatters (plus, on the join path, the in-kernel table gather)
        # - route scatter-core variants to the scatter-friendly CPU
        # runtime (runtime/dispatch.py)
        scatter = self.agg._scatter_core_hint(
            self.agg.children[0].schema,
            [e for e, _ in self.agg.keys],
        )

        def sync(outs, n_groups):
            # the single-batch-per-partition hot path: states + count
            # in ONE packed transfer (a single device round trip
            # however many state columns). Later batches (multi-batch
            # stream headed for the device FINAL merge) stay
            # device-resident and pay only the scalar sync. `first`
            # stays set until a NON-EMPTY batch was host-fetched, so a
            # filtered-out leading batch doesn't push the sole
            # survivor onto the per-column-fetch path.
            # A count the host knows already (a later cut of the same
            # result, run_grouped_kernel) is not fetched again.
            if self.fetch_host and first:
                known = isinstance(n_groups, int)
                flat = [] if known else [n_groups]
                for v, m in outs:
                    flat.append(v)
                    flat.append(m)
                host = iter(get_packed(flat))
                n = n_groups if known else int(next(host))
                return [(next(host), next(host)) for _ in outs], n
            return outs, _group_count(n_groups)

        def fetch(outs, n_groups):
            if not (self.fetch_host and first) and not self.agg.keys:
                # keyless partial: exactly one group, no collision /
                # overflow retry possible - skip the per-batch
                # blocking scalar sync (each one stalls the host on
                # the device queue)
                return outs, 1
            if obs_trace.ACTIVE:
                # obs seam: the agg_fetch stage - the wait for this
                # batch's program and the read-back of its states or
                # of its group count
                with obs_trace.span("agg_fetch"):
                    return sync(outs, n_groups)
            return sync(outs, n_groups)

        # group-capacity slicing: state arrays leave the kernel cut
        # to a static slot count so a small grouped result never
        # crosses the wire (or feeds downstream kernels) at input
        # capacity (run_grouped_kernel owns the tiers and the
        # hash-collision retry; on the sort core the packed first
        # fetch climbs the cuts of one result, a pack a rung).
        gcap = (1 if not self.agg.keys
                else min(cap, get_config().agg_group_capacity))
        if gcap >= cap:
            gcap = None
        host_outs, n = run_grouped_kernel(
            base_key, build_kernel, args, fetch, gcap,
            scatter_class=scatter,
        )
        if self.fetch_host and first and n > 0:
            first = False
        if n == 0:
            return None, first
        cols = [
            Column(f.dtype, v, m, None)
            for f, (v, m) in zip(self._schema.fields, host_outs)
        ]
        return ColumnBatch(self._schema, cols, n), first

    def _build_join_kernel(self, mode, p_layout, b_layout, b_eq_layout,
                           p_eq_layout, force_lexsort: bool = False,
                           group_cap=None):
        """Fused INNER-join feed, lookup included: hash the probe keys,
        walk the build hash table, gather the build side at the match
        indices, splice probe buffers through untouched, then run the
        standard stage+aggregate composition over the joined column
        view (selection = the matched flags). One kernel covers
        lookup+join+stages+aggregate; build columns nothing downstream
        reads are dead code XLA eliminates - column pruning for free."""
        from blaze_tpu.ops.joins import _table_lookup, _unflatten_eq

        joined_layout = (
            p_layout[0], tuple(b_layout[1]) + tuple(p_layout[1])
        )
        inner = self._build_kernel(
            joined_layout, force_lexsort=force_lexsort,
            group_cap=group_cap,
        )
        pcap = p_layout[0]
        bcap = b_layout[0]
        b_cols_desc = b_layout[1]

        def kernel(b_bufs, p_bufs, b_eq, p_eq, tab, num_rows):
            # num_rows=None: full probe batch; the constant mask folds
            live = (
                jnp.ones(pcap, dtype=jnp.bool_) if num_rows is None
                else jnp.arange(pcap, dtype=jnp.int32) < num_rows
            )
            pkeys = _unflatten_eq(p_eq_layout, p_eq)
            for _, m in pkeys:
                if m is not None:
                    live = live & m  # NULL join keys never match
            match_idx, matched = _table_lookup(
                mode, tab, pkeys, _unflatten_eq(b_eq_layout, b_eq),
                live, bcap,
            )
            g = jnp.clip(match_idx, 0, bcap - 1)
            joined = []
            it = iter(b_bufs)
            for _tid, _prec, _scale, has_mask in b_cols_desc:
                joined.append(jnp.take(next(it), g, axis=0))
                if has_mask:
                    joined.append(jnp.take(next(it), g, axis=0))
            joined.extend(p_bufs)
            return inner(tuple(joined), matched, num_rows)

        return kernel

    def _build_join_probe_kernel(self, mode, p_layout, b_layout,
                                 b_eq_layout, probe_keys, probe_pipe,
                                 force_lexsort: bool = False,
                                 group_cap=None):
        """Deepest fusion tier: the probe side's OWN stage chain folds
        in ahead of the table walk, so scan -> filter -> project ->
        probe -> build gather -> aggregate stages run as ONE program
        over the RAW probe leaf batch - the probe relation never
        materializes at all. Probe join keys come out of the in-kernel
        stage evaluation; filtered-out rows drop via the stage
        selection before the lookup, and NULL keys never match via the
        evaluated masks."""
        from blaze_tpu.ops.joins import _table_lookup, _unflatten_eq

        pipe_kernel = probe_pipe._build_kernel(p_layout)
        mid_schema = probe_pipe.schema
        pcap = p_layout[0]
        bcap = b_layout[0]
        b_cols_desc = b_layout[1]
        joined_layout = (
            pcap,
            tuple(b_cols_desc) + tuple(
                (f.dtype.id.value, f.dtype.precision, f.dtype.scale,
                 True)
                for f in mid_schema
            ),
        )
        inner = self._build_kernel(
            joined_layout, force_lexsort=force_lexsort,
            group_cap=group_cap,
        )
        expect = tuple(
            np.dtype(mid_schema.fields[i].dtype.physical_dtype())
            for i in probe_keys
        )

        def kernel(b_bufs, p_bufs, b_eq, tab, selection, num_rows):
            mid_bufs, sel = pipe_kernel(p_bufs, selection)
            live = (
                jnp.ones(pcap, dtype=jnp.bool_) if num_rows is None
                else jnp.arange(pcap, dtype=jnp.int32) < num_rows
            )
            if sel is not None:
                live = live & sel
            pkeys = [
                (mid_bufs[2 * i], mid_bufs[2 * i + 1])
                for i in probe_keys
            ]
            # table_state_static decided the mode from the fields'
            # physical dtypes; hold the evaluator to that contract
            assert tuple(k.dtype for k, _ in pkeys) == expect, (
                [k.dtype for k, _ in pkeys], expect)
            for _, m in pkeys:
                live = live & m  # NULL join keys never match
            match_idx, matched = _table_lookup(
                mode, tab, pkeys, _unflatten_eq(b_eq_layout, b_eq),
                live, bcap,
            )
            g = jnp.clip(match_idx, 0, bcap - 1)
            joined = []
            it = iter(b_bufs)
            for _tid, _prec, _scale, has_mask in b_cols_desc:
                joined.append(jnp.take(next(it), g, axis=0))
                if has_mask:
                    joined.append(jnp.take(next(it), g, axis=0))
            joined.extend(mid_bufs)
            return inner(tuple(joined), matched, num_rows)

        return kernel

    def _build_join_probe_kernel_packed(self, pv, mode, b_layout,
                                        b_eq_layout, probe_keys,
                                        probe_pipe,
                                        force_lexsort: bool = False,
                                        group_cap=None):
        """Packed-probe-input variant of the folded join: H2D wire
        buffer split + probe stages + table walk + build gather +
        aggregate, ONE traced program."""
        unflatten = pv.build_unflatten()
        inner = self._build_join_probe_kernel(
            mode, pv.layout, b_layout, b_eq_layout, probe_keys,
            probe_pipe, force_lexsort=force_lexsort,
            group_cap=group_cap,
        )

        def kernel(b_bufs, buf, b_eq, tab, selection, num_rows):
            return inner(
                b_bufs, unflatten(buf), b_eq, tab, selection, num_rows
            )

        return kernel

    def _build_kernel_packed(self, pv, force_lexsort: bool = False,
                             group_cap=None):
        """Packed-input variant: H2D wire-buffer split + stage chain +
        partial aggregate in ONE traced program."""
        unflatten = pv.build_unflatten()
        inner = self._build_kernel(
            pv.layout, force_lexsort=force_lexsort, group_cap=group_cap
        )

        def kernel(buf, selection, num_rows):
            return inner(unflatten(buf), selection, num_rows)

        return kernel

    def _build_kernel(self, layout, force_lexsort: bool = False,
                      group_cap=None):
        pipe_kernel = self.pipeline._build_kernel(layout)
        mid_schema = self.pipeline.schema
        cap = layout[0]
        mid_layout = (
            cap,
            tuple(
                (f.dtype.id.value, f.dtype.precision, f.dtype.scale, True)
                for f in mid_schema
            ),
        )
        agg = self.agg
        key_exprs = [e for e, _ in agg.keys]
        child_map = {
            i: a.child
            for i, (a, _) in enumerate(agg.aggs)
            if a.child is not None
        }
        agg_kernel = agg._build_kernel(
            mid_schema, cap, key_exprs, child_map, False, mid_layout,
            force_lexsort=force_lexsort, group_cap=group_cap,
        )

        def kernel(bufs, selection, num_rows):
            mid_bufs, sel = pipe_kernel(bufs, selection)
            return agg_kernel(mid_bufs, sel, num_rows)

        return kernel


class _PackedStateBatch(ColumnBatch):
    """A kernel's state batch that is still on the device, inside the
    u8 buffer the kernel packed it into. Nothing waits for the kernel
    until `.columns` is read: then ONE plain fetch brings the states to
    the host (`_fetch_packed_states`). HostFinalAggExec reads them
    inside its `agg_fetch` stage, so the wait for the stream's last
    program, the read-back and the finalize are one span on one
    thread."""

    def __init__(self, states, packed, schema: Schema):
        self.schema = schema
        self.num_rows = len(states[0][0]) if states else 1
        self.selection = None
        self._states = states
        self._packed = packed
        self._cols: Optional[List[Column]] = None

    @property
    def columns(self) -> List[Column]:  # type: ignore[override]
        if self._cols is None:
            self._cols = _fetch_packed_states(
                self._states, self._packed, self.schema
            )
            self._states = self._packed = None
        return self._cols


def _fetch_packed_states(states, packed, schema: Schema) -> List[Column]:
    """Turn a kernel's (state cols, in-kernel-packed u8) pair into
    host-resident state columns: ONE plain fetch, no pack dispatch (the
    kernel already packed)."""
    from blaze_tpu.runtime.dispatch import record
    from blaze_tpu.runtime.pack import unpack_host

    specs = []
    for v, m in states:
        specs.append((str(np.dtype(v.dtype)), tuple(v.shape)))
        if m is not None:
            specs.append((str(np.dtype(m.dtype)), tuple(m.shape)))
    record("d2h_fetches")
    host = iter(unpack_host(np.asarray(packed), specs))
    cols: List[Column] = []
    for (v, m), field in zip(states, schema.fields):
        hv = next(host)
        hm = next(host) if m is not None else None
        cols.append(Column(field.dtype, hv, hm, None))
    return cols


class FusedWindowAggExec(PhysicalOp):
    """Whole-task fusion of a KEYLESS aggregate over a window: folded
    stage chain + the shared (partition, order) argsort + gather + every
    frame pass + the keyless partial aggregate + state packing, ONE
    program per partition.

    Beyond the dispatch count, the fusion lets XLA dead-code the sorted
    gather of every window column the aggregate never reads - the
    dominant cost of a checksum/rollup consumer over a wide window. The
    sort permutation rides the window's cross-execution cache
    (WindowExec._sort_cache), so repeated queries over the same staged
    table skip the argsort entirely. Emits one single-row partial-state
    batch per partition for HostFinalAggExec."""

    def __init__(self, window, agg):
        self.window = window
        self.children = list(window.children)
        self.agg = agg  # keyless PARTIAL HashAggregateExec
        self._schema = agg.schema

    @property
    def schema(self) -> Schema:
        return self._schema

    def describe(self) -> str:
        return "FusedWindowAggExec[window -> keyless partial]"

    def execute(self, partition: int, ctx: ExecContext
                ) -> Iterator[ColumnBatch]:
        from blaze_tpu.config import get_config, resolve_core_choice
        from blaze_tpu.ops.sort import SortKey
        from blaze_tpu.ops.util import concat_batches

        win = self.window
        src = self.children[0]
        cb = concat_batches(
            list(src.execute(partition, ctx)), schema=src.schema,
        )
        if cb.num_rows == 0:
            return  # HostFinalAggExec emits the keyless global row
        keys = [
            SortKey(e, True, True) for e in win.partition_by
        ] + list(win.order_by)
        core = resolve_core_choice(
            "BLAZE_SORT_CORE", get_config().sort_core
        )
        layout = cb.layout()
        bufs = cb.device_buffers()
        pipe = win._fused_pipeline
        base = ("fusedwinagg",
                pipe.structure_key() if pipe is not None else None,
                tuple(win.partition_by),
                tuple((k.expr, k.ascending, k.nulls_first)
                      for k in win.order_by),
                tuple((f.kind, f.source, f.offset, f.frame)
                      for f in win.functions),
                tuple((a.fn, a.child) for a, _ in self.agg.aggs),
                layout, core)
        # full batch: a constant row count lets every live-mask fold
        num_rows = (
            None if cb.num_rows == cb.capacity else cb.num_rows
        )
        idx = win._cached_sort_idx(bufs, cb.num_rows)
        if idx is None:
            fn = cached_kernel(
                base + ("sort", num_rows is None),
                lambda: self._build_kernel(layout, keys, with_idx=False),
            )
            idx, outs, packed = fn(bufs, num_rows)
            win._store_sort_idx(bufs, cb.num_rows, idx)
        else:
            fn = cached_kernel(
                base + ("reuse", num_rows is None),
                lambda: self._build_kernel(layout, keys, with_idx=True),
            )
            outs, packed = fn(bufs, num_rows, idx)
        yield _PackedStateBatch(outs, packed, self._schema)

    def _build_kernel(self, layout, keys, with_idx: bool):
        from blaze_tpu.runtime.pack import pack_in_kernel

        win = self.window
        body, mid_layout = win._fused_body(
            layout, keys, win._fused_pipeline
        )
        win_schema = win.schema
        cap = layout[0]
        win_layout = (
            cap,
            tuple(
                (f.dtype.id.value, f.dtype.precision, f.dtype.scale,
                 True)
                for f in win_schema
            ),
        )
        agg = self.agg
        child_map = {
            i: a.child
            for i, (a, _) in enumerate(agg.aggs)
            if a.child is not None
        }
        agg_kernel = agg._build_kernel(
            win_schema, cap, [], child_map, False, win_layout,
            group_cap=1,
        )

        def run(bufs, num_rows, idx):
            if num_rows is None:
                num_rows = cap  # python constant: live masks fold
            idx, sorted_bufs, outs = body(bufs, num_rows, idx)
            flat = []
            it = iter(sorted_bufs)
            for _tid, _p, _s, has_m in mid_layout[1]:
                flat.append(next(it))
                flat.append(
                    next(it) if has_m
                    else jnp.ones(cap, dtype=jnp.bool_)
                )
            for v, m in outs:
                flat.append(v)
                flat.append(
                    m if m is not None
                    else jnp.ones(cap, dtype=jnp.bool_)
                )
            states, _n = agg_kernel(flat, None, num_rows)
            pk = []
            for v, m in states:
                pk.append(v)
                if m is not None:
                    pk.append(m)
            return idx, states, pack_in_kernel(pk)

        if with_idx:
            def kernel(bufs, num_rows, idx):
                _, states, packed = run(bufs, num_rows, idx)
                return states, packed

            return kernel

        def kernel(bufs, num_rows):
            return run(bufs, num_rows, None)

        return kernel


def _keyless_merge_plan(aggs, partial_fields):
    """Per-state-column merge ops for the keyless streaming carry, or
    None when an aggregate's partial state cannot be merged by a pure
    elementwise combine (FIRST/LAST: their (value, validity) state
    cannot distinguish "no rows yet" from "first value was NULL").

    Ops: "add" (sums/counts/moments/decimal chunks - an empty state
    holds 0, the additive neutral), "min"/"max" (an empty state holds
    the respective neutral: +-inf or the integer extreme). Validity
    merges as OR on every masked state column."""
    from blaze_tpu.ops.hash_aggregate import (
        _parse_dsum_scale,
        _state_width,
    )

    plan: List[str] = []
    pos = 0
    for a, _ in aggs:
        dscale = _parse_dsum_scale(partial_fields[pos].name)
        w = _state_width(a.fn, dscale is not None)
        fn = a.fn
        if fn in (AggFn.COUNT, AggFn.COUNT_STAR, AggFn.SUM, AggFn.AVG,
                  AggFn.VAR_SAMP, AggFn.VAR_POP, AggFn.STDDEV_SAMP,
                  AggFn.STDDEV_POP):
            plan.extend(["add"] * w)
        elif fn is AggFn.MIN:
            plan.append("min")
        elif fn is AggFn.MAX:
            plan.append("max")
        else:  # FIRST/LAST (order-sensitive) or unknown
            return None
        pos += w
    return plan


def _build_carry_kernel(inner, plan, with_carry: bool):
    """Wrap a keyless fused-aggregate kernel with carry merging and
    in-kernel state packing (see _execute_keyless_carry)."""
    from blaze_tpu.runtime.pack import pack_in_kernel

    def merge(carry, outs):
        merged = []
        for op, (cv, cm), (nv, nm) in zip(plan, carry, outs):
            if op == "min":
                v = jnp.minimum(cv, nv)
            elif op == "max":
                v = jnp.maximum(cv, nv)
            else:
                v = cv + nv
            m = None if cm is None else (cm | nm)
            merged.append((v, m))
        return merged

    def finish(outs):
        flat = []
        for v, m in outs:
            flat.append(v)
            if m is not None:
                flat.append(m)
        return outs, pack_in_kernel(flat)

    if not with_carry:
        def kernel(bufs, selection, num_rows):
            outs, _n = inner(bufs, selection, num_rows)
            return finish(outs)

        return kernel

    def kernel(bufs, selection, num_rows, carry):
        outs, _n = inner(bufs, selection, num_rows)
        return finish(merge(carry, outs))

    return kernel


class _IterChild(PhysicalOp):
    """Single-partition, single-shot child that replays a batch head plus
    a live stream (feeds the device-FINAL fallback of HostFinalAggExec
    without materializing the stream)."""

    def __init__(self, batches: List[ColumnBatch], schema: Schema,
                 rest=None):
        self.children = []
        self.batches = batches
        self.rest = rest
        self._schema = schema

    @property
    def schema(self) -> Schema:
        return self._schema

    @property
    def partition_count(self) -> int:
        return 1

    def execute(self, partition: int, ctx: ExecContext):
        yield from self.batches
        if self.rest is not None:
            yield from self.rest


class HostFinalAggExec(PhysicalOp):
    """Finalize a stream of device-produced PARTIAL aggregate states on
    the HOST - the other half of the COMPLETE-mode rewrite.

    Rationale: after the fused device partial, the state is one row per
    group per batch - orders of magnitude smaller than the input. When a
    partition produced exactly ONE partial batch (the common case with
    large shape buckets), groups are already unique, so finalization is a
    pure vectorized numpy pass: no dispatch, no transfer (the states
    arrived host-resident from FusedAggregateExec's batched fetch). With
    multiple partial batches the proven device FINAL kernel merges them
    (one extra dispatch). Mirrors the reference's partial/final split
    (NativeHashAggregateExec.scala:98-161) with the final leg moved off
    the critical dispatch path."""

    def __init__(self, child: PhysicalOp, template):
        # template: the original COMPLETE HashAggregateExec (carries the
        # final schema, bound keys and agg fns)
        self.children = [child]
        self.template = template
        self._schema = template.schema

    @property
    def schema(self) -> Schema:
        return self._schema

    def describe(self) -> str:
        return "HostFinalAggExec"

    def execute(self, partition: int, ctx: ExecContext
                ) -> Iterator[ColumnBatch]:
        from blaze_tpu.ops.hash_aggregate import (
            AggMode,
            HashAggregateExec,
            _SchemaStub,
            _empty_global_row,
        )

        stream = (
            cb for cb in self.children[0].execute(partition, ctx)
            if cb.num_rows > 0
        )
        first = next(stream, None)
        if first is None:
            if not self.template.keys:
                yield _empty_global_row(self.template)
            return
        second = next(stream, None)
        if second is None:
            if obs_trace.ACTIVE:
                # obs seam: the agg_fetch stage - the wait for the
                # stream's last program, the read-back of its packed
                # state (a _PackedStateBatch fetches here) and the
                # host's finalize
                with obs_trace.span("agg_fetch"):
                    out = self._finalize_host(first)
            else:
                out = self._finalize_host(first)
            yield out
            return
        # multi-batch: hand the STREAM to the device FINAL kernel, whose
        # execute() owns the max_materialize_rows cap and grace-spill
        # ladder - partials are not accumulated here
        partial_schema = self.children[0].schema
        final = HashAggregateExec(
            _SchemaStub(partial_schema),
            keys=[
                (ir.BoundCol(i, partial_schema.fields[i].dtype), name)
                for i, (_, name) in enumerate(self.template.keys)
            ],
            aggs=[(a, n) for a, n in self.template.aggs],
            mode=AggMode.FINAL,
        )
        final.children = [
            _IterChild([first, second], partial_schema, rest=stream)
        ]
        yield from final.execute(0, ctx)

    # ------------------------------------------------------------------
    # number of live state rows flows into the decimal reassembly so the
    # bigint work is O(groups), not O(capacity)
    def _finalize_host(self, cb: ColumnBatch) -> ColumnBatch:
        """Vectorized numpy finalization of one unique-group state batch."""
        from blaze_tpu.ops.hash_aggregate import (
            _parse_dsum_scale,
            _state_width,
        )

        n = cb.num_rows
        n_keys = len(self.template.keys)
        partial_fields = self.children[0].schema.fields
        host = [
            (np.asarray(c.values),
             np.asarray(c.validity) if c.validity is not None else None)
            for c in cb.columns
        ]
        out_cols: List[Column] = []
        for i in range(n_keys):
            field = self._schema.fields[i]
            v, m = host[i]
            out_cols.append(
                Column(field.dtype, v, m, cb.columns[i].dictionary)
            )
        pos = n_keys
        for (a, name), field in zip(
            self.template.aggs, self._schema.fields[n_keys:]
        ):
            dscale = _parse_dsum_scale(partial_fields[pos].name)
            w = _state_width(a.fn, dscale is not None)
            states = host[pos: pos + w]
            pos += w
            out_cols.append(
                Column(
                    field.dtype,
                    *self._finalize_agg(a, field, states, dscale, n),
                )
            )
        return ColumnBatch(self._schema, out_cols, n)

    @staticmethod
    def _finalize_agg(a: AggExpr, field, states, dscale=None,
                      n_live=None):
        from blaze_tpu.ops.hash_aggregate import _reassemble_decimal

        fn = a.fn
        if dscale is not None and fn in (AggFn.SUM, AggFn.AVG):
            chunks = [v for v, _ in states[:4]]
            any_v = states[0][1]
            count = states[4][0] if fn is AggFn.AVG else None
            limbs, mask, dt = _reassemble_decimal(
                chunks, any_v, count, dscale, fn is AggFn.AVG,
                n_live=n_live,
            )
            assert dt == field.dtype, (dt, field.dtype)
            return limbs, mask
        if fn in (AggFn.COUNT, AggFn.COUNT_STAR):
            return states[0][0], None
        if fn in (AggFn.SUM, AggFn.MIN, AggFn.MAX, AggFn.FIRST,
                  AggFn.LAST):
            return states[0]
        if fn is AggFn.AVG:
            (s, sm), (c, _) = states
            safe = np.maximum(c, 1)
            valid = c > 0 if sm is None else (sm & (c > 0))
            return (
                s.astype(np.float64) / safe.astype(np.float64), valid
            )
        # var/stddev family from (n, s1, s2) moments
        (nv, _), (s1, _), (s2, _) = states
        mean = s1 / np.maximum(nv, 1.0)
        m2 = s2 - s1 * mean
        pop = fn in (AggFn.VAR_POP, AggFn.STDDEV_POP)
        denom = np.maximum(nv if pop else nv - 1.0, 1.0)
        var = np.maximum(m2, 0.0) / denom
        valid = nv > (0.0 if pop else 1.0)
        out = var
        if fn in (AggFn.STDDEV_SAMP, AggFn.STDDEV_POP):
            out = np.sqrt(var)
        return out, valid


def fuse_pipelines(op: PhysicalOp) -> PhysicalOp:
    """The plan-level fusion pass - moved to planner/fuse.py (this
    re-export keeps the historical entry point working)."""
    from blaze_tpu.planner.fuse import fuse_pipelines as _pass

    return _pass(op)
