"""Parquet scan: host decode -> device columns.

Reference counterpart: DataFusion ParquetExec with pruning predicate,
driven by per-partition FileGroups (from_proto.rs:202-212; Spark side
NativeParquetScanExec.scala:61-107 builds the groups/projection/filters).

TPU-first shape (SURVEY 7 step 4): Parquet decode is host-tier work
(pyarrow's C++ reader), producing record batches of `batch_size` rows that
are dictionary-encoded/padded/transferred once each. Row-group pruning
evaluates the pruning predicate against row-group statistics before any IO,
like the reference's pruning predicate; byte ranges in a FileRange select
row groups the way Spark's splits do."""

from __future__ import annotations

import dataclasses
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

from blaze_tpu.types import Schema, from_arrow_schema
from blaze_tpu.batch import ColumnBatch
from blaze_tpu.exprs import ir
from blaze_tpu.ops.base import ExecContext, PhysicalOp
from blaze_tpu.testing import chaos


@dataclasses.dataclass(frozen=True)
class FileRange:
    path: str
    start: int = 0
    length: int = 0  # 0 = whole file


class ParquetScanExec(PhysicalOp):
    def __init__(
        self,
        file_groups: Sequence[Sequence[FileRange]],
        schema: Optional[Schema] = None,
        projection: Optional[Sequence[str]] = None,
        pruning_predicate: Optional[ir.Expr] = None,
    ):
        import pyarrow.parquet as pq

        self.children = []
        self.file_groups = [list(g) for g in file_groups]
        self.projection = list(projection) if projection else None
        self.pruning_predicate = pruning_predicate
        if schema is None:
            from blaze_tpu.io.object_store import store_for

            first = self.file_groups[0][0].path
            aschema = pq.read_schema(store_for(first).open_input(first))
            if self.projection:
                aschema = __import__("pyarrow").schema(
                    [aschema.field(n) for n in self.projection]
                )
            schema = from_arrow_schema(aschema)
        elif self.projection and list(schema.names()) != self.projection:
            # index-bound pruning-predicate columns were bound against
            # the FULL file schema; rewrite them to name references
            # before the schema narrows so stats pruning keeps reading
            # the right row-group columns
            if pruning_predicate is not None:
                full = schema
                pruning_predicate = ir.transform(
                    pruning_predicate,
                    lambda e: ir.Col(full.fields[e.index].name)
                    if isinstance(e, ir.BoundCol)
                    else e,
                )
                self.pruning_predicate = pruning_predicate
            # a producer following the reference's NativeParquetScanExec
            # contract sends the FULL file schema plus a projection of
            # field indices (NativeParquetScanExec.scala:105-107); the
            # operator's schema is the PROJECTED one - normalizing here
            # keeps every downstream consumer (output schema, pruned-
            # batch assembly) positionally consistent
            schema = Schema(
                [
                    schema.fields[schema.index_of(n)]
                    for n in self.projection
                ]
            )
        self._schema = schema

    @property
    def schema(self) -> Schema:
        return self._schema

    @property
    def partition_count(self) -> int:
        return len(self.file_groups)

    _FINGERPRINT_STABLE = True

    def _fingerprint_params(self) -> str:
        # content identity = file ranges + projection + pruning
        # predicate. File CONTENT changes under the same path are not
        # captured - the serving tier's result cache covers that with
        # TTL + explicit invalidation (docs/SERVICE.md)
        groups = "|".join(
            ",".join(f"{fr.path}:{fr.start}:{fr.length}" for fr in g)
            for g in self.file_groups
        )
        proj = ",".join(self.projection) if self.projection else "*"
        return f"{groups};proj={proj};prune={self.pruning_predicate!r}"

    def batch_bound(self, partition: int, batch_size: int) -> int:
        """The most batches `execute` can yield for this partition, from
        the files' footers alone: what a consumer that sizes a buffer
        before the scan runs (the mesh group-by) goes by. The filters
        pushed down only ever lower the count."""
        import pyarrow.parquet as pq

        from blaze_tpu.io.object_store import store_for

        total = 0
        for fr in self.file_groups[partition]:
            pf = pq.ParquetFile(store_for(fr.path).open_input(fr.path))
            for g in self._select_row_groups(pf, fr):
                total += -(-pf.metadata.row_group(g).num_rows
                           // batch_size)
        return total

    def execute(self, partition: int, ctx: ExecContext, deal=None
                ) -> Iterator[ColumnBatch]:
        """`deal`, a sharding over a mesh's devices, has the prefetch
        thread cut every batch into a run of rows a device and yield
        `DealtBatch`es (batch.py) for the mesh group-by's staging."""
        import pyarrow.parquet as pq

        from blaze_tpu.io.object_store import store_for
        from blaze_tpu.runtime.prefetch import prefetch

        cfg = ctx.config
        cols = self.projection or [f.name for f in self._schema]

        # planner/colprune hints: columns no ancestor reads are neither
        # decoded nor transferred (device zero placeholders keep schema
        # positions valid); filter conjuncts pushed from the FilterExec
        # directly above run on the host during decode, like DataFusion's
        # CPU-side row-filter pushdown in ParquetExec (from_proto.rs:
        # 202-212 builds the same pruning predicate)
        required = getattr(self, "_hint_required", None)
        filters = list(getattr(self, "_hint_filters", ()) or ())
        if required is not None:
            req_names = {cols[i] for i in required if i < len(cols)}
            filt_names = {name for name, _, _ in filters}
            read_names = [
                c for c in cols if c in req_names or c in filt_names
            ]
            if not read_names:
                # COUNT(*)-style scans still need row counts: read the
                # cheapest column (strings cost parquet decode +
                # dictionary encoding regardless of code width)
                def decode_cost(c):
                    dt = self._schema.fields[
                        self._schema.index_of(c)
                    ].dtype
                    penalty = 100 if dt.is_dictionary_encoded else 0
                    return penalty + dt.physical_dtype().itemsize

                read_names = [min(cols, key=decode_cost)]
            keep_names = [c for c in cols if c in req_names] or read_names[:1]
            present = [cols.index(c) for c in keep_names]
            if keep_names == cols and read_names == cols:
                present = None
        else:
            read_names = cols
            keep_names = cols
            present = None

        def decode() -> Iterator[ColumnBatch]:
            from blaze_tpu.obs import trace as obs_trace

            for fr in self.file_groups[partition]:
                # obs seam: one span per file-range decode (open,
                # row-group selection, and the batch iteration - the
                # inclusive decode wall time for this range)
                # rec= explicitly: decode() is drained by a prefetch
                # worker thread, which has no thread-current recorder
                span_cm = (
                    obs_trace.span(
                        "parquet_decode", rec=ctx.tracer,
                        partition=partition, path=fr.path,
                    )
                    if obs_trace.ACTIVE else obs_trace.NULL
                )
                with span_cm:
                    if chaos.ACTIVE:
                        # chaos seam: parquet decode / object-store
                        # read failure for this file range (inside
                        # the span, so the injected fault lands as a
                        # chaos.fault event on THIS span)
                        chaos.fire(
                            "parquet.decode", partition=partition,
                            path=fr.path,
                        )
                    # all byte IO flows through the object-store seam
                    # (the reference's registered ObjectStore,
                    # exec.rs:96-103)
                    pf = pq.ParquetFile(
                        store_for(fr.path).open_input(fr.path)
                    )
                    groups = self._select_row_groups(pf, fr, filters)
                    if not groups:
                        continue
                    batches = pf.iter_batches(
                        batch_size=cfg.batch_size, row_groups=groups,
                        columns=read_names, use_threads=True,
                    )
                    while True:
                        # obs seam: one batch's decode to host arrays
                        # and its packing, a stage (no yield inside;
                        # the range span above also holds the waits on
                        # a full prefetch queue)
                        with (obs_trace.span("decode_batch")
                              if obs_trace.ACTIVE else obs_trace.NULL):
                            rb = next(batches, None)
                            if rb is None:
                                break
                            cb = self._decode_batch(
                                rb, ctx, filters, keep_names, present,
                                deal)
                        if cb is not None:
                            yield cb

        # overlap parquet decode + H2D with downstream device compute
        # (SURVEY 7 streaming model: double-buffered host pipeline)
        yield from prefetch(decode(), depth=2)

    def _decode_batch(self, rb, ctx: ExecContext, filters, keep_names,
                      present, deal=None) -> Optional[ColumnBatch]:
        """One decoded RecordBatch to a packed device batch (None when
        the pushed-down filters leave no row)."""
        ctx.metrics.add("input_rows", rb.num_rows)
        ctx.metrics.add("input_batches", 1)
        if filters and ctx.config.host_filter_pushdown:
            before = rb.num_rows
            rb = _apply_host_filters(rb, filters)
            ctx.metrics.add(
                "pushdown_filtered_rows", before - rb.num_rows,
            )
        if rb.num_rows == 0:
            return None
        if present is not None:
            import pyarrow as pa

            rb = pa.record_batch(
                [rb.column(c) for c in keep_names], names=keep_names,
            )
        if deal is not None:
            from blaze_tpu.batch import DealtBatch

            return DealtBatch.from_arrow(
                rb, self._schema, present,
                ctx.config.bucket_for(ctx.config.batch_size), deal)
        if present is None:
            return ColumnBatch.from_arrow(rb)
        return ColumnBatch.from_arrow_pruned(rb, self._schema, present)

    # ------------------------------------------------------------------
    def _select_row_groups(self, pf, fr: FileRange,
                           filters=()) -> List[int]:
        """Row groups whose byte midpoint falls in the split range (Spark's
        split ownership rule) and that survive stats pruning (the explicit
        pruning predicate plus any pushed-down filter conjuncts)."""
        md = pf.metadata
        out = []
        for i in range(md.num_row_groups):
            rg = md.row_group(i)
            if fr.length > 0:
                start = rg.column(0).file_offset
                mid = start + rg.total_byte_size // 2
                if not (fr.start <= mid < fr.start + fr.length):
                    continue
            if self.pruning_predicate is not None and not _may_match(
                self.pruning_predicate, rg, self._schema
            ):
                continue
            if any(
                not _stats_may_match(name, op, value, rg)
                for name, op, value in filters
            ):
                continue
            out.append(i)
        return out


def _apply_host_filters(rb, filters):
    """Evaluate pushed-down `(name, cmp, literal)` conjuncts with pyarrow
    compute (vectorized C++) and compact the batch before any padding or
    device transfer. NULL comparison results drop the row - exactly what
    the device selection mask would do - and the device FilterExec still
    re-applies the full predicate, so a conjunct that fails to evaluate
    here is simply skipped."""
    import pyarrow.compute as pc

    fns = {
        ir.Op.LT: pc.less, ir.Op.LTE: pc.less_equal,
        ir.Op.GT: pc.greater, ir.Op.GTE: pc.greater_equal,
        ir.Op.EQ: pc.equal, ir.Op.NEQ: pc.not_equal,
    }
    mask = None
    for name, op, value in filters:
        try:
            m = fns[op](rb.column(name), value)
        except Exception:
            continue  # device filter re-checks; skipping is only slower
        mask = m if mask is None else pc.and_(mask, m)
    if mask is None:
        return rb
    return rb.filter(mask)


def _rg_stats(name: str, rg):
    for ci in range(rg.num_columns):
        c = rg.column(ci)
        if c.path_in_schema == name:
            return c.statistics
    return None


def _minmax_may_match(stats, op: ir.Op, value) -> bool:
    """min/max-vs-comparison core shared by the pruning-predicate and
    pushed-conjunct row-group checks: False only when the whole group
    provably fails the comparison."""
    if stats is None or not stats.has_min_max:
        return True
    lo, hi = stats.min, stats.max
    try:
        if op is ir.Op.EQ:
            return lo <= value <= hi
        if op is ir.Op.LT:
            return lo < value
        if op is ir.Op.LTE:
            return lo <= value
        if op is ir.Op.GT:
            return hi > value
        if op is ir.Op.GTE:
            return hi >= value
    except TypeError:
        return True
    return True


def _stats_may_match(name: str, op: ir.Op, value, rg) -> bool:
    return _minmax_may_match(_rg_stats(name, rg), op, value)


def _may_match(pred: ir.Expr, rg, schema: Schema) -> bool:
    """Conservative stats-based pruning: False only when the predicate
    provably rejects the whole row group. Handles comparisons between a
    column and a literal plus AND/OR composition (the reference gets the
    equivalent from DataFusion's PruningPredicate)."""
    from blaze_tpu.exprs.ir import BinaryOp, Col, BoundCol, Literal, Op

    if isinstance(pred, BinaryOp) and pred.op in (Op.AND, Op.OR):
        l = _may_match(pred.left, rg, schema)
        r = _may_match(pred.right, rg, schema)
        return (l and r) if pred.op is Op.AND else (l or r)
    if not isinstance(pred, BinaryOp):
        return True
    col, lit, op = None, None, pred.op
    flip = {Op.LT: Op.GT, Op.GT: Op.LT, Op.LTE: Op.GTE, Op.GTE: Op.LTE}
    if isinstance(pred.left, (Col, BoundCol)) and isinstance(
        pred.right, Literal
    ):
        col, lit = pred.left, pred.right
    elif isinstance(pred.right, (Col, BoundCol)) and isinstance(
        pred.left, Literal
    ):
        col, lit = pred.right, pred.left
        op = flip.get(op, op)
    if col is None or lit.value is None:
        return True
    name = col.name if isinstance(col, Col) else schema.fields[col.index].name
    return _minmax_may_match(_rg_stats(name, rg), op, lit.value)
