"""Hash aggregate with PARTIAL / FINAL / COMPLETE modes.

Reference counterpart: DataFusion AggregateExec built from proto
(from_proto.rs:452-545) with Spark's two-phase mode mapping
(NativeHashAggregateExec.scala:98-161). Supported functions mirror the
reference's converter surface: MIN/MAX/SUM/AVG/COUNT/VAR/STDDEV
(NativeConverters.scala:491-501) plus FIRST/LAST.

TPU-first design (SURVEY 7): instead of a row-at-a-time hash table, grouping
is a sort-based segmented reduction - one stable multi-key sort pass, group
boundaries by comparing adjacent sorted keys (SQL semantics: NULL groups
with NULL), then `jax.ops.segment_*` reductions with a static segment count
(the batch capacity), so every step is one fused XLA program with static
shapes. Variance/stddev state is (count, sum, sum-of-squares) so every
merge is a plain sum of states. Where the rows lie sorted by group (the
sort core, keyed) an integer sum takes no scatter: it is a running sum
read where the groups start (`_SegOps.sum`, ops/running.py), the same
bits as `segment_sum`'s; float sums, min/max, the scatter core and the
keyless slot reduce as before.

PARTIAL mode streams: each input batch aggregates independently (bounded
state, like the reference's partial aggregation). FINAL/COMPLETE are
pipeline breakers that materialize the partition.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from blaze_tpu.config import get_config
from blaze_tpu.types import DataType, Field, Schema, TypeId
from blaze_tpu.batch import Column, ColumnBatch, row_mask
from blaze_tpu.exprs import ir
from blaze_tpu.exprs.optimize import bind_opt
from blaze_tpu.exprs.ir import AggExpr, AggFn
from blaze_tpu.exprs.eval import DeviceEvaluator
from blaze_tpu.exprs.typing import infer_dtype
from blaze_tpu.ops.base import ExecContext, PhysicalOp
from blaze_tpu.ops.host_lower import lower_strings_host
from blaze_tpu.ops.project import _unflatten_cvs
from blaze_tpu.ops.running import running_scan
from blaze_tpu.ops.util import concat_batches, sort_indices
from blaze_tpu.runtime.dispatch import cached_kernel, host_int
from blaze_tpu.runtime.dispatch import count as _count


class AggMode(enum.Enum):
    PARTIAL = "partial"
    FINAL = "final"
    COMPLETE = "complete"


def _group_core_choice() -> str:
    """Grouping-core knob (config.group_core / env BLAZE_GROUP_CORE)."""
    from blaze_tpu.config import resolve_core_choice

    return resolve_core_choice(
        "BLAZE_GROUP_CORE", get_config().group_core
    )


class _SchemaStub:
    """Placeholder child carrying only a schema (internal op wiring)."""

    def __init__(self, schema: Schema):
        self.children = []
        self._schema = schema

    @property
    def schema(self) -> Schema:
        return self._schema

    @property
    def partition_count(self) -> int:
        return 1


@dataclasses.dataclass(frozen=True)
class NamedAgg:
    agg: AggExpr
    name: str


def _decimal_chunks(cv):
    """Split decimal unscaled values into four 32-bit chunk columns so
    segment sums never overflow i64: value = sum(c_k * 2^(32k)), c3
    carries the sign. Narrow input is a 1-D i64 array; wide input is the
    (capacity, 2) [lo-bit-pattern, hi] limb pair (types.is_wide_decimal,
    the reference's 16-byte decimal slot, shuffle_writer_exec.rs:
    196-220)."""
    mask = jnp.int64(0xFFFFFFFF)
    if cv.ndim == 1:
        c0 = cv & mask
        c1 = cv >> 32  # arithmetic: carries the sign
        z = jnp.zeros_like(cv)
        return [c0, c1, z, z]
    lo = cv[:, 0]
    hi = cv[:, 1]
    lo_u = lo.astype(jnp.uint64)
    c0 = (lo_u & jnp.uint64(0xFFFFFFFF)).astype(jnp.int64)
    c1 = (lo_u >> jnp.uint64(32)).astype(jnp.int64)
    c2 = hi & mask
    c3 = hi >> 32  # arithmetic: the 128-bit sign
    return [c0, c1, c2, c3]


def _group_count(n_groups) -> int:
    """The group count on the host: one blocking scalar read-back, or
    nothing where a later cut of the same result is fetched and the
    count is the host's already (run_grouped_kernel hands it on as an
    int)."""
    return n_groups if isinstance(n_groups, int) else host_int(n_groups)


def _group_tiers(gcap) -> list:
    """Static state sizes a grouped result may leave at, smallest
    first; None is the input's capacity. BLAZE_AGG_TIER1 <= 0 disables
    the small first tier (one fewer compiled kernel variant per
    aggregate shape on the scatter core): the test suite sets it
    because jaxlib's CPU client segfaults under cumulative compile
    volume (docs/JAXLIB_SEGFAULT.md) and the ladder's extra variants
    pushed the largest exchange-tier query over the cliff."""
    import os

    tier1 = int(os.environ.get("BLAZE_AGG_TIER1", "4096"))
    if gcap is None:
        return [None]
    if tier1 <= 0 or tier1 >= gcap:
        return [gcap, None]
    return [tier1, gcap, None]


def run_grouped_kernel(base_key, build, args, fetch_n, gcap,
                       scatter_class: bool = False):
    """Dispatch a grouped-aggregate kernel for HashAggregateExec and
    FusedAggregateExec, and hand its states on at the smallest tier
    (_group_tiers) that holds the group count: most aggregates resolve
    to a few thousand groups, so a small result never crosses the wire
    or feeds a downstream kernel at input capacity. Correctness never
    depends on the slot guess. What a tier is depends on the core:

    - sort core (_cut_tiers): an OUTPUT size. The program runs once at
      the input's capacity and returns each tier as a cut of its
      states; the host picks the cut once the count is known.
    - scatter core (_climb_tiers): the size of the hash TABLE, part of
      the program's work. The first attempt probes a small table, and
      more groups than a tier holds re-run the program a tier up. The
      keyless single slot (gcap == 1, a reduce and not a scatter)
      takes this path too and never climbs.
    - n_groups == -1, either way: narrow-key hash collision between
      DIFFERENT keys -> re-run the exact full-width lexsort kernel, a
      sort-core program. Rare for a few thousand groups, certain for
      several hundred thousand (n * n / 2**33 pairs expected in a
      32-bit hash, and the Spark hash skips a NULL, so (NULL, k) and
      (k, NULL) always collide).

    `build(force_lexsort, group_cap)` returns the python kernel to jit;
    `fetch_n(outs, n_groups) -> (outs', n)` owns the host sync policy
    (`n_groups` is the device scalar, or the host's int where a second
    cut of a result whose count is known is fetched: _group_count).

    `scatter_class` says the variant about to build runs the scatter
    core (_scatter_core_hint); it picks the path here and rides
    through to cached_kernel. A wrong guess costs runtime choice and
    launches, never correctness: either path is right for either core.
    A keyed aggregate leaves two counts in its task's metrics (POLL):
    `agg_tier_retries`, programs launched again because the count
    outgrew a tier, 0 on the sort core; and
    `agg_running_sum_launches`, programs launched whose integer sums
    are read off a running sum and not scattered (_rows_by_group, the
    rule `_SegOps.sum` follows), 0 on the scatter core."""
    tiers = _group_tiers(gcap)
    keyless = gcap == 1  # both callers pass 1 for no keys, and only then
    if not keyless:
        _count("agg_tier_retries", 0)
        _count("agg_running_sum_launches", 0)
    if _rows_by_group(keyless, scatter_class):
        host_outs, n = _cut_tiers(
            base_key, build, args, fetch_n, tiers, False
        )
    else:
        host_outs, n = _climb_tiers(
            base_key, build, args, fetch_n, tiers, scatter_class
        )
    if n < 0:
        host_outs, n = _cut_tiers(
            base_key, build, args, fetch_n, tiers, True
        )
    return host_outs, n


def _climb_tiers(base_key, build, args, fetch_n, tiers, scatter_class):
    """One program a tier, smallest first, until the count fits (the
    collision sentinel -1 leaves at once, for the caller to see)."""
    for gc in tiers:
        fn = cached_kernel(
            base_key + (False, gc),
            lambda g=gc: build(False, g),
            scatter_class=scatter_class,
        )
        host_outs, n = fetch_n(*fn(*args))
        if gc is None or n <= gc:
            return host_outs, n
        _count("agg_tier_retries", 1)


def _cut_tiers(base_key, build, args, fetch_n, tiers, force_lex):
    """One program at the input's capacity, its states returned whole
    and cut to each tier; fetch the first cut with the count, then, if
    the count outgrew it, the smallest cut that holds it. On the sort
    core group ids are dense in sorted order, dead rows park in the
    last segment with neutral contributions and the boundary rows come
    first-to-last, so the first t slots are what a kernel built at t
    slots returns whenever n <= t."""
    cuts = tuple(t for t in tiers if t is not None)
    fn = cached_kernel(
        base_key + (force_lex, cuts),
        lambda: _with_cuts(build(force_lex, None), cuts),
    )
    by_cut, n_groups = fn(*args)
    # a cut program is keyed and on the sort core, forced lexsort or not
    _count("agg_running_sum_launches", int(_rows_by_group(False, False)))
    host_outs, n = fetch_n(by_cut[0], n_groups)
    fit = next((i for i, t in enumerate(cuts) if n <= t), len(cuts))
    if fit:
        host_outs, n = fetch_n(by_cut[fit], n)
    return host_outs, n


def _with_cuts(inner, cuts):
    """`inner`'s states once for each cut (the first `t` slots of every
    array) and then whole, beside the count."""

    # named like every cached_kernel program: a device trace finds
    # them as `jit_kernel` (perfbench/trace_patterns.json)
    def kernel(*args):
        outs, n_groups = inner(*args)
        return [
            jax.tree_util.tree_map(lambda x, t=t: x[:t], outs)
            for t in cuts
        ] + [outs], n_groups

    return kernel


def _rows_by_group(keyless: bool, scatter: bool) -> bool:
    """Whether a grouping program leaves its rows sorted by group: the
    sort core with keys. There `_SegOps.sum` reads an integer sum off a
    running sum and a tier is a cut of one result (`_cut_tiers`). The
    scatter core keeps the rows in input order and the keyless slot's
    dead rows lie anywhere: both reduce by segment."""
    return not (keyless or scatter)


class _SegOps:
    """Segmented reductions sized to the group-slot capacity (out_cap),
    not the row capacity. The keyless single-group case collapses to
    plain masked reductions - an XLA reduce instead of a scatter, which
    matters enormously on TPU where scatters serialize. So does an
    integer sum over rows sorted by group (`starts`: each group's first
    row, `n_groups` of them): a running sum read where the groups
    start."""

    def __init__(self, gid, out_cap: int, keyless: bool,
                 domain: int = None, compact_slots=None,
                 starts=None, n_groups=None):
        self.gid = gid
        self.out_cap = out_cap
        self.scalar = keyless and out_cap == 1
        # scatter-core fast path: `gid` may be RAW table slots (domain =
        # table size) instead of dense group ids - reductions scatter
        # into `domain` segments and only the tiny per-group result is
        # compacted to out_cap by gathering at the occupied slots. This
        # skips the dense-id pass (an extra full-row gather) entirely;
        # dead rows carry arbitrary in-range slots, which is safe
        # because every caller masks contributions to the reduction's
        # neutral element first.
        self.domain = out_cap if domain is None else domain
        self.compact_slots = compact_slots
        self.starts = starts
        self.n_groups = n_groups

    def _finish(self, r):
        if self.compact_slots is not None:
            r = jnp.take(r, self.compact_slots, axis=0)
        return r

    def sum(self, x):
        if self.scalar:
            return jnp.sum(x, axis=0, keepdims=True)
        if (self.starts is not None and x.ndim == 1
                and jnp.issubdtype(x.dtype, jnp.integer)):
            return self._sum_by_group(x)
        return self._finish(jax.ops.segment_sum(
            x, self.gid, num_segments=self.domain
        ))

    def _sum_by_group(self, x):
        """`segment_sum`'s bits with no scatter: a group's sum is the
        running sum where the next group starts less the running sum
        where it starts itself, the last group closed by the total
        (dead rows add 0 wherever they lie; integers wrap alike on both
        sides). A float sum would round differently and stays a
        segment sum."""
        if self.out_cap < x.shape[0]:
            # groups past the slots add nothing: segment_sum drops them
            x = jnp.where(self.gid < self.out_cap, x, jnp.zeros_like(x))
        run = running_scan(x, lax.cumsum)
        start = jnp.take(run - x, self.starts)
        slot = jnp.arange(self.out_cap, dtype=jnp.int32)
        end = jnp.where(
            slot + 1 < self.n_groups,
            jnp.concatenate([start[1:], run[-1:]]),
            run[-1],
        )
        return jnp.where(slot < self.n_groups, end - start,
                         jnp.zeros_like(start))

    def min(self, x):
        if self.scalar:
            return jnp.min(x, axis=0, keepdims=True)
        return self._finish(jax.ops.segment_min(
            x, self.gid, num_segments=self.domain
        ))

    def max(self, x):
        if self.scalar:
            return jnp.max(x, axis=0, keepdims=True)
        return self._finish(jax.ops.segment_max(
            x, self.gid, num_segments=self.domain
        ))


_DEC38_MAX = 10**38 - 1
_U64 = (1 << 64) - 1


def _reassemble_decimal(chunk_cols: List[np.ndarray],
                        any_v: Optional[np.ndarray],
                        count: Optional[np.ndarray],
                        scale: int, avg: bool,
                        n_live: Optional[int] = None):
    """Host-exact reassembly of chunked decimal sums -> (values, mask,
    DataType). SUM overflowing decimal(38) nulls out (Spark non-ANSI);
    AVG divides at scale+4 with HALF_UP using full-precision ints.
    Python-bigint work is O(n_live groups), not O(padded capacity):
    results zero-pad back to the buffer length."""
    cap = len(chunk_cols[0])
    n = cap if n_live is None else min(n_live, cap)
    total = (
        chunk_cols[0][:n].astype(object)
        + (chunk_cols[1][:n].astype(object) << 32)
        + (chunk_cols[2][:n].astype(object) << 64)
        + (chunk_cols[3][:n].astype(object) << 96)
    )
    out_scale = scale
    if avg:
        out_scale = min(scale + 4, 38)
        mul = 10 ** (out_scale - scale)
        safe = np.maximum(count[:n], 1).astype(object)
        num = total * mul
        q = num // safe
        r = num - q * safe
        half_up = np.where(num >= 0, 2 * r >= safe, 2 * r > safe)
        total = q + half_up.astype(object)
    overflow = np.abs(total) > _DEC38_MAX
    mask = np.zeros(cap, dtype=bool)
    mask[:n] = (
        any_v[:n] if any_v is not None else True
    ) & ~overflow
    safe_total = np.where(overflow, 0, total)
    t_mod = np.mod(safe_total, 1 << 128)  # two's complement 128
    lo = t_mod & _U64
    hi = t_mod >> 64
    to_i64 = lambda x: np.where(
        x >= (1 << 63), x - (1 << 64), x
    ).astype(np.int64)
    limbs = np.zeros((cap, 2), dtype=np.int64)
    limbs[:n] = np.stack([to_i64(lo), to_i64(hi)], axis=1)
    return limbs, mask, DataType.decimal(38, out_scale)


def _state_fields(agg: AggExpr, name: str, in_schema: Schema) -> List[Field]:
    fn = agg.fn
    if fn in (AggFn.COUNT, AggFn.COUNT_STAR):
        return [Field(f"{name}#count", DataType.int64(), False)]
    ct = infer_dtype(agg.child, in_schema)
    if fn in (AggFn.SUM, AggFn.AVG) and ct.id is TypeId.DECIMAL:
        # chunked 128-bit-exact sum state; the scale rides in the field
        # name so the FINAL side (which only sees the partial schema,
        # e.g. across a shuffle) can finalize exactly
        fields = [
            Field(
                f"{name}#dsum{ct.scale}_c{k}", DataType.int64(),
                k == 0,
            )
            for k in range(4)
        ]
        if fn is AggFn.AVG:
            fields.append(
                Field(f"{name}#count", DataType.int64(), False)
            )
        return fields
    if fn is AggFn.SUM:
        return [Field(f"{name}#sum", _sum_type(ct), True)]
    if fn in (AggFn.MIN, AggFn.MAX, AggFn.FIRST, AggFn.LAST):
        return [Field(f"{name}#{fn.value}", ct, True)]
    if fn is AggFn.AVG:
        return [
            Field(f"{name}#sum", _sum_type(ct), True),
            Field(f"{name}#count", DataType.int64(), False),
        ]
    # var/stddev family: plain-summable moments
    return [
        Field(f"{name}#n", DataType.float64(), False),
        Field(f"{name}#s1", DataType.float64(), False),
        Field(f"{name}#s2", DataType.float64(), False),
    ]


def _state_width(fn: AggFn, chunked: bool) -> int:
    """Positional state width per aggregate (immune to duplicate output
    aliases - the layout is deterministic given fn + whether the first
    state field carries the chunked-decimal #dsum marker)."""
    if fn in (AggFn.COUNT, AggFn.COUNT_STAR, AggFn.MIN, AggFn.MAX,
              AggFn.FIRST, AggFn.LAST):
        return 1
    if fn is AggFn.SUM:
        return 4 if chunked else 1
    if fn is AggFn.AVG:
        return 5 if chunked else 2
    return 3  # var/stddev moments


def _parse_dsum_scale(field_name: str) -> Optional[int]:
    """Scale encoded in a chunked-decimal state field name, or None."""
    marker = "#dsum"
    i = field_name.find(marker)
    if i < 0:
        return None
    rest = field_name[i + len(marker):]
    j = rest.find("_c")
    if j <= 0:
        return None
    try:
        return int(rest[:j])
    except ValueError:
        return None


def _sum_type(ct: DataType) -> DataType:
    if ct.is_integer:
        return DataType.int64()
    if ct.id is TypeId.DECIMAL:
        return DataType.decimal(38, ct.scale)
    return DataType.float64()


class HashAggregateExec(PhysicalOp):
    def __init__(
        self,
        child: PhysicalOp,
        keys: Sequence[Tuple[ir.Expr, str]],
        aggs: Sequence[Tuple[AggExpr, str]],
        mode: AggMode = AggMode.COMPLETE,
    ):
        self.children = [child]
        self.mode = mode
        in_schema = child.schema
        self.keys = [(bind_opt(e, in_schema), n) for e, n in keys]
        if mode is AggMode.FINAL:
            # child refs are ignored in FINAL mode; states are located
            # positionally in the partial output (keys first, then states
            # in agg order) - mirror of the reference's partial/final
            # column splice (NativeHashAggregateExec.scala:98-161).
            # Widths come from the partial schema's "{name}#..." field
            # names, which also carry the chunked-decimal scale marker.
            self.aggs = []
            self._final_widths: List[int] = []
            pos = len(self.keys)
            fields = in_schema.fields
            for a, n in aggs:
                chunked = (
                    _parse_dsum_scale(fields[pos].name) is not None
                )
                width = _state_width(a.fn, chunked)
                first_state = fields[pos]
                self.aggs.append(
                    (AggExpr(a.fn, ir.BoundCol(pos, first_state.dtype)), n)
                )
                self._final_widths.append(width)
                pos += width
        else:
            self.aggs = [
                (
                    AggExpr(
                        a.fn,
                        bind_opt(a.child, in_schema)
                        if a.child is not None
                        else None,
                    ),
                    n,
                )
                for a, n in aggs
            ]
        for a, n in self.aggs:
            if a.fn in (AggFn.MIN, AggFn.MAX) and a.child is not None:
                if infer_dtype(a.child, in_schema).is_string_like:
                    raise NotImplementedError(
                        "MIN/MAX over strings is host-tier work (planned)"
                    )
            if (
                mode is not AggMode.FINAL
                and a.child is not None
                and a.fn not in (AggFn.SUM, AggFn.AVG, AggFn.COUNT,
                                 AggFn.FIRST, AggFn.LAST)
                and infer_dtype(a.child, in_schema).is_wide_decimal
            ):
                # 128-bit ordering/moments need host math; SUM/AVG use
                # the chunked state, FIRST/LAST/COUNT are passthrough
                raise NotImplementedError(
                    f"{a.fn.value} over decimal(>18) is host-tier work"
                )
        key_fields = [
            Field(n, infer_dtype(e, in_schema), True) for e, n in self.keys
        ]
        for f in key_fields:
            if f.dtype.is_wide_decimal:
                raise NotImplementedError(
                    "group keys of decimal(>18) are host-tier work"
                )
        if mode is AggMode.PARTIAL:
            state_fields: List[Field] = []
            for a, n in self.aggs:
                state_fields += _state_fields(a, n, in_schema)
            self._schema = Schema(key_fields + state_fields)
        else:
            self._schema = Schema(
                key_fields
                + [
                    Field(n, _result_type(a, in_schema, mode), True)
                    for a, n in self.aggs
                ]
            )

    @property
    def schema(self) -> Schema:
        return self._schema

    _FINGERPRINT_STABLE = True

    def _fingerprint_params(self) -> str:
        keys = ";".join(f"{n}={e!r}" for e, n in self.keys)
        aggs = ";".join(f"{n}={a!r}" for a, n in self.aggs)
        return f"{self.mode.name};keys[{keys}];aggs[{aggs}]"

    # ------------------------------------------------------------------
    def execute(self, partition: int, ctx: ExecContext
                ) -> Iterator[ColumnBatch]:
        child_it = self.children[0].execute(partition, ctx)
        if self.mode is AggMode.PARTIAL:
            for cb in child_it:
                out = self._aggregate_batch(cb)
                if out.num_rows > 0:
                    yield out
            return
        from blaze_tpu.ops.external import bucket_stream, collect_until

        batches, exceeded = collect_until(
            child_it, ctx.config.max_materialize_rows
        )
        if exceeded:
            yield from self._execute_external(batches, child_it, ctx)
            return
        # a stream of batches is materialized at least one batch wide:
        # a capacity from the row count alone would be a new grouping
        # program whenever a task's count crosses a shape bucket (TPC-DS
        # query 3's first stage joins about 930 to 1,050 rows a split,
        # across 1,024), and under a batch a wider program costs next to
        # nothing
        cb = concat_batches(
            batches, schema=self.children[0].schema,
            min_capacity=ctx.config.batch_size if len(batches) > 1 else 0,
        )
        if cb.num_rows == 0 and self.keys:
            return
        out = self._aggregate_batch(cb)
        if cb.num_rows == 0 and not self.keys:
            # global aggregate over empty input still emits one row
            yield _empty_global_row(self)
            return
        yield out

    def _execute_external(self, head, rest, ctx: ExecContext
                          ) -> Iterator[ColumnBatch]:
        """Grace aggregation for oversized inputs (ops/external.py): every
        group lands wholly in one hash bucket, so buckets aggregate
        independently. The keyless case folds per-batch partial states
        instead (one state row per batch, always bounded)."""
        in_schema = self.children[0].schema
        if not self.keys:
            if self.mode is AggMode.FINAL:
                # keyless FINAL consumes tiny partial-state rows (one per
                # upstream batch); crossing the row cap here implies an
                # absurd upstream batch count - concat is still bounded
                batches = list(head) + list(rest)
                yield self._aggregate_batch(
                    concat_batches(batches, schema=in_schema)
                )
                return
            # keyless COMPLETE: fold per-batch partial states, then one
            # final merge (one state row per input batch)
            partial = HashAggregateExec(
                self.children[0],
                keys=[],
                aggs=[(a, n) for a, n in self.aggs],
                mode=AggMode.PARTIAL,
            )
            partials = []
            for cb in list(head) + list(rest):
                p = partial._aggregate_batch(cb)
                if p.num_rows:
                    partials.append(p)
            if not partials:
                yield _empty_global_row(self)
                return
            final = HashAggregateExec(
                _SchemaStub(partial.schema),
                keys=[],
                aggs=[(a, n) for a, n in self.aggs],
                mode=AggMode.FINAL,
            )
            yield final._aggregate_batch(
                concat_batches(partials, schema=partial.schema)
            )
            return
        key_exprs = [e for e, _ in self.keys]
        from blaze_tpu.runtime.memory import (
            batch_device_bytes,
            choose_external_bucket_count,
            get_device_tracker,
        )

        head_bytes = sum(batch_device_bytes(b) for b in head)
        tracker = get_device_tracker()
        track_key = (id(self), ctx.partition_id)
        tracker.track(track_key, head_bytes)
        try:
            n_b = choose_external_bucket_count(
                2 * head_bytes, ctx.config
            )
            yield from self._grace_agg(
                rest, head, ctx, in_schema, n_b, depth=0
            )
        finally:
            tracker.release(track_key)

    _MAX_GRACE_DEPTH = 2
    _GRACE_FANOUT = 4

    def _grace_agg(self, rest, head, ctx: ExecContext, in_schema,
                   n_b: int, depth: int,
                   modulus: Optional[int] = None
                   ) -> Iterator[ColumnBatch]:
        """One grace level. Overflowing buckets re-bucket recursively by
        the next hash bits (splits many-distinct-key overflow); at max
        depth - a single hot key - COMPLETE mode aggregates the bucket
        CHUNK-WISE (partial per sub-chunk + one final merge), which a
        hash split can never achieve."""
        from blaze_tpu.ops.external import (
            bucket_stream,
            collect_until,
            subdivide_pid_fn,
        )

        key_exprs = [e for e, _ in self.keys]
        if modulus is None:
            modulus = n_b
            pid = None
        else:
            pid = subdivide_pid_fn(key_exprs, modulus, n_b)
            modulus *= n_b
        bucketed = bucket_stream(
            rest, key_exprs, n_b, ctx, in_schema, head=head, pid_fn=pid,
        )
        ctx.metrics.add("external_agg_buckets", n_b)
        try:
            limit = ctx.config.max_materialize_rows
            for b in range(n_b):
                it = bucketed.bucket(b)
                chunk, exceeded = collect_until(it, limit)
                if not chunk:
                    continue
                if exceeded and depth < self._MAX_GRACE_DEPTH:
                    ctx.metrics.add("external_agg_rebuckets", 1)
                    yield from self._grace_agg(
                        it, chunk, ctx, in_schema,
                        self._GRACE_FANOUT, depth + 1, modulus,
                    )
                    continue
                if exceeded and self.mode is AggMode.COMPLETE:
                    ctx.metrics.add("external_agg_hot_buckets", 1)
                    yield from self._aggregate_chunked(
                        chunk, it, in_schema, limit
                    )
                    continue
                chunk += list(it)  # exceeded FINAL: states stay mergeable
                out = self._aggregate_batch(
                    concat_batches(chunk, schema=in_schema)
                )
                if out.num_rows:
                    yield out
        finally:
            bucketed.cleanup()

    def _aggregate_chunked(self, head, rest, in_schema, limit
                           ) -> Iterator[ColumnBatch]:
        """Partial-per-chunk + final-merge for one oversized bucket."""
        partial = HashAggregateExec(
            self.children[0],
            keys=[(e, n) for e, n in self.keys],
            aggs=[(a, n) for a, n in self.aggs],
            mode=AggMode.PARTIAL,
        )
        partials: List[ColumnBatch] = []

        def drain(batches):
            chunk: List[ColumnBatch] = []
            rows = 0
            for cb in batches:
                chunk.append(cb)
                rows += cb.num_rows
                if rows > limit:
                    p = partial._aggregate_batch(
                        concat_batches(chunk, schema=in_schema)
                    )
                    if p.num_rows:
                        partials.append(p)
                    chunk, rows = [], 0
            if chunk:
                p = partial._aggregate_batch(
                    concat_batches(chunk, schema=in_schema)
                )
                if p.num_rows:
                    partials.append(p)

        import itertools

        # STREAM the bucket: materializing it here would re-create the
        # exact blow-up this path exists to avoid
        drain(itertools.chain(head, rest))
        if not partials:
            return
        final = HashAggregateExec(
            _SchemaStub(partial.schema),
            keys=[
                (ir.BoundCol(i, partial.schema.fields[i].dtype), n)
                for i, (_, n) in enumerate(self.keys)
            ],
            aggs=[(a, n) for a, n in self.aggs],
            mode=AggMode.FINAL,
        )
        out = final._aggregate_batch(
            concat_batches(partials, schema=partial.schema)
        )
        if out.num_rows:
            yield out

    # ------------------------------------------------------------------
    def _aggregate_batch(self, cb: ColumnBatch) -> ColumnBatch:
        merging = self.mode is AggMode.FINAL
        key_exprs = [e for e, _ in self.keys]
        child_exprs: List[ir.Expr] = []
        for a, _ in self.aggs:
            if merging:
                continue
            if a.child is not None:
                child_exprs.append(a.child)
        exprs, _, aug = lower_strings_host(key_exprs + child_exprs, cb)
        key_exprs_l = exprs[: len(key_exprs)]
        child_map = {}
        if not merging:
            it = iter(exprs[len(key_exprs):])
            for i, (a, _) in enumerate(self.aggs):
                if a.child is not None:
                    child_map[i] = next(it)

        base_key = ("hashagg", self.mode.value,
                    tuple((a.fn, a.child) for a, _ in self.aggs),
                    tuple(key_exprs_l), tuple(child_map.items()),
                    aug.layout(), merging, _group_core_choice())
        gcap = (1 if not self.keys
                else min(aug.capacity, get_config().agg_group_capacity))
        if gcap >= aug.capacity:
            gcap = None
        outs, n = run_grouped_kernel(
            base_key,
            lambda fl, gc: self._build_kernel(
                aug.schema, aug.capacity, key_exprs_l, child_map,
                merging, aug.layout(), force_lexsort=fl, group_cap=gc,
            ),
            (aug.device_buffers(), aug.selection,
             None if aug.num_rows == aug.capacity else aug.num_rows),
            # keyless: exactly one group, no collision/overflow retry -
            # skip the blocking scalar sync (a device round trip each)
            (lambda o, ng: (o, 1)) if not self.keys
            else (lambda o, ng: (o, _group_count(ng))),
            gcap,
            scatter_class=self._scatter_core_hint(
                aug.schema, key_exprs_l
            ),
        )
        cols: List[Column] = []
        # recover dictionaries for string key passthroughs
        for (v, m), field, e in zip(
            outs[: len(self.keys)],
            self._schema.fields[: len(self.keys)],
            key_exprs_l,
        ):
            dictionary = None
            if field.dtype.is_dictionary_encoded and isinstance(
                e, ir.BoundCol
            ):
                dictionary = aug.columns[e.index].dictionary
            cols.append(Column(field.dtype, v, m, dictionary))
        agg_fields = self._schema.fields[len(self.keys):]
        it = iter(outs[len(self.keys):])
        if self.mode is AggMode.PARTIAL:
            # state fields align 1:1 with kernel outputs
            for (v, m), field in zip(it, agg_fields):
                cols.append(Column(field.dtype, v, m, None))
        else:
            staged = []
            fetch_list: List = []
            for (a, _), field in zip(self.aggs, agg_fields):
                spec = self._agg_spec(a, aug.schema)
                if spec[0] == "plain":
                    staged.append((spec, field, next(it)))
                    continue
                # chunked decimal: stage the chunk arrays; ALL of them
                # fetch in one packed transfer below
                pairs = [next(it) for _ in range(4)]
                count = next(it)[0] if spec[0] == "dec_avg" else None
                staged.append((spec, field, (pairs, count)))
                fetch_list.extend(v for v, _ in pairs)
                fetch_list.append(pairs[0][1])
                if count is not None:
                    fetch_list.append(count)
            if fetch_list:
                from blaze_tpu.runtime.pack import get_packed

                host_it = iter(get_packed(fetch_list))
            for spec, field, payload in staged:
                if spec[0] == "plain":
                    v, m = payload
                    cols.append(Column(field.dtype, v, m, None))
                    continue
                _, count = payload
                chunks = [np.asarray(next(host_it)) for _ in range(4)]
                any_np = np.asarray(next(host_it))
                count_np = (
                    np.asarray(next(host_it)) if count is not None
                    else None
                )
                limbs, mask, dt = _reassemble_decimal(
                    chunks, any_np, count_np, spec[1],
                    spec[0] == "dec_avg", n_live=n,
                )
                assert dt == field.dtype, (dt, field.dtype)
                cols.append(Column(field.dtype, limbs, mask, None))
        return ColumnBatch(self._schema, cols, n)

    # ------------------------------------------------------------------
    def _scatter_core_hint(self, in_schema, key_exprs) -> bool:
        """Mirror of _build_kernel's use_scatter gate, evaluated at
        dispatch time: True when the kernel variant about to build will
        run the scatter grouping core, so cached_kernel can route it to
        the scatter-friendly CPU runtime (dispatch._scatter_jit_kwargs).
        A wrong guess only costs runtime choice, never correctness."""
        return (
            bool(key_exprs)
            and _group_core_choice() == "scatter"
            and self._narrow_key_dtypes(
                in_schema, key_exprs, allow_floats=True
            )
            is not None
        )

    def _narrow_key_dtypes(self, in_schema, key_exprs,
                           allow_floats: bool = False):
        """Hash dtypes for the narrow-key grouping fast path, or None
        when ineligible. Eligible: fixed-width non-float keys (ints,
        dates, timestamps, bool, decimal<=18, dictionary codes) - the
        sort then runs on ONE i32 hash lane instead of K emulated-64-bit
        lanes (ROADMAP 'aggregate/sort key widths'). Floats keep the
        lexsort path there (NaN/-0.0 normalization), but the SCATTER
        core compares exact key values (_pairwise_eq groups NaN with
        NaN; cheap_hash normalizes -0.0/NaN payloads), so it passes
        allow_floats=True."""
        from blaze_tpu.exprs.hashing import device_hash_supported

        dtypes = []
        for e in key_exprs:
            dt = infer_dtype(e, in_schema)
            if dt.is_dictionary_encoded:
                dt = DataType.int32()  # group equality == code equality
            if dt.id in (TypeId.FLOAT32, TypeId.FLOAT64):
                if not allow_floats:
                    return None
                dtypes.append(dt)
                continue
            if dt.is_wide_decimal or not device_hash_supported(dt):
                return None
            dtypes.append(dt)
        return dtypes

    def _build_kernel(self, in_schema, capacity, key_exprs, child_map,
                      merging, layout, force_lexsort: bool = False,
                      group_cap=None):
        from blaze_tpu.exprs.hashing import hash_columns_device

        aggs = self.aggs
        n_keys = len(key_exprs)
        state_offsets = self._state_offsets(in_schema) if merging else None
        use_scatter = False
        if not force_lexsort and _group_core_choice() == "scatter":
            # the scatter core's exact-equality probing also handles
            # float keys (NaN groups with NaN, -0.0 == 0.0), which the
            # hash-lane sort cannot
            use_scatter = (
                self._narrow_key_dtypes(
                    in_schema, key_exprs, allow_floats=True
                )
                is not None
            )
        # hash-lane dtypes only matter when the scatter gate fails
        hash_dtypes = (
            None if force_lexsort or use_scatter
            else self._narrow_key_dtypes(in_schema, key_exprs)
        )

        # Segment-output capacity: with a small static group bound the
        # reductions scatter into out_cap slots instead of `capacity`
        # (keyless aggregates collapse to plain masked reductions), so
        # both the compute AND the transfer scale with groups, not rows.
        out_cap = (
            group_cap
            if group_cap is not None and group_cap < capacity
            else capacity
        )

        def kernel(bufs, selection, num_rows):
            cols = _unflatten_cvs(layout, bufs)
            ev = DeviceEvaluator(in_schema, cols, capacity)
            # num_rows=None: FULL batch (host-known at dispatch). The
            # constant-true mask folds every downstream where() away,
            # letting XLA fuse expensive projections (log/sqrt chains)
            # straight into the reductions instead of materializing
            # them for a masked select (8M expr_chain: 254ms -> 140ms)
            live = (
                jnp.ones(capacity, dtype=jnp.bool_)
                if num_rows is None
                else jnp.arange(capacity, dtype=jnp.int32) < num_rows
            )
            if selection is not None:
                live = live & selection

            keys_cv = [ev.evaluate(e) for e in key_exprs]
            collision = jnp.asarray(False)
            if n_keys and use_scatter:
                # ---- group ids by hash-table insertion (sort-free) ----
                # every live row resolves to a slot via exact-key probing
                # (ops/hash_table.py), so unlike the hash-lane sort path
                # there is no collision sentinel: equality is verified,
                # not inferred from hash adjacency
                from blaze_tpu.ops import hash_table as ht

                # table sized to the group-slot capacity, not the row
                # capacity: dense_group_ids scans the whole table, so a
                # row-capacity table costs ~0.5s/8M rows in cumsum+
                # nonzero alone. More distinct keys than the small
                # table holds trips `overflow`, which reuses the
                # group-capacity retry (re-run unsliced -> full table).
                full_t = ht.table_size_for(capacity)
                small_t = ht.table_size_for(min(capacity, 2 * out_cap))
                tsize = min(small_t, full_t)
                slot, rep_tab, overflow = ht.group_slots(
                    [(v, m) for v, m in keys_cv],
                    live,
                    capacity,
                    tsize,
                    max_rounds=16 if tsize < full_t else None,
                )
                # reductions run on RAW slots (domain = tsize); only
                # the (out_cap,)-sized states compact through the
                # occupied-slot gather below, skipping dense_group_ids'
                # extra full-row gather (8M rows / 4k groups: the whole
                # group stage drops ~35%). Dead rows keep arbitrary
                # in-range slots - every reduction masks their
                # contribution to its neutral element.
                occupied = rep_tab != jnp.int32(capacity)
                n_groups = jnp.sum(occupied.astype(jnp.int32))
                occ_slots = jnp.nonzero(
                    occupied, size=out_cap, fill_value=0
                )[0]
                bpos = jnp.clip(
                    jnp.take(rep_tab, occ_slots), 0, capacity - 1
                )
                gid_sorted = slot
                seg_domain = tsize
                seg_compact = occ_slots
                n_groups = jnp.where(
                    overflow, jnp.int32(out_cap + 1), n_groups
                )
                idx = None  # identity: rows stay in input order
                s_live = live
            # ---- group ids by stable sort + boundary detection ----
            elif n_keys and hash_dtypes is not None:
                # narrow-key fast path: ONE stable i32 sort by the key
                # hash; true-key boundary detection below splits hash
                # collisions into correct runs, and a collision between
                # DIFFERENT keys (which could scatter one key across
                # runs) is detected and reported via the n_groups
                # sentinel so the caller re-runs the lexsort kernel
                h = hash_columns_device(
                    [
                        (v, m, dt)
                        for (v, m), dt in zip(keys_cv, hash_dtypes)
                    ],
                    capacity,
                ).astype(jnp.int32)
                order = jnp.lexsort(
                    (h, jnp.where(live, 0, 1).astype(jnp.int8))
                )
                idx = order
                sh = jnp.take(h, idx)
                shp = jnp.concatenate([sh[:1], sh[:-1]])
                hash_neq = sh != shp
            elif n_keys:
                # sort priority: live rows first, then per key a (validity,
                # value) pair so NULL forms its own ordering class and never
                # interleaves with the dtype-extreme sentinel values
                priority = [jnp.where(live, 0, 1).astype(jnp.int8)]
                for v, m in keys_cv:
                    if m is not None:
                        priority.append(
                            jnp.where(m, jnp.int8(1), jnp.int8(0))
                        )
                    priority.append(_null_last_key(v, m))
                    if jnp.issubdtype(v.dtype, jnp.floating):
                        # NaN encodes as +inf for ordering; this extra
                        # component keeps the NaN run adjacent but
                        # SEPARATE from a real +inf run
                        priority.append(jnp.isnan(v).astype(jnp.int8))
                # jnp.lexsort: last key is the primary -> reverse
                order = jnp.lexsort(tuple(reversed(priority)))
                idx = order
                hash_neq = None
            if n_keys and not use_scatter:
                s_live = jnp.take(live, idx)
                prev_live = jnp.concatenate(
                    [jnp.zeros(1, dtype=jnp.bool_), s_live[:-1]]
                )
                first_live = s_live & ~prev_live
                diff = jnp.zeros(capacity, dtype=jnp.bool_)
                for v, m in keys_cv:
                    if jnp.issubdtype(v.dtype, jnp.floating):
                        # group NaN with NaN (Spark normalizes NaN keys);
                        # the isnan flag separates it from real +inf
                        nanf = jnp.take(
                            jnp.isnan(v).astype(jnp.int8), idx
                        )
                        sv = jnp.take(
                            jnp.where(jnp.isnan(v), jnp.inf, v), idx
                        )
                        nanp = jnp.concatenate([nanf[:1], nanf[:-1]])
                        extra = nanf != nanp
                    else:
                        sv = jnp.take(v, idx)
                        extra = jnp.zeros(capacity, dtype=jnp.bool_)
                    svp = jnp.concatenate([sv[:1], sv[:-1]])
                    neq = (sv != svp) | extra
                    if m is not None:
                        sm = jnp.take(m, idx)
                        smp = jnp.concatenate([sm[:1], sm[:-1]])
                        neq = jnp.where(
                            sm & smp, neq, sm != smp
                        )
                    diff = diff | neq
                if hash_neq is not None:
                    # a same-hash adjacency between DIFFERENT keys means
                    # equal keys may be scattered across runs - bail to
                    # the lexsort kernel via the n_groups sentinel
                    collision = jnp.any(
                        s_live & prev_live & ~hash_neq & diff
                    )
                boundary = s_live & (diff | first_live)
                gid_sorted = jnp.cumsum(boundary.astype(jnp.int32)) - 1
                # dead rows park in the last segment; every reduction
                # masks them to its neutral element so they never count
                gid_sorted = jnp.where(s_live, gid_sorted, out_cap - 1)
                n_live = jnp.sum(boundary.astype(jnp.int32))
                n_groups = jnp.where(collision, jnp.int32(-1), n_live)
                # boundary row index per group, padded with 0: the
                # boundary rows' numbers sorted to the front (a TPU sorts
                # a lane of i32 in no time; jnp.nonzero counts the rows
                # into place with a scatter-add of i64)
                first = lax.sort(jnp.where(
                    boundary, jnp.arange(capacity, dtype=jnp.int32),
                    jnp.int32(capacity),
                ))[:out_cap]
                bpos = jnp.where(first < capacity, first, 0)
            elif not n_keys:
                idx = None
                s_live = live
                gid_sorted = jnp.where(live, 0, out_cap - 1)
                n_groups = jnp.asarray(1, jnp.int32)
                bpos = jnp.zeros(out_cap, dtype=jnp.int32)

            if not (n_keys and use_scatter):
                seg_domain = None
                seg_compact = None

            outs = []
            for (v, m) in keys_cv:
                sv = _tk(v, idx)
                kv = jnp.take(sv, bpos)
                km = None
                if m is not None:
                    km = jnp.take(_tk(m, idx), bpos)
                outs.append((kv, km))

            by_group = _rows_by_group(n_keys == 0, use_scatter)
            segops = _SegOps(
                gid_sorted, out_cap, n_keys == 0,
                domain=seg_domain, compact_slots=seg_compact,
                starts=bpos if by_group else None,
                n_groups=n_live if by_group else None,
            )
            for i, (a, name) in enumerate(aggs):
                outs.extend(
                    self._agg_state(
                        a, i, ev, idx, s_live, segops, capacity,
                        child_map, merging, state_offsets, cols,
                    )
                )
            return outs, n_groups

        return kernel

    def _state_offsets(self, in_schema: Schema):
        """In FINAL mode, locate each agg's state columns positionally:
        keys first, then state columns in agg order (widths were scanned
        from the partial schema's field names at construction)."""
        offs = {}
        pos = len(self.keys)
        for i, (a, n) in enumerate(self.aggs):
            width = self._final_widths[i]
            offs[i] = (pos, width)
            pos += width
        return offs

    def _agg_spec(self, a: AggExpr, in_schema: Schema):
        """Output classification: ("plain", None) or
        ("dec_sum"|"dec_avg", scale) for chunked-exact decimal
        aggregation whose result reassembles on the host."""
        if a.fn not in (AggFn.SUM, AggFn.AVG):
            return ("plain", None)
        if self.mode is AggMode.FINAL:
            s = _parse_dsum_scale(in_schema.fields[a.child.index].name)
            if s is not None:
                return (
                    "dec_avg" if a.fn is AggFn.AVG else "dec_sum", s
                )
            return ("plain", None)
        ct = infer_dtype(a.child, in_schema)
        if ct.id is TypeId.DECIMAL:
            return ("dec_avg" if a.fn is AggFn.AVG else "dec_sum",
                    ct.scale)
        return ("plain", None)

    def _agg_state(self, a, i, ev, idx, s_live, segops, capacity,
                   child_map, merging, state_offsets, cols):
        """Emit the output (value, validity) columns for one aggregate."""
        fn = a.fn
        seg = segops.sum
        live_f = s_live

        if merging:
            pos, width = state_offsets[i]
            states = [
                (_tk(cols[pos + k][0], idx),
                 _tk(cols[pos + k][1], idx)
                 if cols[pos + k][1] is not None else None)
                for k in range(width)
            ]
            spec = self._agg_spec(a, ev.schema)
            return self._merge_states(
                a, states, segops, live_f, capacity, spec
            )

        # raw input -> state/result
        if fn is AggFn.COUNT_STAR:
            c = seg(live_f.astype(jnp.int64))
            return [(c, None)]
        cv, cm = ev.evaluate(child_map[i])
        cv = _tk(cv, idx)
        cm_s = _tk(cm, idx) if cm is not None else None
        contrib = live_f if cm_s is None else (live_f & cm_s)
        if fn is AggFn.COUNT:
            return [(seg(contrib.astype(jnp.int64)), None)]
        if fn in (AggFn.SUM, AggFn.AVG):
            st = _sum_type(infer_dtype_of(a, ev.schema))
            if st.id is TypeId.DECIMAL:
                # chunked 128-bit-exact sum; result reassembles on host
                chunks = _decimal_chunks(cv)
                sums = [
                    seg(jnp.where(contrib, c, jnp.zeros_like(c)))
                    for c in chunks
                ]
                any_v = seg(contrib.astype(jnp.int64)) > 0
                out = [(sums[0], any_v)] + [
                    (c, None) for c in sums[1:]
                ]
                if fn is AggFn.AVG:
                    out.append((seg(contrib.astype(jnp.int64)), None))
                return out
            acc = jnp.where(contrib, cv, jnp.zeros_like(cv)).astype(
                st.physical_dtype()
            )
            s = seg(acc)
            any_v = seg(contrib.astype(jnp.int64)) > 0
            if fn is AggFn.SUM:
                return [(s, any_v)]
            cnt = seg(contrib.astype(jnp.int64))
            if self.mode is AggMode.PARTIAL:
                return [(s, any_v), (cnt, None)]
            safe = jnp.maximum(cnt, 1)
            return [(s / safe.astype(jnp.float64), any_v)]
        if fn in (AggFn.MIN, AggFn.MAX):
            phys = cv.dtype
            if jnp.issubdtype(phys, jnp.floating):
                neutral = jnp.inf if fn is AggFn.MIN else -jnp.inf
            elif phys == jnp.bool_:
                cv = cv.astype(jnp.int8)
                neutral = 1 if fn is AggFn.MIN else 0
                phys = jnp.int8
            else:
                info = jnp.iinfo(phys)
                neutral = info.max if fn is AggFn.MIN else info.min
            acc = jnp.where(contrib, cv, jnp.asarray(neutral, phys))
            red = segops.min if fn is AggFn.MIN else segops.max
            m = red(acc)
            any_v = seg(contrib.astype(jnp.int64)) > 0
            return [(m, any_v)]
        if fn in (AggFn.FIRST, AggFn.LAST):
            pos_in = jnp.arange(capacity, dtype=jnp.int32)
            big = capacity + 1
            if fn is AggFn.FIRST:
                rank = jnp.where(contrib, pos_in, big)
                best = segops.min(rank)
            else:
                rank = jnp.where(contrib, pos_in, -1)
                best = segops.max(rank)
            has = (best >= 0) & (best < big)
            safe_best = jnp.clip(best, 0, capacity - 1)
            vals = jnp.take(cv, safe_best, axis=0)
            return [(vals, has)]
        # var/stddev family: moments
        x = jnp.where(contrib, cv, jnp.zeros_like(cv)).astype(jnp.float64)
        n = seg(contrib.astype(jnp.float64))
        s1 = seg(x)
        s2 = seg(x * x)
        if self.mode is AggMode.PARTIAL:
            return [(n, None), (s1, None), (s2, None)]
        return [_finalize_var(a.fn, n, s1, s2)]

    def _merge_states(self, a, states, segops, live_f, capacity,
                      spec=("plain", None)):
        fn = a.fn
        seg = segops.sum
        if spec[0] in ("dec_sum", "dec_avg"):
            # chunk sums merge by plain segment addition
            c0, m0 = states[0]
            contrib = live_f if m0 is None else (live_f & m0)
            sums = [
                seg(jnp.where(live_f, c, jnp.zeros_like(c)))
                for c, _ in states[:4]
            ]
            any_v = seg(contrib.astype(jnp.int64)) > 0
            out = [(sums[0], any_v)] + [(c, None) for c in sums[1:]]
            if spec[0] == "dec_avg":
                cnt, _ = states[4]
                out.append(
                    (seg(jnp.where(live_f, cnt, jnp.zeros_like(cnt))),
                     None)
                )
            return out
        if fn in (AggFn.COUNT, AggFn.COUNT_STAR):
            v, _ = states[0]
            return [(seg(jnp.where(live_f, v, 0)), None)]
        if fn is AggFn.SUM:
            v, m = states[0]
            contrib = live_f if m is None else (live_f & m)
            s = seg(jnp.where(contrib, v, jnp.zeros_like(v)))
            any_v = seg(contrib.astype(jnp.int64)) > 0
            return [(s, any_v)]
        if fn in (AggFn.MIN, AggFn.MAX):
            v, m = states[0]
            contrib = live_f if m is None else (live_f & m)
            phys = v.dtype
            if jnp.issubdtype(phys, jnp.floating):
                neutral = jnp.inf if fn is AggFn.MIN else -jnp.inf
            else:
                info = jnp.iinfo(phys)
                neutral = info.max if fn is AggFn.MIN else info.min
            acc = jnp.where(contrib, v, jnp.asarray(neutral, phys))
            red = segops.min if fn is AggFn.MIN else segops.max
            out = red(acc)
            any_v = seg(contrib.astype(jnp.int64)) > 0
            return [(out, any_v)]
        if fn is AggFn.AVG:
            (sv, sm), (cv2, _) = states
            contrib = live_f if sm is None else (live_f & sm)
            s = seg(jnp.where(contrib, sv, jnp.zeros_like(sv)))
            c = seg(jnp.where(live_f, cv2, jnp.zeros_like(cv2)))
            any_v = c > 0
            safe = jnp.maximum(c, 1)
            # decimal AVG runs on the chunked path above; this is the
            # int/float double AVG
            return [(s.astype(jnp.float64)
                     / safe.astype(jnp.float64), any_v)]
        if fn in (AggFn.FIRST, AggFn.LAST):
            v, m = states[0]
            contrib = live_f if m is None else (live_f & m)
            pos_in = jnp.arange(capacity, dtype=jnp.int32)
            big = capacity + 1
            if fn is AggFn.FIRST:
                rank = jnp.where(contrib, pos_in, big)
                best = segops.min(rank)
            else:
                rank = jnp.where(contrib, pos_in, -1)
                best = segops.max(rank)
            has = (best >= 0) & (best < big)
            vals = jnp.take(v, jnp.clip(best, 0, capacity - 1), axis=0)
            return [(vals, has)]
        # moments merge
        (nv, _), (s1v, _), (s2v, _) = states
        n = seg(jnp.where(live_f, nv, 0.0))
        s1 = seg(jnp.where(live_f, s1v, 0.0))
        s2 = seg(jnp.where(live_f, s2v, 0.0))
        return [_finalize_var(fn, n, s1, s2)]


def _tk(x, idx):
    """Permute by the grouping order; `idx is None` means identity (the
    scatter core keeps rows in input order - skipping the gather saves a
    full-capacity pass per aggregated column)."""
    if idx is None:
        return x
    return jnp.take(x, idx, axis=0)


def _null_last_key(v, m):
    if jnp.issubdtype(v.dtype, jnp.floating):
        v = jnp.where(jnp.isnan(v), jnp.inf, v)
    if m is None:
        return v
    # nulls group first: shift valid values up by using a rank pair trick -
    # lexsort handles composite keys, so encode null rank into the value
    # domain where possible; use where() with dtype extremes
    if jnp.issubdtype(v.dtype, jnp.floating):
        return jnp.where(m, v, -jnp.inf)
    if v.dtype == jnp.bool_:
        return jnp.where(m, v.astype(jnp.int8), jnp.int8(-1))
    info = jnp.iinfo(v.dtype)
    return jnp.where(m, v, info.min)


def _finalize_var(fn: AggFn, n, s1, s2):
    mean = s1 / jnp.maximum(n, 1.0)
    m2 = s2 - s1 * mean  # sum((x-mean)^2) = s2 - s1^2/n
    pop = fn in (AggFn.VAR_POP, AggFn.STDDEV_POP)
    denom = jnp.maximum(n if pop else n - 1.0, 1.0)
    var = jnp.maximum(m2, 0.0) / denom
    valid = n > (0.0 if pop else 1.0)
    out = var
    if fn in (AggFn.STDDEV_SAMP, AggFn.STDDEV_POP):
        out = jnp.sqrt(var)
    return (out, valid)


def _result_type(a: AggExpr, in_schema: Schema, mode: AggMode) -> DataType:
    if mode is AggMode.FINAL:
        # child is a BoundCol at the first state column (see __init__)
        if a.fn in (AggFn.COUNT, AggFn.COUNT_STAR):
            return DataType.int64()
        dscale = _parse_dsum_scale(in_schema.fields[a.child.index].name)
        if dscale is not None:
            if a.fn is AggFn.AVG:
                return DataType.decimal(38, min(dscale + 4, 38))
            return DataType.decimal(38, dscale)
        st = a.child.dtype
        if a.fn is AggFn.SUM or a.fn in (
            AggFn.MIN, AggFn.MAX, AggFn.FIRST, AggFn.LAST
        ):
            return st
        if a.fn is AggFn.AVG:
            return DataType.float64()
        return DataType.float64()  # var/stddev
    return infer_dtype(a, in_schema)


def infer_dtype_of(a: AggExpr, schema: Schema) -> DataType:
    return infer_dtype(a.child, schema)


def _empty_global_row(op: HashAggregateExec) -> ColumnBatch:
    """Global aggregate of an empty stream: COUNT=0, others NULL."""
    cols = []
    cap = get_config().shape_buckets[0]
    for field, (a, _) in zip(op.schema.fields, op.aggs):
        phys = field.dtype.physical_dtype()
        shape = (cap, 2) if field.dtype.is_wide_decimal else (cap,)
        v = jnp.zeros(shape, dtype=phys)
        if a.fn in (AggFn.COUNT, AggFn.COUNT_STAR):
            cols.append(Column(field.dtype, v, None, None))
        else:
            cols.append(
                Column(
                    field.dtype, v, jnp.zeros(cap, dtype=jnp.bool_), None
                )
            )
    return ColumnBatch(op.schema, cols, 1)
