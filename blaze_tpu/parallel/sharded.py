"""Mesh-sharded query execution: whole pipeline stages as ONE pjit'd
program over the device mesh.

This is the intra-slice fast path (SURVEY 2.4 TPU mapping): N query
partitions execute simultaneously, one per device on the mesh 'data' axis,
inside a single XLA program; the repartitioning exchange between a partial
and a final aggregate is a `lax.all_to_all` on ICI instead of the
segmented-IPC file shuffle. The file tier (parallel/exchange) remains the
fabric between hosts - this module replaces it only within a slice.

`DistributedGroupBy` is the flagship distributed step: per-shard
filter -> project -> partial sort-based aggregate, hash repartition of the
partial states by group key over ICI, per-shard final merge. One jit, no
host round-trips - the engine's equivalent of a "training step" for
__graft_entry__.dryrun_multichip.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from blaze_tpu.types import DataType, Schema, TypeId
from blaze_tpu.exprs import ir
from blaze_tpu.exprs.optimize import bind_opt
from blaze_tpu.exprs.eval import DeviceEvaluator
from blaze_tpu.exprs.hashing import hash_columns_device, pmod
from blaze_tpu.exprs.ir import AggFn
from blaze_tpu.exprs.typing import infer_dtype
from blaze_tpu.ops.running import running_scan as _running
from blaze_tpu.parallel.repartition import _bucket_live, _bucketize
from blaze_tpu.runtime.dispatch import launch


@dataclasses.dataclass(frozen=True)
class DistAgg:
    fn: AggFn  # SUM / COUNT / COUNT_STAR / MIN / MAX / AVG
    expr: Optional[ir.Expr]  # bound against input schema; None for COUNT_*


# a key's sort flag, two bits a key in one `flags` operand: 0 a value,
# 1 NULL, 2 NaN (a float key's NaNs are one group); a dead slot's flags
# are _DEAD, which sorts last
_NULL, _NAN, _DEAD = 1, 2, 1 << 30


def _reduce_sorted(flags, kvals, states, kinds, n: int):
    """One segmented reduce of `n` slots: sort by (flags, key values),
    so that a group's slots lie together and dead slots last, and
    combine each group's states by their kind. Returns (flags, kvals,
    states, rep) in sorted order; `rep` marks the last slot of each
    group, which holds the group's reduced states.

    The sort carries the keys and a row number only, the states follow
    by a gather: each operand more multiplies the time XLA takes to
    compile a TPU sort (8 s for two operands of 262,144 rows, 36 s for
    four, 123 s for six). An integer sum is a running sum less what it
    was where the group started: no scatter, which on a TPU costs a
    hundred times what the sort of the same rows does (PERF.md, PR
    30). Float sums (a difference of running sums would round) and
    min/max go through `jax.ops.segment_*`."""
    rows = jnp.arange(n, dtype=jnp.int32)
    srt = lax.sort((flags,) + tuple(kvals) + (rows,),
                   num_keys=1 + len(kvals))
    flags, kvals, order = srt[0], list(srt[1:-1]), srt[-1]
    live = flags != _DEAD
    differs = jnp.zeros(n, dtype=jnp.bool_)
    for k in [flags] + kvals:
        differs = differs | jnp.concatenate(
            [jnp.ones(1, jnp.bool_), k[1:] != k[:-1]])
    # a slot that differs from the one before it starts a group; the
    # slot before that one is the last of its group
    rep = live & jnp.concatenate([differs[1:], jnp.ones(1, jnp.bool_)])
    start = _running(jnp.where(differs, rows, 0), lax.cummax)
    gid = None
    out = []
    for s, kind in zip(states, kinds):
        s = jnp.take(s, order)
        if kind == "sum" and jnp.issubdtype(s.dtype, jnp.integer):
            run = _running(s, lax.cumsum)
            out.append(run - jnp.take(run - s, start))
            continue
        if gid is None:
            gid = _running(differs.astype(jnp.int32), lax.cumsum) - 1
        red = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
               "max": jax.ops.segment_max}[kind]
        out.append(jnp.take(red(
            s, gid, num_segments=n, indices_are_sorted=True), gid))
    return flags, kvals, out, rep


def _front(first: jax.Array, classes: int, n: int) -> jax.Array:
    """The order that moves the slots with the lower `first` (one of
    `classes` small non-negative classes a slot) to the front, each
    class in slot order: one sort of one operand, the slot's number in
    the key's low part."""
    if classes * n >= 1 << 31:
        raise NotImplementedError("shards too long for a 32-bit key")
    key = lax.sort(first.astype(jnp.int32) * n
                   + jnp.arange(n, dtype=jnp.int32))
    return key % n


class GroupByResult(NamedTuple):
    """What one launch hands back, every array stacked [n_dev, ...]:
    `keys` and `aggs` are (values, validity or None) pairs with a
    device's groups in its first `counts[d]` slots; `overflow[d]` is
    true where device d had more groups for one target than a bucket
    holds (the result is then short of them: the caller falls back)."""

    keys: list
    aggs: list
    counts: jax.Array
    overflow: jax.Array


def _neutral(kind: str, dtype):
    """What a state of this kind holds for no row at all."""
    if kind == "sum":
        return jnp.zeros((), dtype)
    lo = kind == "max"
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(-jnp.inf if lo else jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.min if lo else info.max, dtype)


class DistributedGroupBy:
    """filter -> project-keys -> partial agg -> ICI repartition -> final.

    All group-key dtypes must be device-hashable (ints/dates/f32/bool);
    string keys go through the file-shuffle tier instead (host hashing).
    Validity goes through the program: a NULL key is a group of its own
    (on whichever device its hash, which skips a NULL, names), a NULL
    input adds nothing, and SUM/MIN/MAX/AVG over no value at all is
    NULL.

    `slack` sizes the exchange's buckets: None keeps the worst case (a
    shard's every group to one target, `cap` slots a bucket); a number
    gives `cap * slack / n_dev`, the expected share times a margin, and
    the launch reports `overflow` where a bucket would not hold its
    groups."""

    def __init__(self, mesh: Mesh, schema: Schema,
                 keys: Sequence[ir.Expr],
                 aggs: Sequence[DistAgg],
                 filter_pred: Optional[ir.Expr] = None,
                 axis: str = "data",
                 slack: Optional[float] = None):
        self.mesh = mesh
        self.axis = axis
        self.schema = schema
        self.slack = slack
        self.keys = [bind_opt(k, schema) for k in keys]
        self.aggs = [
            DistAgg(a.fn, bind_opt(a.expr, schema)
                    if a.expr is not None else None)
            for a in aggs
        ]
        self.filter_pred = (
            bind_opt(filter_pred, schema) if filter_pred is not None else None
        )
        self._fn = None
        self._exec = None  # AOT-compiled executable (prepare())
        self._exec_sig = None
        self._traced_sigs = set()

    # ------------------------------------------------------------------
    @staticmethod
    def signature(*args) -> Tuple:
        """(shape, dtype) of every array argument, None where a
        column brings no validity: what a compiled program is for."""
        return tuple(
            None if a is None else (tuple(a.shape), str(a.dtype))
            for a in jax.tree.leaves(args, is_leaf=lambda x: x is None)
        )

    def bucket_cap(self, cap: int) -> int:
        """Slots of one exchange bucket for shards of `cap` rows."""
        n_dev = self.mesh.shape[self.axis]
        if self.slack is None or n_dev == 1:
            return cap
        return min(cap, -(-int(cap * self.slack) // n_dev))

    def prepare(self, stacked_cols: Sequence[jax.Array],
                rows: jax.Array, valids=None) -> bool:
        """Trace + compile ahead of the launch (jax AOT `lower().compile()`)
        so the caller can time the trace as its own sub-phase. Returns True
        iff a trace actually ran (first time this instance sees this arg
        signature); a warm repeat is a no-op returning False. Where the
        installed jax lacks the AOT path the jitted function stays in
        place and the first launch folds the trace (mesh_trace ~ 0)."""
        args = self._args(stacked_cols, rows, valids)
        sig = self.signature(*args)
        if self._fn is None:
            self._fn = self._compile()
        if sig in self._traced_sigs:
            return False
        self._traced_sigs.add(sig)
        try:
            self._exec = self._fn.lower(*args).compile()
            self._exec_sig = sig
        except Exception:  # noqa: BLE001 - AOT unsupported: trace at launch
            self._exec = None
            self._exec_sig = None
        return True

    @staticmethod
    def _args(stacked_cols, rows, valids):
        cols = list(stacked_cols)
        valids = list(valids) if valids is not None else [None] * len(cols)
        return rows, cols, valids

    def run(self, stacked_cols: Sequence[jax.Array], rows: jax.Array,
            valids=None) -> GroupByResult:
        """stacked_cols: [n_dev, cap] per input column (sharded or
        shardable on axis 0); valids: its validity ([n_dev, cap] bool)
        or None, a column; rows: [n_dev] live rows a shard (its first
        rows) or a [n_dev, cap] bool mask of the live ones."""
        args = self._args(stacked_cols, rows, valids)
        if self._fn is None:
            self._fn = self._compile()
        fn = self._fn
        if self._exec is not None and self._exec_sig == self.signature(*args):
            fn = self._exec
        return GroupByResult(*launch(fn, *args))

    def __call__(self, stacked_cols: Sequence[jax.Array],
                 num_rows: jax.Array):
        """The values alone, for inputs without NULLs: (key_out,
        agg_out, group_counts), stacked [n_dev, ...] with
        group_counts[d] = groups owned by device d."""
        r = self.run(stacked_cols, num_rows)
        return ([k for k, _ in r.keys], [a for a, _ in r.aggs], r.counts)

    # ------------------------------------------------------------------
    def _compile(self):
        mesh, axis = self.mesh, self.axis
        n_dev = mesh.shape[axis]
        schema = self.schema
        keys = self.keys
        aggs = self.aggs
        pred = self.filter_pred
        key_dtypes = [_key_dtype(k, schema) for k in keys]

        def key_operands(key_cvs, live):
            """The keys as sort operands: one `flags` word and a value
            a key, NULLs and NaNs at the value 0 so that equal flags
            compare equal."""
            flags = jnp.zeros(live.shape, jnp.int32)
            kvals = []
            for i, (v, m) in enumerate(key_cvs):
                flag = jnp.zeros(live.shape, jnp.int32)
                if jnp.issubdtype(v.dtype, jnp.floating):
                    flag = jnp.where(jnp.isnan(v), _NAN, flag)
                if m is not None:
                    flag = jnp.where(m, flag, _NULL)
                kvals.append(jnp.where(flag != 0, 0, v).astype(v.dtype))
                flags = flags | (flag << (2 * i))
            return jnp.where(live, flags, _DEAD), kvals

        def key_flag(flags, i):
            return (flags >> (2 * i)) & 3

        def row_states(ev, live):
            """Every row as the partial state of a group of one."""
            states, kinds = [], []

            def put(s, kind):
                states.append(s)
                kinds.append(kind)

            for a in aggs:
                if a.fn is AggFn.COUNT_STAR:
                    put(live.astype(jnp.int64), "sum")
                    continue
                x, m = ev.evaluate(a.expr)
                ok = live if m is None else live & m
                if a.fn is AggFn.COUNT:
                    put(ok.astype(jnp.int64), "sum")
                    continue
                if a.fn in (AggFn.SUM, AggFn.AVG):
                    # accumulate in SUM's result type (int64 /
                    # float64, exprs/typing.py) like the single-device
                    # aggregate: an int32 or f32 column must not wrap
                    # or round in its own width
                    wide = (jnp.float64
                            if jnp.issubdtype(x.dtype, jnp.floating)
                            else jnp.int64)
                    put(jnp.where(ok, x, 0).astype(wide), "sum")
                elif a.fn in (AggFn.MIN, AggFn.MAX):
                    kind = "min" if a.fn is AggFn.MIN else "max"
                    put(jnp.where(ok, x, _neutral(kind, x.dtype)), kind)
                else:
                    raise NotImplementedError(a.fn)
                # the values the state holds: none at all is NULL
                put(ok.astype(jnp.int64), "sum")
            return states, kinds

        def per_shard(rows_s, cols_s, valids_s):
            cols = [
                (c[0], None if m is None else m[0])
                for c, m in zip(cols_s, valids_s)
            ]
            cap = cols[0][0].shape[0]
            ev = DeviceEvaluator(schema, cols, cap)
            if rows_s.ndim == 2:
                live = rows_s[0]
            else:
                live = jnp.arange(cap, dtype=jnp.int32) < rows_s[0]
            if pred is not None:
                live = live & ev.evaluate_predicate(pred)
            states, kinds = row_states(ev, live)
            flags, kvals = key_operands(
                [ev.evaluate(k) for k in keys], live)
            flags, kvals, states, rep = _reduce_sorted(
                flags, kvals, states, kinds, cap)
            # ---- ICI repartition of partial groups by key hash ----
            target = jnp.where(rep, pmod(hash_columns_device(
                [(v, key_flag(flags, i) == 0, dt)
                 for i, (v, dt) in enumerate(zip(kvals, key_dtypes))],
                cap), n_dev), n_dev).astype(jnp.int32)
            bcap = self.bucket_cap(cap)
            order = _front(target, n_dev + 1, cap)
            sends = jnp.stack([
                jnp.sum((target == t).astype(jnp.int32))
                for t in range(n_dev)])
            starts = jnp.cumsum(sends) - sends
            got = lax.all_to_all(sends, axis, 0, 0, tiled=True)
            exchanged = []
            for arr in [flags] + kvals + states:
                # a target's groups lie together: its bucket is a slice
                arr = jnp.concatenate(
                    [jnp.take(arr, order), jnp.zeros(bcap, arr.dtype)])
                b = jnp.stack([
                    lax.dynamic_slice(arr, (starts[t],), (bcap,))
                    for t in range(n_dev)])
                exchanged.append(lax.all_to_all(
                    b, axis, 0, 0, tiled=True).reshape(n_dev * bcap))
            # ---- final merge on the owning shard ----
            big = n_dev * bcap
            live_rx = (jnp.arange(bcap, dtype=jnp.int32)[None, :]
                       < jnp.minimum(got, bcap)[:, None]).reshape(big)
            flags = jnp.where(live_rx, exchanged[0], _DEAD)
            kvals = exchanged[1:1 + len(keys)]
            states = [
                jnp.where(live_rx, s, _neutral(kind, s.dtype))
                for s, kind in zip(exchanged[1 + len(keys):], kinds)
            ]
            flags, kvals, fs, rep = _reduce_sorted(
                flags, kvals, states, kinds, big)
            # the groups to the front, in key order
            order = _front(~rep, 2, big)
            flags = jnp.take(flags, order)
            kvals = [jnp.take(v, order) for v in kvals]
            fs = [jnp.take(s, order) for s in fs]
            key_out = []
            for i, v in enumerate(kvals):
                if jnp.issubdtype(v.dtype, jnp.floating):
                    v = jnp.where(key_flag(flags, i) == _NAN, jnp.nan, v)
                key_out.append(
                    (v[None, :], (key_flag(flags, i) != _NULL)[None, :]))
            agg_out = []
            si = 0
            for a in aggs:
                if a.fn in (AggFn.COUNT, AggFn.COUNT_STAR):
                    agg_out.append((fs[si][None, :], None))
                    si += 1
                    continue
                v, some = fs[si], fs[si + 1] > 0
                if a.fn is AggFn.AVG:
                    v = (v.astype(jnp.float64)
                         / jnp.maximum(fs[si + 1], 1).astype(jnp.float64))
                agg_out.append((v[None, :], some[None, :]))
                si += 2
            return (key_out, agg_out,
                    jnp.sum(rep.astype(jnp.int32))[None],
                    (jnp.max(sends) > bcap)[None])

        def mesh_groupby(rows, cols, valids):
            # every input and every output is sharded on its first axis
            return shard_map(
                per_shard, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
            )(rows, cols, valids)

        return jax.jit(mesh_groupby)


class DistributedBroadcastJoin:
    """Mesh-wide broadcast equi-join against a unique-key build side.

    The intra-slice analog of the broadcast hash join (reference BHJ /
    CollectLeft): the build relation is sharded over the mesh, replicated
    to every device with ONE lax.all_gather over ICI, sorted once, and
    each shard probes its rows with searchsorted - all inside a single
    pjit program, no host round trips. Build keys must be unique (the
    dimension-table case: every probe row matches at most one build row),
    which keeps output shapes static; general many-match joins go through
    the host-tier join (ops/joins.py).
    """

    def __init__(self, mesh: Mesh, probe_schema: Schema,
                 build_schema: Schema, probe_key: ir.Expr,
                 build_key: ir.Expr, axis: str = "data"):
        self.mesh = mesh
        self.axis = axis
        self.probe_schema = probe_schema
        self.build_schema = build_schema
        self.probe_key = bind_opt(probe_key, probe_schema)
        self.build_key = bind_opt(build_key, build_schema)
        self._fn = None
        self._exec = None  # AOT-compiled executable (prepare())
        self._exec_sig = None
        self._traced_sigs = set()

    @staticmethod
    def _sig(probe_cols, probe_rows, build_cols, build_rows) -> Tuple:
        return (
            tuple((tuple(c.shape), str(c.dtype)) for c in probe_cols),
            (tuple(probe_rows.shape), str(probe_rows.dtype)),
            tuple((tuple(c.shape), str(c.dtype)) for c in build_cols),
            (tuple(build_rows.shape), str(build_rows.dtype)),
        )

    def prepare(self, probe_cols, probe_rows, build_cols,
                build_rows) -> bool:
        """AOT trace+compile (see DistributedGroupBy.prepare): True iff
        a trace actually ran for this argument signature."""
        sig = self._sig(probe_cols, probe_rows, build_cols, build_rows)
        if self._fn is None:
            self._fn = self._compile()
        if sig in self._traced_sigs:
            return False
        self._traced_sigs.add(sig)
        try:
            self._exec = self._fn.lower(
                probe_cols, probe_rows, build_cols, build_rows
            ).compile()
            self._exec_sig = sig
        except Exception:  # noqa: BLE001 - AOT unsupported: trace at launch
            self._exec = None
            self._exec_sig = None
        return True

    def __call__(self, probe_cols, probe_rows, build_cols, build_rows):
        """probe_cols/build_cols: [n_dev, cap] stacked arrays per column;
        *_rows: [n_dev] live counts. Returns (probe_cols, matched mask,
        gathered build cols) all stacked [n_dev, cap_probe]."""
        if self._fn is None:
            self._fn = self._compile()
        fn = self._fn
        if (self._exec is not None and self._exec_sig == self._sig(
                probe_cols, probe_rows, build_cols, build_rows)):
            fn = self._exec
        return launch(fn, probe_cols, probe_rows, build_cols, build_rows)

    def _compile(self):
        mesh, axis = self.mesh, self.axis
        n_dev = mesh.shape[axis]
        p_schema, b_schema = self.probe_schema, self.build_schema
        p_key, b_key = self.probe_key, self.build_key

        def per_shard(p_rows_s, b_rows_s, *cols_s):
            np_cols = len(p_schema)
            p_cols = [c[0] for c in cols_s[:np_cols]]
            b_cols = [c[0] for c in cols_s[np_cols:]]
            p_cap = p_cols[0].shape[0]
            b_cap = b_cols[0].shape[0]
            # replicate the build side over ICI
            g_cols = [
                lax.all_gather(c, axis).reshape(n_dev * b_cap)
                for c in b_cols
            ]
            b_live_local = jnp.arange(b_cap, dtype=jnp.int32) < b_rows_s[0]
            g_live = lax.all_gather(b_live_local, axis).reshape(
                n_dev * b_cap
            )
            ev_b = DeviceEvaluator(
                b_schema, [(c, None) for c in g_cols], n_dev * b_cap
            )
            bk, _ = ev_b.evaluate(b_key)
            # dead rows take the dtype-max sentinel so the array stays
            # GLOBALLY sorted (searchsorted requires it; sorting dead rows
            # last by a separate rank key would break that invariant)
            if jnp.issubdtype(bk.dtype, jnp.floating):
                sentinel = jnp.asarray(jnp.inf, bk.dtype)
            else:
                sentinel = jnp.asarray(jnp.iinfo(bk.dtype).max, bk.dtype)
            bk_keyed = jnp.where(g_live, bk, sentinel)
            order = jnp.argsort(bk_keyed, stable=True)
            bk_sorted = jnp.take(bk_keyed, order)
            n_build = jnp.sum(g_live.astype(jnp.int32))
            ev_p = DeviceEvaluator(
                p_schema, [(c, None) for c in p_cols], p_cap
            )
            pk, _ = ev_p.evaluate(p_key)
            pos = jnp.searchsorted(bk_sorted, pk)
            pos = jnp.clip(pos, 0, n_dev * b_cap - 1)
            hit = (jnp.take(bk_sorted, pos) == pk) & (pos < n_build)
            p_live = jnp.arange(p_cap, dtype=jnp.int32) < p_rows_s[0]
            hit = hit & p_live
            build_idx = jnp.take(order, pos)
            out_build = [
                jnp.take(g, build_idx)[None] for g in g_cols
            ]
            return (hit[None],) + tuple(out_build)

        n_out = 1 + len(b_schema)
        fn = shard_map(
            per_shard, mesh=mesh,
            in_specs=(P(axis), P(axis))
            + tuple(P(axis) for _ in range(len(p_schema)))
            + tuple(P(axis) for _ in range(len(b_schema))),
            out_specs=tuple([P(axis)] * n_out),
        )

        @jax.jit
        def run(probe_cols, probe_rows, build_cols, build_rows):
            outs = fn(
                probe_rows, build_rows, *probe_cols, *build_cols
            )
            return outs[0], list(outs[1:])

        return run


def _key_dtype(e: ir.Expr, schema: Schema) -> DataType:
    dt = infer_dtype(e, schema)
    if dt.is_dictionary_encoded:
        raise NotImplementedError(
            "string group keys use the file-shuffle tier"
        )
    return dt


class DistributedRepartition:
    """Hash repartition of whole rows over ICI: every live row moves to
    the device its key hash owns with one `lax.all_to_all` per column -
    the mesh-native form of the hash ShuffleExchange (what Spark plants
    under a window's PARTITION BY), carrying the FULL row instead of
    partial aggregate states. Same program-holder shape as
    DistributedGroupBy (prepare() returns True only on a real trace),
    so it plugs into the fingerprint-keyed program cache.

    Output shards are [n_dev * cap] column stacks plus a live mask per
    shard; the caller compacts live rows host-side at the mesh
    boundary. Skew bound: a device receiving more than `cap` rows from
    any single sender overflows its fixed bucket; callers size cap from
    the stacked input (every sender holds <= cap live rows), which is
    always sufficient because a sender contributes at most its own cap
    to any one destination."""

    def __init__(self, mesh: Mesh, schema: Schema,
                 keys: Sequence[ir.Expr], axis: str = "data"):
        self.mesh = mesh
        self.axis = axis
        self.schema = schema
        self.keys = [bind_opt(k, schema) for k in keys]
        for k in self.keys:
            _key_dtype(k, schema)  # raises for non-device-hashable keys
        self._fn = None
        self._exec = None
        self._exec_sig = None
        self._traced_sigs = set()

    def _sig(self, stacked_cols, num_rows) -> Tuple:
        return (
            tuple((tuple(c.shape), str(c.dtype)) for c in stacked_cols),
            (tuple(num_rows.shape), str(num_rows.dtype)),
        )

    def prepare(self, stacked_cols: Sequence[jax.Array],
                num_rows: jax.Array) -> bool:
        sig = self._sig(stacked_cols, num_rows)
        if self._fn is None:
            self._fn = self._compile()
        if sig in self._traced_sigs:
            return False
        self._traced_sigs.add(sig)
        try:
            self._exec = self._fn.lower(
                *stacked_cols, num_rows
            ).compile()
            self._exec_sig = sig
        except Exception:  # noqa: BLE001 - AOT unsupported: trace at launch
            self._exec = None
            self._exec_sig = None
        return True

    def __call__(self, stacked_cols: Sequence[jax.Array],
                 num_rows: jax.Array):
        """stacked_cols: [n_dev, cap] per column; num_rows: [n_dev].
        Returns (out_cols, live): out_cols are [n_dev, n_dev * cap]
        stacks, live the matching row mask."""
        if self._fn is None:
            self._fn = self._compile()
        fn = self._fn
        if (self._exec is not None
                and self._exec_sig == self._sig(stacked_cols, num_rows)):
            fn = self._exec
        return launch(fn, *stacked_cols, num_rows)

    def _compile(self):
        mesh, axis = self.mesh, self.axis
        n_dev = mesh.shape[axis]
        schema = self.schema
        keys = self.keys
        n_cols = len(schema.fields)

        def per_shard(num_rows_s, *cols_s):
            cols = [c[0] for c in cols_s]
            nr = num_rows_s[0]
            cap = cols[0].shape[0]
            live = jnp.arange(cap, dtype=jnp.int32) < nr
            ev = DeviceEvaluator(
                schema, [(c, None) for c in cols], cap
            )
            key_vals = [ev.evaluate(k)[0] for k in keys]
            kcols = [
                (v, None, _key_dtype(keys[i], schema))
                for i, v in enumerate(key_vals)
            ]
            target = pmod(hash_columns_device(kcols, cap), n_dev)
            exchanged = []
            for arr in cols:
                b = _bucketize(arr, target, live, n_dev, cap)
                ex = lax.all_to_all(
                    b[None], axis, split_axis=1, concat_axis=0
                )
                exchanged.append(ex.reshape(n_dev * cap))
            lv = _bucket_live(target, live, n_dev, cap)
            lx = lax.all_to_all(
                lv[None], axis, split_axis=1, concat_axis=0
            ).reshape(n_dev * cap)
            return (
                tuple(c[None, :] for c in exchanged) + (lx[None, :],)
            )

        fn = shard_map(
            per_shard, mesh=mesh,
            in_specs=(P(axis),) + tuple(P(axis) for _ in range(n_cols)),
            out_specs=tuple([P(axis)] * (n_cols + 1)),
        )

        @jax.jit
        def run(*args):
            num_rows = args[-1]
            cols = args[:-1]
            outs = fn(num_rows, *cols)
            return list(outs[:-1]), outs[-1]

        return run
