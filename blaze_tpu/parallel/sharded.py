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
from typing import List, Optional, Sequence, Tuple

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
from blaze_tpu.parallel.repartition import _bucket_live, _bucketize


@dataclasses.dataclass(frozen=True)
class DistAgg:
    fn: AggFn  # SUM / COUNT / COUNT_STAR / MIN / MAX / AVG
    expr: Optional[ir.Expr]  # bound against input schema; None for COUNT_*


class DistributedGroupBy:
    """filter -> project-keys -> partial agg -> ICI repartition -> final.

    All group-key dtypes must be device-hashable (ints/dates/f32/bool);
    string keys go through the file-shuffle tier instead (host hashing).
    """

    def __init__(self, mesh: Mesh, schema: Schema,
                 keys: Sequence[ir.Expr],
                 aggs: Sequence[DistAgg],
                 filter_pred: Optional[ir.Expr] = None,
                 axis: str = "data"):
        self.mesh = mesh
        self.axis = axis
        self.schema = schema
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
    def _sig(self, stacked_cols, num_rows) -> Tuple:
        return (
            tuple((tuple(c.shape), str(c.dtype)) for c in stacked_cols),
            (tuple(num_rows.shape), str(num_rows.dtype)),
        )

    def prepare(self, stacked_cols: Sequence[jax.Array],
                num_rows: jax.Array) -> bool:
        """Trace + compile ahead of the launch (jax AOT `lower().compile()`)
        so the caller can time the trace as its own sub-phase. Returns True
        iff a trace actually ran (first time this instance sees this arg
        signature); a warm repeat is a no-op returning False. Where the
        installed jax lacks the AOT path the jitted function stays in
        place and the first launch folds the trace (mesh_trace ~ 0)."""
        sig = self._sig(stacked_cols, num_rows)
        if self._fn is None:
            self._fn = self._compile(
                tuple(c.shape for c in stacked_cols),
                tuple(c.dtype for c in stacked_cols),
            )
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
        """stacked_cols: [n_dev, cap] per input column (sharded or
        shardable on axis 0); num_rows: [n_dev] live rows per shard.
        Returns (key_out, agg_out, group_counts): stacked [n_dev, ...] with
        group_counts[d] = groups owned by device d."""
        if self._fn is None:
            self._fn = self._compile(
                tuple(c.shape for c in stacked_cols),
                tuple(c.dtype for c in stacked_cols),
            )
        if (self._exec is not None
                and self._exec_sig == self._sig(stacked_cols, num_rows)):
            return self._exec(*stacked_cols, num_rows)
        return self._fn(*stacked_cols, num_rows)

    # ------------------------------------------------------------------
    def _compile(self, shapes, dtypes):
        mesh, axis = self.mesh, self.axis
        n_dev = mesh.shape[axis]
        schema = self.schema
        keys = self.keys
        aggs = self.aggs
        pred = self.filter_pred
        n_keys = len(keys)

        def group_reduce(key_vals: List[jax.Array],
                         agg_ins: List[jax.Array],
                         live: jax.Array, cap: int):
            """Sort-based segmented reduce of one shard's rows.

            Returns (sorted key cols at boundaries, reduced states,
            n_groups, live_groups mask)."""
            pri = [jnp.where(live, 0, 1).astype(jnp.int8)]
            for k in key_vals:
                if jnp.issubdtype(k.dtype, jnp.floating):
                    pri.append(jnp.where(jnp.isnan(k), jnp.inf, k))
                    pri.append(jnp.isnan(k).astype(jnp.int8))
                else:
                    pri.append(k)
            order = jnp.lexsort(tuple(reversed(pri)))
            s_live = jnp.take(live, order)
            diff = jnp.zeros(cap, dtype=jnp.bool_)
            s_keys = []
            for k in key_vals:
                sk = jnp.take(k, order)
                s_keys.append(sk)
                if jnp.issubdtype(k.dtype, jnp.floating):
                    # NaN groups with NaN, distinct from real +inf
                    nf = jnp.take(jnp.isnan(k).astype(jnp.int8), order)
                    cv = jnp.where(jnp.isnan(sk), jnp.inf, sk)
                    diff = diff | (
                        cv != jnp.concatenate([cv[:1], cv[:-1]])
                    ) | (nf != jnp.concatenate([nf[:1], nf[:-1]]))
                else:
                    diff = diff | (
                        sk != jnp.concatenate([sk[:1], sk[:-1]])
                    )
            first = s_live & ~jnp.concatenate(
                [jnp.zeros(1, dtype=jnp.bool_), s_live[:-1]]
            )
            boundary = s_live & (diff | first)
            gid = jnp.cumsum(boundary.astype(jnp.int32)) - 1
            gid = jnp.where(s_live, gid, cap - 1)
            n_groups = jnp.sum(boundary.astype(jnp.int32))
            bpos = jnp.nonzero(boundary, size=cap, fill_value=0)[0]
            out_keys = [jnp.take(sk, bpos) for sk in s_keys]
            states = []
            for (a, x) in zip(aggs, agg_ins):
                sx = jnp.take(x, order) if x is not None else None
                if a.fn in (AggFn.COUNT, AggFn.COUNT_STAR):
                    states.append(
                        jax.ops.segment_sum(
                            s_live.astype(jnp.int64), gid,
                            num_segments=cap,
                        )
                    )
                elif a.fn in (AggFn.SUM, AggFn.AVG):
                    # accumulate in SUM's result type (int64 /
                    # float64, exprs/typing.py) like the single-device
                    # aggregate: an int32 or f32 column must not wrap
                    # or round in its own width
                    wide = (jnp.float64
                            if jnp.issubdtype(sx.dtype, jnp.floating)
                            else jnp.int64)
                    v = jnp.where(s_live, sx, jnp.zeros_like(sx)).astype(
                        wide)
                    states.append(
                        jax.ops.segment_sum(v, gid, num_segments=cap)
                    )
                    if a.fn is AggFn.AVG:
                        states.append(
                            jax.ops.segment_sum(
                                s_live.astype(jnp.int64), gid,
                                num_segments=cap,
                            )
                        )
                elif a.fn in (AggFn.MIN, AggFn.MAX):
                    if jnp.issubdtype(sx.dtype, jnp.floating):
                        neutral = jnp.inf if a.fn is AggFn.MIN else -jnp.inf
                    else:
                        info = jnp.iinfo(sx.dtype)
                        neutral = (
                            info.max if a.fn is AggFn.MIN else info.min
                        )
                    v = jnp.where(s_live, sx, jnp.asarray(neutral, sx.dtype))
                    red = (jax.ops.segment_min if a.fn is AggFn.MIN
                           else jax.ops.segment_max)
                    states.append(red(v, gid, num_segments=cap))
                else:
                    raise NotImplementedError(a.fn)
            live_groups = jnp.arange(cap, dtype=jnp.int32) < n_groups
            return out_keys, states, n_groups, live_groups

        def merge_reduce(key_vals, states_in, live, cap):
            """Final merge: same grouping, states combine by their merge op
            (sum for SUM/COUNT/AVG parts, min/max for MIN/MAX)."""
            pri = [jnp.where(live, 0, 1).astype(jnp.int8)]
            for k in key_vals:
                if jnp.issubdtype(k.dtype, jnp.floating):
                    pri.append(jnp.where(jnp.isnan(k), jnp.inf, k))
                    pri.append(jnp.isnan(k).astype(jnp.int8))
                else:
                    pri.append(k)
            order = jnp.lexsort(tuple(reversed(pri)))
            s_live = jnp.take(live, order)
            diff = jnp.zeros(cap, dtype=jnp.bool_)
            s_keys = []
            for k in key_vals:
                sk = jnp.take(k, order)
                s_keys.append(sk)
                if jnp.issubdtype(k.dtype, jnp.floating):
                    # NaN groups with NaN, distinct from real +inf
                    nf = jnp.take(jnp.isnan(k).astype(jnp.int8), order)
                    cv = jnp.where(jnp.isnan(sk), jnp.inf, sk)
                    diff = diff | (
                        cv != jnp.concatenate([cv[:1], cv[:-1]])
                    ) | (nf != jnp.concatenate([nf[:1], nf[:-1]]))
                else:
                    diff = diff | (
                        sk != jnp.concatenate([sk[:1], sk[:-1]])
                    )
            first = s_live & ~jnp.concatenate(
                [jnp.zeros(1, dtype=jnp.bool_), s_live[:-1]]
            )
            boundary = s_live & (diff | first)
            gid = jnp.cumsum(boundary.astype(jnp.int32)) - 1
            gid = jnp.where(s_live, gid, cap - 1)
            n_groups = jnp.sum(boundary.astype(jnp.int32))
            bpos = jnp.nonzero(boundary, size=cap, fill_value=0)[0]
            out_keys = [jnp.take(sk, bpos) for sk in s_keys]
            out_states = []
            si = 0
            for a in aggs:
                width = 2 if a.fn is AggFn.AVG else 1
                for w in range(width):
                    x = jnp.take(states_in[si], order)
                    if a.fn in (AggFn.MIN, AggFn.MAX) and w == 0:
                        if jnp.issubdtype(x.dtype, jnp.floating):
                            neutral = (jnp.inf if a.fn is AggFn.MIN
                                       else -jnp.inf)
                        else:
                            info = jnp.iinfo(x.dtype)
                            neutral = (info.max if a.fn is AggFn.MIN
                                       else info.min)
                        v = jnp.where(s_live, x,
                                      jnp.asarray(neutral, x.dtype))
                        red = (jax.ops.segment_min if a.fn is AggFn.MIN
                               else jax.ops.segment_max)
                        out_states.append(
                            red(v, gid, num_segments=cap)
                        )
                    else:
                        v = jnp.where(s_live, x, jnp.zeros_like(x))
                        out_states.append(
                            jax.ops.segment_sum(v, gid, num_segments=cap)
                        )
                    si += 1
            return out_keys, out_states, n_groups

        def per_shard(num_rows_s, *cols_s):
            cols = [c[0] for c in cols_s]
            nr = num_rows_s[0]
            cap = cols[0].shape[0]
            ev = DeviceEvaluator(
                schema, [(c, None) for c in cols], cap
            )
            live = jnp.arange(cap, dtype=jnp.int32) < nr
            if pred is not None:
                live = live & ev.evaluate_predicate(pred)
            key_vals = [ev.evaluate(k)[0] for k in keys]
            agg_ins = [
                ev.evaluate(a.expr)[0] if a.expr is not None else None
                for a in aggs
            ]
            out_keys, states, _, live_g = group_reduce(
                key_vals, agg_ins, live, cap
            )
            # ---- ICI repartition of partial groups by key hash ----
            kcols = [
                (k, None, _key_dtype(keys[i], schema))
                for i, k in enumerate(out_keys)
            ]
            target = pmod(hash_columns_device(kcols, cap), n_dev)
            payload = out_keys + states
            exchanged = []
            for arr in payload:
                b = _bucketize(arr, target, live_g, n_dev, cap)
                ex = lax.all_to_all(
                    b[None], axis, split_axis=1, concat_axis=0
                )
                exchanged.append(ex.reshape(n_dev * cap))
            lv = _bucket_live(target, live_g, n_dev, cap)
            lx = lax.all_to_all(
                lv[None], axis, split_axis=1, concat_axis=0
            ).reshape(n_dev * cap)
            # ---- final merge on the owning shard ----
            big = n_dev * cap
            fk, fs, ng = merge_reduce(
                exchanged[:n_keys], exchanged[n_keys:], lx, big
            )
            # finalize AVG into a float column
            final_cols = []
            si = 0
            for a in aggs:
                if a.fn is AggFn.AVG:
                    s, c = fs[si], fs[si + 1]
                    final_cols.append(
                        s.astype(jnp.float64)
                        / jnp.maximum(c, 1).astype(jnp.float64)
                    )
                    si += 2
                else:
                    final_cols.append(fs[si])
                    si += 1
            return (
                tuple(k[None, :] for k in fk)
                + tuple(c[None, :] for c in final_cols)
                + (ng[None],)
            )

        n_out = n_keys + len(aggs) + 1
        fn = shard_map(
            per_shard, mesh=mesh,
            in_specs=(P(axis),) + tuple(P(axis) for _ in shapes),
            out_specs=tuple([P(axis)] * n_out),
        )

        @jax.jit
        def run(*args):
            num_rows = args[-1]
            cols = args[:-1]
            outs = fn(num_rows, *cols)
            return (
                list(outs[:n_keys]),
                list(outs[n_keys:-1]),
                outs[-1],
            )

        return run


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
        if (self._exec is not None and self._exec_sig == self._sig(
                probe_cols, probe_rows, build_cols, build_rows)):
            return self._exec(
                probe_cols, probe_rows, build_cols, build_rows
            )
        return self._fn(probe_cols, probe_rows, build_cols, build_rows)

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
        if (self._exec is not None
                and self._exec_sig == self._sig(stacked_cols, num_rows)):
            return self._exec(*stacked_cols, num_rows)
        return self._fn(*stacked_cols, num_rows)

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
