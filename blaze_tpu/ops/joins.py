"""Equi-joins: broadcast hash join and sort-merge join.

Reference counterparts: DataFusion HashJoinExec CollectLeft (from_proto.rs:
349-428, wrapper NativeBroadcastHashJoinExec.scala:96-123) and the custom
streaming SortMergeJoinExec (sort_merge_join_exec.rs, 1897 LoC incl. 20
tests; wrapper NativeSortMergeJoinExec.scala:87-121). Join conditions are
not evaluated inside the join - the Spark-side converter plants a
NativeFilter above (BlazeConverters.scala:244-301) - and we keep that
contract.

TPU-first core (SURVEY 7 "hard parts"): instead of row-at-a-time hash
probing / single-row merge cursors, both joins share one vectorized kernel:

  1. unify string-key dictionaries (host) so key equality == code equality
  2. hash build keys on device (any consistent hash works intra-engine;
     uses the murmur3 lanes), sort build rows by hash
  3. per probe row, binary-search the sorted hash run [lo, hi)
  4. expand candidate pairs by run length (one cumsum + gather, static
     output capacity; one host sync for the pair count)
  5. verify true key equality (hash collisions + NULL keys never match)
  6. outer/semi/anti variants come from matched-flag segment reductions

The sorted-input property of SMJ inputs is exploited by sorting only once
per partition; output order follows the streamed (left) side like the
reference's streaming merge.
"""

from __future__ import annotations

import enum
from functools import partial
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from blaze_tpu.config import get_config
from blaze_tpu.types import DataType, Field, Schema, TypeId
from blaze_tpu.batch import Column, ColumnBatch, row_mask
from blaze_tpu.exprs import ir
from blaze_tpu.exprs.hashing import hash_columns_device
from blaze_tpu.obs import trace as obs_trace
from blaze_tpu.ops.base import ExecContext, PhysicalOp
from blaze_tpu.ops.util import (
    compact,
    concat_batches,
    ensure_compacted,
    take_batch,
)
from blaze_tpu.runtime.dispatch import cached_kernel, host_int
from blaze_tpu.runtime.dispatch import count as _count


class JoinType(enum.Enum):
    INNER = "inner"
    LEFT = "left"
    RIGHT = "right"
    FULL = "full"
    LEFT_SEMI = "left_semi"
    LEFT_ANTI = "left_anti"
    # Spark's NOT IN semantics (null-aware anti join): if the build side
    # contains any NULL key the result is empty; probe rows with NULL keys
    # never qualify either
    LEFT_ANTI_NULL_AWARE = "left_anti_null_aware"


def _joined_schema(left: Schema, right: Schema, jt: JoinType) -> Schema:
    if jt in (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI,
              JoinType.LEFT_ANTI_NULL_AWARE):
        return left
    nullable_left = jt in (JoinType.RIGHT, JoinType.FULL)
    nullable_right = jt in (JoinType.LEFT, JoinType.FULL)
    fields = [
        Field(f.name, f.dtype, f.nullable or nullable_left) for f in left
    ] + [
        Field(f.name, f.dtype, f.nullable or nullable_right) for f in right
    ]
    return Schema(fields)


def _unify_key_pair(bcol: Column, pcol: Column) -> Tuple[Column, Column]:
    """Remap a (build, probe) string key pair onto one dictionary."""
    import pyarrow as pa
    import pyarrow.compute as pc

    if not bcol.dtype.is_dictionary_encoded:
        return bcol, pcol
    bd = bcol.dictionary if bcol.dictionary is not None else pa.array(
        [], type=pa.utf8())
    pd_ = pcol.dictionary if pcol.dictionary is not None else pa.array(
        [], type=pa.utf8())
    unified = pa.concat_arrays(
        [bd.cast(pa.utf8()), pd_.cast(pa.utf8())]
    ).unique()

    def remap(col: Column, old) -> Column:
        if len(old) == 0:
            return Column(col.dtype, col.values, col.validity, unified)
        mapping = np.asarray(
            pc.index_in(old, value_set=unified).fill_null(0)
        ).astype(np.int32)
        codes = jnp.take(
            jnp.asarray(mapping),
            jnp.clip(col.values, 0, len(mapping) - 1),
            axis=0,
        )
        return Column(col.dtype, codes, col.validity, unified)

    return remap(bcol, bd), remap(pcol, pd_)


def _key_hash_cols(cols: List[Column]) -> List[Tuple]:
    """(values, validity, dtype) triples for device hashing; string codes
    hash as int32 (valid intra-engine: equality is code equality after
    dictionary unification)."""
    out = []
    for c in cols:
        if c.dtype.is_wide_decimal:
            raise NotImplementedError(
                "join keys of decimal(>18) are host-tier work"
            )
        dt = c.dtype
        if dt.is_dictionary_encoded:
            dt = DataType.int32()
        if dt.id is TypeId.FLOAT64:
            # avoid the TPU f64-bitcast limitation inside joins: compare
            # hashes of the f32 narrowing only as a *bucketing* step - true
            # equality is verified on the full values afterwards
            out.append(
                (c.values.astype(jnp.float32), c.validity,
                 DataType.float32())
            )
        else:
            out.append((c.values, c.validity, dt))
    return out


def _join_core_choice(backend: Optional[str] = None) -> str:
    """Join-core knob (config.join_core / env BLAZE_JOIN_CORE):
    "scatter" (every table core), "sort", or "direct", what `auto`
    takes off the CPU: the direct key->row array where the build
    qualifies, else the sort core."""
    from blaze_tpu.config import resolve_core_choice

    return resolve_core_choice(
        "BLAZE_JOIN_CORE", get_config().join_core, chip="direct",
        backend=backend,
    )


class _JoinCore:
    """Shared vectorized equi-join over one materialized build batch.

    Two cores behind one interface:

    - "table" (unique build keys): the build relation inserts into an
      open-addressing hash table (ops/hash_table.py, one bounded
      scatter/gather probe loop); each probe batch then runs ONE lookup
      kernel (no sort, no searchsorted, no pair expansion, and NO
      blocking host sync - output capacity is statically the probe
      capacity) and ONE emission kernel that only gathers the build
      side: probe columns pass through untouched. Duplicate build keys
      are detected at insert time (one scalar sync per build relation)
      and demote to the sorted core. Off the CPU only the direct
      key->row array of this core is taken (`_join_core_choice`
      "direct"): one unique integer key column whose span fits 1 << 24
      slots; every other build takes the sorted core.
    - "sorted": build rows sort by key hash; per probe batch ONE
      counting kernel + ONE blocking scalar readback (the dynamic pair
      count picks the static output bucket) + ONE emission kernel that
      expands candidate runs, verifies equality and gathers both sides.

    Either way the dispatch budget per probe batch is O(1) kernels
    (the per-dispatch cost model of runtime/dispatch.py) - instead of the ~20
    eager ops a naive translation of the reference's cursor loop
    would dispatch. Whichever core runs, a probe batch's programs are
    named `join_probe` (the lookup, or the counting kernel) and
    `join_emit` on the device (`jit_join_probe`, `jit_join_emit` on a
    profiler trace's module line), the build side's `join_index`."""

    def __init__(self, build: ColumnBatch, build_keys: List[int]):
        import threading

        self.build = build
        self.build_keys = build_keys
        self.matched_build = jnp.zeros(build.capacity, dtype=jnp.bool_)
        self._index = None
        # a core may be shared across concurrently executing probe
        # partitions (fused.py caches it on the join op); index
        # (re)builds and downgrades mutate self._index, so they run
        # under this lock and readers capture a local snapshot
        self._index_lock = threading.Lock()
        # remembered demotion: duplicate build keys mean the table core
        # can never apply to this build relation - don't re-attempt (and
        # re-pay the insert pass + blocking dup sync) per probe batch
        # when dictionary-encoded keys force an index rebuild
        self._table_demoted = False
        # kr -> generic downgrade (probe key wider than the 32-bit kr
        # encoding); remembered for the same reason
        self._force_generic = False

    def index_build(self) -> None:
        """Build the index before the first probe batch, where it does
        not depend on the probe (no build key is dictionary-encoded:
        those are re-coded against each probe batch's dictionary)."""
        cols = [self.build.columns[i] for i in self.build_keys]
        if any(c.dtype.is_dictionary_encoded for c in cols):
            return
        with self._index_lock:
            self._ensure_index(cols)

    def _ensure_index(self, build_cols: List[Column]):
        # the index is probe-invariant unless a build key is
        # dictionary-encoded (dictionary unification re-maps build codes
        # per probe batch); cache it so multi-batch probes pay the index
        # kernel once
        if self._index is not None and not any(
            c.dtype.is_dictionary_encoded for c in build_cols
        ):
            return
        cap = self.build.capacity
        choice = _join_core_choice()

        # one eligibility decision for both table attempts below: when
        # True, the first block always runs and defines eq_layout /
        # kr / ht for the second
        tables_ok = (
            not self._table_demoted
            and choice != "sort"
            # wide-decimal keys are host-tier work either way; the
            # sorted path below carries the NotImplementedError guard
            and not any(c.dtype.is_wide_decimal for c in build_cols)
        )
        if tables_ok:
            from blaze_tpu.ops import hash_table as ht

            eq_layout = _eq_layout(build_cols)
            kr = _kr_eligible(build_cols) and not self._force_generic

            # dense-domain dimension keys (TPC-DS surrogate keys are
            # near-contiguous ints; Spark's LongHashedRelation has the
            # same dense-array fast path): replace the hash table with
            # a direct key->row array. Probing drops from hash + probe
            # rounds over an 8x-oversized u64 table to ONE gather into
            # a 4-byte-per-slot array that fits in L2 (measured at
            # 131k keys / 8M probes on XLA:CPU: 398ms -> 47ms). Off the
            # CPU it is the only table core (`direct`), for an integer
            # key of any width.
            if (
                (kr or choice == "direct")
                and not self._force_generic
                and len(build_cols) == 1
                and jnp.issubdtype(
                    build_cols[0].values.dtype, jnp.integer
                )
                # dictionary-encoded keys rebuild the index per probe
                # batch (per-batch code unification): the extra kmin/
                # kmax host sync per batch would outweigh the direct
                # table's probe win where each sync stalls the host
                and not build_cols[0].dtype.is_dictionary_encoded
                and int(self.build.num_rows) > 0
            ):
                def build_span():
                    def join_keyspan(eq_bufs, num_rows):
                        live = (
                            jnp.arange(cap, dtype=jnp.int32) < num_rows
                        )
                        ((v, m),) = _unflatten_eq(eq_layout, eq_bufs)
                        if m is not None:
                            live = live & m
                        info = jnp.iinfo(v.dtype)
                        kmin = jnp.min(jnp.where(live, v, info.max))
                        kmax = jnp.max(jnp.where(live, v, info.min))
                        return jnp.stack(
                            [kmin.astype(jnp.int64),
                             kmax.astype(jnp.int64)]
                        )

                    return join_keyspan

                span_fn = cached_kernel(
                    ("join_keyspan", eq_layout, cap), build_span
                )
                kmin, kmax = (
                    int(x) for x in np.asarray(
                        span_fn(
                            _flatten_cols(build_cols),
                            self.build.num_rows,
                        )
                    )
                )
                span = kmax - kmin + 1
                nrows = int(self.build.num_rows)
                # on the CPU sparse domains would waste memory and
                # cache: beyond 8x the row count (or 16M slots) the u64
                # table wins. HBM gathers have no L2 to fall out of, so
                # off the CPU the 16M slots (64 MiB) alone bound it
                limit = 1 << 24
                if choice != "direct":
                    limit = min(limit, max(4096, 8 * nrows))
                if 0 < span <= limit:
                    tsize_d = ht.direct_table_size(span)

                    def build_direct():
                        def join_index(eq_bufs, base, num_rows):
                            live = (
                                jnp.arange(cap, dtype=jnp.int32)
                                < num_rows
                            )
                            ((v, m),) = _unflatten_eq(
                                eq_layout, eq_bufs
                            )
                            if m is not None:
                                live = live & m
                            return ht.insert_direct(
                                v, live, cap, base, tsize_d
                            )

                        return join_index

                    dfn = cached_kernel(
                        ("join_table_direct", eq_layout, cap, tsize_d),
                        build_direct,
                        scatter_class=True,
                    )
                    base = jnp.asarray(kmin, jnp.int64)
                    tab, dup = dfn(
                        _flatten_cols(build_cols), base,
                        self.build.num_rows,
                    )
                    if not host_int(dup):
                        self._index = (
                            "table_direct",
                            (tab, base, jnp.asarray(span, jnp.int64)),
                        )
                        return
                    # duplicate build keys: no single-row table core
                    # applies - demote straight to the sorted core
                    # (don't re-pay an insert + sync on the kr table)
                    self._table_demoted = True

        if choice == "scatter" and tables_ok and not self._table_demoted:
            # size off the LIVE row count (host-known), not the padded
            # shape-bucket capacity: a 131k-row dim table in a 1M
            # bucket would otherwise get an 8M-slot table whose random
            # gathers fall out of cache
            tsize = ht.probe_table_size(
                max(1, int(self.build.num_rows))
            )

            def build_table():
                def join_index(eq_bufs, num_rows):
                    live = jnp.arange(cap, dtype=jnp.int32) < num_rows
                    key_cols = _unflatten_eq(eq_layout, eq_bufs)
                    # NULL join keys never match: keep them (and the
                    # shape-bucket padding rows) out of the table
                    for _, m in key_cols:
                        if m is not None:
                            live = live & m
                    h = ht.cheap_hash(key_cols, cap)
                    if kr:
                        # fused (key32|row) entries: probes need ONE
                        # gather per round instead of table->row->key
                        k32 = ht.key_u32(*key_cols[0])
                        tab, dup = ht.insert_kr(
                            k32, h, live, cap, tsize
                        )
                        return tab, dup
                    _slot, tab, dup, _ovf = ht.insert(
                        h, key_cols, live, cap, tsize,
                        null_equal=False,
                    )
                    return tab, dup

                return join_index

            fn = cached_kernel(
                ("join_table", eq_layout, cap, tsize, kr), build_table,
                scatter_class=True,
            )
            tab, dup = fn(
                _flatten_cols(build_cols),
                self.build.num_rows,
            )
            # one blocking scalar per build relation: unique keys take
            # the table core; duplicates demote to the sorted core
            if not host_int(dup):
                self._index = ("table_kr" if kr else "table", tab)
                return
            self._table_demoted = True

        bufs = _key_hash_cols(build_cols)
        dtypes = tuple(d for _, _, d in bufs)

        def build():
            def join_index(values, valids, num_rows):
                cols = list(zip(values, valids, dtypes))
                h = hash_columns_device(cols, cap).astype(jnp.int32)
                # NULL keys hash like values and are rejected later by
                # the equality check, so collisions only cost
                # verification work. Padding rows must not enter the
                # index: a build table well under its shape bucket
                # would otherwise contribute cap-num_rows phantom
                # candidates per probe row whose key equals the padding
                # value (observed 11x pair expansion on a 131k-row dim
                # table in a 1M bucket). INT32_MAX herds them into one
                # run at the top; a genuine probe hash there still
                # verifies by exact key + liveness in emit_pairs.
                live = jnp.arange(cap, dtype=jnp.int32) < num_rows
                h = jnp.where(live, h, jnp.int32(0x7FFFFFFF))
                order = jnp.argsort(h, stable=True)
                return jnp.take(h, order), order

            return join_index

        fn = cached_kernel(("join_index", dtypes, cap), build)
        h_sorted, order = fn(
            tuple(v for v, _, _ in bufs), tuple(m for _, m, _ in bufs),
            self.build.num_rows,
        )
        self._index = ("sorted", h_sorted, order)

    def _check_probe_dtypes(self, unified_b, unified_p):
        """The kr table's 32-bit key encoding cannot express a wider
        probe key (i64/f64 vs an i32/f32 build): rebuild as a GENERIC
        table, whose cheap_hash is value-consistent across widths and
        whose equality check promotes - mixed-width keys then join
        correctly (the sorted core's murmur3 is dtype-semantic, Spark
        hashInt vs hashLong, and would silently miss them)."""
        if self._index[0] == "table_direct":
            # the direct lookup subtracts in int64, so ANY integer
            # probe width is exact; a non-integer probe (float-unified
            # keys) would truncate and must rebuild generic
            if all(
                jnp.issubdtype(p.values.dtype, jnp.integer)
                for p in unified_p
            ):
                return
            self._force_generic = True
            self._index = None
            self._ensure_index(unified_b)
            return
        if self._index[0] != "table_kr":
            return
        if all(
            b.values.dtype == p.values.dtype
            for b, p in zip(unified_b, unified_p)
        ):
            return
        self._force_generic = True
        self._index = None
        self._ensure_index(unified_b)

    def table_state(self, probe_cb: ColumnBatch,
                    probe_keys: List[int]):
        """Table-core state WITHOUT dispatching the lookup kernel, for
        callers that fuse the lookup into their own program (the fused
        join+aggregate path). Returns ((probe_cb, unified_b, unified_p,
        tab, mode) | None, probe_cb): `mode` is "table" (row-index
        table, ht.lookup), "table_kr" (fused key|row u64 entries,
        ht.lookup_kr), or "table_direct" (dense-domain key->row array,
        ht.lookup_direct, tab = (array, base, span));
        None means the core resolved to sorted
        (duplicate keys or the sort knob) and the caller should use
        probe()/emit_pairs()."""
        probe_cb = ensure_compacted(probe_cb)
        build_cols = [self.build.columns[i] for i in self.build_keys]
        probe_cols = [probe_cb.columns[i] for i in probe_keys]
        unified_b, unified_p = [], []
        for bc, pc_ in zip(build_cols, probe_cols):
            b2, p2 = _unify_key_pair(bc, pc_)
            unified_b.append(b2)
            unified_p.append(p2)
        with self._index_lock:
            self._ensure_index(unified_b)
            self._check_probe_dtypes(unified_b, unified_p)
            index = self._index
        if index[0] not in ("table", "table_kr", "table_direct"):
            return None, probe_cb
        if index[0] == "table_direct":
            _count("join_direct_batches", 1)
        return (
            (probe_cb, unified_b, unified_p, index[1], index[0]),
            probe_cb,
        )

    def table_state_static(self, probe_keys: List[int],
                           probe_schema: Schema):
        """Table-core state WITHOUT a materialized probe batch, for the
        probe-chain-folded fused join: the probe keys are evaluated
        INSIDE the consumer's kernel, so eligibility must be decided
        from static probe dtypes alone. Dictionary-encoded keys on
        either side are out (unification needs host key values); the
        kr/direct width checks mirror _check_probe_dtypes using the
        probe fields' physical dtypes (the engine-wide invariant that
        evaluated buffers carry their field's physical dtype - the
        folded kernel asserts it at trace time). Returns (mode, tab) or
        None (sorted core / ineligible shape); None means the caller
        should materialize the probe batch and use table_state()."""
        build_cols = [self.build.columns[i] for i in self.build_keys]
        if any(c.dtype.is_dictionary_encoded for c in build_cols):
            return None
        p_fields = [probe_schema.fields[i] for i in probe_keys]
        if any(
            f.dtype.is_dictionary_encoded
            or f.dtype.is_string_like
            or f.dtype.is_wide_decimal
            for f in p_fields
        ):
            return None
        p_dtypes = [
            np.dtype(f.dtype.physical_dtype()) for f in p_fields
        ]
        with self._index_lock:
            self._ensure_index(build_cols)
            # width demotions, statically (mirror _check_probe_dtypes)
            if self._index[0] == "table_direct" and not all(
                np.issubdtype(dt, np.integer) for dt in p_dtypes
            ):
                self._force_generic = True
                self._index = None
                self._ensure_index(build_cols)
            elif self._index[0] == "table_kr" and not all(
                b.values.dtype == dt
                for b, dt in zip(build_cols, p_dtypes)
            ):
                self._force_generic = True
                self._index = None
                self._ensure_index(build_cols)
            index = self._index
        if index[0] not in ("table", "table_kr", "table_direct"):
            return None
        return index[0], index[1]

    def probe(self, probe_cb: ColumnBatch, probe_keys: List[int]):
        """Hash the probe keys and size the pair expansion (one host
        sync). Returns the state tuple for emit_pairs(); emission - and
        the matched_build update - happens only when emit_pairs() runs,
        so read core.matched_build only after that call."""
        probe_cb = ensure_compacted(probe_cb)
        build_cols = [self.build.columns[i] for i in self.build_keys]
        probe_cols = [probe_cb.columns[i] for i in probe_keys]
        unified_b, unified_p = [], []
        for bc, pc_ in zip(build_cols, probe_cols):
            b2, p2 = _unify_key_pair(bc, pc_)
            unified_b.append(b2)
            unified_p.append(p2)
        with self._index_lock:
            self._ensure_index(unified_b)
            self._check_probe_dtypes(unified_b, unified_p)
            index = self._index
        pcap = probe_cb.capacity

        if index[0] in ("table", "table_kr", "table_direct"):
            mode = index[0]
            tab = index[1]
            bcap = self.build.capacity
            b_eq_layout = _eq_layout(unified_b)
            p_eq_layout = _eq_layout(unified_p)

            def build_lookup():
                def join_probe(b_eq, p_eq, tab, num_rows):
                    # num_rows=None: full probe batch (constant mask
                    # folds into the downstream selects)
                    live = (
                        jnp.ones(pcap, dtype=jnp.bool_)
                        if num_rows is None
                        else jnp.arange(pcap, dtype=jnp.int32)
                        < num_rows
                    )
                    pkeys = _unflatten_eq(p_eq_layout, p_eq)
                    for _, m in pkeys:
                        if m is not None:
                            live = live & m  # NULL never matches
                    return _table_lookup(
                        mode, tab, pkeys,
                        _unflatten_eq(b_eq_layout, b_eq),
                        live, bcap,
                    )

                return join_probe

            fn = cached_kernel(
                ("join_lookup", mode, b_eq_layout, p_eq_layout, bcap,
                 pcap),
                build_lookup,
            )
            match_idx, matched = fn(
                _flatten_cols(unified_b),
                _flatten_cols(unified_p),
                tab,
                None if probe_cb.num_rows == pcap
                else probe_cb.num_rows,
            )
            if mode == "table_direct":
                _count("join_direct_batches", 1)
            # NO host sync: output capacity is statically the probe
            # capacity (each probe row matches at most one build row)
            return (
                "table", probe_cb, match_idx, matched, pcap
            )

        _tag, h_sorted, order = index
        # hash-time cast for mixed-width keys: murmur3 is dtype-semantic
        # (Spark hashInt != hashLong for equal values), so a wider probe
        # key hashes into the wrong run and silently misses. Casting the
        # probe to the build dtype FOR BUCKETING ONLY is safe: values
        # outside the build dtype's range wrap into some run whose
        # candidates the emit kernel's exact (promoting) equality check
        # rejects, and in-range/representable values cast losslessly.
        hash_p = [
            p2 if p2.values.dtype == b2.values.dtype
            or p2.dtype.is_dictionary_encoded
            else Column(
                b2.dtype, p2.values.astype(b2.values.dtype),
                p2.validity, p2.dictionary,
            )
            for b2, p2 in zip(unified_b, unified_p)
        ]
        pbufs = _key_hash_cols(hash_p)
        pdtypes = tuple(d for _, _, d in pbufs)

        def build_counts():
            def join_probe(values, valids, h_sorted, num_rows):
                cols = list(zip(values, valids, pdtypes))
                h = hash_columns_device(cols, pcap).astype(jnp.int32)
                lo = jnp.searchsorted(h_sorted, h, side="left")
                hi = jnp.searchsorted(h_sorted, h, side="right")
                counts = (hi - lo).astype(jnp.int32)
                live = jnp.arange(pcap, dtype=jnp.int32) < num_rows
                counts = jnp.where(live, counts, 0)
                return counts, lo.astype(jnp.int32), jnp.sum(counts)

            return join_probe

        fn = cached_kernel(("join_counts", pdtypes, pcap), build_counts)
        counts, lo, total_dev = fn(
            tuple(v for v, _, _ in pbufs),
            tuple(m for _, m, _ in pbufs),
            h_sorted,
            probe_cb.num_rows,
        )
        total = host_int(total_dev)
        _count("join_pair_syncs", 1)
        pair_cap = max(get_config().bucket_for(total), 1)
        return (
            "sorted", probe_cb, unified_b, unified_p, counts, lo,
            order, pair_cap,
        )

    def emit_pairs(self, probe_state, out_build_cols: List[Column],
                   out_probe_cols: List[Column], build_first: bool,
                   fold_build: bool = True):
        """ONE kernel: expand candidate pairs, verify key equality, gather
        both sides' output columns, fold matched flags. Returns
        (out_columns, valid, pair_cap, matched_probe) and updates
        matched_build; `fold_build` False says the caller never reads
        matched_build, and the table core's emission then holds no
        scatter."""
        if probe_state[0] == "table":
            return self._emit_table(
                probe_state, out_build_cols, out_probe_cols,
                build_first, fold_build,
            )
        (_tag, probe_cb, unified_b, unified_p, counts, lo, order,
         pair_cap) = probe_state
        bcap = self.build.capacity
        pcap = probe_cb.capacity
        b_layout = _eq_layout(out_build_cols)
        p_layout = _eq_layout(out_probe_cols)
        k_layout = tuple(
            (b2.values.dtype.str, b2.validity is not None,
             p2.values.dtype.str, p2.validity is not None)
            for b2, p2 in zip(unified_b, unified_p)
        )
        n_b = len(out_build_cols)
        n_p = len(out_probe_cols)

        def build_emit():
            def join_emit(counts, lo, order, bkey_bufs, pkey_bufs,
                       bout_bufs, pout_bufs, build_rows, probe_rows,
                       matched_build):
                # ---- expand ----
                offsets = jnp.cumsum(counts) - counts
                ends = jnp.cumsum(counts)
                total = jnp.sum(counts)
                pos = jnp.arange(pair_cap, dtype=jnp.int32)
                pair_p = jnp.searchsorted(ends, pos, side="right")
                pair_p = jnp.clip(
                    pair_p, 0, counts.shape[0] - 1
                ).astype(jnp.int32)
                within = pos - jnp.take(offsets, pair_p)
                sorted_pos = jnp.take(lo, pair_p) + within
                sorted_pos = jnp.clip(sorted_pos, 0, order.shape[0] - 1)
                pair_b = jnp.take(order, sorted_pos)
                valid = pos < total
                # ---- verify true key equality ----
                live_b = jnp.arange(bcap, dtype=jnp.int32) < build_rows
                valid = valid & jnp.take(live_b, pair_b)
                ki = iter(zip(bkey_bufs, pkey_bufs))
                for _ in k_layout:
                    bv_all, (pv_all, bmask, pmask) = next(ki)
                    bv = jnp.take(bv_all, pair_b)
                    pv = jnp.take(pv_all, pair_p)
                    eq = bv == pv
                    if jnp.issubdtype(bv.dtype, jnp.floating):
                        eq = eq | (jnp.isnan(bv) & jnp.isnan(pv))
                    if bmask is not None:
                        eq = eq & jnp.take(bmask, pair_b)
                    if pmask is not None:
                        eq = eq & jnp.take(pmask, pair_p)
                    valid = valid & eq
                # ---- matched flags ----
                live_p = jnp.arange(pcap, dtype=jnp.int32) < probe_rows
                mp = (
                    jax.ops.segment_sum(
                        valid.astype(jnp.int32),
                        jnp.clip(pair_p, 0, pcap - 1),
                        num_segments=pcap,
                    ) > 0
                ) & live_p
                mb = matched_build | (
                    jax.ops.segment_sum(
                        valid.astype(jnp.int32),
                        jnp.clip(pair_b, 0, bcap - 1),
                        num_segments=bcap,
                    ) > 0
                )
                # ---- gather output columns ----
                def gather(bufs, layout, idx, cap_in):
                    out = []
                    it = iter(bufs)
                    ci = jnp.clip(idx, 0, cap_in - 1)
                    for _, has_m in layout:
                        v = next(it)
                        out.append(jnp.take(v, ci, axis=0))
                        if has_m:
                            out.append(jnp.take(next(it), ci, axis=0))
                        else:
                            out.append(None)
                    return out

                bout = gather(bout_bufs, b_layout, pair_b, bcap)
                pout = gather(pout_bufs, p_layout, pair_p, pcap)
                return bout, pout, valid, mp, mb

            return join_emit

        fn = cached_kernel(
            ("join_emit", k_layout, b_layout, p_layout, bcap, pcap,
             pair_cap, n_b, n_p),
            build_emit,
            scatter_class=True,
        )
        bkey_bufs = tuple(b2.values for b2 in unified_b)
        pkey_bufs = tuple(
            (p2.values, b2.validity, p2.validity)
            for b2, p2 in zip(unified_b, unified_p)
        )
        bout_bufs = _flatten_cols(out_build_cols)
        pout_bufs = _flatten_cols(out_probe_cols)
        bout, pout, valid, matched_p, mb = fn(
            counts, lo, order, bkey_bufs, pkey_bufs, bout_bufs,
            pout_bufs, self.build.num_rows, probe_cb.num_rows,
            self.matched_build,
        )
        self.matched_build = mb
        bcols = _rewrap_cols(out_build_cols, bout)
        pcols = _rewrap_cols(out_probe_cols, pout)
        if build_first:
            out_cols = bcols + pcols
        else:
            out_cols = pcols + bcols
        return out_cols, valid, pair_cap, matched_p

    def _emit_table(self, probe_state, out_build_cols: List[Column],
                    out_probe_cols: List[Column], build_first: bool,
                    fold_build: bool):
        """Table-core emission: output row i IS probe row i (unique
        build keys guarantee at most one match per probe row), so the
        probe columns pass through untouched and only the build side
        gathers - plus, where the caller reads matched_build, one
        scatter to fold the matched-build flags."""
        _tag, probe_cb, match_idx, matched, pair_cap = probe_state
        bcap = self.build.capacity
        pcap = probe_cb.capacity
        b_layout = _eq_layout(out_build_cols)

        def build_emit():
            def join_emit(match_idx, matched, bout_bufs, probe_rows,
                       matched_build):
                live_p = (
                    jnp.arange(pcap, dtype=jnp.int32) < probe_rows
                )
                valid = matched & live_p
                pair_b = jnp.clip(match_idx, 0, bcap - 1)
                mb = None
                if fold_build:
                    mb = matched_build | (
                        jnp.zeros(bcap, jnp.int32)
                        .at[pair_b]
                        .add(valid.astype(jnp.int32), mode="drop")
                        > 0
                    )
                out = []
                it = iter(bout_bufs)
                for _, has_m in b_layout:
                    v = next(it)
                    out.append(jnp.take(v, pair_b, axis=0))
                    if has_m:
                        out.append(
                            jnp.take(next(it), pair_b, axis=0)
                        )
                    else:
                        out.append(None)
                return out, valid, mb

            return join_emit

        fn = cached_kernel(
            ("join_emit_table", b_layout, bcap, pcap,
             len(out_build_cols), fold_build),
            build_emit,
            scatter_class=fold_build,
        )
        bout, valid, mb = fn(
            match_idx, matched, _flatten_cols(out_build_cols),
            probe_cb.num_rows,
            self.matched_build if fold_build else None,
        )
        if fold_build:
            self.matched_build = mb
        bcols = _rewrap_cols(out_build_cols, bout)
        pcols = list(out_probe_cols)
        if build_first:
            out_cols = bcols + pcols
        else:
            out_cols = pcols + bcols
        return out_cols, valid, pair_cap, valid


def _eq_layout(cols: List[Column]) -> Tuple:
    """Hashable layout of (values dtype, has-validity) per key column -
    MUST stay the single source for both kernel cache keys and
    _unflatten_eq buffer reconstruction."""
    return tuple(
        (c.values.dtype.str, c.validity is not None) for c in cols
    )


def _kr_eligible(cols: List[Column]) -> bool:
    """Single narrow key -> the fused (key|row) u64 table applies."""
    if len(cols) != 1:
        return False
    dt = cols[0].values.dtype
    return bool(
        dt == jnp.bool_
        or dt == jnp.float32
        or (jnp.issubdtype(dt, jnp.integer) and dt.itemsize <= 4)
    )


def _table_lookup(mode, tab, pkeys, bkeys, live, bcap):
    """Mode-dispatched table probe shared by the standalone lookup
    kernel and the fused join+aggregate kernel."""
    from blaze_tpu.ops import hash_table as ht

    if mode == "table_direct":
        # no hash, no probe rounds: callers already folded NULL masks
        # into `live`
        tab_arr, base, span = tab
        return ht.lookup_direct(tab_arr, base, span, pkeys[0][0], live)
    h = ht.cheap_hash(pkeys, live.shape[0])
    if mode == "table_kr":
        k32 = ht.key_u32(*pkeys[0])
        return ht.lookup_kr(tab, k32, h, live)
    return ht.lookup(
        tab, h, pkeys, bkeys, live, bcap, null_equal=False
    )


def _unflatten_eq(layout, bufs):
    """Inverse of _flatten_cols for (values, validity) key pairs."""
    out = []
    it = iter(bufs)
    for _, has_m in layout:
        v = next(it)
        m = next(it) if has_m else None
        out.append((v, m))
    return out


def _flatten_cols(cols: List[Column]):
    bufs = []
    for c in cols:
        bufs.append(c.values)
        if c.validity is not None:
            bufs.append(c.validity)
    return tuple(bufs)


def _rewrap_cols(cols: List[Column], flat) -> List[Column]:
    out = []
    it = iter(flat)
    for c in cols:
        v = next(it)
        m = next(it)
        out.append(Column(c.dtype, v, m, c.dictionary))
    return out


def _null_side(schema_fields, capacity: int) -> List[Column]:
    # numpy zeros: all-NULL padding columns cost no device dispatch; they
    # upload lazily only if a downstream kernel actually consumes them
    cols = []
    for f in schema_fields:
        phys = f.dtype.physical_dtype()
        shape = (capacity, 2) if f.dtype.is_wide_decimal else (capacity,)
        cols.append(
            Column(
                f.dtype,
                np.zeros(shape, dtype=phys),
                np.zeros(capacity, dtype=bool),
                None,
            )
        )
    return cols


class HashJoinExec(PhysicalOp):
    """Broadcast hash join, CollectLeft: the LEFT child is materialized
    (broadcast relation), the RIGHT child streams (reference
    from_proto.rs:349-428 PartitionMode::CollectLeft)."""

    # join types whose build-side epilogue (unmatched-build padding,
    # semi/anti output) depends on matched state across ALL probe
    # partitions. Probes still run per-partition in parallel; each
    # partition OR-merges its local matched-build bitmap into a shared
    # accumulator and the LAST partition to finish emits the epilogue
    # (reference CollectLeft probes per-partition the same way,
    # from_proto.rs:349-428)
    _BUILD_EMITTING = frozenset(
        {JoinType.LEFT, JoinType.FULL, JoinType.LEFT_SEMI,
         JoinType.LEFT_ANTI, JoinType.LEFT_ANTI_NULL_AWARE}
    )

    def __init__(self, left: PhysicalOp, right: PhysicalOp,
                 left_keys: Sequence[str], right_keys: Sequence[str],
                 join_type: JoinType = JoinType.INNER):
        if join_type is JoinType.LEFT_ANTI_NULL_AWARE:
            raise NotImplementedError(
                "null-aware anti join runs through SortMergeJoinExec"
            )
        self.children = [left, right]
        self.left_keys = [left.schema.index_of(k) for k in left_keys]
        self.right_keys = [right.schema.index_of(k) for k in right_keys]
        self.join_type = join_type
        self._schema = _joined_schema(
            left.schema, right.schema, join_type
        )
        self._build: Optional[ColumnBatch] = None
        import threading

        self._build_lock = threading.Lock()
        # epilogue coordination (epoch-reset so a plan object can run
        # more than once, e.g. benchmark warmup loops). A SET of
        # completed partition ids - not a counter - so abandoned
        # generators (LimitExec early return, sampling passes) and
        # partition re-runs stay idempotent
        self._epi_lock = threading.Lock()
        self._epi_matched = None
        self._epi_parts: set = set()

    _FINGERPRINT_STABLE = True

    def _fingerprint_params(self) -> str:
        return (f"{self.join_type.name};l={self.left_keys};"
                f"r={self.right_keys}")

    @property
    def schema(self) -> Schema:
        return self._schema

    @property
    def partition_count(self) -> int:
        return self.children[1].partition_count

    def _collect_build(self, ctx: ExecContext) -> ColumnBatch:
        """Collect the build relation ONCE and share it across probe
        partitions (reference CollectLeft collects one shared build)."""
        with self._build_lock:
            if self._build is None:
                left = self.children[0]
                if getattr(left, "is_broadcast", False):
                    # a broadcast child replays the FULL relation from any
                    # one partition; collecting all would duplicate rows
                    batches = list(left.execute(0, ctx))
                else:
                    batches = [
                        b
                        for p in range(left.partition_count)
                        for b in left.execute(p, ctx)
                    ]
                self._build = concat_batches(
                    batches, schema=left.schema
                )
            return self._build

    def build_side(self, ctx: ExecContext, shared: bool = False
                   ) -> Tuple[ColumnBatch, "_JoinCore"]:
        """(the build relation, its core with the index built), before
        the first probe batch: the stage `join_build` covers reading the
        relation, its concatenation and the index with its blocking
        scalar. `shared` keeps one core on the op for every partition
        (the fused path, which needs no matched-build state). Leaves
        `join_build_rows` in the task's metrics, and the join's other
        three counts at 0."""
        with (obs_trace.span("join_build") if obs_trace.ACTIVE
              else obs_trace.NULL):
            build = self._collect_build(ctx)
            if shared:
                with self._build_lock:
                    core = getattr(self, "_fused_core", None)
                    if core is None or core.build is not build:
                        core = self._fused_core = _JoinCore(
                            build, self.left_keys)
            else:
                core = _JoinCore(build, self.left_keys)
            core.index_build()
        _count("join_build_rows", int(build.num_rows))
        _count("join_probe_batches", 0)
        _count("join_pair_syncs", 0)
        _count("join_direct_batches", 0)
        return build, core

    def execute(self, partition: int, ctx: ExecContext
                ) -> Iterator[ColumnBatch]:
        left, right = self.children
        jt = self.join_type
        build, core = self.build_side(ctx)
        emit_pairs = jt in (
            JoinType.INNER, JoinType.LEFT, JoinType.RIGHT, JoinType.FULL
        )
        for pb in right.execute(partition, ctx):
            _count("join_probe_batches", 1)
            state = core.probe(pb, self.right_keys)
            pb = state[1]
            bcols = build.columns if emit_pairs else []
            pcols = pb.columns if emit_pairs else []
            out_cols, valid, pair_cap, matched_p = core.emit_pairs(
                state, bcols, pcols, build_first=True,
                fold_build=jt in self._BUILD_EMITTING,
            )
            if emit_pairs:
                yield ColumnBatch(
                    self._schema, out_cols, pair_cap, valid
                )
            if jt in (JoinType.RIGHT, JoinType.FULL):
                un = row_mask(pb.num_rows, pb.capacity) & ~matched_p
                lnull = _null_side(left.schema.fields, pb.capacity)
                yield ColumnBatch(
                    self._schema, lnull + list(pb.columns),
                    pb.num_rows, un,
                )
        if jt in self._BUILD_EMITTING:
            yield from self._build_epilogue(
                core.matched_build, build, partition,
                right.partition_count,
            )

    def _build_epilogue(self, local_matched, build: ColumnBatch,
                        partition: int, n_parts: int
                        ) -> Iterator[ColumnBatch]:
        """OR-merge this partition's matched-build bitmap; the run that
        completes the partition set emits the build-side output, then
        resets the epoch so the plan object can run again."""
        left, right = self.children
        jt = self.join_type
        with self._epi_lock:
            if self._epi_matched is None:
                self._epi_matched = local_matched
            else:
                self._epi_matched = self._epi_matched | local_matched
            self._epi_parts.add(partition)
            if len(self._epi_parts) < n_parts:
                return
            matched = self._epi_matched
            self._epi_matched = None
            self._epi_parts = set()
        live_b = row_mask(build.num_rows, build.capacity)
        if jt in (JoinType.LEFT, JoinType.FULL):
            un = live_b & ~matched
            rnull = _null_side(right.schema.fields, build.capacity)
            yield ColumnBatch(
                self._schema, list(build.columns) + rnull,
                build.num_rows, un,
            )
        elif jt is JoinType.LEFT_SEMI:
            yield ColumnBatch(
                self._schema, list(build.columns), build.num_rows,
                live_b & matched,
            )
        elif jt is JoinType.LEFT_ANTI:
            yield ColumnBatch(
                self._schema, list(build.columns), build.num_rows,
                live_b & ~matched,
            )


class SortMergeJoinExec(PhysicalOp):
    """Sort-merge join over co-partitioned sorted inputs.

    The reference streams both sides with single-row cursors
    (sort_merge_join_exec.rs:293-601); that shape is hostile to
    vectorization (SURVEY 7 hard parts), so here each partition pair is
    materialized and joined with the shared vectorized core - the LEFT
    (streamed) side's order is preserved in the output, matching the
    reference's emission order. Semi/Anti are left-side like the
    reference's join_semi (sort_merge_join_exec.rs:603)."""

    def __init__(self, left: PhysicalOp, right: PhysicalOp,
                 left_keys: Sequence[str], right_keys: Sequence[str],
                 join_type: JoinType = JoinType.INNER):
        self.children = [left, right]
        self.left_keys = [left.schema.index_of(k) for k in left_keys]
        self.right_keys = [right.schema.index_of(k) for k in right_keys]
        self.join_type = join_type
        self._schema = _joined_schema(left.schema, right.schema, join_type)

    _FINGERPRINT_STABLE = True

    def _fingerprint_params(self) -> str:
        return (f"{self.join_type.name};l={self.left_keys};"
                f"r={self.right_keys}")

    @property
    def schema(self) -> Schema:
        return self._schema

    @property
    def partition_count(self) -> int:
        return self.children[0].partition_count

    def execute(self, partition: int, ctx: ExecContext
                ) -> Iterator[ColumnBatch]:
        from blaze_tpu.ops.external import bucket_stream, collect_until

        left, right = self.children
        limit = ctx.config.max_materialize_rows
        r_it = right.execute(partition, ctx)
        r_head, r_exc = collect_until(r_it, limit)
        l_it = left.execute(partition, ctx)
        l_head, l_exc = collect_until(l_it, limit)
        if self.join_type is JoinType.LEFT_ANTI_NULL_AWARE:
            # "any build NULL -> empty result" is a GLOBAL property, so
            # NAAJ cannot bucket; NOT-IN subquery build sides are small
            l_head += list(l_it)
            r_head += list(r_it)
            yield from self._join_bucket(l_head, r_head)
            return
        if not (r_exc or l_exc):
            yield from self._join_bucket(l_head, r_head)
            return
        # grace join: co-bucket both sides on the join keys; equal keys
        # land in the same bucket, so every join type is correct per
        # bucket. Bucket count comes from the HBM budget: one bucket's
        # materialization must fit the device headroom (the collected
        # heads are at the materialize cap, so 2x them estimates the
        # stream)
        from blaze_tpu.runtime.memory import (
            batch_device_bytes,
            choose_external_bucket_count,
            get_device_tracker,
        )

        head_bytes = sum(batch_device_bytes(b) for b in l_head) + sum(
            batch_device_bytes(b) for b in r_head
        )
        est = 2 * head_bytes
        tracker = get_device_tracker()
        # key includes the partition: concurrent partitions of one op
        # account (and release) independently
        track_key = (id(self), ctx.partition_id)
        tracker.track(track_key, head_bytes)
        try:
            n_b = choose_external_bucket_count(est, ctx.config)
            yield from self._grace_join(
                l_it, r_it, l_head, r_head, ctx, n_b, depth=0
            )
        finally:
            tracker.release(track_key)

    _MAX_GRACE_DEPTH = 2
    _GRACE_FANOUT = 4

    def _grace_join(self, l_it, r_it, l_head, r_head, ctx: ExecContext,
                    n_b: int, depth: int, modulus: Optional[int] = None
                    ) -> Iterator[ColumnBatch]:
        """One grace level: bucket both sides, join fitting buckets; a
        bucket still over the materialize cap RE-BUCKETS recursively by
        the NEXT hash bits (fanout-way split of just that bucket -
        splits many-key overflow; a single hot key can't split and is
        joined materialized at max depth)."""
        from blaze_tpu.ops.external import (
            bucket_stream,
            collect_until,
            subdivide_pid_fn,
        )

        left, right = self.children
        lkeys = [
            ir.BoundCol(i, left.schema.fields[i].dtype)
            for i in self.left_keys
        ]
        rkeys = [
            ir.BoundCol(i, right.schema.fields[i].dtype)
            for i in self.right_keys
        ]
        if modulus is None:
            modulus = n_b
            l_pid = r_pid = None
        else:
            l_pid = subdivide_pid_fn(lkeys, modulus, n_b)
            r_pid = subdivide_pid_fn(rkeys, modulus, n_b)
            modulus *= n_b
        bl = br = None
        try:
            bl = bucket_stream(l_it, lkeys, n_b, ctx, left.schema,
                               head=l_head, pid_fn=l_pid)
            br = bucket_stream(r_it, rkeys, n_b, ctx, right.schema,
                               head=r_head, pid_fn=r_pid)
            ctx.metrics.add("external_join_buckets", n_b)
            limit = ctx.config.max_materialize_rows
            for b in range(n_b):
                lb_it = bl.bucket(b)
                rb_it = br.bucket(b)
                lb_head, l_exc = collect_until(lb_it, limit)
                rb_head, r_exc = collect_until(rb_it, limit)
                if (l_exc or r_exc) and depth < self._MAX_GRACE_DEPTH:
                    ctx.metrics.add("external_join_rebuckets", 1)
                    yield from self._grace_join(
                        lb_it, rb_it, lb_head, rb_head, ctx,
                        self._GRACE_FANOUT, depth + 1, modulus,
                    )
                    continue
                if l_exc or r_exc:
                    # single hot key survives every re-bucket; join it
                    # materialized (correct, memory-heavy) and record it
                    ctx.metrics.add("external_join_hot_buckets", 1)
                    lb_head += list(lb_it)
                    rb_head += list(rb_it)
                if lb_head or rb_head:
                    yield from self._join_bucket(lb_head, rb_head)
        finally:
            if bl is not None:
                bl.cleanup()
            if br is not None:
                br.cleanup()

    def _join_bucket(self, left_batches, right_batches
                     ) -> Iterator[ColumnBatch]:
        left, right = self.children
        jt = self.join_type
        build = concat_batches(right_batches, schema=right.schema)
        core = _JoinCore(build, self.right_keys)
        probe = concat_batches(left_batches, schema=left.schema)
        state = core.probe(probe, self.left_keys)
        probe = state[1]
        emit = jt in (JoinType.INNER, JoinType.LEFT, JoinType.RIGHT,
                      JoinType.FULL)
        bcols = build.columns if emit else []
        pcols = probe.columns if emit else []
        out_cols, valid, pair_cap, matched_p = core.emit_pairs(
            state, bcols, pcols, build_first=False,
            fold_build=jt in (JoinType.RIGHT, JoinType.FULL),
        )
        live_p = row_mask(probe.num_rows, probe.capacity)
        if emit:
            yield ColumnBatch(self._schema, out_cols, pair_cap, valid)
            if jt in (JoinType.LEFT, JoinType.FULL):
                un = live_p & ~matched_p
                rnull = _null_side(right.schema.fields, probe.capacity)
                yield ColumnBatch(
                    self._schema, list(probe.columns) + rnull,
                    probe.num_rows, un,
                )
            if jt in (JoinType.RIGHT, JoinType.FULL):
                live_b = row_mask(build.num_rows, build.capacity)
                un = live_b & ~core.matched_build
                lnull = _null_side(left.schema.fields, build.capacity)
                yield ColumnBatch(
                    self._schema, lnull + list(build.columns),
                    build.num_rows, un,
                )
        elif jt is JoinType.LEFT_SEMI:
            yield ColumnBatch(
                self._schema, list(probe.columns), probe.num_rows,
                live_p & matched_p,
            )
        elif jt is JoinType.LEFT_ANTI:
            yield ColumnBatch(
                self._schema, list(probe.columns), probe.num_rows,
                live_p & ~matched_p,
            )
        elif jt is JoinType.LEFT_ANTI_NULL_AWARE:
            # NOT IN: probe rows with NULL keys never qualify, and any
            # NULL key on the build side empties the result entirely
            def keys_valid(cb, idxs, live):
                ok = jnp.ones(cb.capacity, dtype=jnp.bool_)
                for i in idxs:
                    c = cb.columns[i]
                    if c.validity is not None:
                        ok = ok & c.validity
                return ok

            live_b = row_mask(build.num_rows, build.capacity)
            build_has_null = jnp.any(
                live_b & ~keys_valid(build, self.right_keys, live_b)
            )
            probe_ok = keys_valid(probe, self.left_keys, live_p)
            sel = (
                live_p & ~matched_p & probe_ok & ~build_has_null
            )
            yield ColumnBatch(
                self._schema, list(probe.columns), probe.num_rows, sel
            )
