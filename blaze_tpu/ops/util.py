"""Shared batch utilities for operators: device gather/compact, a result
sink's read-back, host-side dictionary unification, batch concatenation."""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from blaze_tpu.config import get_config
from blaze_tpu.obs import trace as obs_trace
from blaze_tpu.runtime.dispatch import current_task, launch
from blaze_tpu.types import Schema, TypeId
from blaze_tpu.batch import Column, ColumnBatch, row_mask


@jax.jit
def _take_many(arrays, indices):
    # one dispatch for the whole batch instead of one per column buffer
    return [jnp.take(a, indices, axis=0) for a in arrays]


def take_batch(cb: ColumnBatch, indices: jax.Array, num_rows: int
               ) -> ColumnBatch:
    """Gather rows by index (device). `indices` length defines capacity."""
    bufs = []
    slots = []
    for c in cb.columns:
        slots.append((len(bufs), c.validity is not None))
        bufs.append(c.values)
        if c.validity is not None:
            bufs.append(c.validity)
    taken = launch(_take_many, bufs, indices)
    cols = []
    for c, (i, has_m) in zip(cb.columns, slots):
        cols.append(
            Column(c.dtype, taken[i],
                   taken[i + 1] if has_m else None, c.dictionary)
        )
    return ColumnBatch(cb.schema, cols, num_rows)


@partial(jax.jit, static_argnames=("capacity",))
def _compact_indices(mask: jax.Array, capacity: int):
    idx = jnp.nonzero(mask, size=capacity, fill_value=0)[0]
    return idx, jnp.sum(mask.astype(jnp.int32))


def compact(cb: ColumnBatch, mask: Optional[jax.Array] = None) -> ColumnBatch:
    """Keep rows where mask (AND the batch's own selection) is True, packed
    to the front (one D2H sync for the surviving row count). Paid by the
    callers that go on computing on the device with the rows (shuffle
    write, limit, joins, sort, concat_batches, the mesh tier); a sink
    that reads the whole batch back takes `sink_arrow` and pays none."""
    if obs_trace.ACTIVE:
        # obs seam: the compact stage - the live mask's three eager
        # launches, the index program, the wait for the row count and
        # the gather launch
        with obs_trace.span("compact"):
            return _compact(cb, mask)
    return _compact(cb, mask)


def _compact(cb: ColumnBatch, mask: Optional[jax.Array]) -> ColumnBatch:
    live = cb.live_mask()
    if mask is not None:
        live = live & mask
    idx, n = launch(_compact_indices, live, cb.capacity)
    return take_batch(cb, idx, int(n))


def ensure_compacted(cb: ColumnBatch) -> ColumnBatch:
    """Materialize a pending selection vector (no-op when none)."""
    if cb.selection is None:
        return cb
    return compact(cb)


def sink_arrow(cb: ColumnBatch, ctx):
    """A result sink's read-back: the batch leaves the device as the
    program left it, selection and all, in `to_arrow`'s one packed
    transfer, and is trimmed on the host - no device compaction and no
    wait for a row count. None when no row survives."""
    if cb.num_rows == 0:
        return None
    if cb.selection is not None:
        ctx.metrics.add("sink_trim_batches", 1)
    rb = cb.to_arrow()
    return rb if rb.num_rows else None


def unify_dictionaries(batches: List[ColumnBatch]) -> List[ColumnBatch]:
    """Rewrite all batches so every string column shares one dictionary.

    Host-side (pyarrow) dictionary merge + device-side code remap via
    jnp.take of the old->new mapping. Required before any cross-batch
    compute on string codes (sort, group-by, join): per-batch dictionaries
    are not comparable. TPU-first normalization per SURVEY 7: all device
    string compute happens on unified int32 codes.
    """
    import pyarrow as pa

    if not batches:
        return batches
    schema = batches[0].schema
    string_cols = [
        i for i, f in enumerate(schema)
        if f.dtype.is_dictionary_encoded
    ]
    if not string_cols:
        return batches
    out = [list(b.columns) for b in batches]
    for ci in string_cols:
        dicts = []
        for b in batches:
            d = b.columns[ci].dictionary
            dicts.append(d if d is not None else pa.array([], type=pa.utf8()))
        unified = pa.concat_arrays(
            [d.cast(dicts[0].type) for d in dicts]
        ).unique()
        # old-code -> new-code mapping per batch
        for bi, b in enumerate(batches):
            old = dicts[bi]
            if len(old) == 0:
                mapping = np.zeros(1, dtype=np.int32)
            else:
                mapping = np.asarray(
                    pa.compute.index_in(old, value_set=unified).fill_null(0)
                ).astype(np.int32)
            # pad the mapping to a power-of-two capacity so the remap
            # program depends only on (bucket, codes-shape), not the
            # exact dictionary size — otherwise every distinct
            # dictionary length compiles a fresh XLA executable
            # (hundreds over a TPC-DS run; jaxlib's CPU client
            # segfaults after enough cumulative compilations).
            pad_cap = 1 << max(0, (len(mapping) - 1)).bit_length()
            if pad_cap > len(mapping):
                mapping = np.pad(mapping, (0, pad_cap - len(mapping)))
            c = b.columns[ci]
            new_codes = jnp.take(
                jnp.asarray(mapping),
                jnp.clip(c.values, 0, len(mapping) - 1),
                axis=0,
            )
            out[bi][ci] = Column(c.dtype, new_codes, c.validity, unified)
    return [
        ColumnBatch(b.schema, cols, b.num_rows)
        for b, cols in zip(batches, out)
    ]


def concat_batches(batches: List[ColumnBatch],
                   schema: Optional[Schema] = None,
                   min_capacity: int = 0) -> ColumnBatch:
    """Concatenate live rows of many batches into one padded batch
    (pipeline-breaker materialization). Unifies string dictionaries.
    The capacity is the row count's shape bucket, and at least
    `min_capacity`. Leaves `concat_slice_parts`, the parts written by the
    launch, in the task's metrics (POLL)."""
    batches = [ensure_compacted(b) for b in batches]
    batches = [b for b in batches if b.num_rows > 0]
    if not batches:
        from blaze_tpu.batch import empty_batch

        assert schema is not None, "empty concat needs an explicit schema"
        return empty_batch(schema)
    batches = unify_dictionaries(batches)
    schema = batches[0].schema
    total = sum(b.num_rows for b in batches)
    cap = max(get_config().bucket_for(total), min_capacity)
    if len(batches) == 1 and batches[0].capacity == cap:
        return batches[0]  # already compact at the right bucket
    ncols = len(schema)
    any_mask = [
        any(b.columns[ci].validity is not None for b in batches)
        for ci in range(ncols)
    ]
    values_in = [[b.columns[ci].values for b in batches]
                 for ci in range(ncols)]
    masks_in = [
        [b.columns[ci].validity for b in batches] if any_mask[ci] else None
        for ci in range(ncols)
    ]
    lengths = jnp.asarray(
        np.array([b.num_rows for b in batches], dtype=np.int32)
    )
    vs, ms = launch(
        _concat_many, values_in, masks_in, lengths, cap, tuple(any_mask)
    )
    task = current_task()
    if task is not None:
        task.metrics.add("concat_slice_parts", len(batches))
    cols: List[Column] = []
    for ci in range(ncols):
        ref = batches[0].columns[ci]
        cols.append(
            Column(ref.dtype, vs[ci], ms[ci] if any_mask[ci] else None,
                   ref.dictionary)
        )
    return ColumnBatch(schema, cols, total)


@partial(jax.jit, static_argnames=("cap", "any_mask"))
def _concat_many(values_in, masks_in, lengths, cap: int, any_mask):
    """Concatenate all columns of all batches in one dispatch.

    Row counts (`lengths`) stay TRACED: a filter upstream makes them
    data-dependent, and baking them in statically would recompile this
    program for every distinct combination. Every part is compacted
    (concat_batches), so its live rows are its first `lengths[i]`: each
    part is written WHOLE at its traced offset, in part order, and the
    next part overwrites its padding - no index array and no scatter.
    The output holds `room` rows past `cap`, the largest part: XLA
    clamps a slice's start so the update fits, and without the room the
    last parts would land at a clamped, wrong offset. Rows at and past
    the total read 0 and invalid. One compile covers every row-count
    mix with the same shapes/layout."""
    offsets = jnp.concatenate(
        [jnp.zeros(1, dtype=jnp.int32),
         jnp.cumsum(lengths)[:-1].astype(jnp.int32)]
    )
    live = jnp.arange(cap, dtype=jnp.int32) < jnp.sum(lengths)

    def place(parts, shape, dtype):
        room = max(p.shape[0] for p in parts)
        out = jnp.zeros((cap + room,) + shape, dtype=dtype)
        for i, p in enumerate(parts):
            out = jax.lax.dynamic_update_slice_in_dim(out, p, offsets[i], 0)
        keep = live.reshape((cap,) + (1,) * len(shape))
        return jnp.where(keep, out[:cap], jnp.zeros((), dtype))

    vs = []
    ms = []
    for ci, parts in enumerate(values_in):
        # trailing dims (e.g. wide-decimal limb pairs) ride along
        vs.append(place(parts, parts[0].shape[1:], parts[0].dtype))
        if any_mask[ci]:
            mparts = [
                mp if mp is not None
                else jnp.ones(p.shape[0], dtype=jnp.bool_)
                for p, mp in zip(parts, masks_in[ci])
            ]
            ms.append(place(mparts, (), jnp.bool_))
        else:
            ms.append(None)
    return vs, ms


def slice_to_batches(cb: ColumnBatch, batch_size: int) -> List[ColumnBatch]:
    """Split a large materialized batch back into bucket-sized batches."""
    if cb.num_rows <= batch_size:
        return [cb]
    out = []
    for start in range(0, cb.num_rows, batch_size):
        n = min(batch_size, cb.num_rows - start)
        cap = get_config().bucket_for(n)
        cols = []
        for c in cb.columns:
            v = jax.lax.dynamic_slice_in_dim(c.values, start, cap) \
                if start + cap <= c.capacity else \
                jnp.pad(c.values[start:start + n], (0, cap - n))
            m = None
            if c.validity is not None:
                m = jax.lax.dynamic_slice_in_dim(c.validity, start, cap) \
                    if start + cap <= c.capacity else \
                    jnp.pad(c.validity[start:start + n], (0, cap - n))
            cols.append(Column(c.dtype, v, m, c.dictionary))
        out.append(ColumnBatch(cb.schema, cols, n))
    return out


def _order_key_u32(v: jax.Array, asc: bool) -> jax.Array:
    """Map a <=32-bit value lane to a u32 whose unsigned order equals the
    requested SQL order: ints sign-flip; floats use the sign-magnitude
    flip with NaN normalized to canonical +NaN (Spark: NaN greatest) and
    -0.0 to +0.0 (Spark: equal); descending bit-inverts."""
    if jnp.issubdtype(v.dtype, jnp.floating):
        f = v.astype(jnp.float32)
        f = jnp.where(f == 0.0, jnp.float32(0.0), f)  # -0.0 == 0.0
        bits = jax.lax.bitcast_convert_type(f, jnp.uint32)
        bits = jnp.where(
            jnp.isnan(f), jnp.uint32(0x7FC00000), bits
        )
        neg = (bits >> jnp.uint32(31)).astype(jnp.bool_)
        u = bits ^ jnp.where(
            neg, jnp.uint32(0xFFFFFFFF), jnp.uint32(0x80000000)
        )
    elif v.dtype == jnp.bool_:
        u = v.astype(jnp.uint32)
    elif jnp.issubdtype(v.dtype, jnp.unsignedinteger):
        # already in unsigned order: no sign flip. The signed path's
        # astype(int32) would wrap values >= 2^31 and the flip would
        # then order them BELOW small values. (types.py defines no
        # unsigned TypeId today, so this is future-proofing, but the
        # packed-sort eligibility gate admits any <=4-byte integer.)
        u = v.astype(jnp.uint32)
    else:
        u = v.astype(jnp.int32).astype(jnp.uint32) ^ jnp.uint32(
            0x80000000
        )
    if not asc:
        u = ~u
    return u


def _sort_indices_packed(keys, num_rows, capacity: int) -> jax.Array:
    """One u64 VALUE sort per key instead of a 3-lane index lexsort per
    key plus a final padding argsort: each pass packs
    (null-rank:2 | order-key:32 | position:posbits) into a u64 and
    sorts it; the low bits carry the permutation, so the pass is stable
    by construction and padding rows (rank 3) always sink to the end.
    ~5x faster than the lexsort ladder on XLA:CPU at 8M rows."""
    posbits = max(1, (capacity - 1).bit_length())
    live = jnp.arange(capacity, dtype=jnp.int32) < num_rows
    pos = jnp.arange(capacity, dtype=jnp.uint64)
    posmask = jnp.uint64((1 << posbits) - 1)
    idx = None
    for values, validity, asc, nulls_first in reversed(list(keys)):
        v = values if idx is None else jnp.take(values, idx, axis=0)
        u = _order_key_u32(v, asc)
        lv = live if idx is None else jnp.take(live, idx)
        if validity is not None:
            mv = (
                validity if idx is None
                else jnp.take(validity, idx)
            )
            rank = jnp.where(
                mv, jnp.uint64(1),
                jnp.uint64(0 if nulls_first else 2),
            )
            # NULL rows carry arbitrary payload values; zero them so
            # the null run keeps the previous pass's (stable) order
            # instead of shuffling by garbage
            u = jnp.where(mv, u, jnp.uint32(0))
        else:
            rank = jnp.uint64(1)
        rank = jnp.where(lv, rank, jnp.uint64(3))
        lane = (
            ((rank << jnp.uint64(32)) | u.astype(jnp.uint64))
            << jnp.uint64(posbits)
        ) | pos
        order = (jnp.sort(lane) & posmask).astype(jnp.int32)
        idx = order if idx is None else jnp.take(idx, order)
    if idx is None:  # no keys: padding-last identity
        idx = jnp.argsort(
            jnp.where(live, 0, 1).astype(jnp.int8), stable=True
        ).astype(jnp.int32)
    return idx


def sort_indices(
    keys: Sequence[Tuple[jax.Array, Optional[jax.Array], bool, bool]],
    num_rows,
    capacity: int,
) -> jax.Array:
    """Stable multi-key argsort. keys = [(values, validity, ascending,
    nulls_first)]; padding rows always sort last.

    Keys whose values fit 32 bits (ints, f32, dict codes, dates, bool)
    take the packed-u64 path; wider keys (i64, f64, timestamps) fall
    back to iterated stable sorts from the least-significant key
    (classic radix-style lexsort) - every pass is one XLA sort op.
    """
    from blaze_tpu.config import get_config, resolve_core_choice

    packed_ok = (
        resolve_core_choice("BLAZE_SORT_CORE", get_config().sort_core)
        == "scatter"
    )
    if packed_ok and capacity < (1 << 30) and all(
        v.ndim == 1
        and (
            v.dtype == jnp.bool_
            or (
                jnp.issubdtype(v.dtype, jnp.integer)
                and v.dtype.itemsize <= 4
            )
            or v.dtype == jnp.float32
        )
        for v, _, _, _ in keys
    ):
        return _sort_indices_packed(keys, num_rows, capacity)
    idx = jnp.arange(capacity, dtype=jnp.int32)
    live = jnp.arange(capacity, dtype=jnp.int32) < num_rows
    for values, validity, asc, nulls_first in reversed(list(keys)):
        v = jnp.take(values, idx, axis=0)
        lv = jnp.take(live.astype(jnp.int8), idx, axis=0)
        if jnp.issubdtype(v.dtype, jnp.floating):
            # Spark ordering: NaN sorts greater than any value
            nan = jnp.isnan(v)
            v = jnp.where(nan, jnp.inf, v)
            tie = nan.astype(jnp.int8)
        else:
            tie = jnp.zeros_like(v, dtype=jnp.int8)
        if not asc:
            v = _invert_order(v)
            tie = -tie
        # null ranking: 0 = nulls first, 2 = nulls last, live padding > all
        if validity is not None:
            mv = jnp.take(validity, idx, axis=0)
            rank = jnp.where(mv, 1, 0 if nulls_first else 2)
            # NULL rows carry arbitrary payload values: neutralize the
            # value and tie lanes so the null run keeps the previous
            # pass's (stable) order instead of shuffling by garbage
            zero = jnp.zeros_like(v[:1])[0]
            v = jnp.where(mv, v, zero)
            tie = jnp.where(mv, tie, jnp.int8(0))
        else:
            rank = jnp.ones_like(v, dtype=jnp.int32)
        rank = jnp.where(lv.astype(bool), rank, 3)
        order = jnp.lexsort((tie, v, rank))
        idx = jnp.take(idx, order, axis=0)
    # final pass: push padding to the end while keeping everything stable
    lv = jnp.take(live.astype(jnp.int8), idx, axis=0)
    order = jnp.argsort(-lv, stable=True)
    return jnp.take(idx, order, axis=0)


def _invert_order(v: jax.Array) -> jax.Array:
    if jnp.issubdtype(v.dtype, jnp.floating):
        return -v
    if v.dtype == jnp.bool_:
        return ~v
    # bitwise NOT (-v - 1) is an order-reversing bijection on two's-
    # complement ints with no overflow: plain negation maps INT64_MIN to
    # itself and would sort it first in a descending sort
    return jnp.bitwise_not(v.astype(jnp.int64))
