"""Columnar batch substrate.

A `ColumnBatch` is the engine's unit of data flow - the TPU-native equivalent
of the reference's Arrow `RecordBatch` streaming through DataFusion operators
(reference exec.rs:196-255 hot loop). Differences, by design (SURVEY 7):

- Every column is a fixed-capacity device array padded up to a shape bucket,
  so XLA compiles one kernel per (pipeline, bucket) rather than per batch.
  The live row count is carried separately (`num_rows`); rows past it are
  padding with unspecified contents that kernels mask out.
- SQL NULLs are a separate bool validity array per column (None == all
  valid), matching Arrow validity semantics without bit-packing (TPU
  vectorizes bool arrays fine; bit-unpacking would serialize).
- utf8/binary columns are dictionary-encoded at the host boundary: int32
  codes on device + a host-side pyarrow dictionary. All device compute
  (group-by, join keys, comparisons) happens on codes or on 32-bit hashes
  computed from the real bytes by the host runtime.

`ColumnBatch` itself is a host object, NOT a pytree: jitted pipelines receive
the flat list of device arrays (`device_buffers()`) plus the row count, and
the host wrapper reassembles. This keeps non-traceable state (dictionaries,
schema) out of jit caching keys.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from blaze_tpu.config import get_config
from blaze_tpu.obs import trace as obs_trace
from blaze_tpu.types import (
    DataType,
    Field,
    Schema,
    TypeId,
    from_arrow_schema,
    to_arrow_type,
)


@dataclasses.dataclass
class Column:
    """One column: padded device values + optional validity + host dict."""

    dtype: DataType
    values: jax.Array  # physical dtype, shape (capacity,)
    validity: Optional[jax.Array] = None  # bool, shape (capacity,) or None
    dictionary: Optional[object] = None  # pyarrow Array for utf8/binary

    @property
    def capacity(self) -> int:
        return self.values.shape[0]

    def valid_mask(self, capacity: Optional[int] = None) -> jax.Array:
        if self.validity is not None:
            return self.validity
        return jnp.ones(capacity or self.capacity, dtype=jnp.bool_)


@dataclasses.dataclass
class ColumnBatch:
    """Batch of padded device columns.

    `selection` is an optional device-resident row mask (the deferred
    selection vector of SURVEY 7): a row is live iff its index < num_rows
    AND selection[i]. Filters set it lazily so no host sync happens
    mid-pipeline; pipeline breakers (sort/aggregate/join/exchange) and the
    host boundary compact it away.
    """

    schema: Schema
    columns: List[Column]
    num_rows: int
    selection: Optional[jax.Array] = None

    @property
    def capacity(self) -> int:
        if not self.columns:
            return 0
        return self.columns[0].capacity

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    def column(self, name_or_index) -> Column:
        if isinstance(name_or_index, int):
            return self.columns[name_or_index]
        return self.columns[self.schema.index_of(name_or_index)]

    # ------------------------------------------------------------------
    # flat device-buffer view for jitted pipelines
    # ------------------------------------------------------------------
    def device_buffers(self) -> List[jax.Array]:
        """Flat list of device arrays: [v0, m0?, v1, m1?, ...].

        The layout (which columns carry validity) is part of the batch's
        `layout()` descriptor, which jit-compiled pipelines key on.
        """
        bufs: List[jax.Array] = []
        for c in self.columns:
            bufs.append(c.values)
            if c.validity is not None:
                bufs.append(c.validity)
        return bufs

    def layout(self) -> Tuple:
        """Hashable descriptor of the device-buffer layout (jit cache key)."""
        return (
            self.capacity,
            tuple(
                (c.dtype.id.value, c.dtype.precision, c.dtype.scale,
                 c.validity is not None)
                for c in self.columns
            ),
        )

    @staticmethod
    def from_device_buffers(
        schema: Schema,
        layout: Tuple,
        bufs: Sequence[jax.Array],
        num_rows: int,
        dictionaries: Optional[Sequence[Optional[object]]] = None,
    ) -> "ColumnBatch":
        _, col_layout = layout
        cols: List[Column] = []
        it = iter(bufs)
        for i, (tid, prec, scale, has_mask) in enumerate(col_layout):
            dt = DataType(TypeId(tid), prec, scale)
            values = next(it)
            validity = next(it) if has_mask else None
            d = dictionaries[i] if dictionaries else None
            cols.append(Column(dt, values, validity, d))
        return ColumnBatch(schema, cols, num_rows)

    def dictionaries(self) -> List[Optional[object]]:
        return [c.dictionary for c in self.columns]

    # ------------------------------------------------------------------
    # host boundary: pyarrow interop
    # ------------------------------------------------------------------
    @staticmethod
    def from_arrow(rb, capacity: Optional[int] = None) -> "ColumnBatch":
        """Build from a pyarrow RecordBatch (dictionary-encode strings,
        pad to a shape bucket, move to device)."""
        n = rb.num_rows
        cap = capacity or get_config().bucket_for(n)
        schema, entries, col_meta = _host_entries(rb, cap)
        from blaze_tpu.runtime.pack import put_packed_padded_lazy

        buf, metas, pairs = put_packed_padded_lazy(entries)
        if buf is None:  # zero-column schema
            return ColumnBatch(schema, [], n)
        return PackedColumnBatch(schema, n, cap, buf, metas, pairs,
                                 col_meta)

    @staticmethod
    def from_arrow_pruned(rb, schema: Schema, present: Sequence[int],
                          capacity: Optional[int] = None) -> "ColumnBatch":
        """Build a batch with `schema` positions intact from a RecordBatch
        holding only the columns at `present` (ascending). Pruned
        positions get zero placeholders - never decoded, never
        transferred (constant-folded zeros inside fused kernels, shared
        device arrays on the classic path) - valid only when no consumer
        reads them (guaranteed by planner/colprune's conservative
        analysis)."""
        sub = ColumnBatch.from_arrow(rb, capacity)
        pres = set(present)
        if isinstance(sub, PackedColumnBatch) and sub.is_packed:
            # keep the packed wire buffer lazy: a fused consumer splices
            # unpack + placeholders + its whole chain into one dispatch
            it = iter(sub._col_meta)
            full_meta = []
            for i, field in enumerate(schema):
                if i in pres:
                    full_meta.append(next(it))
                else:
                    full_meta.append((field.dtype, False, None, False))
            return PackedColumnBatch(
                schema, rb.num_rows, sub.capacity, sub._buf,
                sub._metas, sub._pairs, full_meta,
            )
        cap = sub.capacity if sub.columns else (
            capacity or get_config().bucket_for(rb.num_rows)
        )
        it = iter(sub.columns)
        cols: List[Column] = []
        for i, field in enumerate(schema):
            if i in pres:
                cols.append(next(it))
            else:
                cols.append(
                    Column(field.dtype, _placeholder(cap, field.dtype))
                )
        return ColumnBatch(schema, cols, rb.num_rows)

    def live_mask(self) -> jax.Array:
        m = row_mask(self.num_rows, self.capacity)
        if self.selection is not None:
            m = m & self.selection
        return m

    def to_arrow(self):
        """Materialize the live rows back to a pyarrow RecordBatch.

        All device buffers travel in ONE packed transfer (a single device
        round trip regardless of column count), sliced on device to the
        smallest shape bucket covering the live rows so padding beyond it
        never crosses the wire. A pending selection travels with them
        and the rows are trimmed by it on the host: the result sinks
        (`ops/util.py: sink_arrow`) read a filtered batch back this way,
        with no device compaction and no wait for a row count."""
        if obs_trace.ACTIVE:
            # obs seam: the d2h stage - the packed transfer, its wait
            # on the device, and the Arrow assembly
            with obs_trace.span("d2h", rows=self.num_rows) as sp:
                rb = self._to_arrow()
                sp.tag(bytes=rb.nbytes)
                return rb
        return self._to_arrow()

    def _to_arrow(self):
        import pyarrow as pa

        from blaze_tpu.runtime.pack import get_packed

        cap = self.capacity
        k = None
        if cap and self.num_rows < cap:
            k = min(get_config().bucket_for(self.num_rows), cap)
            if k >= cap:
                k = None
        device_bufs = [self.selection] + self.device_buffers()
        host_bufs = get_packed(device_bufs, slice_rows=k)
        host_sel, host_iter = host_bufs[0], iter(host_bufs[1:])
        host_cols = []
        for c in self.columns:
            v = next(host_iter)
            m = next(host_iter) if c.validity is not None else None
            host_cols.append((v, m))

        n = self.num_rows
        keep = None
        if self.selection is not None:
            # the host trim: one index vector shared by every column
            keep = np.flatnonzero(np.asarray(host_sel)[:n])
            n = len(keep)
        arrays = []
        fields = []
        for field, col, (hv, hm) in zip(
            self.schema, self.columns, host_cols
        ):
            vals = np.asarray(hv)[: self.num_rows]
            mask = None
            if hm is not None:
                mask = ~np.asarray(hm)[: self.num_rows]
            if keep is not None:
                vals = vals[keep]
                if mask is not None:
                    mask = mask[keep]
            dt = field.dtype
            if dt.is_dictionary_encoded:
                codes = vals.astype(np.int32)
                if mask is not None:
                    codes = np.where(mask, 0, codes)
                dict_arr = col.dictionary
                if dict_arr is None and len(codes):
                    # pruned placeholder column (codes=0 with no
                    # dictionary) reaching a materializing consumer
                    # (DebugExec logging, sort spill, grace-join
                    # externalization): render all-null rather than
                    # indexing an empty dictionary - the values were
                    # never read, so nulls are the honest rendering
                    arr = pa.nulls(len(codes), type=to_arrow_type(dt))
                else:
                    if dict_arr is None:
                        dict_arr = pa.array([], type=to_arrow_type(dt))
                    indices = pa.array(codes, mask=mask)
                    arr = pa.DictionaryArray.from_arrays(
                        indices, dict_arr
                    ).cast(to_arrow_type(dt))
            elif dt.id is TypeId.DECIMAL:
                if vals.ndim == 2:
                    arr = _decimal_from_limbs(
                        vals.astype(np.int64), mask,
                        dt.precision, dt.scale,
                    )
                else:
                    arr = _decimal_from_unscaled_i64(
                        vals.astype(np.int64), mask,
                        dt.precision, dt.scale,
                    )
            elif dt.id is TypeId.DATE32:
                arr = pa.array(
                    vals.astype(np.int32), mask=mask, type=pa.int32()
                ).cast(pa.date32())
            elif dt.id is TypeId.TIMESTAMP_US:
                arr = pa.array(
                    vals.astype(np.int64), mask=mask, type=pa.int64()
                ).cast(pa.timestamp("us"))
            elif dt.id is TypeId.NULL:
                arr = pa.nulls(n)
            else:
                arr = pa.array(vals, mask=mask, type=to_arrow_type(dt))
            arrays.append(arr)
            fields.append(pa.field(field.name, arr.type, field.nullable))
        return pa.RecordBatch.from_arrays(arrays, schema=pa.schema(fields))

    @staticmethod
    def from_pydict(data: dict, schema: Optional[Schema] = None,
                    capacity: Optional[int] = None) -> "ColumnBatch":
        """Test/interop helper: build from {name: list} via pyarrow."""
        import pyarrow as pa

        if schema is not None:
            from blaze_tpu.types import to_arrow_schema

            rb = pa.RecordBatch.from_pydict(
                data, schema=to_arrow_schema(schema)
            )
        else:
            rb = pa.RecordBatch.from_pydict(data)
        return ColumnBatch.from_arrow(rb, capacity=capacity)

    def to_pydict(self) -> dict:
        return self.to_arrow().to_pydict()

    # ------------------------------------------------------------------
    def slice_host(self, start: int, length: int) -> "ColumnBatch":
        """Host-side row slice (used by spill/IPC writers)."""
        rb = self.to_arrow().slice(start, length)
        return ColumnBatch.from_arrow(rb)


def _host_entries(rb, cap: int):
    """A RecordBatch's columns as the host arrays a packed transfer
    takes: (schema, entries, col_meta) with an entry `(vals, cap,
    tail_fill)` for each column's values and, where it holds a NULL,
    its validity; `col_meta` is PackedColumnBatch's."""
    import pyarrow as pa
    import pyarrow.compute as pc

    schema = from_arrow_schema(rb.schema)
    n = rb.num_rows
    # (vals, cap, tail_fill) triples: padding and transfer-packing
    # fuse into one host copy (pack.put_packed_padded)
    entries: List[Tuple[np.ndarray, int, int]] = []
    col_meta: List[Tuple[DataType, bool, Optional[object]]] = []
    for i, field in enumerate(schema):
        arr = rb.column(i)
        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks()
        dt = field.dtype
        has_nulls = arr.null_count > 0
        null_np = np.asarray(arr.is_null()) if has_nulls else None
        dictionary = None
        if dt.is_dictionary_encoded:
            if not pa.types.is_dictionary(arr.type):
                arr = pc.dictionary_encode(arr)
            dictionary = arr.dictionary
            np_vals = arr.indices.fill_null(0).to_numpy(
                zero_copy_only=False)
            np_vals = np.ascontiguousarray(np_vals, dtype=np.int32)
        elif dt.id is TypeId.DECIMAL:
            if dt.is_wide_decimal:
                np_vals = _decimal_limbs(arr)  # (n, 2) [lo, hi]
            else:
                np_vals = _decimal_unscaled_i64(arr)
        elif dt.id is TypeId.TIMESTAMP_US:
            arr = arr.cast(pa.timestamp("us"))
            np_vals = arr.to_numpy(zero_copy_only=False).astype(
                "datetime64[us]").view(np.int64)
        elif dt.id is TypeId.DATE32:
            np_vals = arr.to_numpy(zero_copy_only=False).astype(
                "datetime64[D]").view(np.int64).astype(np.int32)
        elif dt.id is TypeId.NULL:
            np_vals = np.zeros(n, dtype=np.int8)
        else:
            if has_nulls:
                # pyarrow surfaces nullable ints as float64 with NaN;
                # fill first (nulls are tracked in validity anyway).
                arr = arr.fill_null(
                    False if dt.id is TypeId.BOOL else 0)
            np_vals = arr.to_numpy(zero_copy_only=False)
        phys = dt.physical_dtype()
        if np_vals.dtype != phys:
            np_vals = np_vals.astype(phys)
        entries.append((np_vals, cap, 0))
        has_validity = has_nulls or dt.id is TypeId.NULL
        if has_validity:
            if dt.id is TypeId.NULL:
                # all-invalid including the padding tail
                entries.append((np.zeros(0, dtype=bool), cap, 0))
            else:
                entries.append(
                    (~null_np, cap, 1)  # padding rows stay "valid"
                )
        col_meta.append((dt, has_validity, dictionary, True))
    return schema, entries, col_meta


class PackedColumnBatch(ColumnBatch):
    """A ColumnBatch whose device columns still live inside the single
    packed H2D wire buffer (runtime/pack.put_packed_padded_lazy).

    Two consumption modes:

    - `packed_view()` (pipeline fusion): the fused operator composes the
      buffer splitter into its OWN jitted kernel, so transfer-unpack +
      the whole operator chain is ONE dispatch per batch. Pruned scan
      positions materialize as jnp.zeros inside the kernel - XLA folds
      the constants and dead-codes unread columns.
    - `.columns` / `device_buffers()` (any classic operator): first
      access runs the shared cached unpack kernel once (exactly the old
      put_packed_padded dispatch) and the batch behaves as a plain
      ColumnBatch thereafter.

    `col_meta` is `[(dtype, has_validity, dictionary, packed)]` per
    schema position; `packed=False` marks a colprune placeholder that has
    no segment in the wire buffer."""

    def __init__(self, schema: Schema, num_rows: int, cap: int,
                 buf: jax.Array, metas: Tuple, pairs: bool, col_meta):
        self.schema = schema
        self.num_rows = num_rows
        self.selection = None
        self._cap = cap
        self._buf = buf
        self._metas = metas
        self._pairs = pairs
        self._col_meta = list(col_meta)
        self._cols: Optional[List[Column]] = None

    # -- lazy plain-batch view ----------------------------------------
    @property
    def is_packed(self) -> bool:
        return self._cols is None

    @property
    def columns(self) -> List[Column]:  # type: ignore[override]
        if self._cols is None:
            self._unpack()
        return self._cols

    @columns.setter
    def columns(self, cols) -> None:
        self._cols = list(cols)

    @property
    def capacity(self) -> int:
        return self._cap

    def layout(self) -> Tuple:
        return (
            self._cap,
            tuple(
                (dt.id.value, dt.precision, dt.scale, has_validity)
                for dt, has_validity, _, _ in self._col_meta
            ),
        )

    def dictionaries(self) -> List[Optional[object]]:
        return [d for _, _, d, _ in self._col_meta]

    def _unpack(self) -> None:
        from blaze_tpu.runtime.pack import unpack_kernel

        arrays = iter(unpack_kernel(self._metas, self._pairs)(self._buf))
        cols: List[Column] = []
        for dt, has_validity, dictionary, packed in self._col_meta:
            if not packed:
                cols.append(Column(dt, _placeholder(self._cap, dt)))
                continue
            values = next(arrays)
            validity = next(arrays) if has_validity else None
            cols.append(Column(dt, values, validity, dictionary))
        self._cols = cols

    # -- fused-kernel view --------------------------------------------
    def packed_view(self) -> Optional["PackedView"]:
        """The fusion contract, or None once the batch was unpacked."""
        if self._cols is not None:
            return None
        return PackedView(
            self._buf,
            (
                self._metas,
                self._pairs,
                tuple(
                    (dt.id.value, dt.precision, dt.scale,
                     has_validity, packed)
                    for dt, has_validity, _, packed in self._col_meta
                ),
            ),
            self._build_unflatten,
            self.layout(),
        )

    def _build_unflatten(self):
        from blaze_tpu.runtime.pack import build_unpack_at

        split = build_unpack_at(self._metas, self._pairs)
        # capture only what unflatten reads: the closure lives in the
        # process-global kernel cache, so it must not pin this batch's
        # pyarrow dictionaries in host memory
        col_meta = [
            (dt, has_validity, packed)
            for dt, has_validity, _, packed in self._col_meta
        ]
        cap = self._cap

        def unflatten(u8):
            arrays = iter(split(u8))
            bufs: List[jax.Array] = []
            for dt, has_validity, packed in col_meta:
                if not packed:
                    phys = dt.physical_dtype()
                    shape = (
                        (cap, 2) if dt.is_wide_decimal else (cap,)
                    )
                    bufs.append(jnp.zeros(shape, dtype=phys))
                    continue
                bufs.append(next(arrays))
                if has_validity:
                    bufs.append(next(arrays))
            return bufs

        return unflatten


class DealtBatch:
    """One decoded batch cut into runs of rows, a run on each device of
    a mesh, still packed (runtime/pack.put_packed_dealt): what a scan
    yields to the mesh group-by in place of a ColumnBatch. `buf` is
    [n_dev, bytes] sharded on its first axis; `runs` the live rows of
    each run, `run_cap` its capacity; `col_meta` is PackedColumnBatch's.
    No operator but the mesh group-by's staging reads one."""

    __slots__ = ("schema", "num_rows", "buf", "metas", "pairs",
                 "run_cap", "runs", "col_meta")

    @staticmethod
    def from_arrow(rb, schema: Schema, present: Optional[Sequence[int]],
                   capacity: int, sharding) -> "DealtBatch":
        """`from_arrow_pruned` for a dealt batch: `rb` holds the columns
        of `schema` at `present` (all of them for None); every entry is
        padded to `capacity`, a whole batch's, whatever rows `rb` has,
        so that a split's batches share one layout."""
        from blaze_tpu.runtime.pack import put_packed_dealt

        _, entries, sub_meta = _host_entries(rb, capacity)
        self = DealtBatch()
        self.schema = schema
        self.num_rows = rb.num_rows
        (self.buf, self.metas, self.pairs, self.run_cap,
         self.runs) = put_packed_dealt(entries, rb.num_rows, sharding)
        it = iter(sub_meta)
        self.col_meta = [
            next(it) if present is None or i in present
            else (f.dtype, False, None, False)
            for i, f in enumerate(schema)
        ]
        return self

    def layout(self) -> Tuple:
        """What a program that unpacks this batch is keyed by."""
        return (self.metas, self.pairs, self.run_cap, tuple(
            (has_validity, packed)
            for _, has_validity, _, packed in self.col_meta))


@dataclasses.dataclass(frozen=True)
class PackedView:
    """What a fused kernel needs from a still-packed batch: the wire
    buffer (the kernel's traced input), a hashable cache-key component,
    a builder returning the traceable u8 -> device_buffers splitter, and
    the batch's layout descriptor (feeds the classic inner kernel)."""

    buf: jax.Array = dataclasses.field(compare=False)
    key: Tuple = ()
    build_unflatten: object = dataclasses.field(
        default=None, compare=False
    )
    layout: Tuple = ()


def packed_view(cb: ColumnBatch) -> Optional[PackedView]:
    """PackedView of a batch when fusion can consume it directly."""
    if isinstance(cb, PackedColumnBatch):
        return cb.packed_view()
    return None


import collections
import threading
import weakref

_PLACEHOLDER_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_PLACEHOLDER_CACHE_CAP = 32
_PLACEHOLDER_TRACK_ID = id(_PLACEHOLDER_CACHE)
_PLACEHOLDER_LOCK = threading.Lock()


def _placeholder(cap: int, dtype: DataType) -> jax.Array:
    """Shared all-zeros device column for pruned (never-read) scan
    positions. Safe to share across batches/plans: engine kernels are
    pure functions and never mutate input buffers. LRU-bounded (under a
    lock - prefetch worker threads race here) and accounted in the
    device-memory tracker so grace/spill budgeting sees the pinned HBM.
    Evicted arrays release their tracked bytes via weakref finalizer -
    only once the LAST in-flight batch referencing them drops - so the
    accounting never under-counts live HBM."""
    phys = dtype.physical_dtype()
    shape = (cap, 2) if dtype.is_wide_decimal else (cap,)
    key = (shape, str(phys))
    with _PLACEHOLDER_LOCK:
        arr = _PLACEHOLDER_CACHE.get(key)
        if arr is not None:
            _PLACEHOLDER_CACHE.move_to_end(key)
            return arr
    from blaze_tpu.runtime.memory import get_device_tracker

    new = jnp.zeros(shape, dtype=phys)
    tracker = get_device_tracker()
    with _PLACEHOLDER_LOCK:
        arr = _PLACEHOLDER_CACHE.get(key)
        if arr is not None:  # lost a double-miss race: reuse, drop ours
            _PLACEHOLDER_CACHE.move_to_end(key)
            return arr
        _PLACEHOLDER_CACHE[key] = new
        tracker.track(_PLACEHOLDER_TRACK_ID, int(new.nbytes))
        evicted = []
        while len(_PLACEHOLDER_CACHE) > _PLACEHOLDER_CACHE_CAP:
            _, old = _PLACEHOLDER_CACHE.popitem(last=False)
            evicted.append(old)
    for old in evicted:
        nbytes = int(old.nbytes)
        try:
            # release only when the last in-flight reference drops
            weakref.finalize(
                old, tracker.release, _PLACEHOLDER_TRACK_ID, nbytes
            )
        except TypeError:  # object not weak-referenceable
            tracker.release(_PLACEHOLDER_TRACK_ID, nbytes)
    return new


def _decimal_unscaled_i64(arr) -> np.ndarray:
    """Extract decimal128 unscaled values that fit in i64 (the engine's
    decimal representation; matches the reference's i64-only decimals,
    plan.proto:598-601)."""
    import pyarrow as pa

    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    buf = arr.buffers()[1]
    if buf is None:
        return np.zeros(len(arr), dtype=np.int64)
    raw = np.frombuffer(buf, dtype=np.int64)
    # decimal128 is 16 bytes little-endian; low limb is the i64 value for
    # anything within i64 range.
    lo = raw[arr.offset * 2::2][: len(arr)]
    return np.ascontiguousarray(lo)


def _decimal_limbs(arr) -> np.ndarray:
    """(n, 2) little-endian int64 limbs [lo bit-pattern, hi] of a
    decimal128 array - the full 16-byte representation."""
    import pyarrow as pa

    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    buf = arr.buffers()[1]
    n = len(arr)
    if buf is None:
        return np.zeros((n, 2), dtype=np.int64)
    raw = np.frombuffer(buf, dtype=np.int64)
    start = arr.offset * 2
    return np.ascontiguousarray(
        raw[start: start + 2 * n].reshape(n, 2)
    )


def _decimal_from_limbs(vals: np.ndarray, mask, precision: int,
                        scale: int):
    """(n, 2) [lo, hi] limbs -> Decimal128Array."""
    import pyarrow as pa

    n = len(vals)
    data = pa.py_buffer(np.ascontiguousarray(vals).tobytes())
    if mask is not None:
        validity = pa.array(~mask).buffers()[1]
    else:
        validity = None
    return pa.Array.from_buffers(
        pa.decimal128(precision, scale), n, [validity, data]
    )


def _decimal_from_unscaled_i64(vals: np.ndarray, mask, precision: int,
                               scale: int):
    """Inverse of _decimal_unscaled_i64: i64 unscaled -> Decimal128Array."""
    import pyarrow as pa

    n = len(vals)
    limbs = np.zeros(2 * n, dtype=np.int64)
    limbs[0::2] = vals  # low limb, little-endian
    limbs[1::2] = np.where(vals < 0, -1, 0)  # sign extension
    data = pa.py_buffer(limbs.tobytes())
    if mask is not None:
        validity = pa.array(~mask).buffers()[1]
    else:
        validity = None
    return pa.Array.from_buffers(
        pa.decimal128(precision, scale), n, [validity, data]
    )


def empty_batch(schema: Schema, capacity: Optional[int] = None) -> ColumnBatch:
    cap = capacity if capacity is not None else get_config().shape_buckets[0]
    cols = []
    for f in schema:
        phys = f.dtype.physical_dtype()
        shape = (cap, 2) if f.dtype.is_wide_decimal else (cap,)
        cols.append(
            Column(f.dtype, jnp.zeros(shape, dtype=phys), None, None)
        )
    return ColumnBatch(schema, cols, 0)


def row_mask(num_rows, capacity: int) -> jax.Array:
    """Mask of live rows for a padded batch; `num_rows` may be traced."""
    return jnp.arange(capacity, dtype=jnp.int32) < num_rows
