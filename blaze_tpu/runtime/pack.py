"""Packed host<->device transfers: O(1) round trips per batch.

Why this exists: every `jax.Array` leaf in a `device_get` and every
`device_put` pays its own host<->device round trip. On a network-attached
TPU each round trip is tens of milliseconds, so a 20-column batch costs
20x the latency of a 1-column batch even when the bytes are tiny. The
reference hands a whole batch across its FFI boundary as ONE pointer
pair per batch (exec.rs:205-255); the TPU-native equivalent is to pack
all of a batch's buffers into ONE uint8 buffer on one side and split it
on the other:

- D2H (`get_packed`): a cached jit kernel slices each buffer to the live
  prefix, bitcasts to bytes and concatenates -> one fetch -> host views
  split it back (zero-copy numpy views into the fetched buffer).
- H2D (`put_packed`): host concatenates raw bytes -> one device_put ->
  a cached jit kernel splits and bitcasts back to typed device arrays.

Byte order: XLA's bitcast-convert to/from uint8 enumerates bytes in
little-endian element order on all supported backends, matching numpy's
`.view` on little-endian hosts; `tests/test_pack.py` round-trips every
engine dtype to pin this.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from blaze_tpu.runtime.dispatch import cached_kernel, record
from blaze_tpu.obs import trace as obs_trace
from blaze_tpu.testing import chaos


def _np_dtype(a) -> np.dtype:
    return np.dtype(a.dtype)


def _packed_nbytes(shape: Tuple[int, ...], dt: np.dtype) -> int:
    n = int(np.prod(shape)) if shape else 1
    return n * (1 if dt == np.bool_ else dt.itemsize)


def _f64_pairs() -> bool:
    """True when float64 must travel as exact (hi, lo) float32 pairs.

    The TPU backend has no hardware f64: XLA emulates it as a
    double-single (two-float32) pair with an f32 exponent range, and the
    TPU compiler refuses bitcast-convert from f64 ("rewriting
    computation to not contain X64 element types ... not implemented";
    tests/test_chip_compile.py pins the refusal for the v5e).
    hi = f32(x), lo = f32(x - hi) is the exact double-single
    decomposition - it round-trips every value the device itself can
    represent, using only arithmetic + f32 bitcasts. CPU (true IEEE f64)
    keeps the direct byte bitcast, which is lossless there."""
    return jax.default_backend() != "cpu"


def _build_pack(slice_rows: Optional[int], f64_pairs: bool):
    """Device kernel: [arrays] -> one uint8 buffer. Shapes/dtypes are
    picked up from the traced inputs; jax.jit specializes per signature
    under the single cache entry."""

    def pack(bufs):
        parts = []
        for b in bufs:
            if slice_rows is not None and b.ndim >= 1:
                b = b[:slice_rows]
            if b.dtype == jnp.bool_:
                b = b.astype(jnp.uint8)
            if f64_pairs and b.dtype == jnp.float64:
                hi = b.astype(jnp.float32)
                lo = (b - hi.astype(jnp.float64)).astype(jnp.float32)
                lo = jnp.where(jnp.isfinite(hi), lo, jnp.float32(0))
                b = jnp.stack([hi, lo], axis=-1)
            b = b.reshape(-1)
            if b.dtype != jnp.uint8:
                b = jax.lax.bitcast_convert_type(b, jnp.uint8)
                b = b.reshape(-1)
            parts.append(b)
        if not parts:
            return jnp.zeros(0, dtype=jnp.uint8)
        return jnp.concatenate(parts)

    return pack


def _build_unpack(metas: Tuple[Tuple[str, Tuple[int, ...]], ...],
                  f64_pairs: bool):
    """Device kernel: one uint8 buffer -> [typed arrays] per metas
    (contiguous layout: the `concatenate`d put_packed wire format)."""
    at = []
    off = 0
    for dt_s, shape in metas:
        nb = _packed_nbytes(shape, np.dtype(dt_s))
        at.append((dt_s, shape, off, nb))
        off += nb
    return _build_unpack_at(tuple(at), f64_pairs)


def _f64_to_pair_bytes(a: np.ndarray) -> np.ndarray:
    """Host-side exact double-single split, little-endian f32-pair bytes."""
    hi = a.astype(np.float32)
    with np.errstate(invalid="ignore"):
        lo = (a - hi.astype(np.float64)).astype(np.float32)
    lo = np.where(np.isfinite(hi), lo, np.float32(0))
    pair = np.empty(a.shape + (2,), dtype=np.float32)
    pair[..., 0] = hi
    pair[..., 1] = lo
    return pair.reshape(-1).view(np.uint8)


def _pair_bytes_to_f64(seg: np.ndarray, n: int) -> np.ndarray:
    pair = seg.view(np.float32).reshape(n, 2)
    hi = pair[:, 0].astype(np.float64)
    lo = pair[:, 1].astype(np.float64)
    return np.where(pair[:, 1] == 0, hi, hi + lo)


def put_packed(arrays: Sequence[np.ndarray]) -> List[jax.Array]:
    """Move host arrays to device in ONE transfer + ONE split dispatch."""
    if not arrays:
        return []
    if chaos.ACTIVE:
        # chaos seam: the host->device staging transfer fails (a
        # network-attached device drops the RPC)
        chaos.fire("h2d.transfer", n_arrays=len(arrays))
    if obs_trace.ACTIVE:
        # obs seam: the H2D staging transfer as one span (pack +
        # device_put + unpack-kernel launch); no-op without a
        # thread-current recorder
        with obs_trace.span("h2d", n_arrays=len(arrays)):
            return _put_packed(arrays)
    return _put_packed(arrays)


def _put_packed(arrays: Sequence[np.ndarray]) -> List[jax.Array]:
    pairs = _f64_pairs()
    metas = tuple((str(_np_dtype(a)), tuple(a.shape)) for a in arrays)
    parts = []
    for a in arrays:
        a = np.ascontiguousarray(a)
        if a.dtype == np.bool_:
            a = a.astype(np.uint8)
        if pairs and a.dtype == np.float64:
            parts.append(_f64_to_pair_bytes(a))
            continue
        parts.append(a.reshape(-1).view(np.uint8))
    buf = np.concatenate(parts) if parts else np.zeros(0, np.uint8)
    record("h2d_batches")
    dev = jax.device_put(buf)
    fn = cached_kernel(
        ("h2d_unpack", metas, pairs),
        lambda: _build_unpack(metas, pairs),
    )
    return list(fn(dev))


_ALIGN = 16  # segment alignment so host typed views into the buffer work


def _aligned_metas(entries):
    """[(dtype_str, full_shape, off, nb)] with aligned offsets + total."""
    metas = []
    off = 0
    for vals, cap, _fill in entries:
        tail = tuple(vals.shape[1:])
        dt = np.dtype(vals.dtype)
        nb = _packed_nbytes((cap,) + tail, dt)
        metas.append((str(dt), (cap,) + tail, off, nb))
        off += (nb + _ALIGN - 1) // _ALIGN * _ALIGN
    return tuple(metas), off


def _build_unpack_at(metas, f64_pairs: bool):
    """Device kernel: one uint8 buffer -> typed arrays at given offsets."""

    def unpack(u8):
        outs = []
        for dt_s, shape, off, nb in metas:
            dt = np.dtype(dt_s)
            n = int(np.prod(shape)) if shape else 1
            seg = jax.lax.slice(u8, (off,), (off + nb,))
            if dt == np.bool_:
                arr = seg.astype(jnp.bool_)
            elif f64_pairs and dt == np.float64:
                pair = jax.lax.bitcast_convert_type(
                    seg.reshape(2 * n, 4), jnp.float32
                ).reshape(n, 2)
                hi = pair[:, 0].astype(jnp.float64)
                lo = pair[:, 1].astype(jnp.float64)
                arr = jnp.where(pair[:, 1] == 0, hi, hi + lo)
            elif dt.itemsize == 1:
                arr = jax.lax.bitcast_convert_type(seg, jnp.dtype(dt))
            else:
                arr = jax.lax.bitcast_convert_type(
                    seg.reshape(n, dt.itemsize), jnp.dtype(dt)
                )
            outs.append(arr.reshape(shape))
        return outs

    return unpack


def put_packed_padded(entries: Sequence[Tuple[np.ndarray, int, int]]
                      ) -> List[jax.Array]:
    """Pad + pack + transfer in ONE host copy and ONE device round trip.

    Each entry is `(vals, cap, fill)`: a host array whose leading axis has
    n live rows, the padded capacity, and the scalar tail-fill value. The
    returned device arrays have shape `(cap,) + vals.shape[1:]`. This
    fuses the shape-bucket padding copy (previously a separate
    `np.zeros(cap); padded[:n] = vals` per column) with the transfer
    packing copy - the padded column is written directly into its
    aligned segment of the single wire buffer."""
    dev, metas, pairs = put_packed_padded_lazy(entries)
    if dev is None:
        return []
    fn = cached_kernel(
        ("h2d_unpack_at", metas, pairs),
        lambda: _build_unpack_at(metas, pairs),
    )
    return list(fn(dev))


def put_packed_padded_lazy(
    entries: Sequence[Tuple[np.ndarray, int, int]]
) -> Tuple[Optional[jax.Array], Tuple, bool]:
    """Pad + pack + transfer WITHOUT the unpack dispatch.

    Returns `(device_u8_buffer, metas, f64_pairs)`; the caller either
    splits the buffer later with `unpack_kernel(metas, pairs)` (one
    dispatch, the classic path) or - the pipeline-fusion fast path -
    composes `build_unpack_at(metas, pairs)` into its OWN jitted kernel
    so transfer-unpacking and the consuming operator chain cost a single
    dispatch total (batch.PackedColumnBatch owns that deferral)."""
    if not entries:
        return None, (), _f64_pairs()
    if obs_trace.ACTIVE:
        # obs seam: the scan path's h2d stage (pad + pack +
        # device_put); `put_packed` above has no caller left in the
        # engine, so its span alone never showed in a served task
        with obs_trace.span("h2d", n_arrays=len(entries)):
            return _put_packed_padded_lazy(entries)
    return _put_packed_padded_lazy(entries)


def _put_packed_padded_lazy(entries):
    pairs = _f64_pairs()
    norm = []
    for vals, cap, fill in entries:
        vals = np.asarray(vals)
        norm.append((vals, cap, fill))
    metas, total = _aligned_metas(norm)
    buf = np.empty(total, dtype=np.uint8)
    _fill_packed(buf, norm, metas, pairs)
    record("h2d_batches")
    dev = jax.device_put(buf)
    return dev, metas, pairs


def _fill_packed(buf: np.ndarray, norm, metas, pairs: bool) -> None:
    """Write each entry, padded to its capacity, into its segment."""
    for (vals, cap, fill), (dt_s, shape, off, nb) in zip(norm, metas):
        n = vals.shape[0] if vals.ndim else 0
        dt = np.dtype(dt_s)
        seg = buf[off: off + nb]
        if dt == np.bool_:
            view = seg.reshape(shape)
            view[:n] = vals.astype(np.uint8).reshape(vals.shape)
            view[n:] = 1 if fill else 0
        elif pairs and dt == np.float64:
            # the pair tail encodes only 0.0; a nonzero fill would be
            # silently wrong, so enforce the contract (ValueError, not
            # assert: must survive python -O)
            if fill:
                raise ValueError(
                    "f64-pair padding supports fill=0 only (got "
                    f"{fill!r})"
                )
            pb = _f64_to_pair_bytes(np.ascontiguousarray(vals))
            seg[: pb.size] = pb
            seg[pb.size:] = 0
        else:
            view = seg.view(dt).reshape(shape)
            view[:n] = vals
            view[n:] = fill


def deal_cuts(num_rows: int, n_dev: int) -> List[Tuple[int, int]]:
    """[(first row, end row)] of the `n_dev` runs a batch of `num_rows`
    rows is dealt in: equal shares, the last ones shorter or empty."""
    share = -(-num_rows // n_dev)
    return [(min(d * share, num_rows), min((d + 1) * share, num_rows))
            for d in range(n_dev)]


def put_packed_dealt(entries: Sequence[Tuple[np.ndarray, int, int]],
                     num_rows: int, sharding
                     ) -> Tuple[jax.Array, Tuple, bool, int, List[int]]:
    """`put_packed_padded_lazy` for a batch dealt over a mesh: the
    batch's rows are cut into as many runs as `sharding` has devices,
    every run is packed as a batch of its own (its first segment holds
    the run's row count) into one row of a [n_dev, bytes] host buffer,
    and one sharded `device_put` lands a run on each device. Every
    entry's capacity is a whole batch's; a run's is its share of it.

    Returns (buffer, metas, f64_pairs, run capacity, rows a run);
    `build_unpack_at(metas, pairs)` splits a device's row, the count
    first."""
    with (obs_trace.span("h2d", n_arrays=len(entries))
          if obs_trace.ACTIVE else obs_trace.NULL):
        return _put_packed_dealt(entries, num_rows, sharding)


def _put_packed_dealt(entries, num_rows: int, sharding):
    pairs = _f64_pairs()
    n_dev = len(sharding.device_set)
    cuts = deal_cuts(num_rows, n_dev)
    norm = [(np.asarray(v), -(-cap // n_dev), fill)
            for v, cap, fill in entries]
    run_cap = max(c for _, c, _ in norm)
    count = (np.zeros(1, np.int32), 4, 0)
    metas, total = _aligned_metas([count] + norm)
    buf = np.empty((n_dev, total), dtype=np.uint8)
    for d, (lo, hi) in enumerate(cuts):
        run = [(np.full(1, hi - lo, np.int32), 4, 0)] + [
            (v[lo:hi] if v.ndim else v, c, f) for v, c, f in norm]
        _fill_packed(buf[d], run, metas, pairs)
    record("h2d_batches")
    dev = jax.device_put(buf, sharding)
    return dev, metas, pairs, run_cap, [hi - lo for lo, hi in cuts]


def unpack_kernel(metas, pairs: bool):
    """The cached one-dispatch splitter for a lazily packed buffer (same
    cache key as the classic put_packed_padded path, so both share one
    compiled executable per layout)."""
    return cached_kernel(
        ("h2d_unpack_at", metas, pairs),
        lambda: _build_unpack_at(metas, pairs),
    )


def build_unpack_at(metas, pairs: bool):
    """Traceable u8-buffer splitter for composing into a larger jitted
    kernel (pipeline fusion: unpack + operator chain = one program)."""
    return _build_unpack_at(metas, pairs)


def get_packed(arrays: Sequence[object],
               slice_rows: Optional[int] = None) -> List[np.ndarray]:
    """Fetch a mixed list of jax/numpy arrays in ONE device round trip.

    numpy entries pass through untouched. `slice_rows` statically caps the
    FIRST axis of every device array with ndim>=1 before the transfer (the
    caller knows live rows << capacity); the returned host arrays reflect
    the capped shapes."""
    out: List[object] = list(arrays)
    dev_idx = [
        i for i, a in enumerate(arrays)
        if isinstance(a, jax.Array)
    ]
    if not dev_idx:
        return out  # type: ignore[return-value]
    pairs = _f64_pairs()
    fn = cached_kernel(
        ("d2h_pack", slice_rows, pairs),
        lambda: _build_pack(slice_rows, pairs),
    )
    packed = fn([arrays[i] for i in dev_idx])
    record("d2h_fetches")
    host = np.asarray(packed)
    off = 0
    for i in dev_idx:
        a = arrays[i]
        shape = tuple(a.shape)
        if slice_rows is not None and len(shape) >= 1:
            shape = (min(slice_rows, shape[0]),) + shape[1:]
        dt = _np_dtype(a)
        nb = _packed_nbytes(shape, dt)
        seg = host[off: off + nb]
        if dt == np.bool_:
            vals = seg.view(np.bool_)
        elif pairs and dt == np.float64:
            n = int(np.prod(shape)) if shape else 1
            vals = _pair_bytes_to_f64(seg, n)
        else:
            vals = seg.view(dt)
        out[i] = vals.reshape(shape)
        off += nb
    return out  # type: ignore[return-value]


def pack_in_kernel(arrays: Sequence[jax.Array]) -> jax.Array:
    """Traceable packer: concatenate typed device arrays into one uint8
    buffer INSIDE an enclosing jitted kernel (f64 travels as exact
    double-single pairs off-CPU, mirroring `_build_pack`). Pair with
    `unpack_host` so a kernel's small auxiliary outputs (streaming
    aggregate carry states) reach the host in one fetch with no extra
    pack dispatch."""
    return _build_pack(None, _f64_pairs())(list(arrays))


def unpack_host(host_u8: np.ndarray,
                specs: Sequence[Tuple[str, Tuple[int, ...]]]
                ) -> List[np.ndarray]:
    """Split a host copy of a `pack_in_kernel` buffer back into typed
    arrays per `(dtype_str, shape)` specs (the wire format of
    `_build_pack`: contiguous, unaligned, bool as u8, f64 as f32 pairs
    off-CPU)."""
    pairs = _f64_pairs()
    out: List[np.ndarray] = []
    off = 0
    for dt_s, shape in specs:
        dt = np.dtype(dt_s)
        n = int(np.prod(shape)) if shape else 1
        nb = n * (1 if dt == np.bool_ else dt.itemsize)
        if pairs and dt == np.float64:
            nb = n * 8  # two f32 per element
        seg = host_u8[off: off + nb]
        if dt == np.bool_:
            vals = seg.view(np.uint8).astype(np.bool_)
        elif pairs and dt == np.float64:
            vals = _pair_bytes_to_f64(seg, n)
        else:
            vals = seg.view(dt)
        out.append(vals.reshape(shape))
        off += nb
    return out
