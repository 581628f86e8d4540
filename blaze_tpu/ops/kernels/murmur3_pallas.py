"""Pallas kernel: Spark murmur3 + pmod partition ids.

The shuffle writer's per-row hot op (reference computes it row-batched in
Rust, spark_hash.rs create_hashes + pmod; SURVEY 7 calls for it as a Pallas
kernel). Pure VPU uint32 integer ops over (8, 128)-tiled row blocks; the
partition count is compile-time static so the modulo strengthens to
multiply-shift.

64-bit inputs enter pre-split as two uint32 word planes (the TPU backend
neither loads s64 tiles natively nor bitcasts them - the split is two
cheap emulated i64 ops outside the kernel, amortized over the whole
column).

The two jitted entry points keep their names: a device trace lists their
programs on the `XLA Modules` line as `jit_partition_ids_int32(...)` and
`jit_partition_ids_int64(...)`, and the benchmark's
`shuffle_hash_roofline` finds them by those names
(tests/test_repart_key.py pins them).
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# All arithmetic runs in int32: two's-complement wrap-around makes add /
# multiply / xor / shifts bit-identical to the uint32 formulation, and
# Mosaic's int32 lowering is the well-trodden path. Right shifts must be
# LOGICAL (lax.shift_right_logical), never arithmetic.
_i32 = lambda x: np.int32(np.uint32(x))  # noqa: E731
_C1 = _i32(0xCC9E2D51)
_C2 = _i32(0x1B873593)
_M5 = _i32(0xE6546B64)
_FX1 = _i32(0x85EBCA6B)
_FX2 = _i32(0xC2B2AE35)
_SEED = np.int32(42)

_LANES = 128
_SUBLANES = 8
_BLOCK_ROWS = _LANES * _SUBLANES  # minimum row granularity
# One pallas invocation processes a VMEM-sized chunk (the whole-block
# form, no grid); larger columns run through an outer lax.map.
_CHUNK_ROWS = 1 << 19  # 512K rows = 2 MB int32 in / 2 MB out of ~16MB VMEM


def _shr(x, r: int):
    return jax.lax.shift_right_logical(x, np.int32(r))


def _rotl(x, r: int):
    return (x << np.int32(r)) | _shr(x, 32 - r)


def _mix_k1(k1):
    k1 = k1 * _C1
    k1 = _rotl(k1, 15)
    return k1 * _C2


def _mix_h1(h1, k1):
    h1 = h1 ^ k1
    h1 = _rotl(h1, 13)
    return h1 * np.int32(5) + _M5


def _fmix(h1, length):
    h1 = h1 ^ np.int32(length)
    h1 = h1 ^ _shr(h1, 16)
    h1 = h1 * _FX1
    h1 = h1 ^ _shr(h1, 13)
    h1 = h1 * _FX2
    return h1 ^ _shr(h1, 16)


def _pmod_i32(h, n: int):
    r = h % np.int32(n)
    return jnp.where(r < 0, r + np.int32(n), r)


def _kernel_int32(v_ref, out_ref, *, n_parts: int):
    v = v_ref[:]
    h = _fmix(_mix_h1(_SEED, _mix_k1(v)), 4)
    out_ref[:] = _pmod_i32(h, n_parts)


def _kernel_int64(lo_ref, hi_ref, out_ref, *, n_parts: int):
    h1 = _mix_h1(_SEED, _mix_k1(lo_ref[:]))
    h1 = _mix_h1(h1, _mix_k1(hi_ref[:]))
    h = _fmix(h1, 8)
    out_ref[:] = _pmod_i32(h, n_parts)


def _chunked(cap: int):
    assert cap % _BLOCK_ROWS == 0, "shape buckets are multiples of 1024"
    chunk = min(cap, _CHUNK_ROWS)
    while cap % chunk:
        chunk //= 2
    return cap // chunk, chunk


def _call_1in(kernel, v2, interpret):
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(v2.shape, jnp.int32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
    )(v2)


def _call_2in(kernel, lo, hi, interpret):
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(lo.shape, jnp.int32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
    )(lo, hi)


@partial(jax.jit, static_argnames=("n_parts", "interpret"))
def partition_ids_int32(values: jax.Array, n_parts: int,
                        interpret: bool = False) -> jax.Array:
    """Spark partition id per row for one int32/date32 key column.
    `values` length must be a multiple of 1024 (shape buckets are)."""
    cap = values.shape[0]
    n_chunks, chunk = _chunked(cap)
    kernel = partial(_kernel_int32, n_parts=n_parts)
    v3 = values.astype(jnp.int32).reshape(
        n_chunks, chunk // _LANES, _LANES
    )
    out = jax.lax.map(
        lambda v2: _call_1in(kernel, v2, interpret), v3
    )
    return out.reshape(cap)


@partial(jax.jit, static_argnames=("n_parts", "interpret"))
def partition_ids_int64(values: jax.Array, n_parts: int,
                        interpret: bool = False) -> jax.Array:
    """Spark partition id per row for one int64/timestamp key column."""
    cap = values.shape[0]
    n_chunks, chunk = _chunked(cap)
    v = values.astype(jnp.int64)
    lo = jnp.bitwise_and(v, 0xFFFFFFFF).astype(jnp.int32)
    hi = jnp.bitwise_and(jnp.right_shift(v, 32), 0xFFFFFFFF).astype(
        jnp.int32
    )
    shape3 = (n_chunks, chunk // _LANES, _LANES)
    kernel = partial(_kernel_int64, n_parts=n_parts)
    out = jax.lax.map(
        lambda b: _call_2in(kernel, b[0], b[1], interpret),
        (lo.reshape(shape3), hi.reshape(shape3)),
    )
    return out.reshape(cap)


def supports(dtype_id: str, capacity: int) -> bool:
    return capacity % _BLOCK_ROWS == 0 and dtype_id in (
        "int32", "date32", "int64", "timestamp_us"
    )
