"""Pallas kernel: fused masked column statistics (sum/min/max/count).

SURVEY 7's build plan calls for segmented-reduce-class Pallas kernels
beyond murmur3. Full sorted-segment reductions need scatter stores;
what IS expressible in the whole-block form murmur3_pallas uses is the
single-group
core every keyless aggregate and every range-sampling/statistics pass
runs: ONE memory pass over a masked f32/i32 column producing all four
reduction states at once, instead of four separate XLA reductions each
re-reading the column from HBM.

Layout mirrors murmur3_pallas: (rows/128, 128) VMEM blocks, chunked
through an outer lax.map; per-chunk partials (shape (4,) per chunk)
combine outside the kernel - the combine is O(chunks), the pass is
O(rows). Masked-out lanes contribute the operation identity (0 for
sum/count, +inf/-inf for min/max); an all-masked column reports
count 0 and the caller maps min/max to NULL, exactly like the
aggregate's masked reductions.

Status: a STANDALONE fast path with its own API - `supports()` gates
eligibility (f32/i32, bucket-aligned) but nothing dispatches to it yet.
Interpret mode pins semantics on the CPU test mesh
(tests/test_pallas_kernels.py); the v5e compiler accepts it
(tests/test_chip_compile.py); it has not run on a chip.

Accuracy: per-chunk partials accumulate in f32 (512K-row chunks keep
counts exact; value sums carry f32 rounding - rtol ~1e-5); the
cross-chunk combine runs in f64 outside the kernel. Callers needing
exact integer sums must keep the XLA int64 path.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_BLOCK_ROWS = 1024
_CHUNK_ROWS = 1 << 19  # 512K rows: 2MB values + 2MB mask in VMEM

_POS_INF = np.float32(np.inf)
_NEG_INF = np.float32(-np.inf)


def supports(capacity: int, dtype) -> bool:
    return (
        capacity % _BLOCK_ROWS == 0
        and jnp.dtype(dtype) in (jnp.dtype(jnp.float32),
                                 jnp.dtype(jnp.int32))
    )


def _kernel(v_ref, m_ref, out_ref):
    v = v_ref[:].astype(jnp.float32)
    m = m_ref[:]
    live = m != 0
    s = jnp.sum(jnp.where(live, v, np.float32(0.0)))
    lo = jnp.min(jnp.where(live, v, _POS_INF))
    hi = jnp.max(jnp.where(live, v, _NEG_INF))
    n = jnp.sum(m.astype(jnp.float32))
    # (1, 4) output tile: scalar reductions packed on the lane axis
    out_ref[0, 0] = s
    out_ref[0, 1] = lo
    out_ref[0, 2] = hi
    out_ref[0, 3] = n


def _call(v2, m2, interpret):
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((1, 4), jnp.float32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        interpret=interpret,
    )(v2, m2)


def _chunked(cap: int):
    chunk = min(cap, _CHUNK_ROWS)
    while cap % chunk:
        chunk //= 2
    return cap // chunk, chunk


@partial(jax.jit, static_argnames=("interpret",))
def masked_stats(values: jax.Array, mask: jax.Array,
                 interpret: bool = False) -> jax.Array:
    """(sum, min, max, count) over rows where mask!=0, as one f32[4].
    `values` length must be a multiple of 1024 (shape buckets are);
    empty selection -> (0, +inf, -inf, 0)."""
    cap = values.shape[0]
    n_chunks, chunk = _chunked(cap)
    shape3 = (n_chunks, chunk // _LANES, _LANES)
    v3 = values.astype(jnp.float32).reshape(shape3)
    m3 = mask.astype(jnp.int32).reshape(shape3)
    parts = jax.lax.map(
        lambda b: _call(b[0], b[1], interpret), (v3, m3)
    )  # (n_chunks, 1, 4)
    # combine across chunks in f64: counts stay exact past 2^24 rows
    # and the sum-of-partials adds no further f32 rounding
    parts = parts.reshape(n_chunks, 4).astype(jnp.float64)
    return jnp.stack([
        jnp.sum(parts[:, 0]),
        jnp.min(parts[:, 1]),
        jnp.max(parts[:, 2]),
        jnp.sum(parts[:, 3]),
    ])
