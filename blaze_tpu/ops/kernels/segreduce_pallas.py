"""Pallas kernel: segmented reduction (group-by core) for bounded key
domains.

Reference counterpart: the per-row accumulator update loops inside
DataFusion's grouped aggregation that the reference reuses
(from_proto.rs:452-545); SURVEY 7 names segmented-reduce as a TPU-first
Pallas target. A row-at-a-time hash-table update is the wrong shape for
a systolic array, and XLA lowers `segment_sum` to a serialized scatter
on TPU. This kernel instead reformulates the reduction as matmul:

    out[k] = sum_i v[i] * onehot(gid[i])[k]

i.e. a (rows x K) one-hot contraction - which runs on the MXU at full
tile utilization. The grid tiles rows (ROWS_BLK) x segments (K_BLK);
each (row-block, k-tile) step contracts the block's one-hot slice and
accumulates into the K-tile's output block (constant index_map over the
row dimension - the canonical Pallas accumulation pattern). FLOP cost is
rows*K, so this is the right core exactly where the scatter core's
direct-domain branch lives: group counts bounded by a few thousand
(TPC-DS rollup keys: brand/year/month/quarter/store). MIN/MAX ride the
same contraction with +/-inf masking and a max-reduction instead of a
dot - still VPU/MXU shaped, no scatter anywhere.

Tested with interpret=True on CPU (tests/test_pallas_kernels.py) and
nowhere else: the v5e compiler REFUSES both kernels. First the
(_K_BLK // 128, 128) = (4, 128) output block is not a legal TPU block
shape (pinned in tests/test_chip_compile.py); with a 1024-wide k-tile
Mosaic next refuses the in-kernel (8, 128) -> (1024, 1) relayout of
`gid` ("unsupported shape cast") and the sum kernel trips the 32-bit
scalar rule under x64. It has never run on a chip, and the
BLAZE_SEGREDUCE selector that reached it from ops/hash_aggregate is
gone (ROADMAP D7 decides the file's fate).
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_ROWS_BLK = 1024      # rows per grid step (8 sublanes x 128 lanes)
_K_BLK = 512          # segment slots per grid step
_MAX_K = 8192         # beyond this, rows*K FLOPs lose to the sort core


def _sum_kernel(gid_ref, v_ref, out_ref):
    """One (row-block, k-tile) step: out[k] += v . onehot(gid)[:, k]."""
    rb = pl.program_id(1)
    k0 = pl.program_id(0) * _K_BLK
    gid = gid_ref[:].reshape(_ROWS_BLK)
    v = v_ref[:].reshape(_ROWS_BLK).astype(jnp.float32)
    # one-hot slice for this k-tile: (ROWS_BLK, K_BLK)
    cols = jax.lax.broadcasted_iota(
        jnp.int32, (_ROWS_BLK, _K_BLK), 1
    ) + k0
    hit = gid[:, None] == cols
    # IEEE hazard: 0 * NaN/inf = NaN, so one non-finite row anywhere in
    # the block would poison EVERY segment the contraction touches. The
    # MXU dot runs over sanitized values only; non-finite rows re-enter
    # through a where-masked VPU reduction (a select, not a multiply,
    # so unselected NaN/inf rows truly contribute nowhere) - gated by
    # pl.when so the all-finite common case pays nothing extra.
    finite = jnp.isfinite(v)
    part = jax.lax.dot_general(
        jnp.where(finite, v, jnp.float32(0.0))[None, :],
        hit.astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        # HIGHEST: default precision truncates f32 operands to bf16 on
        # the MXU, which would silently diverge from the XLA scatter
        # path this kernel must match
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).reshape(_K_BLK)

    @pl.when(rb == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    out_ref[:] = out_ref[:] + part.reshape(out_ref.shape)

    @pl.when(jnp.any(~finite))
    def _nonfinite_fixup():
        corr = jnp.sum(
            jnp.where(
                hit & ~finite[:, None], v[:, None], jnp.float32(0.0)
            ),
            axis=0,
        )
        out_ref[:] = out_ref[:] + corr.reshape(out_ref.shape)


def _minmax_kernel(gid_ref, v_ref, out_ref, *, is_min: bool):
    rb = pl.program_id(1)
    k0 = pl.program_id(0) * _K_BLK
    gid = gid_ref[:].reshape(_ROWS_BLK)
    v = v_ref[:].reshape(_ROWS_BLK).astype(jnp.float32)
    cols = jax.lax.broadcasted_iota(
        jnp.int32, (_ROWS_BLK, _K_BLK), 1
    ) + k0
    neutral = jnp.float32(np.inf if is_min else -np.inf)
    masked = jnp.where(
        gid[:, None] == cols, v[:, None], neutral
    )
    part = (
        jnp.min(masked, axis=0) if is_min else jnp.max(masked, axis=0)
    )

    @pl.when(rb == 0)
    def _init():
        out_ref[:] = jnp.full_like(out_ref, neutral)

    cur = out_ref[:].reshape(_K_BLK)
    out_ref[:] = (
        jnp.minimum(cur, part) if is_min else jnp.maximum(cur, part)
    ).reshape(out_ref.shape)


def _call(kernel, gid, v, k: int):
    cap = gid.shape[0]
    n_rb = cap // _ROWS_BLK
    n_kb = k // _K_BLK
    grid = (n_kb, n_rb)
    gid2 = gid.reshape(n_rb, _ROWS_BLK // _LANES, _LANES)
    v2 = v.reshape(n_rb, _ROWS_BLK // _LANES, _LANES)
    blk = (_ROWS_BLK // _LANES, _LANES)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1,) + blk, lambda kb, rb: (rb, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1,) + blk, lambda kb, rb: (rb, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (_K_BLK // _LANES, _LANES), lambda kb, rb: (kb, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct(
            (k // _LANES, _LANES), jnp.float32
        ),
        interpret=_interpret(),
    )(gid2, v2).reshape(k)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def supports(capacity: int, k: int) -> bool:
    """Static applicability: row/segment tiles must divide evenly and
    the rows*K FLOP budget must stay MXU-cheap."""
    return (
        capacity % _ROWS_BLK == 0
        and k % _K_BLK == 0
        and k <= _MAX_K
    )


@partial(jax.jit, static_argnames=("k",))
def segment_sum(gid: jax.Array, v: jax.Array, k: int) -> jax.Array:
    """sum of v per segment, f32, for gid in [0, k). Rows with gid
    outside [0, k) contribute nowhere (the one-hot row is all zero) -
    callers park dead rows at an out-of-range id or pre-zero them."""
    return _call(_sum_kernel, gid.astype(jnp.int32), v, k)


@partial(jax.jit, static_argnames=("k", "is_min"))
def segment_minmax(gid: jax.Array, v: jax.Array, k: int,
                   is_min: bool) -> jax.Array:
    """min/max of v per segment, f32; empty segments hold +/-inf."""
    return _call(
        partial(_minmax_kernel, is_min=is_min),
        gid.astype(jnp.int32), v, k,
    )
