"""Pallas kernel: mask compaction (the filter pipeline-breaker).

Reference counterpart: the selection-vector materialization inside
FilterExec that the reference gets from DataFusion's `filter` compute
kernel (from_proto.rs FilterExec arm); SURVEY 7 names compaction as the
second TPU-first Pallas target. The engine usually DEFERS selection
(batch.ColumnBatch.selection rides through fused kernels), but pipeline
breakers (shuffle writers, external spill, host hand-off) must
physically drop dead rows.

A naive gather-by-sorted-indices serializes on TPU. This kernel keeps
the index computation matrix-shaped:

  per row-block (1024 rows):
    pos[i]  = cumsum(keep)[i] - 1          (block-local target slot)
    out[j]  = sum_i idx[i] * (pos[i] == j & keep[i])   - an MXU
              contraction of block-LOCAL ROW INDICES against the
              permutation one-hot
  per block it also emits the block's keep-count.

The kernel compacts INDICES, not data: local indices are in [0, 1024),
always exact in f32, so the IEEE 0*NaN hazard of contracting raw data
(one non-finite row anywhere in a block would poison every surviving
row of that block) cannot arise. Cross-block stitching happens in jnp
glue (`compact_perm`): block outputs are dense prefixes, so indices
derived from the per-block count prefix sum compose into one global
source-row permutation. Data columns of ANY dtype then move by a
single bit-exact gather - one kernel launch serves every column
compacted by the same mask.

Tested with interpret=True on CPU (tests/test_pallas_kernels.py) and
nowhere else: the v5e compiler REFUSES this kernel (the (1, 1) SMEM
count block is not a legal TPU block shape - the refusal is pinned in
tests/test_chip_compile.py), it has never run on a chip, and nothing in
blaze_tpu/ calls it (ROADMAP D7).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_ROWS_BLK = 1024


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _compact_kernel(v_ref, keep_ref, out_ref, cnt_ref):
    v = v_ref[:].reshape(_ROWS_BLK).astype(jnp.float32)
    keep = keep_ref[:].reshape(_ROWS_BLK)
    kept = keep != 0
    pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
    pos = jnp.where(kept, pos, -1)
    cols = jax.lax.broadcasted_iota(
        jnp.int32, (_ROWS_BLK, _ROWS_BLK), 1
    )
    oh = (pos[:, None] == cols).astype(jnp.float32)
    out = jax.lax.dot_general(
        v[None, :], oh,
        (((1,), (0,)), ((), ())),
        # HIGHEST: default MXU precision truncates operands to bf16,
        # which would corrupt the "moved exactly once" guarantee (and
        # the int32 plane reconstruction) on real hardware
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).reshape(_ROWS_BLK)
    out_ref[:] = out.reshape(out_ref.shape)
    cnt_ref[0, 0] = jnp.sum(keep.astype(jnp.int32))


def _call_compact(v2, keep2, n_blocks: int):
    blk = (_ROWS_BLK // _LANES, _LANES)
    return pl.pallas_call(
        _compact_kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec(
                (1,) + blk, lambda b: (b, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1,) + blk, lambda b: (b, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1,) + blk, lambda b: (b, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1), lambda b: (b, 0), memory_space=pltpu.SMEM
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(
                (n_blocks,) + blk, jnp.float32
            ),
            jax.ShapeDtypeStruct((n_blocks, 1), jnp.int32),
        ],
        interpret=_interpret(),
    )(v2, keep2)


def supports(capacity: int) -> bool:
    return capacity % _ROWS_BLK == 0


@jax.jit
def compact_perm(keep: jax.Array):
    """Compute the compaction PERMUTATION: for every global output slot,
    the global source row, plus the live count.

    The kernel contracts block-LOCAL row indices (values in [0, 1024),
    always exactly representable in f32 - so the IEEE 0*NaN hazard of
    contracting raw data can never arise) against the one-hot; data
    columns then move by a plain gather. One kernel launch serves every
    column and dtype compacted by the same mask."""
    cap = keep.shape[0]
    n_blocks = cap // _ROWS_BLK
    shape3 = (n_blocks, _ROWS_BLK // _LANES, _LANES)
    local_idx = jnp.broadcast_to(
        jnp.arange(_ROWS_BLK, dtype=jnp.float32), (n_blocks, _ROWS_BLK)
    )
    blocks, cnts = _call_compact(
        local_idx.reshape(shape3),
        keep.astype(jnp.int32).reshape(shape3),
        n_blocks,
    )
    flat = blocks.reshape(n_blocks, _ROWS_BLK)
    cnts = cnts.reshape(n_blocks)
    # stitch: global position of block b's local slot j is
    # offset[b] + j; invert so each output slot knows its source
    offsets = jnp.cumsum(cnts) - cnts
    n_live = jnp.sum(cnts)
    out_pos = jnp.arange(cap, dtype=jnp.int32)
    # for each output slot, which (block, local) produced it?
    blk_of = jnp.searchsorted(
        jnp.cumsum(cnts), out_pos, side="right"
    ).astype(jnp.int32)
    blk_of = jnp.clip(blk_of, 0, n_blocks - 1)
    local = out_pos - jnp.take(offsets, blk_of)
    slot = blk_of * _ROWS_BLK + jnp.clip(local, 0, _ROWS_BLK - 1)
    src = blk_of * _ROWS_BLK + jnp.take(
        flat.reshape(cap), slot
    ).astype(jnp.int32)
    return src, n_live


@jax.jit
def compact_column_f32(v: jax.Array, keep: jax.Array):
    """Compact one f32 column by a boolean mask.

    Returns (compacted, n_live): `compacted` has the input's length,
    live rows packed at the front, zeros after. Exact for EVERY f32
    bit pattern including NaN/inf - values move by gather through the
    index permutation, never through arithmetic."""
    src, n_live = compact_perm(keep)
    out_pos = jnp.arange(v.shape[0], dtype=jnp.int32)
    gathered = jnp.take(v.astype(jnp.float32), src)
    return (
        jnp.where(out_pos < n_live, gathered, jnp.float32(0.0)),
        n_live,
    )


@jax.jit
def compact_column_i32(v: jax.Array, keep: jax.Array):
    """Exact int32 compaction via the same index permutation."""
    src, n_live = compact_perm(keep)
    out_pos = jnp.arange(v.shape[0], dtype=jnp.int32)
    gathered = jnp.take(v.astype(jnp.int32), src)
    return jnp.where(out_pos < n_live, gathered, jnp.int32(0)), n_live


def compact_columns(cols, keep):
    """Compact many columns by ONE mask: the permutation kernel runs
    once, each column moves by a single gather. `cols` is a sequence of
    1-D arrays (any dtype, same capacity as `keep`); returns
    ([compacted...], n_live) with dead tail slots zeroed."""
    src, n_live = compact_perm(keep)
    cap = keep.shape[0]
    out_pos = jnp.arange(cap, dtype=jnp.int32)
    outs = []
    for v in cols:
        g = jnp.take(v, src)
        outs.append(
            jnp.where(out_pos < n_live, g, jnp.zeros((), g.dtype))
        )
    return outs, n_live
