"""Intra-slice hash repartition: the ICI tier of the shuffle.

Where the reference always spills shuffle data through segmented-IPC files
(shuffle_writer_exec.rs), HBM-resident batches inside one TPU slice can be
re-bucketed with a single `lax.all_to_all` over ICI - no host round trip,
no compression, no disk (SURVEY 2.4 TPU mapping). The inter-node tier
(parallel/exchange.ShuffleExchangeExec) still uses the reference-compatible
file format.

Shape discipline: each shard sorts its rows by target device (one stable
argsort - the same counting-sort-as-sort trick as the file shuffle writer),
scatters them into per-target buckets of a fixed size, and all_to_all
exchanges the bucket axis. Bucket capacity defaults to the EXPECTED
per-target share times a slack factor (uniform hash spread), cutting the
bytes over ICI by ~n_dev/slack versus worst-case sizing; per-bucket
overflow is detected on device (one scalar readback) and the exchange
retries once with worst-case capacity, so pathological skew stays
correct.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from blaze_tpu.types import DataType
from blaze_tpu.exprs.hashing import hash_columns_device, pmod


def partition_ids_for_devices(key_cols, capacity: int, num_devices: int
                              ) -> jax.Array:
    """Spark-murmur3 pmod over num_devices (per shard)."""
    h = hash_columns_device(key_cols, capacity)
    return pmod(h, num_devices)


def _bucketize(values: jax.Array, target: jax.Array, live: jax.Array,
               num_devices: int, cap: int) -> jax.Array:
    """Scatter one shard's rows into [num_devices, cap] padded buckets.

    target/live: per-row device id and liveness. Rows are stably sorted by
    target so each bucket is contiguous; then every bucket is shifted to
    its own fixed-size slot."""
    t = jnp.where(live, target, num_devices)  # dead rows sort last
    order = jnp.argsort(t, stable=True)
    sv = jnp.take(values, order, axis=0)
    st = jnp.take(t, order)
    # row index within its bucket
    ones = jnp.ones_like(st)
    idx_in_bucket = jnp.cumsum(ones) - 1
    bucket_start = jnp.searchsorted(st, jnp.arange(num_devices + 1, dtype=jnp.int32))
    within = idx_in_bucket - jnp.take(bucket_start, st)
    # scatter into [num_devices * cap]
    flat_pos = jnp.where(
        st < num_devices, st * cap + within, num_devices * cap
    )
    out = jnp.zeros((num_devices * cap + 1,) + values.shape[1:],
                    dtype=values.dtype)
    out = out.at[flat_pos].set(sv)
    return out[:-1].reshape((num_devices, cap) + values.shape[1:])


def _bucket_live(target: jax.Array, live: jax.Array, num_devices: int,
                 cap: int) -> jax.Array:
    t = jnp.where(live, target, num_devices)
    order = jnp.argsort(t, stable=True)
    st = jnp.take(t, order)
    bucket_start = jnp.searchsorted(st, jnp.arange(num_devices + 1, dtype=jnp.int32))
    counts = bucket_start[1:] - bucket_start[:-1]  # rows per target
    return jnp.arange(cap, dtype=jnp.int32)[None, :] < counts[:, None]


def _exchange(mesh: Mesh, arrays, target, live, axis: str,
              bucket_cap: int):
    """One all_to_all pass at a fixed per-target bucket capacity.
    Returns (arrays', live', max_bucket_count) - the count lets the
    caller detect overflow without any per-row host traffic."""
    n_dev = mesh.shape[axis]

    def per_shard(target_s, live_s, *arr_s):
        target_s = target_s[0]
        live_s = live_s[0]
        outs = []
        for a in arr_s:
            b = _bucketize(a[0], target_s, live_s, n_dev, bucket_cap)
            # all_to_all: split axis 0 (targets), concat received buckets
            ex = lax.all_to_all(
                b[None], axis, split_axis=1, concat_axis=0,
                tiled=False,
            )
            outs.append(
                ex.reshape((n_dev * bucket_cap,) + a.shape[2:])[None]
            )
        lv = _bucket_live(target_s, live_s, n_dev, bucket_cap)
        lx = lax.all_to_all(
            lv[None], axis, split_axis=1, concat_axis=0, tiled=False
        )
        # rows per target bucket on this shard (before clipping to
        # bucket_cap); global max detects overflow
        t = jnp.where(live_s, target_s, n_dev)
        counts = jax.ops.segment_sum(
            jnp.ones_like(t), jnp.clip(t, 0, n_dev),
            num_segments=n_dev + 1,
        )[:n_dev]
        max_count = lax.pmax(jnp.max(counts), axis)
        return tuple(outs) + (
            lx.reshape(n_dev * bucket_cap)[None],
            max_count[None],
        )

    out_specs = tuple([P(axis)] * (len(arrays) + 1)) + (P(axis),)
    fn = shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(axis), P(axis)) + tuple(P(axis) for _ in arrays),
        out_specs=out_specs,
    )
    res = fn(target, live, *arrays)
    return list(res[:-2]), res[-2], res[-1]


def all_to_all_repartition(
    mesh: Mesh,
    arrays: Sequence[jax.Array],  # each [n_dev, cap, ...] sharded on axis 0
    target: jax.Array,  # [n_dev, cap] device ids
    live: jax.Array,  # [n_dev, cap]
    axis: str = "data",
    slack: float = 1.5,
):
    """Exchange rows so row r of shard d moves to device target[d, r].

    Returns (arrays', live') with shapes [n_dev, n_dev*bucket_cap, ...]:
    each shard's new rows are the concatenation of what every peer sent
    it; live' marks real rows.

    Buckets are sized to the expected per-target share times `slack`
    (bytes over ICI drop ~n_dev/slack vs worst-case). If any shard's
    per-target count exceeds that (skew), ONE retry runs at worst-case
    capacity - always correct, never silently lossy. slack <= 0 forces
    worst-case sizing directly."""
    n_dev = mesh.shape[axis]
    cap = target.shape[-1]
    bucket_cap = cap
    if slack > 0 and n_dev > 1:
        bucket_cap = min(
            cap, max(1, int(np.ceil(cap * slack / n_dev)))
        )
    outs, lv, max_count = _exchange(
        mesh, arrays, target, live, axis, bucket_cap
    )
    if bucket_cap < cap and int(np.max(np.asarray(max_count))) > \
            bucket_cap:
        # skew overflow: retry once at worst-case capacity
        outs, lv, _ = _exchange(mesh, arrays, target, live, axis, cap)
    return outs, lv
