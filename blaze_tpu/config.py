"""Engine configuration.

Mirrors the reference's engine-sizing knobs (spark.blaze.batchSize, memory
fraction, tmp dirs: reference NativeSupports.scala:241-253 -> exec.rs:53-107)
plus TPU-specific sizing (shape buckets, device memory budget).
"""

from __future__ import annotations

import dataclasses
import os
import tempfile
from typing import Optional, Sequence


@dataclasses.dataclass
class EngineConfig:
    # Max rows per device batch (reference default 16384, exec.rs:105).
    batch_size: int = 16384
    # Fraction of the memory budget the engine may use before spilling
    # (reference MemoryManagerConfig memory_fraction, exec.rs:79-94).
    memory_fraction: float = 0.75
    # Total host-side memory budget in bytes for buffered shuffle/agg state.
    max_memory: int = 4 << 30
    # Device (HBM) budget for resident partition buffers before host spill.
    device_memory_budget: int = 8 << 30
    # Spill directories (reference DiskManagerConfig::NewSpecified tmp_dirs).
    tmp_dirs: Sequence[str] = dataclasses.field(
        default_factory=lambda: [tempfile.gettempdir()]
    )
    # Row-count buckets for padding batches to static shapes. Each batch is
    # padded up to the smallest bucket >= its row count so XLA compiles one
    # kernel per (pipeline, bucket) instead of per exact shape.
    shape_buckets: Sequence[int] = (256, 1024, 4096, 16384)
    # zstd level for segmented-IPC shuffle segments (reference uses level 1,
    # util/ipc.rs:20-49).
    ipc_compression_level: int = 1
    # Default shuffle partition count when a plan does not specify one.
    default_shuffle_partitions: int = 200
    # Pipeline-breaker materialization cap: aggregates/joins whose input
    # exceeds this many rows switch to external (grace) hash-bucketed
    # execution through the segmented-IPC spill format (ops/external.py).
    max_materialize_rows: int = 1 << 22
    # Bucket count for external execution.
    external_buckets: int = 32
    # Enable per-operator timing metrics.
    collect_metrics: bool = True
    # Static output capacity for grouped-aggregate kernels: state arrays
    # are sliced to this many group slots on device before leaving the
    # kernel, so a small result never transfers (or feeds downstream
    # kernels at) full input capacity. Overflow (more groups than slots)
    # re-dispatches an unsliced kernel - correctness never depends on it.
    agg_group_capacity: int = 65536
    # Grouping-core selection for hash aggregates: "scatter" (open-
    # addressing hash table built from scatter/gather, sort-free - the
    # O(n) path), "sort" (stable lexsort + boundary detection), or
    # "auto" (scatter on the CPU backend where an 8M-row sort costs
    # ~3.5s vs ~0.1s for the table; sort on TPU, where the scatter
    # core has no chip measurement yet - resolve_core_choice below).
    # Env override: BLAZE_GROUP_CORE.
    group_core: str = "auto"
    # Join-core selection for the unique-build fast path (a table probe,
    # no sort/searchsorted/pair-expansion/pair-count read-back): "scatter"
    # (every table: the direct key->row array, the key|row table, the
    # generic hash table), "sort" (hash sort + binary searches), or
    # "auto": the scatter choice on the CPU backend; on a TPU the direct
    # key->row array alone, for one unique integer key column whose span
    # fits 1 << 24 slots, and the sort core for every other build (the
    # key|row table's lookup does not compile for a v5e,
    # tests/test_chip_compile.py). Env override: BLAZE_JOIN_CORE.
    join_core: str = "auto"
    # Multi-key argsort selection: "scatter" here means the packed-u64
    # single-lane value sort (one XLA sort per key); "sort" the 3-lane
    # index lexsort ladder. "auto" = packed on CPU; on TPU the lexsort
    # ladder (the no-X64 rewrite pass lacks full u64 support,
    # exprs/hashing.py:83, so the packed permutation must be validated
    # on the chip before timing may select it).
    # Env override: BLAZE_SORT_CORE.
    sort_core: str = "auto"
    # Evaluate pushed-down filter conjuncts host-side during parquet
    # decode (pyarrow C++), compacting rows before padding/transfer.
    # Halves transfer bytes at 50% selectivity but costs host CPU; the
    # right default depends on the host->device link (keep on for a
    # network-attached chip, consider off when decode is the
    # bottleneck). Row-group STATS pruning is unaffected by this flag.
    host_filter_pushdown: bool = True

    def bucket_for(self, num_rows: int) -> int:
        for b in self.shape_buckets:
            if num_rows <= b:
                return b
        # Round up to a multiple of the largest bucket for oversized batches.
        top = self.shape_buckets[-1]
        return ((num_rows + top - 1) // top) * top

    def spill_dir(self) -> str:
        d = self.tmp_dirs[0]
        os.makedirs(d, exist_ok=True)
        return d


def resolve_core_choice(env_var: str, cfg_value: str, chip: str = "sort",
                        backend: Optional[str] = None) -> str:
    """Shared resolution for the grouping/join/sort core knobs: env
    override beats config; "auto" picks the scatter core on the CPU
    (where the sort it replaces costs 20-35x more) and `chip` on any
    other backend (`backend`, default `jax.default_backend()`). `chip`
    is "sort" for the grouping and sort cores, the conservative choice
    until a pair of chip runs of the benchmark's own grouped cell under
    BLAZE_GROUP_CORE says otherwise (ROADMAP S4); the join passes
    "direct" (ops/joins.py: _join_core_choice). Unknown values raise so a
    typo'd knob can't silently measure the wrong core."""
    mode = os.environ.get(env_var) or cfg_value
    if mode not in ("auto", "scatter", "sort"):
        raise ValueError(
            f"{env_var}/config must be auto|scatter|sort, got {mode!r}"
        )
    if mode == "auto":
        if backend is None:
            import jax

            backend = jax.default_backend()
        return "scatter" if backend == "cpu" else chip
    return mode


_CONFIG: EngineConfig = EngineConfig()


def get_config() -> EngineConfig:
    return _CONFIG


def set_config(cfg: EngineConfig) -> EngineConfig:
    global _CONFIG
    _CONFIG = cfg
    return cfg
