"""shuffle_encode_ms - layer: executor. Source: POLL's stage table
(program_span). Median per task of `shuffle_encode` (slicing a sorted
batch into partitions, zstd and Arrow IPC) plus `shuffle_finalize` (the
file writes, spills too). Only a shuffle write has them. Moves
queries_per_s."""

from ._stages import median_wall_ms


def read(run: dict):
    return median_wall_ms(run, "shuffle_encode", "shuffle_finalize")
