"""scan_decode_ms - layer: executor. Source: POLL's stage table
(program_span). Median per task of `decode_batch` (a batch's parquet
decode to host arrays) plus `h2d` (pad, pack and device_put), both in
the scan's prefetch thread. Moves queries_per_s."""

from ._stages import median_wall_ms


def read(run: dict):
    return median_wall_ms(run, "decode_batch", "h2d")
