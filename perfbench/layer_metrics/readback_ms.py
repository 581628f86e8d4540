"""readback_ms - layer: executor. Source: POLL's stage table
(program_span). Median per task of `d2h`: the packed transfer back, its
wait on the device, and the Arrow assembly (`ColumnBatch.to_arrow`).
Moves queries_per_s."""

from ._stages import median_wall_ms


def read(run: dict):
    return median_wall_ms(run, "d2h")
