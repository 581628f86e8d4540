"""host_wait_share - layer: executor. Source: POLL's stage table
(program_span). Median per task of `1 - sum(cpu_s) / sum(wall_s)` over
the task's stages, in percent: the share of its stages' time in which
the thread was not on a CPU - waiting for the GIL, the device or the
disk. Moves queries_per_s."""

import statistics

from ._stages import tables


def read(run: dict):
    shares = []
    for table in tables(run):
        wall = sum(row.get("wall_s", 0.0) for row in table.values())
        cpu = sum(row.get("cpu_s", 0.0) for row in table.values())
        if wall > 0:
            shares.append(100.0 * (1.0 - cpu / wall))
    return statistics.median(shares) if shares else None
