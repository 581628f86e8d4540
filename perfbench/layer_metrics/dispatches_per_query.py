"""dispatches_per_query - layer: executor. Source: POLL (program_counter).
Median `dispatches` of the requests that ran on the device. Moves
queries_per_s."""

import statistics

from ._common import device_runs


def read(run: dict):
    d = [r["poll"]["dispatches"] for r in device_runs(run)]
    return float(statistics.median(d)) if d else None
