"""execution_ms - layer: executor. Source: POLL (program_span).
Median `execution_s` of the requests that ran on the device: plan decode,
scan, pack, launches, device time and the read-back. Moves
queries_per_s."""

from ._common import device_runs, median_ms


def read(run: dict):
    return median_ms(r["poll"].get("execution_s", 0.0)
                     for r in device_runs(run))
