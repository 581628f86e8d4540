"""task_dispatches - layer: executor. Source: POLL (program_counter).
Median over the tasks that ran on the device of POLL's `task_dispatches`:
the launches made on behalf of that task alone, counted on the task's
context where `runtime/dispatch.py` wraps a kernel, whatever else runs in
the process. Moves queries_per_s."""

import statistics

from ._common import device_runs


def read(run: dict):
    d = [r["poll"]["task_dispatches"] for r in device_runs(run)
         if "task_dispatches" in r["poll"]]
    return float(statistics.median(d)) if d else None
