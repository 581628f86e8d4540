"""concat_slice_parts - layer: executor. Source: POLL (program_counter).
Median over the tasks that ran on the device of POLL's
`concat_slice_parts`: the compacted parts a task's `concat_batches`
wrote whole at their offsets in one launch, with no scatter
(`ops/util.py: _concat_many`). In this cell that is the FINAL merge's
per-batch partial states, about one a scanned batch; before the slice
form each part cost a `q1_group` task two 1.2 ms scatters a column on
the chip. None where POLL has no such count (a server older than the
counter, a task that materializes nothing on the device). Moves
queries_per_s."""

import statistics

from ._common import device_runs


def read(run: dict):
    d = [r["poll"]["concat_slice_parts"] for r in device_runs(run)
         if "concat_slice_parts" in r["poll"]]
    return float(statistics.median(d)) if d else None
