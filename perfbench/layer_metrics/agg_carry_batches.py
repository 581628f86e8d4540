"""agg_carry_batches - layer: executor. Source: POLL (program_counter).
Median over the tasks that ran on the device of POLL's
`agg_carry_batches`: the batches whose partial aggregate state was merged
into the carry that stays on the device, with no read-back between them
(`ops/fused.py: FusedAggregateExec._execute_keyless_carry`). For a task
of this cell it is the batches of its split; it reads 0 the day a change
pushes these tasks off the carry. None where POLL has no such count (a
server older than the counter). Moves queries_per_s."""

import statistics

from ._common import device_runs


def read(run: dict):
    d = [r["poll"]["agg_carry_batches"] for r in device_runs(run)
         if "agg_carry_batches" in r["poll"]]
    return float(statistics.median(d)) if d else None
