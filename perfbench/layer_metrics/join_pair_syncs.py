"""join_pair_syncs - layer: executor. Source: POLL (program_counter).
Median over the tasks that ran on the device of POLL's
`join_pair_syncs`: the blocking read-backs of a probe batch's pair count
(`ops/joins.py: _JoinCore.probe`), by which the sort core picks the
shape bucket of the batch's pairs before it emits them. One a probe
batch on the sort core, which `auto` takes on a TPU (128 a `q3_join`
task: two joins, 64 batches), 0 on the table core and the day a change
sizes the pairs from what it knows before the count. None where POLL has
no such count (a server older than the counter, a task with no
broadcast hash join). Moves queries_per_s."""

import statistics

from ._common import device_runs


def read(run: dict):
    d = [r["poll"]["join_pair_syncs"] for r in device_runs(run)
         if "join_pair_syncs" in r["poll"]]
    return float(statistics.median(d)) if d else None
