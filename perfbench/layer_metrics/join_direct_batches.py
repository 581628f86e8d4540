"""join_direct_batches - layer: executor. Source: POLL (program_counter).
Median over the tasks that ran on the device of POLL's
`join_direct_batches`: the probe batches of a broadcast hash join that the
direct key->row array answered (`ops/joins.py: _JoinCore.probe`), one
subtract, range check and gather a batch, no binary search and no
read-back of a pair count. 128 a `q3_join` task on a TPU (two joins, 64
batches, both broadcasts with unique integer keys), 0 on the sort core.
None where POLL has no such count (a server older than the counter, a task
with no broadcast hash join). Moves queries_per_s."""

import statistics

from ._common import device_runs


def read(run: dict):
    d = [r["poll"]["join_direct_batches"] for r in device_runs(run)
         if "join_direct_batches" in r["poll"]]
    return float(statistics.median(d)) if d else None
