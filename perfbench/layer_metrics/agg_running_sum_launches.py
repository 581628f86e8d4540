"""agg_running_sum_launches - layer: executor. Source: POLL
(program_counter).
Median over the tasks that ran on the device of POLL's
`agg_running_sum_launches`: the grouping programs a keyed aggregate
launched whose integer sums are read off a running sum at the groups'
boundaries, with no scatter (`ops/hash_aggregate.py: _SegOps.sum`,
counted in `run_grouped_kernel` by the rule the sum itself follows). On
the sort core that is every grouping program of the task: 66 in this
cell, the 64 per-batch programs and the two merges (the narrow-key one,
which reports its hash collision, and the lexsort one that answers); 0
the day a change sends the sums back to a scatter, which costs a
`q1_group` task 1.2 ms for every 16,384 `i64` updates. None where POLL
has no such count (a server older than the counter, a task with no
keyed aggregate). Moves queries_per_s."""

import statistics

from ._common import device_runs


def read(run: dict):
    d = [r["poll"]["agg_running_sum_launches"] for r in device_runs(run)
         if "agg_running_sum_launches" in r["poll"]]
    return float(statistics.median(d)) if d else None
