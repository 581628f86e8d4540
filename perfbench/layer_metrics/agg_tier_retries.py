"""agg_tier_retries - layer: executor. Source: POLL (program_counter).
Median over the tasks that ran on the device of POLL's
`agg_tier_retries`: the grouping programs a keyed aggregate launched
again because the group count outgrew a group-capacity tier
(`ops/hash_aggregate.py: run_grouped_kernel`). On the sort core a tier
is a cut of one program's result, so a task reads 0 whatever its group
count; a task of this cell read 2 while each tier was a run of its own
(sort, gathers and scatters of 737,280 partial rows three times for
724,000 groups). None where POLL has no such count (a server older than
the counter, a task with no keyed aggregate). Moves queries_per_s."""

import statistics

from ._common import device_runs


def read(run: dict):
    d = [r["poll"]["agg_tier_retries"] for r in device_runs(run)
         if "agg_tier_retries" in r["poll"]]
    return float(statistics.median(d)) if d else None
