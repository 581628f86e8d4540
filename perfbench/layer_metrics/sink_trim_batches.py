"""sink_trim_batches - layer: executor. Source: POLL (program_counter).
Median over the tasks that ran on the device of POLL's
`sink_trim_batches`: the batches that reached a result sink with a
selection, were read back whole in the one packed transfer and trimmed on
the host (`ops/util.py: sink_arrow`), with no compaction on the device
and no wait for a row count. For a task of these cells it is the batches
of its split; it is absent the day a change packs the rows on the device
again before the sink. None where POLL has no such count (a server older
than the counter, or a task whose sink saw no selection). Moves
queries_per_s."""

import statistics

from ._common import device_runs


def read(run: dict):
    d = [r["poll"]["sink_trim_batches"] for r in device_runs(run)
         if "sink_trim_batches" in r["poll"]]
    return float(statistics.median(d)) if d else None
