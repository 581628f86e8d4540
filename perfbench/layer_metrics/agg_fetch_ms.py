"""agg_fetch_ms - layer: executor. Source: POLL's stage table
(program_span). Median per task of the stage `agg_fetch`: the wait for
the last batch's program, the one read-back of the packed aggregate state
and the host's finalize (`ops/fused.py`). None where no task's table has
the stage (a server older than the span). Moves latency_p50_ms."""

import statistics

from ._stages import tables, wall_s


def read(run: dict):
    got = [wall_s(t, "agg_fetch") for t in tables(run) if "agg_fetch" in t]
    return 1e3 * statistics.median(got) if got else None
