"""join_build_ms - layer: executor. Source: POLL's stage table
(program_span). Median per task of the stage `join_build`
(`ops/joins.py: HashJoinExec.build_side`), summed over the task's joins:
reading a broadcast relation from its file segments, its concatenation
on the device and its index, with the index's blocking scalar where the
core has one; one span a join a task (two in `q3_join`: 6,000 dates and
about 300 items). None where no task has the stage (a cell with no
broadcast join, a server older than the span, `--no-trace`). Moves
queries_per_s."""

import statistics

from ._stages import tables


def read(run: dict):
    got = [t["join_build"]["wall_s"] for t in tables(run)
           if "join_build" in t]
    return 1e3 * statistics.median(got) if got else None
