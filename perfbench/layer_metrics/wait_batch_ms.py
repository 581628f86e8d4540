"""wait_batch_ms - layer: executor. Source: POLL's `waits` (program_span).
Median per task of `waits.wait_batch.wall_s`, in ms: the draining thread
blocked in the scan's prefetch queue (`runtime/prefetch.py: prefetch`,
the consumer's `q.get()` after `get_nowait` found it empty), idle for
want of a decoded batch. Large where the scan sets the task's pace.
Moves queries_per_s."""

from ._waits import median_wait_ms


def read(run: dict):
    return median_wait_ms(run, "wait_batch")
