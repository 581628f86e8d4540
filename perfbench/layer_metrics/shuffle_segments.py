"""shuffle_segments - layer: executor. Source: POLL (program_counter).
Median over the tasks that ran on the device of POLL's `shuffle_segments`:
the parts (`[u64 length][zstd(Arrow IPC stream)]`) the task's shuffle
write encoded, counted where `ops/shuffle_writer.py` freezes a
partition's staged rows. One part costs an IPC stream, a zstd frame and
the Python between them, so fewer parts for the same rows is less host
time in `shuffle_encode_ms`. None where POLL has no such count (a task
that wrote no shuffle; a server older than the counter). Moves
queries_per_s."""

import statistics

from ._common import device_runs


def read(run: dict):
    d = [r["poll"]["shuffle_segments"] for r in device_runs(run)
         if "shuffle_segments" in r["poll"]]
    return float(statistics.median(d)) if d else None
