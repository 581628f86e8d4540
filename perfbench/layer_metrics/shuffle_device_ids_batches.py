"""shuffle_device_ids_batches - layer: executor. Source: POLL
(program_counter). Median over the tasks that ran on the device of
POLL's `shuffle_device_ids_batches`: the batches whose partition ids
went from the hash program to the sort-and-gather program on the device
with no read-back between them, counted in `ops/shuffle_writer.py:
sort_by_partition` where it is handed a device array. A hash shuffle on
keys the device hashes reads its batches a task (64 of a 1,048,576-row
split, 128 of a 2,097,152-row one); the key is absent for a key hashed on
the host (strings), the day a change reads the ids back before the sort,
and for a server older than the counter. Moves queries_per_s."""

import statistics

from ._common import device_runs


def read(run: dict):
    d = [r["poll"]["shuffle_device_ids_batches"] for r in device_runs(run)
         if "shuffle_device_ids_batches" in r["poll"]]
    return float(statistics.median(d)) if d else None
