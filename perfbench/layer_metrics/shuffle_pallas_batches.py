"""shuffle_pallas_batches - layer: executor. Source: POLL
(program_counter). Median over the tasks that ran on the device of
POLL's `shuffle_pallas_batches`: the batches whose partition ids the
Pallas murmur3 program computed, counted in `ops/shuffle_writer.py:
spark_partition_ids` where that program answers. A shuffle on a key that
is never NULL reads its batches a task (64 of a 1,048,576-row split, 128
of a 2,097,152-row one); the key is absent the day a task leaves the
program, and for a server older than the counter. Moves queries_per_s."""

import statistics

from ._common import device_runs


def read(run: dict):
    d = [r["poll"]["shuffle_pallas_batches"] for r in device_runs(run)
         if "shuffle_pallas_batches" in r["poll"]]
    return float(statistics.median(d)) if d else None
