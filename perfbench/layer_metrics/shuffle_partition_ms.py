"""shuffle_partition_ms - layer: executor. Source: POLL's stage table
(program_span). Median per task of the stage `shuffle_partition`
(`ops/shuffle_writer.py: ShuffleWriterExec.execute`): a batch's partition
ids (the Pallas murmur3 program where the key's batch holds no NULL,
`exprs/hashing.py`'s eager chain where it does), their read-back, the
sort by partition and the gather, up to the batch's read-back (`d2h`).
The same span on both hash paths, so the shuffle cells side by side show
what the path costs. None where no task has the stage. Moves
queries_per_s."""

import statistics

from ._stages import tables


def read(run: dict):
    got = [t["shuffle_partition"]["wall_s"] for t in tables(run)
           if "shuffle_partition" in t]
    return 1e3 * statistics.median(got) if got else None
