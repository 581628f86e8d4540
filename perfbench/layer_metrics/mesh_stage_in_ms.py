"""mesh_stage_in_ms - layer: executor. Source: POLL's stage table
(program_span). Median per task of the stage `mesh_stage_in`
(`parallel/mesh_ops.py: MeshGroupByExec._stage_dealt`): what the task's
draining thread spends placing the split's rows in the four chips'
column buffers, one launch a batch, beyond the scan's own `decode_batch`
and `h2d` on its prefetch thread. None where no task has the stage.
Moves queries_per_s."""

import statistics

from ._stages import tables


def read(run: dict):
    got = [t["mesh_stage_in"]["wall_s"] for t in tables(run)
           if "mesh_stage_in" in t]
    return 1e3 * statistics.median(got) if got else None
