"""mesh_sync_ms - layer: executor. Source: POLL's stage table
(program_span). Median per task of the stages `mesh_sync + mesh_gather`
(`parallel/mesh_ops.py: MeshGroupByExec._run`): the wait for the mesh
program on the four chips, and the one batched fetch of the groups. None
where no task has the stages. Moves queries_per_s."""

import statistics

from ._stages import tables, wall_s


def read(run: dict):
    got = [wall_s(t, "mesh_sync", "mesh_gather") for t in tables(run)
           if "mesh_sync" in t]
    return 1e3 * statistics.median(got) if got else None
