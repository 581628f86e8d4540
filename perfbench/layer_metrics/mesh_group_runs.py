"""mesh_group_runs - layer: executor. Source: POLL (program_counter).
Median over the tasks that ran on the device of POLL's `mesh_group_runs`:
the mesh programs that produced the task's answer, counted in
`parallel/mesh_ops.py: MeshGroupByExec`. 1 a task where the four chips
grouped its split; 0 the day the op falls back to its single-device plan
(`mesh_degraded` then counts the fall); the key is absent for a server
older than the counter, whose mesh op cannot take a NULL and falls back
in silence. Moves queries_per_s."""

import statistics

from ._common import device_runs


def read(run: dict):
    d = [r["poll"]["mesh_group_runs"] for r in device_runs(run)
         if "mesh_group_runs" in r["poll"]]
    return float(statistics.median(d)) if d else None
