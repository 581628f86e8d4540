"""task_launches - layer: executor. Source: POLL (program_counter).
Median per task of `launches`: every program launch made for the task
(`runtime/dispatch.py: _launch`), the cached kernels that
`task_dispatches` counts and the plain jits `dispatch.launch` wraps (the
Pallas murmur3 program, `ops/util.py`'s gathers and concat, the mesh
programs). Moves queries_per_s."""

from ._waits import median_field


def read(run: dict):
    return median_field(run, "launches")
