"""mesh_group_roofline - layer: kernels. Source: device_trace.
`query_roofline`'s quotient for the mesh group-by: the least time the
chips could take for the rows that entered the mesh program in the
traced slice (the template's `least_bytes`, over the HBM peak of
`peaks.json` times the devices), over the devices' mean busy time in
that slice, in percent. Rows are the program's launches (`jit_mesh_groupby`
on the `XLA Modules` line, a launch counted once and not once a device)
times the median POLL `mesh_rows_in` (the scan drops rows on the host as
it decodes); groups out by the window's exact ratio of rows returned to
rows in. Bound by bytes. None where the trace holds no launch of the
program. Moves queries_per_s."""

import statistics

from . import _mesh_trace
from ._common import device_runs


def read(run: dict):
    trace = _mesh_trace.read(run)
    runs = [r for r in device_runs(run) if r["poll"].get("mesh_rows_in")]
    if trace is None or not trace["launches"] or not runs \
            or "peaks" not in run:
        return None
    cell = run["cell"]
    rows_in = trace["launches"] * statistics.median(
        r["poll"]["mesh_rows_in"] for r in runs)
    out_per_row = (sum(r["rows_out"] for r in runs)
                   / sum(r["poll"]["mesh_rows_in"] for r in runs))
    least = cell.template(runs[0]["template"]).least_bytes(
        rows_in, rows_in * out_per_row, cell.types)
    busy = sum(trace["busy_s"]) / len(trace["busy_s"])
    peak = run["peaks"]["hbm_bytes_per_s"] * len(trace["busy_s"])
    return 100.0 * least / peak / busy if busy > 0 else None
