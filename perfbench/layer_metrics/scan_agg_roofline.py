"""scan_agg_roofline - layer: kernels. Source: device_trace.
The least time the chip could take for the rows that entered it in the
traced slice of a scan-filter-aggregate cell, over the time the device
was busy in that slice, in percent. Rows are the launches of the
per-batch program (`ops/fused.py`'s carry kernel, `jit_kernel` on the
device's `XLA Modules` line, from the reduction's own `launches`) times
the configuration's `batch_rows`; the bytes a row must move are the
template's `least_bytes` for the columns each request reads, weighted as
the window's device runs mix them; the peak is `peaks.json`'s HBM
bandwidth. Bound by bytes: a comparison and an addition a value is no
arithmetic against 197 TFLOP/s. None where the trace has no such launch.
Moves queries_per_s."""

from ._common import device_runs

PROGRAM = "jit_kernel"


def traced_rows(run: dict) -> int:
    launches = (run["trace"].get("launches") or {}).get(PROGRAM, 0)
    return launches * int(run["cell"].config["batch_rows"])


def least_bytes(run: dict, rows: int) -> float:
    """The window's device runs share the traced rows evenly: every task
    scans one split, so a shape's share of the rows is its share of the
    tasks."""
    cell = run["cell"]
    runs = device_runs(run)
    if not runs:
        return 0.0
    per_task = rows / len(runs)
    split = int(cell.table_cfg["split_rows"])
    total = 0.0
    for r in runs:
        tmpl = cell.template(r["template"])
        total += tmpl.least_bytes(
            per_task, per_task * r["rows_out"] / split, cell.types,
            tmpl.columns_read(r["params"]))
    return total


def read(run: dict):
    trace = run.get("trace")
    if not trace or not trace.get("busy_s") or "peaks" not in run:
        return None
    least = least_bytes(run, traced_rows(run))
    if least <= 0:
        return None
    return 100.0 * least / run["peaks"]["hbm_bytes_per_s"] / trace["busy_s"]
