"""query_roofline - layer: kernels. Source: device_trace.
The least time the chip could take for the rows that entered it in the
traced slice (the bytes each query must move for them, by its template's
`least_bytes`, over the HBM peak of `peaks.json`), over the time the
device was busy in that slice, in percent. Rows are counted from the
trace (`_common.traced_rows`), busy time is the union of the device's
operations. Bound by bytes: none of these queries does arithmetic worth
counting against 197 TFLOP/s. Moves queries_per_s."""

from ._common import out_per_row, traced_rows


def read(run: dict):
    trace = run.get("trace")
    if not trace or not trace.get("busy_s") or "peaks" not in run:
        return None
    cell = run["cell"]
    least = 0
    for template, rows in traced_rows(run).items():
        least += cell.template(template).least_bytes(
            rows, rows * out_per_row(run, template), cell.types)
    if least <= 0:
        return None
    return 100.0 * least / run["peaks"]["hbm_bytes_per_s"] / trace["busy_s"]
