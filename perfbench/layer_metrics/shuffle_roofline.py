"""shuffle_roofline - layer: kernels. Source: device_trace.
`query_roofline`'s quotient for the `repart_key` cells: the least time
the chip could take for the rows that entered it in the traced slice
(every row read and written once at its narrowest width, the template's
`least_bytes`, over the HBM peak of `peaks.json`), over the time the
device was busy in that slice, in percent. Rows are the launches of the
unpack program (`jit_unpack` on the `XLA Modules` line, one a batch a
shuffle write reads) times the configuration's `batch_rows`. A reader of
its own because `query_roofline` finds a template's per-batch program
only through `trace_patterns.json`. None where the trace holds no such
launch. Moves queries_per_s."""

from . import _shuffle_trace
from ._common import device_runs


def read(run: dict):
    trace = _shuffle_trace.reduced(run)
    runs = device_runs(run)
    if trace is None or not runs:
        return None
    cell = run["cell"]
    rows = trace["kernel_events"]["shuffle.batch"] \
        * int(cell.config["batch_rows"])
    least = cell.template(runs[0]["template"]).least_bytes(
        rows, rows, cell.types)
    if least <= 0:
        return None
    return 100.0 * least / run["peaks"]["hbm_bytes_per_s"] / trace["busy_s"]
