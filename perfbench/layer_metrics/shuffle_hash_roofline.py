"""shuffle_hash_roofline - layer: kernels. Source: device_trace.
The Pallas murmur3 program's share of its HBM roofline in the traced
slice, in percent: the bytes its launches must move (the template's
`hash_bytes`: the key in at its width and an `int` id out, for every row
of a launch, `batch_rows` a launch) over the HBM peak of `peaks.json`,
over the program's own device time (its events on the `XLA Modules`
line). Bound by bytes: some thirty integer operations a key are nothing
against the vector unit's peak. None where the trace holds no launch of
the program. Moves queries_per_s."""

from . import _shuffle_trace
from ._common import device_runs


def read(run: dict):
    trace = _shuffle_trace.reduced(run)
    if trace is None:
        return None
    launches = trace["kernel_events"]["shuffle.hash"]
    seconds = trace["kernel_s"]["shuffle.hash"]
    runs = device_runs(run)
    if not launches or seconds <= 0 or not runs:
        return None
    cell = run["cell"]
    # one template and one key a cell: the traffic file fixes both
    r = runs[0]
    least = cell.template(r["template"]).hash_bytes(
        launches * int(cell.config["batch_rows"]),
        cell.types[r["params"]["key"]])
    return 100.0 * least / run["peaks"]["hbm_bytes_per_s"] / seconds
