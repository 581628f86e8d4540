"""join_probe_roofline - layer: kernels. Source: device_trace.
The join's programs' share of their HBM roofline in the traced slice, in
percent: the template's `least_bytes` for the rows that entered them
(launches of the probe program, `jit_join_probe` on the device's `XLA
Modules` line, whichever core runs, times `batch_rows`; groups out by the
window's exact ratio of rows returned to rows scanned), over the HBM peak
of `peaks.json`, over the device time of the probe and emit programs
(`jit_join_probe`, `jit_join_emit`) in the slice. Bound by bytes. The
trace file is read once more with two patterns of this reader's own, as
`_shuffle_trace.py` does: `trace_patterns.json` is not edited. None
where the trace holds no launch of the probe program. Moves
queries_per_s."""

import glob
import os

from .. import xplane
from ._common import device_runs, out_per_row

# ops/joins.py names a probe batch's programs; the module line names a
# launch `jit_<function>(<id>)`
PATTERNS = {
    "join.probe": {"line": "^XLA Modules$", "name": r"^jit_join_probe\("},
    "join.emit": {"line": "^XLA Modules$", "name": r"^jit_join_emit\("},
}


def reduced(run: dict):
    """The reduction with `kernel_s` and `kernel_events` for PATTERNS,
    or None where the run has no device trace; kept on the run."""
    if "join_trace" not in run:
        run["join_trace"] = _reduce(run)
    return run["join_trace"]


def _reduce(run: dict):
    trace = run.get("trace")
    if not trace or not trace.get("busy_s") or "peaks" not in run:
        return None
    files = sorted(glob.glob(os.path.join(
        run["cell"].workdir, "trace", "plugins", "profile", "*",
        "*.xplane.pb")))
    if not files:
        return None
    patterns = xplane.load_patterns()
    patterns["kernels"] = dict(PATTERNS)
    patterns["host_plane"] = "^$"  # the run's own reduction named the gaps
    out = xplane.reduce_file(files[-1], patterns)
    return out if out.get("devices") else None


def read(run: dict):
    trace = reduced(run)
    runs = device_runs(run)
    if trace is None or not runs:
        return None
    launches = trace["kernel_events"]["join.probe"]
    seconds = trace["kernel_s"]["join.probe"] + trace["kernel_s"]["join.emit"]
    if not launches or seconds <= 0:
        return None
    cell = run["cell"]
    template = runs[0]["template"]  # one template a cell
    rows = launches * int(cell.config["batch_rows"])
    least = cell.template(template).least_bytes(
        rows, rows * out_per_row(run, template), cell.types)
    return 100.0 * least / run["peaks"]["hbm_bytes_per_s"] / seconds
