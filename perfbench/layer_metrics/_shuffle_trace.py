"""What the two roofline readers of the `repart_key` cells share: the
traced slice reduced once more, with two patterns of their own beside
those of `trace_patterns.json`. `xplane.reduce_planes` keeps a program's
device seconds only for the patterns it is given, and the run's own
reduction caps `launches` at its twelve most frequent programs, so the
readers read the trace file itself: it is still under the cell's
`workdir/trace` when `layers.read_all` calls them."""

from __future__ import annotations

import glob
import os

from .. import xplane

# the device's module line names a jitted program `jit_<function>(<id>)`
PATTERNS = {
    # ops/kernels/murmur3_pallas.py: the partition-id program, a launch a
    # batch whose key holds no NULL
    "shuffle.hash": {"line": "^XLA Modules$",
                     "name": r"^jit_partition_ids_int(32|64)\("},
    # runtime/pack.py: the unpack of a packed batch, a launch a batch a
    # shuffle write reads (`batch.repart200` of trace_patterns.json)
    "shuffle.batch": {"line": "^XLA Modules$", "name": r"^jit_unpack\("},
}


def reduced(run: dict):
    """The reduction with `kernel_s` and `kernel_events` for PATTERNS, or
    None where the run has no device trace. Kept on the run: two readers
    ask."""
    if "shuffle_trace" not in run:
        run["shuffle_trace"] = _reduce(run)
    return run["shuffle_trace"]


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
    patterns["kernels"] = dict(patterns["kernels"], **PATTERNS)
    # the host's spans, most of a trace, name idle gaps: the run's own
    # reduction has done that, and nothing here reads them
    patterns["host_plane"] = "^$"
    out = xplane.reduce_file(files[-1], patterns)
    return out if out.get("devices") else None
