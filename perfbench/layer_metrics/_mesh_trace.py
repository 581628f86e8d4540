"""What the three device readers of the cell `mesh_group.s1` share: the
traced slice read once more, device by device. `xplane.reduce_planes`
hands back the devices' mean busy time and, for the patterns of
`trace_patterns.json`, sums over all of them; these readers need each
device's own busy time, the all-to-all operations on the `XLA Ops` line
and the mesh program's launches on the `XLA Modules` line, so they read
the trace file itself: it is still under the cell's `workdir/trace` when
`layers.read_all` calls them."""

from __future__ import annotations

import glob
import os
import re

import numpy as np

from .. import xplane

# parallel/sharded.py jits the group-by's program as `mesh_groupby`; the
# device's module line names a launch `jit_<function>(<id>)`
PROGRAM = re.compile(r"^jit_mesh_groupby\(")
# an operation's name on the `XLA Ops` line is its HLO instruction: the
# collective is the instruction whose opcode is all-to-all (its operands
# are `%all_to_all.N`, with underscores)
EXCHANGE = re.compile(r" all-to-all(-start|-done)?\(")


def read(run: dict):
    """{"busy_s": [a device's busy seconds], "exchange_s": [a device's
    seconds inside all-to-all operations], "launches": the mesh
    program's launches, counted once a launch and not once a device},
    or None where the run has no device trace. Kept on the run: three
    readers ask."""
    if "mesh_trace" not in run:
        run["mesh_trace"] = _read(run)
    return run["mesh_trace"]


def _read(run: dict):
    trace = run.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    files = sorted(glob.glob(os.path.join(
        run["cell"].workdir, "trace", "plugins", "profile", "*",
        "*.xplane.pb")))
    return of_file(files[-1]) if files else None


def of_file(path: str):
    patterns = xplane.load_patterns()
    patterns["kernels"] = {"modules": {"line": "^XLA Modules$"}}
    patterns["host_plane"] = "^$"  # the run's own reduction named the gaps
    devices = xplane.read_planes(path, patterns)["devices"]
    if not devices:
        return None
    busy, exchange, launches = [], [], []
    for dev in devices.values():
        names, starts, ends = dev["ops"]
        busy.append(xplane.union_seconds(starts, ends)[0])
        # a slice holds a few thousand names and a million events
        uniq, inv = np.unique(names.astype(str), return_inverse=True)
        hit = np.array([bool(EXCHANGE.search(n)) for n in uniq],
                       dtype=bool)[inv]
        exchange.append(xplane.union_seconds(starts[hit], ends[hit])[0])
        launches.append(sum(
            bool(PROGRAM.search(str(n)))
            for mod_names, _, _ in dev["lines"].values()
            for n in mod_names))
    return {"busy_s": busy, "exchange_s": exchange,
            # every device runs its part of every launch
            "launches": max(launches)}
