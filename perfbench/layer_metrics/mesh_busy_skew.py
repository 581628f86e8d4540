"""mesh_busy_skew - layer: device. Source: device_trace.
The busiest device's busy seconds in the traced slice over the mean of
all the devices': 1.0 when the chips share the work, near their number
when one of them holds the split. None where the trace holds fewer than
two devices. Moves queries_per_s."""

from . import _mesh_trace


def read(run: dict):
    trace = _mesh_trace.read(run)
    if trace is None or len(trace["busy_s"]) < 2:
        return None
    mean = sum(trace["busy_s"]) / len(trace["busy_s"])
    return max(trace["busy_s"]) / mean if mean > 0 else None
