"""mesh_exchange_share - layer: kernels. Source: device_trace.
Of the devices' busy time in the traced slice, all of them together, the
share inside the all-to-all operations on the `XLA Ops` line, in percent:
what the exchange of the partial groups over ICI costs the mesh
group-by. None where the trace holds no such operation. Moves
queries_per_s."""

from . import _mesh_trace


def read(run: dict):
    trace = _mesh_trace.read(run)
    if trace is None or sum(trace["exchange_s"]) <= 0:
        return None
    return 100.0 * sum(trace["exchange_s"]) / sum(trace["busy_s"])
