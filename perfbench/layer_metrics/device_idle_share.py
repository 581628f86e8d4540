"""device_idle_share - layer: device. Source: device_trace.
1 less the union of the device-operation intervals over the traced slice,
in percent, from the profiler's `.xplane.pb`. Moves queries_per_s."""


def read(run: dict):
    trace = run.get("trace")
    if not trace or trace.get("idle_share") is None \
            or not trace.get("busy_s"):
        return None
    return 100.0 * trace["idle_share"]
