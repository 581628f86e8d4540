"""unnamed_idle_share - layer: device. Source: device_trace.
Of the traced slice's idle time (`window_s - busy_s`), the share in gaps
that no host span names (`xplane.py: name_gaps` calls them "unnamed: ..."),
in percent. `idle_gaps` keeps the ten longest names; 0 when "unnamed" is
not among them. Moves queries_per_s."""


def read(run: dict):
    trace = run.get("trace")
    if not trace or not trace.get("devices") \
            or trace.get("idle_gaps") is None:
        return None
    idle = trace["window_s"] - trace["busy_s"]
    if idle <= 0:
        return None
    unnamed = sum(s for name, s in trace["idle_gaps"]
                  if str(name).startswith("unnamed"))
    return 100.0 * unnamed / idle
