"""wire_overhead_ms - layer: wire. Source: host_clock and POLL.
Median over the window's requests of the client's latency less what the
server accounts for (`queue_wait_s + admission_s + execution_s`): frames,
sockets, the wire plane's waits. Moves latency_p50_ms."""

from ._common import median_ms, server_s


def read(run: dict):
    return median_ms(r["latency_s"] - server_s(r)
                     for r in run["records"] if r["ok"])
