"""queue_wait_ms - layer: service. Source: POLL (program_span).
Median of `queue_wait_s + admission_s`: how long a request waited for one
of the service's slots. Moves latency_p50_ms."""

from ._common import median_ms


def read(run: dict):
    return median_ms(
        r["poll"].get("queue_wait_s", 0.0) + r["poll"].get("admission_s", 0.0)
        for r in run["records"] if r["ok"])
