"""Selections the readers share."""

from __future__ import annotations

import statistics


def device_runs(run: dict) -> list:
    return [r for r in run["records"] if r["ok"] and r["device_run"]]


def median_ms(values) -> float | None:
    values = list(values)
    return 1e3 * statistics.median(values) if values else None


def server_s(rec: dict) -> float:
    p = rec["poll"]
    return (p.get("queue_wait_s", 0.0) + p.get("admission_s", 0.0)
            + p.get("execution_s", 0.0))


def traced_rows(run: dict) -> dict:
    """{template: rows that entered the device in the traced slice},
    counted from the trace itself: launches of the template's per-batch
    program (the `batch.<template>` pattern of `trace_patterns.json`, on
    the device's module line) times the rows of a batch, which the
    configuration states (`batch_rows`, spark.blaze.batchSize). A split
    is a whole number of batches. No host clock comes into it."""
    trace = run["trace"]
    batch = int(run["cell"].config["batch_rows"])
    out = {}
    for r in device_runs(run):
        if r["template"] not in out:
            launches = trace["kernel_events"].get(
                "batch." + r["template"], 0)
            out[r["template"]] = launches * batch
    return out


def out_per_row(run: dict, template: str) -> float:
    """Rows a request of this template returned for each row it scanned,
    over the window's device runs: exact counts, applied to the traced
    rows to say how many of them came back out."""
    runs = [r for r in device_runs(run) if r["template"] == template]
    rows_in = len(runs) * int(run["cell"].table_cfg["split_rows"])
    return sum(r["rows_out"] for r in runs) / rows_in if rows_in else 0.0
