"""The traced run's per-layer numbers: reduce the trace once, then ask
each per-layer metric's own reader (`layer_metrics/<name>.py`, found by
the name in BENCHMARK.json) for its value. A reader that finds nothing
to read returns None and the metric is left out of the line."""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_of(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "about":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "perfbench/peaks.json")
    return table[device_kind]


def read_all(run: dict, tracer: dict, out_device: dict):
    """({metric: value}, breakdown or None); puts busy_s and window_s into
    the result's device."""
    from perfbench import xplane

    breakdown = None
    if tracer.get("xplane"):
        reduced = xplane.reduce_file(tracer["xplane"][0])
        reduced["slice"] = (tracer["started"], tracer["stopped"])
        run["trace"] = reduced
        if reduced.get("devices"):
            out_device["busy_s"] = reduced["busy_s"]
            out_device["window_s"] = reduced["window_s"]
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
    elif tracer.get("error"):
        run["trace_error"] = tracer["error"]
    if run["device"]["platform"] == "tpu":
        run["peaks"] = peaks_of(run["device"]["kind"])
    values = {}
    for m in run["cell"].per_layer:
        reader = importlib.import_module(
            f"perfbench.layer_metrics.{m['name']}")
        values[m["name"]] = reader.read(run)
    return values, breakdown
