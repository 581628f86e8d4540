"""What the readers of POLL's launch counters and prefetch waits share.
POLL carries `launches`, `launch_s` and `launch_buffers` for every task
(`runtime/dispatch.py: _launch`, tracing on or off) and, where the
server traces, `waits` (`{wait: {"wall_s", "n"}}`, both names, 0 where
no call blocked: `runtime/prefetch.py`). A program without them (the
parent of the PR that brought them) gives every reader None."""

from __future__ import annotations

import statistics

from ._common import device_runs


def median_field(run: dict, field: str, scale: float = 1.0):
    got = [scale * r["poll"][field] for r in device_runs(run)
           if field in r["poll"]]
    return float(statistics.median(got)) if got else None


def median_wait_ms(run: dict, name: str):
    got = [1e3 * r["poll"]["waits"].get(name, {}).get("wall_s", 0.0)
           for r in device_runs(run)
           if isinstance(r["poll"].get("waits"), dict)]
    return float(statistics.median(got)) if got else None
