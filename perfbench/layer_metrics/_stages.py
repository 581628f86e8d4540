"""What the readers of POLL's per-task stage table share. The table
(`poll["stages"]`: `{stage: {"wall_s", "cpu_s", "n"}}`) is folded by the
serving process from the task's own spans; a program without it (the
parent of the PR that brought it) gives every reader None."""

from __future__ import annotations

import statistics

from ._common import device_runs


def tables(run: dict) -> list:
    """The stage table of each task that ran on the device and has one."""
    return [r["poll"]["stages"] for r in device_runs(run)
            if isinstance(r["poll"].get("stages"), dict)]


def wall_s(table: dict, *stages: str) -> float:
    """Seconds a task's threads spent in these stages; a stage the task
    never entered counts 0."""
    return sum(table.get(s, {}).get("wall_s", 0.0) for s in stages)


def median_wall_ms(run: dict, *stages: str) -> float | None:
    got = [wall_s(t, *stages) for t in tables(run)]
    return 1e3 * statistics.median(got) if got else None
