"""launch_ms - layer: executor. Source: POLL (program_counter).
Median per task of `launch_s`, in ms: the launching threads' wall time
inside every program call made for the task (`runtime/dispatch.py:
_launch`, two clock reads around each cached kernel and each wrapped
plain jit), tracing on or off. Dispatch is async, so this is what a
launch costs its thread, not the device's time. Moves queries_per_s."""

from ._waits import median_field


def read(run: dict):
    return median_field(run, "launch_s", 1e3)
