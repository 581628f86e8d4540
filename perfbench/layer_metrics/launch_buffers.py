"""launch_buffers - layer: executor. Source: POLL (program_counter).
Median per task of `launch_buffers`: the arrays the task's program
launches handed back (`runtime/dispatch.py: _launch`, the leaves of each
call's result). A launch costs its thread about 40 us for each (ROADMAP
S6). Moves queries_per_s."""

from ._waits import median_field


def read(run: dict):
    return median_field(run, "launch_buffers")
