"""wait_room_ms - layer: executor. Source: POLL's `waits` (program_span).
Median per task of `waits.wait_room.wall_s`, in ms: the scan's prefetch
thread blocked putting a batch into the full queue
(`runtime/prefetch.py: prefetch`, the producer's `q.put()` after
`put_nowait` found it full), idle for want of room because the draining
thread or the device sets the pace. A diagnostic of which thread that is;
lower is nominal. Moves queries_per_s."""

from ._waits import median_wait_ms


def read(run: dict):
    return median_wait_ms(run, "wait_room")
