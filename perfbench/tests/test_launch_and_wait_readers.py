"""The readers of POLL's launch counters and prefetch waits
(`launch_ms`, `task_launches`, `launch_buffers`, `wait_batch_ms`,
`wait_room_ms`), on hand-made runs: a value on the change's payload,
None on the parent's."""

import pytest

from perfbench.layer_metrics import (
    launch_buffers,
    launch_ms,
    task_launches,
    wait_batch_ms,
    wait_room_ms,
)


def record(poll, ok=True, device_run=True):
    return {"ok": ok, "device_run": device_run, "poll": poll}


def task(launches, launch_s, buffers, wait_batch=None, wait_room=None,
         traced=True):
    """What the change polls for a served task."""
    poll = {"task_dispatches": 384, "execution_s": 1.3,
            "launches": launches, "launch_s": launch_s,
            "launch_buffers": buffers}
    if traced:
        poll["stages"] = {"d2h": {"wall_s": 0.2, "cpu_s": 0.01, "n": 128}}
        poll["waits"] = {
            "wait_batch": {"wall_s": wait_batch or 0.0,
                           "n": 3 if wait_batch else 0},
            "wait_room": {"wall_s": wait_room or 0.0,
                          "n": 40 if wait_room else 0},
        }
    return poll


# what a server without the counters and waits answers (the parent of
# PR 35)
PARENT_RUN = {"records": [record({
    "task_dispatches": 384, "dispatches": 391, "execution_s": 1.3,
    "stages": {"d2h": {"wall_s": 0.2, "cpu_s": 0.01, "n": 128}}})]}

NOT_DEVICE_RUNS = [
    # a failed task and one a cache answered are no device runs
    record(task(9, 9.0, 99, 9.0, 9.0), ok=False),
    record(task(9, 9.0, 99, 9.0, 9.0), device_run=False),
]

READERS = [launch_ms, task_launches, launch_buffers, wait_batch_ms,
           wait_room_ms]


def test_median_over_device_runs():
    run = {"records": [
        record(task(512, 0.110, 11000, 0.002, 0.700)),
        record(task(512, 0.120, 11000, 0.004, 0.600)),
        record(task(510, 0.130, 10900, None, 0.800)),
    ] + NOT_DEVICE_RUNS}
    assert task_launches.read(run) == 512.0
    assert launch_ms.read(run) == pytest.approx(120.0)
    assert launch_buffers.read(run) == 11000.0
    # a task that never waited reads 0, not nothing
    assert wait_batch_ms.read(run) == pytest.approx(2.0)
    assert wait_room_ms.read(run) == pytest.approx(700.0)


def test_no_trace_payload_keeps_the_counters():
    """`serve --no-trace` polls the counters and no waits."""
    run = {"records": [record(task(128, 0.05, 300, traced=False))]}
    assert task_launches.read(run) == 128.0
    assert launch_ms.read(run) == pytest.approx(50.0)
    assert wait_batch_ms.read(run) is None
    assert wait_room_ms.read(run) is None


@pytest.mark.parametrize("reader", READERS,
                         ids=[r.__name__.split(".")[-1] for r in READERS])
@pytest.mark.parametrize("run", [PARENT_RUN, {"records": []}],
                         ids=["parent", "empty"])
def test_finds_nothing_and_does_not_raise(reader, run):
    assert reader.read(dict(run, trace=None)) is None
