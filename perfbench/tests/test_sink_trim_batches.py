"""The reader of POLL's `sink_trim_batches`, on hand-made runs."""

import pytest

from perfbench.layer_metrics import sink_trim_batches


def record(poll, ok=True, device_run=True):
    return {"ok": ok, "device_run": device_run, "poll": poll}


def scan_task(batches):
    return {"task_dispatches": 2 * batches, "sink_trim_batches": batches,
            "stages": {"d2h": {"wall_s": 0.12, "cpu_s": 0.05,
                               "n": batches}}}


RUN = {"records": [
    record(scan_task(64)), record(scan_task(64)), record(scan_task(64)),
    # a split with a batch fewer
    record(scan_task(63)),
    # a failed task and one a cache answered are no device runs
    record(scan_task(1), ok=False),
    record(scan_task(1), device_run=False),
]}

# what a server without the counter answers (the parent of PR 28), which
# is also what a task answers whose sink saw no selection
PARENT_RUN = {"records": [record({
    "task_dispatches": 128, "execution_s": 0.54,
    "stages": {"compact": {"wall_s": 0.29, "cpu_s": 0.15, "n": 64}}})]}


def test_median_over_device_runs():
    assert sink_trim_batches.read(RUN) == pytest.approx(64.0)


@pytest.mark.parametrize("run", [PARENT_RUN, {"records": []}],
                         ids=["parent", "empty"])
def test_finds_nothing_and_does_not_raise(run):
    assert sink_trim_batches.read(dict(run, trace=None)) is None
