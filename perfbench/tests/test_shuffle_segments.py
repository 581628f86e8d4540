"""The reader of POLL's `shuffle_segments`, on hand-made runs."""

import pytest

from perfbench.layer_metrics import shuffle_segments


def record(poll, ok=True, device_run=True):
    return {"ok": ok, "device_run": device_run, "poll": poll}


def shuffle_task(parts):
    return {"task_dispatches": 128, "shuffle_segments": parts,
            "stages": {"shuffle_encode": {"wall_s": 2.0, "cpu_s": 1.5,
                                          "n": 65}}}


RUN = {"records": [
    record(shuffle_task(200)), record(shuffle_task(200)),
    record(shuffle_task(212)), record(shuffle_task(230)),
    # a failed task and one a cache answered are no device runs
    record(shuffle_task(12800), ok=False),
    record(shuffle_task(12800), device_run=False),
]}

# what a server without the counter answers (the parent of PR 26), and
# a task that wrote no shuffle
PARENT_RUN = {"records": [record({"task_dispatches": 128,
                                  "execution_s": 14.5})]}


def test_median_over_device_runs():
    assert shuffle_segments.read(RUN) == pytest.approx(206.0)


@pytest.mark.parametrize("run", [PARENT_RUN, {"records": []}],
                         ids=["parent", "empty"])
def test_finds_nothing_and_does_not_raise(run):
    assert shuffle_segments.read(dict(run, trace=None)) is None
