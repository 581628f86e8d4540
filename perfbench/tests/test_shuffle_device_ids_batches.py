"""The reader of POLL's `shuffle_device_ids_batches`, on hand-made runs."""

import pytest

from perfbench.layer_metrics import shuffle_device_ids_batches


def record(poll, ok=True, device_run=True):
    return {"ok": ok, "device_run": device_run, "poll": poll}


def shuffle_task(batches):
    return {"task_dispatches": 4 * batches,
            "shuffle_device_ids_batches": batches, "shuffle_segments": 200,
            "stages": {"shuffle_partition": {"wall_s": 0.1, "cpu_s": 0.08,
                                             "n": batches}}}


# what a server without the counter answers (the parent of PR 32), which
# is also what a shuffle on a string key answers
PARENT_RUN = {"records": [record({
    "task_dispatches": 256, "shuffle_pallas_batches": 128,
    "stages": {"shuffle_partition": {"wall_s": 0.94, "cpu_s": 0.7,
                                     "n": 128}}})]}


@pytest.mark.parametrize("batches,want", [([128, 128, 128], 128.0),
                                          ([64, 64, 64, 63], 64.0)])
def test_median_over_device_runs(batches, want):
    run = {"records": [record(shuffle_task(b)) for b in batches] + [
        # a failed task and one a cache answered are no device runs
        record(shuffle_task(1), ok=False),
        record(shuffle_task(1), device_run=False),
    ]}
    assert shuffle_device_ids_batches.read(run) == pytest.approx(want)


@pytest.mark.parametrize("run", [PARENT_RUN, {"records": []}],
                         ids=["parent", "empty"])
def test_finds_nothing_and_does_not_raise(run):
    assert shuffle_device_ids_batches.read(dict(run, trace=None)) is None
