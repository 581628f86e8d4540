"""The reader of POLL's `concat_slice_parts`, on hand-made runs."""

import pytest

from perfbench.layer_metrics import concat_slice_parts


def record(poll, ok=True, device_run=True):
    return {"ok": ok, "device_run": device_run, "poll": poll}


def group_task(parts):
    return {"task_dispatches": 70, "agg_running_sum_launches": 67,
            "concat_slice_parts": parts,
            "stages": {"agg_fetch": {"wall_s": 1.1, "cpu_s": 0.1,
                                     "n": 66}}}


# what a server without the counter answers (the parent of the slice
# form), which is also what a task that concatenates nothing answers
PARENT_RUN = {"records": [record({
    "task_dispatches": 70, "agg_running_sum_launches": 67,
    "execution_s": 3.1,
    "stages": {"agg_fetch": {"wall_s": 1.1, "cpu_s": 0.1, "n": 66}}})]}


@pytest.mark.parametrize("parts,want", [([64, 64, 64], 64.0),
                                        ([64, 63, 64, 62], 63.5),
                                        ([2], 2.0)])
def test_median_over_device_runs(parts, want):
    run = {"records": [record(group_task(n)) for n in parts] + [
        # a failed task and one a cache answered are no device runs
        record(group_task(7), ok=False),
        record(group_task(7), device_run=False),
    ]}
    assert concat_slice_parts.read(run) == pytest.approx(want)


@pytest.mark.parametrize("run", [PARENT_RUN, {"records": []}],
                         ids=["parent", "empty"])
def test_finds_nothing_and_does_not_raise(run):
    assert concat_slice_parts.read(dict(run, trace=None)) is None
