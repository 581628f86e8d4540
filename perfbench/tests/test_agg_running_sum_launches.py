"""The reader of POLL's `agg_running_sum_launches`, on hand-made runs."""

import pytest

from perfbench.layer_metrics import agg_running_sum_launches


def record(poll, ok=True, device_run=True):
    return {"ok": ok, "device_run": device_run, "poll": poll}


def group_task(launches):
    return {"task_dispatches": 70, "agg_tier_retries": 0,
            "agg_running_sum_launches": launches,
            "stages": {"agg_fetch": {"wall_s": 1.1, "cpu_s": 0.1,
                                     "n": 66}}}


# what a server without the counter answers (the parent of PR 34), which
# is also what a task with no keyed aggregate answers
PARENT_RUN = {"records": [record({
    "task_dispatches": 70, "agg_tier_retries": 0, "execution_s": 4.1,
    "stages": {"agg_fetch": {"wall_s": 1.7, "cpu_s": 0.1, "n": 66}}})]}


@pytest.mark.parametrize("launches,want", [([66, 66, 66], 66.0),
                                           ([66, 65, 66, 0], 65.5),
                                           ([0, 0], 0.0)])
def test_median_over_device_runs(launches, want):
    run = {"records": [record(group_task(n)) for n in launches] + [
        # a failed task and one a cache answered are no device runs
        record(group_task(7), ok=False),
        record(group_task(7), device_run=False),
    ]}
    assert agg_running_sum_launches.read(run) == pytest.approx(want)


@pytest.mark.parametrize("run", [PARENT_RUN, {"records": []}],
                         ids=["parent", "empty"])
def test_finds_nothing_and_does_not_raise(run):
    assert agg_running_sum_launches.read(dict(run, trace=None)) is None
