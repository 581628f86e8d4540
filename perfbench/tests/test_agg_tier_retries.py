"""The reader of POLL's `agg_tier_retries`, on hand-made runs."""

import pytest

from perfbench.layer_metrics import agg_tier_retries


def record(poll, ok=True, device_run=True):
    return {"ok": ok, "device_run": device_run, "poll": poll}


def group_task(retries):
    return {"task_dispatches": 69 + retries, "agg_tier_retries": retries,
            "stages": {"agg_fetch": {"wall_s": 1.7, "cpu_s": 0.1,
                                     "n": 66}}}


# what a server without the counter answers (the parent of PR 30), which
# is also what a task with no keyed aggregate answers
PARENT_RUN = {"records": [record({
    "task_dispatches": 71, "execution_s": 5.6,
    "stages": {"agg_fetch": {"wall_s": 2.8, "cpu_s": 0.1, "n": 66}}})]}


@pytest.mark.parametrize("retries,want", [([0, 0, 0], 0.0),
                                          ([2, 2, 2, 0], 2.0)])
def test_median_over_device_runs(retries, want):
    run = {"records": [record(group_task(r)) for r in retries] + [
        # a failed task and one a cache answered are no device runs
        record(group_task(7), ok=False),
        record(group_task(7), device_run=False),
    ]}
    assert agg_tier_retries.read(run) == pytest.approx(want)


@pytest.mark.parametrize("run", [PARENT_RUN, {"records": []}],
                         ids=["parent", "empty"])
def test_finds_nothing_and_does_not_raise(run):
    assert agg_tier_retries.read(dict(run, trace=None)) is None
