"""The reader of POLL's `join_direct_batches`, on hand-made runs."""

import pytest

from perfbench.layer_metrics import join_direct_batches


def record(poll, ok=True, device_run=True):
    return {"ok": ok, "device_run": device_run, "poll": poll}


def join_task(direct, syncs=0):
    return {"task_dispatches": 393, "launches": 906,
            "join_build_rows": 6294, "join_probe_batches": 128,
            "join_pair_syncs": syncs, "join_direct_batches": direct,
            "stages": {"join_build": {"wall_s": 0.02, "cpu_s": 0.01,
                                      "n": 2}}}


# what a server without the counter answers (the parent of the direct
# array on the chip), and another cell's task, which joins nothing
PARENT_RUN = {"records": [record({
    "task_dispatches": 519, "launches": 904, "join_build_rows": 6294,
    "join_probe_batches": 128, "join_pair_syncs": 128,
    "stages": {"join_build": {"wall_s": 0.02, "cpu_s": 0.01, "n": 2}}})]}
GROUP_RUN = {"records": [record({
    "task_dispatches": 70, "agg_tier_retries": 0,
    "stages": {"agg_fetch": {"wall_s": 1.1, "cpu_s": 0.1, "n": 66}}})]}


@pytest.mark.parametrize("direct,want", [([128, 128, 128], 128.0),
                                         ([128, 126, 128, 124], 127.0),
                                         ([0, 0], 0.0)])
def test_median_over_device_runs(direct, want):
    run = {"records": [record(join_task(n)) for n in direct] + [
        # a failed task and one a cache answered are no device runs
        record(join_task(7), ok=False),
        record(join_task(7), device_run=False),
    ]}
    assert join_direct_batches.read(run) == pytest.approx(want)


@pytest.mark.parametrize("run", [PARENT_RUN, GROUP_RUN, {"records": []}],
                         ids=["parent", "group", "empty"])
def test_finds_nothing_and_does_not_raise(run):
    assert join_direct_batches.read(dict(run, trace=None)) is None
