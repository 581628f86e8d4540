"""The readers of POLL's per-task stage table and dispatch count, on a
hand-made run, and `unnamed_idle_share` on the recorded trace
(`data/small.xplane.pb`, described by `data/small.json`)."""

import importlib
import json
import os

import pytest

from perfbench import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def reader(name):
    return importlib.import_module(f"perfbench.layer_metrics.{name}")


def row(wall_s, cpu_s, n=1):
    return {"wall_s": wall_s, "cpu_s": cpu_s, "n": n}


def record(poll, ok=True, device_run=True):
    return {"ok": ok, "device_run": device_run, "poll": poll}


def scan_task(k):
    """A scan task's POLL: its stages grow with k, so medians differ
    from means."""
    return {"task_dispatches": 128 + k, "dispatches": 255, "stages": {
        "decode_batch": row(0.100 * k, 0.050 * k, 65),
        "h2d": row(0.010 * k, 0.010 * k, 64),
        "d2h": row(0.200 * k, 0.050 * k, 64),
        "frame_encode": row(0.004 * k, 0.004 * k, 64),
        "frame_send": row(0.006 * k, 0.001 * k, 64),
    }}


RUN = {"records": [
    record(scan_task(1)), record(scan_task(2)), record(scan_task(4)),
    # a failed task and one a cache answered are no device runs
    record(scan_task(100), ok=False),
    record(scan_task(100), device_run=False),
]}

SHUFFLE_RUN = {"records": [record({"task_dispatches": 128, "stages": {
    "decode_batch": row(1.0, 0.9, 65),
    "d2h": row(2.0, 0.5, 64),
    "shuffle_partition": row(3.0, 1.0, 64),
    "shuffle_encode": row(6.0, 3.5, 64),
    "shuffle_finalize": row(0.5, 0.1, 1),
}})]}

# what the parent of PR 25 answers: no stage table, no per-task count
PARENT_RUN = {"records": [record({"dispatches": 255,
                                  "execution_s": 0.75})]}


@pytest.mark.parametrize("name, want", [
    ("task_dispatches", 130.0),
    ("scan_decode_ms", 220.0),
    ("readback_ms", 400.0),
    ("stream_encode_ms", 20.0),
    ("shuffle_encode_ms", 0.0),
    # 1 - (0.05 + 0.01 + 0.05 + 0.004 + 0.001) / 0.32, the same for
    # every k
    ("host_wait_share", 100.0 * (1 - 0.115 / 0.32)),
])
def test_reader_on_scan_tasks(name, want):
    assert reader(name).read(RUN) == pytest.approx(want)


@pytest.mark.parametrize("name, want", [
    ("task_dispatches", 128.0),
    ("scan_decode_ms", 1000.0),
    ("readback_ms", 2000.0),
    ("shuffle_encode_ms", 6500.0),
    ("stream_encode_ms", 0.0),   # a shuffle write streams no part
    ("host_wait_share", 100.0 * (1 - 6.0 / 12.5)),
])
def test_reader_on_a_shuffle_task(name, want):
    assert reader(name).read(SHUFFLE_RUN) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "task_dispatches", "scan_decode_ms", "readback_ms",
    "shuffle_encode_ms", "stream_encode_ms", "host_wait_share",
    "unnamed_idle_share"])
@pytest.mark.parametrize("run", [PARENT_RUN, {"records": []}],
                         ids=["parent", "empty"])
def test_reader_finds_nothing_and_does_not_raise(name, run):
    assert reader(name).read(dict(run, trace=None)) is None


def test_unnamed_idle_share_by_hand():
    trace = {"devices": 1, "window_s": 10.0, "busy_s": 2.0, "idle_gaps": [
        ["unnamed: no host span on the profiler's clock", 2.0],
        ["blaze.shuffle_encode", 4.0], ["ReadSyncFlag", 1.0]]}
    read = reader("unnamed_idle_share").read
    assert read({"trace": trace}) == pytest.approx(25.0)
    trace["idle_gaps"] = trace["idle_gaps"][1:]
    assert read({"trace": trace}) == 0.0
    assert read({"trace": {"devices": 0}}) is None


def test_unnamed_idle_share_on_the_recorded_trace():
    """`small.xplane.pb`: launches with pauses between them and a host
    that no `blaze.` span covers, so most of the idle time has no name;
    the reader agrees with the sum over the reduction's own gaps."""
    with open(os.path.join(DATA, "small.json")) as f:
        assert json.load(f)["device"]["platform"] == "tpu"
    trace = xplane.reduce_file(os.path.join(DATA, "small.xplane.pb"))
    share = reader("unnamed_idle_share").read({"trace": trace})
    unnamed = sum(s for n, s in trace["idle_gaps"]
                  if n.startswith("unnamed"))
    idle = trace["window_s"] - trace["busy_s"]
    assert share == pytest.approx(100.0 * unnamed / idle)
    # every gap of that trace is unnamed: nothing spans its host
    assert share == pytest.approx(100.0)
