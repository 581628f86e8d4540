"""The reduction from a profiler trace to busy time, idle share, per-name
sums and named gaps: on hand-made intervals, and on `data/small.xplane.pb`,
recorded on one TPU v5e by `record_trace.py` (5 launches of a jitted sum
and 5 of the Pallas murmur3 program, 20 ms pauses between them)."""

import json
import os

import numpy as np
import pytest

from perfbench import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SMALL = os.path.join(DATA, "small.xplane.pb")


# the kernel the recorded trace holds, whatever programs the cells' own
# entries of trace_patterns.json name today
PATTERNS = dict(xplane.load_patterns(), kernels={"murmur3": {
    "line": "^XLA Modules$", "name": r"^jit_partition_ids_int(32|64)\("}})


def ev(*triples):
    return (np.array([t[0] for t in triples], dtype=object),
            np.array([t[1] for t in triples], dtype=np.float64),
            np.array([t[2] for t in triples], dtype=np.float64))


def test_union_counts_overlap_once():
    total, ms, me = xplane.union_seconds(
        np.array([0.0, 1.0, 1.5, 5.0, 5.2]),
        np.array([2.0, 1.2, 3.0, 6.0, 5.5]))
    assert total == pytest.approx(4.0)
    assert ms.tolist() == [0.0, 5.0] and me.tolist() == [3.0, 6.0]
    assert xplane.union_seconds(np.array([]), np.array([]))[0] == 0.0


def test_hand_made_planes():
    patterns = PATTERNS
    planes = {
        "devices": {"/device:TPU:0": {
            # busy 0-2 (two ops overlapping), 4-5, 9-10: 4 s of 10
            "ops": ev(("fusion.1", 0.0, 2.0), ("copy.2", 1.0, 1.5),
                      ("fusion.1", 4.0, 5.0), ("sort.3", 9.0, 10.0)),
            "lines": {"XLA Modules": ev(
                ("jit_partition_ids_int64(123)", 4.0, 4.5),
                ("jit_other(9)", 9.0, 10.0))},
        }},
        # the host decodes through the first gap; nothing covers the second
        "host": ev(("request", 0.0, 6.0), ("decode", 2.1, 3.9),
                   ("tiny", 3.0, 3.0001)),
    }
    r = xplane.reduce_planes(planes, patterns)
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(4.0)
    assert r["window_s"] == pytest.approx(10.0)
    assert r["idle_share"] == pytest.approx(0.6)
    assert dict(map(tuple, r["device_ops"]))["fusion.1"] == pytest.approx(3.0)
    assert r["kernel_s"]["murmur3"] == pytest.approx(0.5)
    assert r["kernel_events"]["murmur3"] == 1
    gaps = dict(map(tuple, r["idle_gaps"]))
    # 2-4 is named by the shortest span over its middle, 5-9 by none
    assert gaps["tiny"] == pytest.approx(2.0)
    assert gaps["unnamed: no host span on the profiler's clock"] \
        == pytest.approx(4.0)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_no_device_plane_reads_nothing():
    r = xplane.reduce_planes({"devices": {}, "host": ev()},
                             xplane.load_patterns())
    assert r == {"devices": 0}


def _brute_union(intervals):
    total, reach = 0.0, None
    for a, b in sorted(intervals):
        if reach is None or a > reach:
            total += b - a
            reach = b
        elif b > reach:
            total += b - reach
            reach = b
    return total


def test_recorded_trace():
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "small.json")) as f:
        meta = json.load(f)
    assert meta["device"] == {"platform": "tpu", "kind": "TPU v5 lite"}
    r = xplane.reduce_file(SMALL, PATTERNS)
    # the same numbers, worked out the slow way from the raw events
    ops, mods = [], []
    for plane in ProfileData.from_file(SMALL).planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            for e in line.events:
                row = (e.name, e.start_ns / 1e9,
                       (e.start_ns + e.duration_ns) / 1e9)
                if line.name == "XLA Ops":
                    ops.append(row)
                elif line.name == "XLA Modules":
                    mods.append(row)
    assert r["devices"] == 1 and r["op_events"] == len(ops) == 45
    assert r["busy_s"] == pytest.approx(
        _brute_union([(a, b) for _, a, b in ops]), rel=1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["idle_share"] == pytest.approx(
        1 - r["busy_s"] / r["window_s"])
    # 10 launches with 20 ms pauses: the device idles nearly all the time
    assert r["window_s"] > 10 * meta["pause_s"] * 0.9
    assert r["idle_share"] > 0.99
    by_name = {}
    for n, a, b in ops:
        by_name[n] = by_name.get(n, 0.0) + (b - a)
    top_name, top_s = max(by_name.items(), key=lambda kv: kv[1])
    assert "multiply_reduce_fusion" in top_name
    assert r["device_ops"][0][0] == xplane.short(top_name)
    assert r["device_ops"][0][1] == pytest.approx(top_s)
    kernel = [(a, b) for n, a, b in mods
              if n.startswith("jit_partition_ids_int64(")]
    assert r["kernel_events"]["murmur3"] == len(kernel) == meta["launches"]
    assert r["kernel_s"]["murmur3"] == pytest.approx(
        sum(b - a for a, b in kernel))
    assert sum(s for _, s in r["idle_gaps"]) <= \
        r["window_s"] - r["busy_s"] + 1e-9
