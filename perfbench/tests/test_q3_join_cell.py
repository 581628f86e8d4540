"""The cell `q3_join.s4`'s three readers: `join_build_ms` and
`join_pair_syncs` on recorded POLLs, `join_probe_roofline` on the
recorded trace (`data/join_small.xplane.pb`: five joins of a 16,384-row
probe batch against 6,000 broadcast keys on the chip's sort core) and on
a hand-made reduction; each finds nothing in another cell's run, in the
parent's, and in a run with no trace."""

import json
import os
import shutil
import types

import pytest

from perfbench.layer_metrics import (
    join_build_ms, join_pair_syncs, join_probe_roofline,
)
from perfbench.templates import q3_join

HERE = os.path.dirname(os.path.abspath(__file__))
TYPES = {"ss_sold_date_sk": "int32", "ss_item_sk": "int32",
         "ss_ext_sales_price": "decimal(7,2)"}


def record(poll, ok=True, device_run=True, rows_out=232):
    return {"ok": ok, "device_run": device_run, "poll": poll,
            "template": "q3_join", "rows_out": rows_out}


def join_task(syncs=128, build_s=0.012):
    """A POLL of a `q3_join` task on the chip's cores."""
    return {"task_dispatches": 519, "launches": 904,
            "join_build_rows": 6294, "join_probe_batches": 128,
            "join_pair_syncs": syncs,
            "stages": {"join_build": {"wall_s": build_s, "cpu_s": 0.01,
                                      "n": 2},
                       "d2h": {"wall_s": 0.002, "cpu_s": 0.001, "n": 1}}}


RUN = {"records": [record(join_task(128, 0.010)),
                   record(join_task(128, 0.014)),
                   record(join_task(128, 0.020)),
                   # failed, or answered with no device run: not read
                   record(join_task(7, 9.0), ok=False),
                   record(join_task(7, 9.0), device_run=False)]}
# what the parent's server answers the same task: no span, no counter
PARENT_RUN = {"records": [record({
    "task_dispatches": 519, "launches": 904,
    "stages": {"d2h": {"wall_s": 0.002, "cpu_s": 0.001, "n": 1}}})]}
# another cell's task: a grouped aggregate with no join
GROUP_RUN = {"records": [record({
    "task_dispatches": 70, "agg_tier_retries": 0,
    "stages": {"agg_fetch": {"wall_s": 1.1, "cpu_s": 0.1, "n": 66}}})]}


def test_span_and_counter_on_a_recorded_poll():
    assert join_build_ms.read(RUN) == pytest.approx(14.0)
    assert join_pair_syncs.read(RUN) == 128.0
    # the table core reads back no pair count: 0 is a reading
    table = {"records": [record(join_task(0))]}
    assert join_pair_syncs.read(table) == 0.0


READERS = [join_build_ms, join_pair_syncs, join_probe_roofline]


@pytest.mark.parametrize("reader", READERS)
@pytest.mark.parametrize("run", [PARENT_RUN, GROUP_RUN, {"records": []}],
                         ids=["parent", "group", "empty"])
def test_reader_finds_nothing_and_does_not_raise(reader, run):
    assert reader.read(dict(run, trace=None)) is None


@pytest.fixture
def traced_run(tmp_path):
    """A run whose cell left the recorded trace where the launcher writes
    one."""
    where = tmp_path / "trace" / "plugins" / "profile" / "2026_10_15"
    os.makedirs(where)
    shutil.copy(os.path.join(HERE, "data", "join_small.xplane.pb"),
                where / "host.xplane.pb")
    cell = types.SimpleNamespace(
        workdir=str(tmp_path), config={"batch_rows": 16384},
        table_cfg={"split_rows": 1048576}, types=TYPES,
        template=lambda name: q3_join)
    return dict(RUN, cell=cell, peaks={"hbm_bytes_per_s": 819e9},
                trace={"devices": 1, "busy_s": 1e-3, "window_s": 0.2})


def test_roofline_on_the_recorded_trace(traced_run):
    with open(os.path.join(HERE, "data", "join_small.json")) as f:
        described = json.load(f)
    assert described["core"] == "sort"
    reduced = join_probe_roofline.reduced(traced_run)
    assert reduced["kernel_events"]["join.probe"] == described["launches"]
    assert reduced["kernel_events"]["join.emit"] == described["launches"]
    seconds = reduced["kernel_s"]["join.probe"] \
        + reduced["kernel_s"]["join.emit"]
    assert 1e-6 < seconds / described["launches"] < 1e-2
    rows = described["launches"] * described["rows"]
    least = q3_join.least_bytes(rows, rows * 232 / 1048576, TYPES)
    share = join_probe_roofline.read(traced_run)
    assert share == pytest.approx(100.0 * least / 819e9 / seconds)
    assert 0.0 < share < 100.0


def test_roofline_on_a_recorded_reduction(traced_run):
    """128 probe batches entered the join's programs in a slice where
    they took 0.05 s: three 4-byte columns and their validity a row in,
    and the groups out at the window's ratio."""
    traced_run["join_trace"] = {
        "devices": 1, "busy_s": 0.5,
        "kernel_events": {"join.probe": 128, "join.emit": 128},
        "kernel_s": {"join.probe": 0.02, "join.emit": 0.03}}
    rows = 128 * 16384
    least = (3 * (4 + 1 / 8)) * rows + (16 + q3_join.BRAND_BYTES) \
        * rows * 232 / 1048576
    assert join_probe_roofline.read(traced_run) == pytest.approx(
        100.0 * least / 819e9 / 0.05)


def test_no_trace_file_no_share(traced_run):
    shutil.rmtree(os.path.join(traced_run["cell"].workdir, "trace"))
    assert join_probe_roofline.read(traced_run) is None
