"""The cell `repart200_inv.s4`'s own pieces: the `inventory`
generator's invariants, the template by hand, the four
readers on a recorded POLL and on the recorded trace
(`data/small.xplane.pb`: five launches of the Pallas murmur3 program over
16,384 `bigint` keys), and one whole run of the cell on the CPU at the
configuration's rehearsal size."""

import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

from perfbench import datagen, murmur3
from perfbench import run as bench_run
from perfbench.generators import tpcds_inventory
from perfbench.layer_metrics import (
    _shuffle_trace, shuffle_hash_roofline, shuffle_pallas_batches,
    shuffle_partition_ms, shuffle_roofline,
)
from perfbench.templates import repart_key

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELLS = ["repart200_inv.s4"]
IDS, WAREHOUSES = 150_000, 20


def cell_of(name, rehearse=False):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return bench_run.Cell(json.load(f), name, rehearse)


# ---- the generator ------------------------------------------------------

@pytest.fixture(scope="module")
def inventory():
    """(data configuration, first row, frames) at 4 splits of 262,144
    rows: seven changes of warehouse, and from this first row a change of
    snapshot."""
    cell = cell_of(CELLS[0])
    t = cell.table_cfg
    t["split_rows"], t["splits"] = 262_144, 4
    t["first_row_range"] = [5_400_000, 5_400_000]
    frames = datagen.gen_tables(cell.data_cfg, cell.config["generator"],
                                11)["inventory"]
    return cell.data_cfg, 5_400_000, frames


def test_inventory_rows_in_dsdgens_order(inventory):
    cfg, first, frames = inventory
    assert [f["rows"] for f in frames] == [262_144] * 4
    assert list(frames[0]["types"]) == [
        "inv_date_sk", "inv_item_sk", "inv_warehouse_sk",
        "inv_quantity_on_hand"]
    v = {c: np.concatenate([f["values"][c] for f in frames])
         for c in frames[0]["types"]}
    assert all(a.dtype == np.int32 for a in v.values())
    i = first + np.arange(4 * 262_144)
    # item fastest, then warehouse, then week, from the row's number
    np.testing.assert_array_equal(v["inv_warehouse_sk"],
                                  i // IDS % WAREHOUSES + 1)
    week = i // (IDS * WAREHOUSES)
    assert set(week) == {1, 2}
    thursday = 2450815  # 1998-01-01
    np.testing.assert_array_equal(v["inv_date_sk"], thursday + 7 * week)
    assert not np.any((v["inv_date_sk"] - thursday) % 7)
    # within one warehouse of one snapshot the item keys climb, a
    # revision of each id, and an id's key does not depend on the
    # warehouse
    start = IDS - first % IDS  # the next warehouse's first row
    run = v["inv_item_sk"][start:start + IDS]
    assert len(np.unique(run)) == IDS and np.all(np.diff(run) > 0)
    assert run.min() >= 1 and run.max() <= 300_000
    nxt = v["inv_item_sk"][start + IDS:start + 2 * IDS]
    np.testing.assert_array_equal(run, nxt)


def test_inventory_item_revisions():
    cfg = {"data_date_range": ["1998-01-01", "2003-12-31"],
           "cardinalities": {"item": 300_000}}
    ids = np.array([1, 2, 3, 4, 5, 6, 149_999, 150_000])
    first = tpcds_inventory.date_sk("1998-01-01")
    early = tpcds_inventory.item_sk(ids, np.full(8, first), cfg)
    late = tpcds_inventory.item_sk(ids, np.full(8, first + 2100), cfg)
    # one, two and three revisions in turn: six keys for three ids
    assert list(early) == [1, 2, 4, 7, 8, 10, 299_996, 299_998]
    assert list(late) == [1, 3, 6, 7, 9, 12, 299_997, 300_000]
    mid = tpcds_inventory.item_sk(ids, np.full(8, first + 900), cfg)
    assert list(mid[:3]) == [1, 2, 5]


def test_inventory_nulls(inventory):
    _, _, frames = inventory
    for f in frames:
        for key in tpcds_inventory.KEY_COLUMNS:
            assert f["valid"][key] is None
        ok = f["valid"]["inv_quantity_on_hand"]
        assert 0.035 < 1 - ok.mean() < 0.055
        q = f["values"]["inv_quantity_on_hand"]
        assert q.min() == 0 and q.max() == 1000


def test_inventory_is_the_seeds(inventory):
    cfg, _, frames = inventory
    again = datagen.gen_tables(cfg, "tpcds_inventory", 11)["inventory"]
    other = datagen.gen_tables(cfg, "tpcds_inventory", 12)["inventory"]
    for c in frames[0]["types"]:
        np.testing.assert_array_equal(frames[1]["values"][c],
                                      again[1]["values"][c])
    assert not np.array_equal(
        frames[1]["values"]["inv_quantity_on_hand"],
        other[1]["values"]["inv_quantity_on_hand"])


# ---- the template -------------------------------------------------------

def test_reference_and_control_by_hand():
    frame = {"rows": 4, "types": {"k": "int32", "x": "int32"},
             "values": {"k": np.array([1, 2, 0, 7], np.int32),
                        "x": np.arange(4, dtype=np.int32)},
             "valid": {"k": np.array([1, 1, 0, 1], bool), "x": None}}
    params = {"key": "k", "partitions": 200}
    want = repart_key.reference(frame, params)
    # Spark: hash(1) = -559580957, hash(2) = 1765031574; a NULL key
    # leaves the seed
    assert list(murmur3.hash_int(np.array([1, 2]))) == [-559580957,
                                                        1765031574]
    assert list(want["partition"]) == [
        -559580957 % 200, 1765031574 % 200, 42,
        int(murmur3.hash_int(np.array([7]))[0]) % 200]
    got = dict(frame, partition=want["partition"], partitions=200)
    assert repart_key.compare(want, got) == {
        "rows_misplaced": 0, "rows_differ": 0, "partitions_wrong": 0}
    wide = repart_key.control(frame, params)
    assert list(wide["partition"][:2]) == [
        int(h) % 200 for h in murmur3.hash_long(np.array([1, 2]))]
    assert wide["partition"][2] == 42
    assert repart_key.compare(want, wide)["rows_misplaced"] == 3
    assert repart_key.compare(want, None)["partitions_wrong"] == 1
    assert repart_key.compare(
        want, dict(got, partitions=8))["partitions_wrong"] == 1
    assert repart_key.least_bytes(10, 10, frame["types"]) == 160
    assert repart_key.hash_bytes(16384, "int32") == 131072
    assert repart_key.hash_bytes(16384, "int64") == 196608


# ---- the readers --------------------------------------------------------

def record(poll, ok=True, device_run=True):
    return {"ok": ok, "device_run": device_run, "poll": poll,
            "template": "repart_key",
            "params": {"key": "k", "partitions": 200}}


def task(batches, partition_s):
    return {"task_dispatches": 5 * batches,
            "shuffle_pallas_batches": batches, "shuffle_segments": 200,
            "stages": {"shuffle_partition": {
                "wall_s": partition_s, "cpu_s": 0.1, "n": batches},
                "d2h": {"wall_s": 0.5, "cpu_s": 0.1, "n": batches}}}


RUN = {"records": [
    record(task(128, 0.30)), record(task(128, 0.50)),
    record(task(128, 0.40)), record(task(127, 0.90)),
    # a failed task and one a cache answered are no device runs
    record(task(0, 9.0), ok=False), record(task(0, 9.0), device_run=False),
]}
# what the parent of this PR answers (no counter), and a scan's task
PARENT_RUN = {"records": [record({"task_dispatches": 640, "stages": {
    "shuffle_partition": {"wall_s": 2.2, "cpu_s": 1.5, "n": 64}}})]}
SCAN_RUN = {"records": [record({"task_dispatches": 128, "stages": {
    "d2h": {"wall_s": 0.3, "cpu_s": 0.1, "n": 64}}})]}


def test_counter_and_span_on_a_recorded_poll():
    assert shuffle_pallas_batches.read(RUN) == 128.0
    assert shuffle_partition_ms.read(RUN) == pytest.approx(450.0)
    assert shuffle_partition_ms.read(PARENT_RUN) == pytest.approx(2200.0)
    assert shuffle_pallas_batches.read(PARENT_RUN) is None
    assert shuffle_partition_ms.read(SCAN_RUN) is None


@pytest.mark.parametrize("reader", [
    shuffle_pallas_batches, shuffle_partition_ms, shuffle_hash_roofline,
    shuffle_roofline])
@pytest.mark.parametrize("run", [SCAN_RUN, {"records": []}],
                         ids=["scan", "empty"])
def test_reader_finds_nothing_and_does_not_raise(reader, run):
    assert reader.read(dict(run, trace=None)) is None


@pytest.fixture
def traced_run(tmp_path):
    """A run whose cell left the recorded trace where the launcher writes
    one, with a key as wide as the recorded program's."""
    where = tmp_path / "trace" / "plugins" / "profile" / "2026_10_03"
    os.makedirs(where)
    shutil.copy(os.path.join(HERE, "data", "small.xplane.pb"),
                where / "host.xplane.pb")
    cell = types.SimpleNamespace(
        workdir=str(tmp_path), config={"batch_rows": 16384},
        types={"k": "int64", "x": "int32"},
        template=lambda name: repart_key)
    return dict(RUN, cell=cell, peaks={"hbm_bytes_per_s": 819e9},
                trace={"devices": 1, "busy_s": 4.7e-5, "window_s": 0.19})


def test_hash_roofline_on_the_recorded_trace(traced_run):
    with open(os.path.join(HERE, "data", "small.json")) as f:
        described = json.load(f)
    reduced = _shuffle_trace.reduced(traced_run)
    assert reduced["kernel_events"]["shuffle.hash"] \
        == described["launches"]
    assert reduced["kernel_events"]["shuffle.batch"] == 0
    seconds = reduced["kernel_s"]["shuffle.hash"]
    assert 1e-6 < seconds / described["launches"] < 1e-5
    least = described["launches"] * described["rows"] * (8 + 4)
    share = shuffle_hash_roofline.read(traced_run)
    assert share == pytest.approx(100.0 * least / 819e9 / seconds)
    assert 1.0 < share < 100.0
    # the recorded process unpacked no batch
    assert shuffle_roofline.read(traced_run) is None


def test_shuffle_roofline_on_a_recorded_reduction(traced_run):
    """256 batches entered the device in a slice that kept it busy 0.5 s:
    every row read and written once, 12 B wide."""
    traced_run["shuffle_trace"] = {
        "devices": 1, "busy_s": 0.5,
        "kernel_events": {"shuffle.batch": 256, "shuffle.hash": 256},
        "kernel_s": {"shuffle.batch": 0.01, "shuffle.hash": 0.0005}}
    rows = 256 * 16384
    assert shuffle_roofline.read(traced_run) == pytest.approx(
        100.0 * 2 * 12 * rows / 819e9 / 0.5)
    assert shuffle_hash_roofline.read(traced_run) == pytest.approx(
        100.0 * 12 * rows / 819e9 / 0.0005)


def test_no_trace_file_no_share(traced_run):
    shutil.rmtree(os.path.join(traced_run["cell"].workdir, "trace"))
    assert shuffle_hash_roofline.read(traced_run) is None
    assert shuffle_roofline.read(traced_run) is None


# ---- one whole run of the cell ------------------------------------------

@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_run(name):
    """Off a TPU the rehearsal ends `correct` false, and only because it
    is not on one."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", name, "--seed", "2147483659", "--seconds", "3",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is False and done.returncode == 1, \
        done.stderr[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    compared = result["compared"]
    assert compared.pop("answers_compared") == 6
    over = [k for k, v in compared.items() if v["value"] > v["limit"]]
    assert over == ["not_on_tpu"], compared
    m = {k: v["value"] for k, v in result["metrics"].items()}
    # two batches a split at the rehearsal size
    assert m["rehearsal.shuffle_partition_ms"] > 0
    assert m["rehearsal.shuffle_segments"] == 200
    assert m["rehearsal.xla_compiles_in_window"] == 0
    # the CPU has no Pallas program and no device plane
    for absent in ("shuffle_pallas_batches", "shuffle_hash_roofline",
                   "shuffle_roofline", "query_roofline"):
        assert "rehearsal." + absent not in m
