"""The cell `mesh_group.s1`'s own pieces: its configuration beside the
one-chip one it was cut from, the six readers on a recorded POLL and on
the recorded four-device trace (`data/mesh_small.xplane.pb`: three
launches of the mesh group-by over 4,096 rows a device, recorded by
`record_mesh_trace.py` on a four-chip v5e host), and one whole run of the
cell on four virtual devices at the configuration's rehearsal size."""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from perfbench import run as bench_run
from perfbench.layer_metrics import (
    _mesh_trace, mesh_busy_skew, mesh_exchange_share, mesh_group_roofline,
    mesh_group_runs, mesh_stage_in_ms, mesh_sync_ms,
)
from perfbench.templates import q1_group

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "mesh_group.s1"
READERS = [mesh_group_runs, mesh_stage_in_ms, mesh_sync_ms, mesh_busy_skew,
           mesh_exchange_share, mesh_group_roofline]


def cell_of(name, rehearse=False):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return bench_run.Cell(json.load(f), name, rehearse)


def test_the_configuration_is_the_one_chip_one_on_four_chips():
    four, one = cell_of(CELL), cell_of("q1_group.s4")
    assert four.entry["chips"] == 4 and one.entry["chips"] == 1
    # every width, type, NULL share, key distribution and the generator
    for key in ("data", "generator", "batch_rows", "parquet", "serve",
                "rehearsal_split_rows"):
        assert four.config[key] == one.config[key], key
    assert four.config["serve"]["flags"] == []
    assert set(four.config["reduced"]) == {"fact_rows", "split_rows"}
    assert one.config["assumed"][0] in four.config["assumed"]
    assert set(one.config["guarantees"]) < set(four.config["guarantees"])
    # the same task, one at a time
    same = dict(four.traffic_spec, streams=4)
    for key in ("block", "input", "table", "compare_sample", "loop",
                "streams"):
        assert same[key] == one.traffic_spec[key], key
    assert four.traffic_spec["streams"] == 1
    assert [m["name"] for m in four.per_layer][-6:] == [
        r.__name__.rsplit(".", 1)[1] for r in READERS]


# ---- the readers on a recorded POLL -------------------------------------

def record(poll, rows_out=700, ok=True, device_run=True):
    return {"ok": ok, "device_run": device_run, "poll": poll,
            "rows_out": rows_out, "template": "q1_group",
            "params": {"year": 2000, "agg_field": "sr_return_amt"}}


def task(stage_in_s, sync_s, rows_in=1000, runs=1):
    return {"task_dispatches": 66, "mesh_group_runs": runs,
            "mesh_degraded": 1 - runs, "mesh_rows_in": rows_in,
            "stages": {
                "mesh_stage_in": {"wall_s": stage_in_s, "cpu_s": 0.01,
                                  "n": 65},
                "mesh_sync": {"wall_s": sync_s, "cpu_s": 0.0, "n": 1},
                "mesh_gather": {"wall_s": 0.010, "cpu_s": 0.01, "n": 1},
                "decode_batch": {"wall_s": 0.4, "cpu_s": 0.2, "n": 64}}}


RUN = {"records": [
    record(task(0.030, 0.050)), record(task(0.050, 0.070)),
    record(task(0.040, 0.060)),
    # a failed task and one a cache answered are no device runs
    record(task(9.0, 9.0, runs=0), ok=False),
    record(task(9.0, 9.0, runs=0), device_run=False),
]}
# what the parent of this PR answers: no counter, no mesh stage (its mesh
# op falls back before a program runs), and the day a task falls back
PARENT_RUN = {"records": [record({"task_dispatches": 200, "stages": {
    "d2h": {"wall_s": 0.3, "cpu_s": 0.1, "n": 1}}})]}
FELL_BACK = {"records": [record({
    "task_dispatches": 200, "mesh_group_runs": 0, "mesh_degraded": 1,
    "mesh_rows_in": 0, "stages": {
        "mesh_stage_in": {"wall_s": 0.2, "cpu_s": 0.1, "n": 3}}})]}


def test_counters_and_spans_on_a_recorded_poll():
    assert mesh_group_runs.read(RUN) == 1.0
    assert mesh_stage_in_ms.read(RUN) == pytest.approx(40.0)
    assert mesh_sync_ms.read(RUN) == pytest.approx(70.0)
    assert mesh_group_runs.read(FELL_BACK) == 0.0
    assert mesh_stage_in_ms.read(FELL_BACK) == pytest.approx(200.0)
    assert mesh_sync_ms.read(FELL_BACK) is None


@pytest.mark.parametrize("reader", READERS)
@pytest.mark.parametrize("run", [PARENT_RUN, {"records": []}],
                         ids=["parent", "empty"])
def test_reader_finds_nothing_and_does_not_raise(reader, run):
    assert reader.read(dict(run, trace=None)) is None


# ---- the readers on the recorded trace ----------------------------------

@pytest.fixture
def described():
    with open(os.path.join(HERE, "data", "mesh_small.json")) as f:
        return json.load(f)


@pytest.fixture
def traced_run(tmp_path, described):
    """A run whose cell left the recorded trace where the launcher writes
    one, each task having placed what the recorded launches took in."""
    where = tmp_path / "trace" / "plugins" / "profile" / "2026_10_04"
    os.makedirs(where)
    shutil.copy(os.path.join(HERE, "data", "mesh_small.xplane.pb"),
                where / "host.xplane.pb")
    rows_in = described["device"]["count"] * described["rows_a_device"]
    cell = types.SimpleNamespace(
        workdir=str(tmp_path), types=cell_of(CELL).types,
        template=lambda name: q1_group)
    records = [record(task(0.03, 0.05, rows_in=rows_in),
                      rows_out=sum(described["groups"]))
               for _ in range(3)]
    return {"records": records, "cell": cell,
            "peaks": {"hbm_bytes_per_s": 819e9},
            "trace": {"devices": 4, "busy_s": 1e-3, "window_s": 0.2}}


def test_device_readers_on_the_recorded_trace(traced_run, described):
    n_dev = described["device"]["count"]
    assert n_dev == 4 and not described["overflow"]
    trace = _mesh_trace.read(traced_run)
    # a launch is counted once, not once a device
    assert trace["launches"] == described["launches"]
    assert len(trace["busy_s"]) == len(trace["exchange_s"]) == n_dev
    for busy, exchange in zip(trace["busy_s"], trace["exchange_s"]):
        assert 0 < exchange < busy < 0.2
    # four chips that share the work
    skew = mesh_busy_skew.read(traced_run)
    assert skew == pytest.approx(
        max(trace["busy_s"]) * n_dev / sum(trace["busy_s"]))
    assert 1.0 <= skew < 1.5
    share = mesh_exchange_share.read(traced_run)
    assert share == pytest.approx(
        100.0 * sum(trace["exchange_s"]) / sum(trace["busy_s"]))
    assert 0.0 < share < 100.0
    rows_in = described["launches"] * n_dev * described["rows_a_device"]
    least = q1_group.least_bytes(
        rows_in, described["launches"] * sum(described["groups"]),
        traced_run["cell"].types)
    roofline = mesh_group_roofline.read(traced_run)
    assert roofline == pytest.approx(
        100.0 * least / (n_dev * 819e9)
        / (sum(trace["busy_s"]) / n_dev))
    assert 0.0 < roofline < 100.0


def test_no_trace_file_no_share(traced_run):
    shutil.rmtree(os.path.join(traced_run["cell"].workdir, "trace"))
    for reader in (mesh_busy_skew, mesh_exchange_share,
                   mesh_group_roofline):
        assert reader.read(traced_run) is None


def test_one_device_has_no_skew(traced_run):
    traced_run["mesh_trace"] = {"busy_s": [0.5], "exchange_s": [0.0],
                                "launches": 0}
    assert mesh_busy_skew.read(traced_run) is None
    assert mesh_exchange_share.read(traced_run) is None
    assert mesh_group_roofline.read(traced_run) is None


# ---- one whole run of the cell ------------------------------------------

def test_rehearsal_run_on_four_virtual_devices():
    """Off a TPU the rehearsal ends `correct` false, and only because it
    is not on one; every task's answer came from one mesh program."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PYTHONPATH", None)
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", CELL, "--seed", "2147483659", "--seconds", "3",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is False and done.returncode == 1, \
        done.stderr[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["count"] == 4
    compared = result["compared"]
    assert compared.pop("answers_compared") == result["attempted"]
    over = [k for k, v in compared.items() if v["value"] > v["limit"]]
    assert over == ["not_on_tpu"], compared
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["rehearsal.mesh_group_runs"] == 1
    assert m["rehearsal.mesh_stage_in_ms"] > 0
    assert m["rehearsal.mesh_sync_ms"] > 0
    assert m["rehearsal.xla_compiles_in_window"] == 0
    # the CPU has no device plane
    for absent in ("mesh_busy_skew", "mesh_exchange_share",
                   "mesh_group_roofline", "query_roofline"):
        assert "rehearsal." + absent not in m
