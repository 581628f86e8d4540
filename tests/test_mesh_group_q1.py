"""Query 1's task on a four-chip host (the cell `mesh_group.s1`): the
served path with its defaults lowers the grouped SUM onto the mesh
group-by wherever more than one device is visible, the split's rows are
dealt over the devices by the scan itself, NULL keys and decimal sums go
through the mesh program's all-to-all, and the answer is the plain
reference's, whichever device computed which group. Here on four of the
eight virtual devices `conftest.py` sets up, at the configuration's
`rehearsal_split_rows`."""

import json
import os

import numpy as np
import pyarrow as pa
import pytest

from perfbench import datagen
from perfbench.templates import _rows, q1_group

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "tpcds_sf1000_store_returns_4chip"
PARAMS = {"year": 2000, "agg_field": "sr_return_amt"}


@pytest.fixture(scope="module", params=[11, 2147491077])
def split(request, tmp_path_factory):
    """(frame, parquet path, the plain reference's answer) of one
    rehearsal split made from the seed."""
    with open(os.path.join(ROOT, "perfbench", "configs",
                           CONFIG + ".json")) as f:
        config = json.load(f)
    data = config["data"]
    table = data["tables"]["store_returns"]
    table["split_rows"] = config["rehearsal_split_rows"]
    table["splits"] = 1
    frame = datagen.gen_tables(
        data, config["generator"], request.param)["store_returns"][0]
    path = str(tmp_path_factory.mktemp("q1") / "store_returns.parquet")
    datagen._write(frame, path, config["parquet"])
    return frame, path, q1_group.reference(frame, PARAMS)


def served(path, devices, monkeypatch):
    """The task through `QueryService` with its defaults, `devices` of
    the eight visible: (fetched batches, POLL, the task's spans)."""
    from blaze_tpu.runtime.gateway import TaskGatewayServer
    from blaze_tpu.service import QueryService, ServiceClient

    monkeypatch.setenv("BLAZE_MESH_DEVICES", str(devices))
    with QueryService() as svc, TaskGatewayServer(service=svc) as srv, \
            ServiceClient(*srv.address) as c:
        st = c.submit(q1_group.build(path, PARAMS, {}))
        batches = c.fetch(st["query_id"])
        poll = c.poll(st["query_id"])
        spans = c.report_full(
            st["query_id"], include_spans=True)["trace_spans"]
    return batches, poll, spans


def keyed(batch) -> set:
    """A batch's groups as (customer, store) pairs, None for NULL."""
    return set(zip(batch.column(0).to_pylist(),
                   batch.column(1).to_pylist()))


def test_four_devices_answer_is_the_reference(split, monkeypatch):
    frame, path, want = split
    batches, poll, spans = served(path, 4, monkeypatch)
    # one mesh program answered, and nothing fell back
    assert poll["state"] == "DONE" and not poll.get("degraded")
    assert poll["mesh_group_runs"] == 1 and poll["mesh_degraded"] == 0
    assert "agg_tier_retries" not in poll
    # exact, NULL cases included: a NULL key on either column and on
    # both, a group whose amounts are all NULL, cents as decimal(17,2)
    assert q1_group.compare(want, q1_group.answer(batches, {})) == {
        "groups_wrong": 0, "answer_shape_wrong": 0}
    ok = want["valid"]
    c_ok, s_ok = ok["ctr_customer_sk"], ok["ctr_store_sk"]
    assert (~c_ok & s_ok).any() and (c_ok & ~s_ok).any() \
        and (~c_ok & ~s_ok).any() and (~ok["ctr_total_return"]).any()
    table = pa.Table.from_batches(batches)
    assert table.schema.field("ctr_total_return").type \
        == pa.decimal128(17, 2)
    # the shares tie to the whole: a batch a device, no group on two
    # devices, and together the reference's groups
    assert len(batches) == 4
    shares = [keyed(b) for b in batches]
    assert sum(len(s) for s in shares) == len(set().union(*shares)) \
        == len(c_ok)
    # every device was given a quarter of the rows, to within a batch
    given = [s["tags"]["rows_in"] for s in spans
             if s["name"] == "mesh_device"]
    assert len(given) == 4 and sum(given) == poll["mesh_rows_in"]
    day = frame["values"]["sr_returned_date_sk"]
    first, last = q1_group.year_keys(PARAMS)
    in_year = _rows.is_valid(frame, "sr_returned_date_sk") \
        & (day >= first) & (day <= last)
    assert poll["mesh_rows_in"] == int(in_year.sum())
    assert max(given) - min(given) <= 16384 // 4
    # the stage's phases are stages of POLL's table
    assert {"mesh_stage_in", "mesh_sync", "mesh_gather"} \
        <= set(poll["stages"])
    assert poll["stages"]["mesh_sync"]["n"] == 1


def test_one_device_gives_the_same_answer_the_old_way(split, monkeypatch):
    _, path, want = split
    batches, poll, _ = served(path, 1, monkeypatch)
    # no mesh code is entered: the fused aggregate and its tiers
    assert "mesh_group_runs" not in poll and "agg_tier_retries" in poll
    one = q1_group.answer(batches, {})
    assert q1_group.compare(want, one) == {
        "groups_wrong": 0, "answer_shape_wrong": 0}
    four = q1_group.answer(served(path, 4, monkeypatch)[0], {})
    assert q1_group.compare(one, four) == {
        "groups_wrong": 0, "answer_shape_wrong": 0}
