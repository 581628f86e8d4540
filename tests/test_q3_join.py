"""TPC-DS query 3's first stage (the cell `q3_join.s4`): a store_sales
split probed against the broadcast date_dim and item relations, shipped
as file segments in the task's blob, and summed by a string brand. The
template's blob runs through `execute_task` and through the served path
with its defaults, on the cores the chip takes (the direct key->row array
for both broadcasts, the sort grouping core), on both sort cores and on
the CPU's (`scatter`), and its answer is the plain
reference's on seeded tables at the configuration's
`rehearsal_split_rows`: a NULL date drops its sale, a group of NULL
amounts alone sums to NULL, a brand comes back byte for byte. Every task
on one device, as the cell's chip has."""

import json
import os
from functools import partial

import numpy as np
import pytest

from perfbench import datagen
from perfbench.generators import tpcds_dims
from perfbench.templates import _rows, q3_join

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "tpcds_sf1000_store_sales_star"
PARAMS = {"config": CONFIG, "month": 11, "manufact": 128}
SEED = 3700000101


def config(name=CONFIG):
    with open(os.path.join(ROOT, "perfbench", "configs",
                           name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def split():
    """One rehearsal split of the cell's store_sales, made from the
    seed: (frame, batches a task scans)."""
    cfg = config()
    data = cfg["data"]
    table = data["tables"]["store_sales"]
    table["split_rows"] = cfg["rehearsal_split_rows"]
    table["splits"] = 1
    frame = datagen.gen_tables(data, cfg["generator"], SEED)[
        "store_sales"][0]
    return frame, cfg["rehearsal_split_rows"] // cfg["batch_rows"]


def _joined(frame, rel):
    """Rows of the split that join both broadcasts: (mask, brand id and
    brand of each row)."""
    dates = set(rel["date_dim"]["d_date_sk"].tolist())
    items = dict(zip(rel["item"]["i_item_sk"].tolist(),
                     range(len(rel["item"]["i_item_sk"]))))
    day, item = frame["values"][q3_join.SOLD], frame["values"][q3_join.ITEM]
    row = np.array([items.get(int(i), -1) for i in item])
    m = _rows.is_valid(frame, q3_join.SOLD) \
        & np.isin(day, list(dates)) & (row >= 0)
    return m, row


def case(frame, how: str, monkeypatch):
    """The split, edited for one case, and the relations both sides
    read. `null_date`: the split as the generator makes it, whose NULL
    dates lie on November days; `null_amounts`: every amount of the
    group of one brand made NULL; `non_ascii`: that brand renamed with
    bytes outside ASCII."""
    rel = q3_join.relations(PARAMS)
    m, row = _joined(frame, rel)
    # the brand of the first joined row
    brand = rel["item"]["i_brand"][row[np.flatnonzero(m)[0]]]
    of_brand = m & (rel["item"]["i_brand"][np.maximum(row, 0)] == brand)
    if how == "null_amounts":
        valid = dict(frame["valid"])
        valid[q3_join.AMOUNT] = _rows.is_valid(
            frame, q3_join.AMOUNT) & ~of_brand
        frame = dict(frame, valid=valid)
    elif how == "non_ascii":
        names = rel["item"]["i_brand"].copy()
        names[names == brand] = "marqué ブランド #7"
        rel = dict(rel, item=dict(rel["item"], i_brand=names))
        monkeypatch.setattr(q3_join, "relations", lambda params: rel)
    return frame, brand, of_brand


def write(frame, tmp_path):
    path = str(tmp_path / "store_sales.parquet")
    datagen._write(frame, path, config()["parquet"])
    return path


@pytest.fixture(params=["sort", "scatter", "direct"])
def core(request, monkeypatch):
    """The join and grouping cores: `scatter` is what `auto` takes on the
    CPU; `direct` what it takes on a TPU (the join resolved for that
    backend: the direct key->row array for both broadcasts, the sort
    grouping core); `sort` both sort cores, what the TPU took before the
    direct array. Under `direct` the broadcasts' join cores are kept on
    the module's list `INDEXED`."""
    from blaze_tpu.ops import joins

    monkeypatch.setenv("BLAZE_MESH_DEVICES", "1")
    if request.param == "direct":
        monkeypatch.delenv("BLAZE_JOIN_CORE", raising=False)
        monkeypatch.setenv("BLAZE_GROUP_CORE", "sort")
        monkeypatch.setattr(joins, "_join_core_choice",
                            partial(joins._join_core_choice, backend="tpu"))
        build_side = joins.HashJoinExec.build_side

        def kept(op, ctx, shared=False):
            build, core_ = build_side(op, ctx, shared)
            INDEXED.append(core_)
            return build, core_

        INDEXED.clear()
        monkeypatch.setattr(joins.HashJoinExec, "build_side", kept)
    else:
        monkeypatch.setenv("BLAZE_JOIN_CORE", request.param)
        monkeypatch.setenv("BLAZE_GROUP_CORE", request.param)
    return request.param


INDEXED = []


def check_join_counts(m, core, batches):
    """Two joins, each probed once a scanned batch: a pair count read
    back for each on the sort core, each answered by the direct array
    under `direct`, whose two broadcasts are both indexed so."""
    assert m["join_probe_batches"] == 2 * batches
    assert m["join_pair_syncs"] == (2 * batches if core == "sort" else 0)
    assert m["join_direct_batches"] == (
        2 * batches if core == "direct" else 0)
    if core == "direct":
        assert [c._index[0] for c in INDEXED] == ["table_direct"] * 2


@pytest.mark.parametrize("how", ["null_date", "null_amounts", "non_ascii"])
def test_task_answers_the_reference(split, core, how, tmp_path,
                                    monkeypatch):
    from blaze_tpu.ops.base import ExecContext
    from blaze_tpu.runtime.executor import execute_task

    frame, batches = split
    frame, brand, of_brand = case(frame, how, monkeypatch)
    want = q3_join.reference(frame, PARAMS)
    ctx = ExecContext()
    got = q3_join.answer(list(execute_task(
        q3_join.build(write(frame, tmp_path), PARAMS, {}), ctx)), {})
    assert q3_join.compare(want, got) == {"groups_wrong": 0,
                                          "answer_shape_wrong": 0}
    sums_ok = want["valid"]["sum_agg"]
    if how == "null_date":
        # sales with a NULL date whose stored day is in the month: the
        # join drops them, which the control does not
        day = frame["values"][q3_join.SOLD]
        assert (~_rows.is_valid(frame, q3_join.SOLD)
                & (day >= 2452215) & (day <= 2452244)).any()
        assert q3_join.compare(want, q3_join.control(frame, PARAMS))[
            "groups_wrong"] > 0
    elif how == "null_amounts":
        assert of_brand.sum() >= 1
        names = got["values"]["brand"]
        assert not got["valid"]["sum_agg"][names == brand].any()
        assert (~sums_ok).sum() >= 1
    else:
        assert "marqué ブランド #7" in set(got["values"]["brand"])
    m = ctx.metrics.counters
    rel = q3_join.relations(PARAMS)
    assert m["join_build_rows"] == len(rel["date_dim"]["d_date_sk"]) \
        + len(rel["item"]["i_item_sk"])
    check_join_counts(m, core, batches)


def test_served_task_polls_the_join(split, core, tmp_path):
    """Through `QueryService` with its defaults: a device run, the three
    counts in POLL, and the two builds folded into its stage table."""
    from blaze_tpu.runtime.gateway import TaskGatewayServer
    from blaze_tpu.service import QueryService, ServiceClient

    frame, batches = split
    want = q3_join.reference(frame, PARAMS)
    blob = q3_join.build(write(frame, tmp_path), PARAMS, {})
    with QueryService() as svc, TaskGatewayServer(service=svc) as srv, \
            ServiceClient(*srv.address) as c:
        st = c.submit(blob)
        fetched = c.fetch(st["query_id"])
        poll = c.poll(st["query_id"])
    assert q3_join.compare(want, q3_join.answer(fetched, {})) == {
        "groups_wrong": 0, "answer_shape_wrong": 0}
    assert poll["state"] == "DONE" and not poll.get("degraded")
    assert poll["dispatches"] > 0 and not poll.get("cache_hits")
    check_join_counts(poll, core, batches)
    assert poll["join_build_rows"] > 6000
    assert poll["stages"]["join_build"]["n"] == 2
    assert poll["stages"]["join_build"]["wall_s"] > 0


@pytest.mark.parametrize("seed", [11, SEED, 2 ** 31 + 7])
def test_control_fails(seed):
    """The date compared without its validity: a NULL date's stored
    November day joins, and its groups sum more."""
    cfg = config()
    data = cfg["data"]
    data["tables"]["store_sales"]["split_rows"] = cfg["rehearsal_split_rows"]
    data["tables"]["store_sales"]["splits"] = 1
    frame = datagen.gen_tables(data, cfg["generator"], seed)[
        "store_sales"][0]
    want = q3_join.reference(frame, PARAMS)
    assert q3_join.compare(want, want)["groups_wrong"] == 0
    assert q3_join.compare(want, q3_join.control(frame, PARAMS))[
        "groups_wrong"] > 0


def test_configuration_is_the_store_sales_files_in_november():
    star, base = config(), config("tpcds_sf1000_store_sales")
    a, b = star["data"], base["data"]
    ta, tb = a["tables"]["store_sales"], b["tables"]["store_sales"]
    assert ta.pop("first_row_range") == [2205310852, 2244212684]
    tb.pop("first_row_range")
    assert a == b
    for k in ("generator", "batch_rows", "rehearsal_split_rows", "parquet",
              "serve"):
        assert star[k] == base[k]
    # every split lies in November 2001: its first row's day and the day
    # of the last row of the eighth split from the range's last start
    per_day, first = a["rows_per_day"], 2450816  # 1998-01-02
    lo = first + 2205310852 // per_day
    hi = first + (2244212684 + 8 * ta["split_rows"] - 1) // per_day
    assert (lo, hi) == (2452215, 2452244)  # 2001-11-01, 2001-11-30
    assert star["broadcast"]["substitutions"] == {
        k: PARAMS[k] for k in ("month", "manufact")}


def test_broadcast_relations_as_dsdgen_shapes_them():
    dims = tpcds_dims.generate(config()["broadcast"])
    d, i = dims["date_dim"]["values"], dims["item"]["values"]
    assert len(d["d_date_sk"]) == 73049 and len(i["i_item_sk"]) == 300000
    # the Julian day, 1900-01-02 to 2100-01-01
    assert (d["d_date_sk"][0], d["d_date_sk"][-1]) == (2415022, 2488070)
    assert (d["d_year"][0], d["d_moy"][0]) == (1900, 1)
    assert (d["d_year"][-1], d["d_moy"][-1]) == (2100, 1)
    assert np.all(np.diff(d["d_date_sk"]) == 1)
    rel = tpcds_dims.broadcast(dims, 11, 128)
    assert len(rel["date_dim"]["d_date_sk"]) == 200 * 30
    assert 240 <= len(rel["item"]["i_item_sk"]) <= 360
    # a brand id and its name say the same category, class and number
    for bid, name in zip(rel["item"]["i_brand_id"][:20],
                         rel["item"]["i_brand"][:20]):
        assert name.endswith(f" #{bid % 1000}")
    assert tpcds_dims.mk_word(115, config()["broadcast"]["item"][
        "brand_syllables"]) == "scholaramalgamalg"
