"""Query 9's scalar subqueries through the served path, at the size a
test can hold: the fifteen shapes of the cell `q9_scalar.s4` (and an
average whose sums go below zero, and ranges no row meets) over
`store_sales` as the benchmark's generator makes it (decimal(7,2) money,
NULLs in both columns read), each answer compared exactly with the plain
reference of `perfbench/templates/q9_scalar.py`, and each task seen to
take the keyless device carry: every batch merged on the device, one
`agg_fetch` stage a task, nothing compacted."""

import copy
import json
import os

import pytest

from blaze_tpu.service import QueryService

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "perfbench", "configs",
                      "tpcds_sf1000_store_sales_128m.json")
SEEDS = [7, 2 ** 31 + 5]
RANGES = [(1, 20), (21, 40), (41, 60), (61, 80), (81, 100)]
# the cell's fifteen, and sums below zero: the HALF_UP of a negative
# quotient
MET = [
    {"lo": lo, "hi": hi, "agg": agg, "column": column}
    for lo, hi in RANGES
    for agg, column in (("count", None), ("avg", "ss_ext_discount_amt"),
                        ("avg", "ss_net_paid"))
] + [{"lo": 41, "hi": 60, "agg": "avg", "column": "ss_net_profit"}]
SHAPES = MET + [
    # a range no row meets: count 0, the average NULL. No quantity is
    # over 100, so the scan prunes the row group by its statistics and
    # the aggregate answers for an empty stream, with no carry
    {"lo": 101, "hi": 120, "agg": "count", "column": None},
    {"lo": 101, "hi": 120, "agg": "avg", "column": "ss_net_paid"},
]


def shape_id(params):
    return f"{params['agg']}-{params['column']}-{params['lo']}-{params['hi']}"


@pytest.fixture(scope="module")
def splits(tmp_path_factory):
    """{seed: (frame, parquet path, batches)}: one split of the
    configuration's `rehearsal_split_rows` a seed."""
    from perfbench import datagen

    with open(CONFIG) as f:
        config = json.load(f)
    data = copy.deepcopy(config["data"])
    table = data["tables"]["store_sales"]
    table["split_rows"] = config["rehearsal_split_rows"]
    table["splits"] = 1
    batches = config["rehearsal_split_rows"] // config["batch_rows"]
    out = {}
    for seed in SEEDS:
        frame = datagen.gen_tables(
            data, config["generator"], seed)["store_sales"][0]
        path = str(tmp_path_factory.mktemp("q9") / f"s{seed}.parquet")
        datagen._write(frame, path, config["parquet"])
        out[seed] = (frame, path, batches)
    return out


@pytest.fixture
def client():
    """A service a test: tests/conftest.py switches tracing off after
    every test, and a task's stage table needs it on at submit."""
    from blaze_tpu.runtime.gateway import TaskGatewayServer
    from blaze_tpu.service import ServiceClient

    with QueryService(max_concurrency=2) as svc:
        with TaskGatewayServer(service=svc) as srv:
            with ServiceClient(*srv.address) as c:
                yield c


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("params", SHAPES, ids=shape_id)
def test_answer_is_exact_and_took_the_carry(params, seed, splits, client):
    from perfbench.templates import q9_scalar

    frame, path, batches = splits[seed]
    st = client.submit(q9_scalar.build(path, params, {}))
    got = q9_scalar.answer(client.fetch(st["query_id"]), {})
    poll = client.poll(st["query_id"])
    want = q9_scalar.reference(frame, params)
    assert q9_scalar.compare(want, got) == {
        "values_wrong": 0, "answer_shape_wrong": 0}, (want, got)
    if params["column"] == "ss_net_profit":
        assert want["value"] < 0
    if params["lo"] > 100:
        assert want["value"] == (0 if params["agg"] == "count" else None)
        assert "agg_carry_batches" not in poll
        return
    # the keyless carry: a launch a batch and nothing else, every batch
    # merged on the device, one fetch of the packed state
    assert poll["state"] == "DONE" and not poll.get("cache_hits")
    assert poll["agg_carry_batches"] == batches
    assert poll["task_dispatches"] == batches
    stages = poll["stages"]
    assert stages["agg_fetch"]["n"] == 1
    assert "compact" not in stages
    assert stages["d2h"]["n"] == 1  # the one row out


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("params", MET, ids=shape_id)
def test_control_breaks_the_answer(params, seed, splits):
    """Each shape's control (a stated guarantee broken) reads as wrong
    by the comparison that decides `correct`."""
    from perfbench.templates import q9_scalar

    frame = splits[seed][0]
    readings = q9_scalar.compare(q9_scalar.reference(frame, params),
                                 q9_scalar.control(frame, params))
    assert readings["values_wrong"] > q9_scalar.LIMITS["values_wrong"]
