"""The shuffle on a key that is never NULL through the served path, at
the size a test can hold: the cell `repart200_inv.s4` (TPC-DS
`inventory` on `inv_item_sk`) and the same template on `store_sales`
keyed on `ss_item_sk` (`repart200.s4` with the key as the only
difference) as the benchmark's generators make their tables, the files a
task wrote compared exactly with the plain reference of
`perfbench/templates/repart_key.py`.

On a TPU such a key's batches take the Pallas murmur3 program
(`ops/shuffle_writer.py: spark_partition_ids`); the CPU has no Mosaic, so
the tests steer that choice themselves: `_pallas_murmur3` is made to
answer with the same programs in interpret mode. The data picks the path a
batch at a time, so one case gives a nullable key a batch without a NULL
and sees both paths answer one task, row for row as the reference.

On either device path the ids go from their program to the sort-and-gather
program without a read-back, which POLL counts a batch at a time
(`shuffle_device_ids_batches`); a string key, hashed on the host, has no
such count."""

import copy
import functools
import json
import os
import types

import numpy as np
import pytest

import jax.numpy as jnp

from blaze_tpu.ops import shuffle_writer
from blaze_tpu.ops.kernels import murmur3_pallas as mp
from blaze_tpu.service import QueryService

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = [7, 2 ** 31 + 5]
# (configuration, table, key): the cell, and `repart200.s4` with another key
CELLS = [
    ("tpcds_sf1000_inventory", "inventory", "inv_item_sk"),
    ("tpcds_sf1000_store_sales", "store_sales", "ss_item_sk"),
]
EXACT = {"rows_misplaced": 0, "rows_differ": 0, "partitions_wrong": 0}


def params(key):
    return {"key": key, "partitions": 200}


@functools.lru_cache(maxsize=None)
def split(config_name, table, seed):
    """(frame, column types, parquet options, batches): one split of the
    configuration's `rehearsal_split_rows`."""
    from perfbench import datagen

    with open(os.path.join(ROOT, "perfbench", "configs",
                           config_name + ".json")) as f:
        config = json.load(f)
    data = copy.deepcopy(config["data"])
    t = data["tables"][table]
    data["tables"] = {table: t}
    t["split_rows"] = config["rehearsal_split_rows"]
    t["splits"] = 1
    frame = datagen.gen_tables(data, config["generator"], seed)[table][0]
    return (frame, dict(frame["types"]), config["parquet"],
            config["rehearsal_split_rows"] // config["batch_rows"])


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


@pytest.fixture
def pallas_in_interpret_mode(monkeypatch):
    """What a TPU's `_pallas_murmur3` answers, with the programs run by
    the Pallas interpreter."""
    shim = types.SimpleNamespace(
        supports=mp.supports,
        partition_ids_int32=functools.partial(
            mp.partition_ids_int32, interpret=True),
        partition_ids_int64=functools.partial(
            mp.partition_ids_int64, interpret=True),
    )
    monkeypatch.setattr(shuffle_writer, "_pallas_murmur3", lambda: shim)


def shuffle(client, tmp_path, frame, parquet_cfg, types_, key):
    """One task through the service: (answer as read back, POLL)."""
    from perfbench import datagen
    from perfbench.templates import repart_key

    path = str(tmp_path / "split.parquet")
    datagen._write(frame, path, parquet_cfg)
    out = {"data": str(tmp_path / "t.data"),
           "index": str(tmp_path / "t.index")}
    st = client.submit(repart_key.build(path, params(key), out))
    read = repart_key.answer(client.fetch(st["query_id"]), out)
    poll = client.poll(st["query_id"])
    assert poll["state"] == "DONE" and not poll.get("cache_hits")
    return read(types_), poll


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("config, table, key", CELLS,
                         ids=[c[1] for c in CELLS])
def test_never_null_key_takes_the_pallas_program(
        config, table, key, seed, client, tmp_path,
        pallas_in_interpret_mode):
    from perfbench.templates import repart_key

    frame, types_, parquet_cfg, batches = split(config, table, seed)
    assert frame["valid"][key] is None  # the generator's promise
    got, poll = shuffle(client, tmp_path, frame, parquet_cfg, types_, key)
    want = repart_key.reference(frame, params(key))
    assert repart_key.compare(want, got) == EXACT
    assert got["rows"] == frame["rows"] and got["partitions"] == 200
    # every batch's ids came from the program, inside the one stage
    assert poll["shuffle_pallas_batches"] == batches
    assert poll["shuffle_device_ids_batches"] == batches
    assert poll["stages"]["shuffle_partition"]["n"] == batches
    assert poll["shuffle_segments"] == 200


@pytest.mark.parametrize("steered", [True, False],
                         ids=["both_paths", "xla_path"])
def test_nullable_key_with_a_batch_that_holds_no_null(
        steered, client, tmp_path, request):
    """`ss_customer_sk` is NULL on 4.5% of rows; here the split's first
    batch happens to hold none, so that batch carries no validity buffer
    and, where the Pallas program can run, takes it, while the second
    batch takes the eager chain: the two halves of one task's files must
    both be the reference's."""
    from perfbench.templates import repart_key

    if steered:
        request.getfixturevalue("pallas_in_interpret_mode")
    config, table, _ = CELLS[1]
    frame, types_, parquet_cfg, batches = split(config, table, SEEDS[0])
    key = "ss_customer_sk"
    valid = frame["valid"][key].copy()
    assert not valid[:16384].all() and not valid[16384:].all()
    valid[:16384] = True
    frame = dict(frame, valid=dict(frame["valid"], **{key: valid}))
    got, poll = shuffle(client, tmp_path, frame, parquet_cfg, types_, key)
    want = repart_key.reference(frame, params(key))
    assert repart_key.compare(want, got) == EXACT
    assert batches == 2
    if steered:
        assert poll["shuffle_pallas_batches"] == 1
    else:
        assert "shuffle_pallas_batches" not in poll
    # the Pallas program's ids and the jitted chain's alike
    assert poll["shuffle_device_ids_batches"] == batches
    assert poll["stages"]["shuffle_partition"]["n"] == batches


def test_string_key_has_no_device_ids(client, tmp_path):
    """A key the device cannot hash (`native`'s murmur3 over the utf8
    bytes, on the host): the ids reach the sort as a host array, so POLL
    carries neither count, and every key's rows still share a partition."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from perfbench import segipc
    from perfbench.templates import repart_key

    rows = 20000
    path = str(tmp_path / "split.parquet")
    pq.write_table(pa.table({
        "k": [f"item-{i % 997}" for i in range(rows)],
        "v": pa.array(range(rows), pa.int32())}), path)
    out = {"data": str(tmp_path / "t.data"),
           "index": str(tmp_path / "t.index")}
    st = client.submit(repart_key.build(path, params("k"), out))
    assert not client.fetch(st["query_id"])
    poll = client.poll(st["query_id"])
    assert poll["state"] == "DONE"
    assert "shuffle_device_ids_batches" not in poll
    assert "shuffle_pallas_batches" not in poll
    assert poll["stages"]["shuffle_partition"]["n"] == 2
    parts = segipc.read_partitions(out["data"], out["index"])
    assert len(parts) == 200
    seen = {}
    for p, t in enumerate(parts):
        for k in (t.column("k").unique().to_pylist() if t else ()):
            assert seen.setdefault(k, p) == p
    assert sum(t.num_rows for t in parts if t) == rows
    assert len(seen) == 997


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("config, table, key", CELLS,
                         ids=[c[1] for c in CELLS])
def test_control_misplaces_rows(config, table, key, seed):
    """The key hashed as a `bigint`: nearly every row lands elsewhere,
    and no row is altered."""
    from perfbench.templates import repart_key

    frame = split(config, table, seed)[0]
    readings = repart_key.compare(
        repart_key.reference(frame, params(key)),
        repart_key.control(frame, params(key)))
    assert readings["rows_misplaced"] > 0.98 * frame["rows"]
    assert readings["rows_differ"] == 0
    assert readings["partitions_wrong"] == 0
    short = repart_key.control(frame, {"key": key, "partitions": 199})
    assert repart_key.compare(
        repart_key.reference(frame, params(key)),
        short)["partitions_wrong"] == 1


@pytest.mark.parametrize("seed", SEEDS)
def test_pallas_program_against_the_benchmark_murmur3(seed):
    """The program alone, in interpret mode, on a split's `inv_item_sk`
    at the served batch's capacity."""
    from perfbench import murmur3

    frame = split(*CELLS[0][:2], seed)[0]
    keys = frame["values"]["inv_item_sk"][:16384]
    assert mp.supports("int32", len(keys))
    got = np.asarray(mp.partition_ids_int32(
        jnp.asarray(keys), 200, interpret=True))
    np.testing.assert_array_equal(
        got, murmur3.pmod(murmur3.hash_int(keys), 200))


@pytest.mark.parametrize("fn, dtype, name", [
    (mp.partition_ids_int32, jnp.int32, "jit_partition_ids_int32"),
    (mp.partition_ids_int64, jnp.int64, "jit_partition_ids_int64"),
], ids=["int32", "int64"])
def test_program_names_on_a_device_trace(fn, dtype, name):
    """`shuffle_hash_roofline` finds the programs on the trace's
    `XLA Modules` line as `jit_partition_ids_int32(...)` and `_int64`:
    the name of the jitted function is the yardstick's."""
    import jax

    text = fn.lower(jax.ShapeDtypeStruct((16384,), dtype), 200,
                    interpret=True).as_text()
    assert f"module @{name}" in text
