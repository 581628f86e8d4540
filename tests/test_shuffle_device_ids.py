"""A shuffle batch's partition ids stay on the device: the two programs of
the stage `shuffle_partition` (`ops/shuffle_writer.py`), each alone on the
CPU against the host arithmetic they replaced - ids through the benchmark's
own Spark murmur3, then `np.argsort(kind="stable")`, a take and
`np.bincount`. The Pallas programs run in interpret mode
(`tests/test_repart_key.py`'s fixture); the served path, with POLL's
`shuffle_device_ids_batches`, is that file's."""

import numpy as np
import pyarrow as pa
import pytest

import jax

from blaze_tpu import ColumnBatch
from blaze_tpu.exprs import ir
from blaze_tpu.ops import ExecContext
from blaze_tpu.ops.external import bucket_stream, subdivide_pid_fn
from blaze_tpu.ops.shuffle_writer import (
    sort_by_partition,
    spark_partition_ids,
)
from blaze_tpu.runtime import dispatch
from tests.test_repart_key import pallas_in_interpret_mode  # noqa: F401

CAPACITY, PARTS = 2048, 200
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def table(rows, null_keys, seed=5):
    """k: the `int` key, NULL on a twentieth of the rows where asked;
    row: the row's number, which tells a stable order from another;
    q: a nullable `bigint`; s: a string."""
    rng = np.random.default_rng(seed)
    k = rng.integers(-2 ** 31, 2 ** 31, rows).astype(np.int32)
    return pa.RecordBatch.from_arrays([
        pa.array(k, mask=(rng.random(rows) < 0.05) if null_keys else None),
        pa.array(np.arange(rows, dtype=np.int32)),
        pa.array(rng.integers(0, 1000, rows), mask=rng.random(rows) < 0.1),
        pa.array([f"s{i % 13}" for i in range(rows)]),
    ], names=["k", "row", "q", "s"])


def spark_ids(rb, n, key="k"):
    """What Spark gives: a NULL key leaves the seed as the hash."""
    from perfbench import murmur3

    col = rb.column(key)
    valid = np.asarray(col.is_valid())
    h = np.where(valid, murmur3.hash_int(
        np.asarray(col.fill_null(0)).astype(np.int32)), np.int32(42))
    return murmur3.pmod(h.astype(np.int32), n)


def key_of(cb, name="k"):
    i = cb.schema.index_of(name)
    return ir.BoundCol(i, cb.schema.fields[i].dtype)


@pytest.fixture
def compiles():
    """Seconds of every program XLA builds while the fixture is held."""
    import jax.monitoring as monitoring

    heard = []

    def listener(event, seconds, **kw):
        if event == BACKEND_COMPILE:
            heard.append(seconds)

    monitoring.register_event_duration_secs_listener(listener)
    try:
        yield heard
    finally:
        monitoring.unregister_event_duration_listener(listener)


@pytest.mark.parametrize("path", ["pallas", "chain"])
@pytest.mark.parametrize("null_keys", [False, True],
                         ids=["never_null", "null_keys"])
def test_two_programs_against_the_host_arithmetic(path, null_keys, compiles,
                                                  request):
    """A full batch, then a short last batch of the same capacity: ids,
    rows and counts as the host's, and the short batch builds no
    program."""
    if path == "pallas":
        request.getfixturevalue("pallas_in_interpret_mode")
    for rows in (CAPACITY, 700):
        rb = table(rows, null_keys, seed=rows)
        cb = ColumnBatch.from_arrow(rb, capacity=CAPACITY)
        assert (cb.column("k").validity is not None) == null_keys
        built = len(compiles)
        pids = spark_partition_ids(cb, [key_of(cb)], PARTS)
        cb_sorted, counts = sort_by_partition(cb, pids, PARTS)
        jax.block_until_ready(counts)
        if rows < CAPACITY:
            assert len(compiles) == built
        # both device paths leave the ids where they were computed
        assert isinstance(pids, jax.Array) and pids.shape == (CAPACITY,)
        want = spark_ids(rb, PARTS)
        np.testing.assert_array_equal(np.asarray(pids)[:rows], want)
        np.testing.assert_array_equal(
            np.asarray(counts), np.bincount(want, minlength=PARTS))
        assert cb_sorted.num_rows == rows
        # the stable order: `row` ascends inside every partition
        assert cb_sorted.to_arrow().equals(
            rb.take(np.argsort(want, kind="stable")))


def test_counter_only_where_the_ids_stayed_on_the_device():
    rb = table(700, True)
    cb = ColumnBatch.from_arrow(rb, capacity=CAPACITY)
    ctx = ExecContext()
    with dispatch.task_scope(ctx):
        pids = spark_partition_ids(cb, [key_of(cb)], PARTS)
        on_device = sort_by_partition(cb, pids, PARTS)
        # ids that went by the host (a change that reads them back, or
        # `perfbench/tests/test_faults.py`'s altered ids), `capacity` or
        # `num_rows` long
        for host_ids in (np.array(pids), np.asarray(pids)[:700]):
            by_host = sort_by_partition(cb, host_ids, PARTS)
            assert by_host[0].to_arrow().equals(on_device[0].to_arrow())
            np.testing.assert_array_equal(by_host[1], on_device[1])
    assert ctx.metrics.flatten()["root"]["shuffle_device_ids_batches"] == 1


def test_string_key_is_hashed_on_the_host():
    rb = table(700, False)
    cb = ColumnBatch.from_arrow(rb, capacity=CAPACITY)
    ctx = ExecContext()
    with dispatch.task_scope(ctx):
        pids = spark_partition_ids(cb, [key_of(cb, "s")], PARTS)
        assert isinstance(pids, np.ndarray) and pids.shape == (700,)
        cb_sorted, counts = sort_by_partition(cb, pids, PARTS)
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(pids, minlength=PARTS))
    assert cb_sorted.to_arrow().equals(
        rb.take(np.argsort(pids, kind="stable")))
    assert "shuffle_device_ids_batches" not in ctx.metrics.flatten()["root"]


@pytest.mark.parametrize("null_keys", [False, True],
                         ids=["never_null", "null_keys"])
def test_bucket_stream_subdivides_through_the_helper(null_keys):
    """Grace recursion: the rows of parent bucket `h pmod 3` spread over
    four children by the next hash bits, `(h pmod 12) // 3`, computed on
    whatever array `spark_partition_ids` hands `subdivide_pid_fn`."""
    batches = [table(rows, null_keys, seed=rows) for rows in (2048, 700)]
    cbs = [ColumnBatch.from_arrow(rb, capacity=CAPACITY) for rb in batches]
    keys = [key_of(cbs[0])]
    ctx = ExecContext()
    with dispatch.task_scope(ctx):
        bucketed = bucket_stream(
            iter(cbs[1:]), keys, 4, ctx, cbs[0].schema, head=cbs[:1],
            pid_fn=subdivide_pid_fn(keys, 3, 4))
    try:
        whole = pa.Table.from_batches(batches)
        child = spark_ids(whole, 12) // 3
        for b in range(4):
            got = pa.Table.from_batches(
                [cb.to_arrow() for cb in bucketed.bucket(b)],
                schema=whole.schema)
            assert got.equals(whole.filter(child == b))
    finally:
        bucketed.cleanup()
    assert ctx.metrics.flatten()["root"]["shuffle_device_ids_batches"] == 2
