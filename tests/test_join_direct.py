"""The join core `auto` takes on a TPU, rehearsed on the CPU: a build
relation whose one integer key is unique and spans at most 1 << 24 slots
is indexed as the direct key->row array (`tab[key - base] = row`), with
no density bound; every other build takes the sort core, never the
key|row or generic hash table (whose lookup the v5e compiler refuses,
tests/test_chip_compile.py). The resolver is steered to the TPU's answer
by its `backend` argument; no environment value stands for it."""

from functools import partial

import numpy as np
import pyarrow as pa
import pytest

from blaze_tpu import ColumnBatch
from blaze_tpu.config import resolve_core_choice
from blaze_tpu.ops import (
    ExecContext,
    HashJoinExec,
    JoinType,
    MemoryScanExec,
    SortMergeJoinExec,
)
from blaze_tpu.ops import joins
from blaze_tpu.ops.util import ensure_compacted
from blaze_tpu.runtime.dispatch import task_scope


@pytest.mark.parametrize("env,backend,want", [
    (None, "cpu", "scatter"),
    (None, "tpu", "direct"),
    ("sort", "tpu", "sort"),
    ("scatter", "tpu", "scatter"),
    ("sort", "cpu", "sort"),
])
def test_join_core_resolution(monkeypatch, env, backend, want):
    if env is None:
        monkeypatch.delenv("BLAZE_JOIN_CORE", raising=False)
    else:
        monkeypatch.setenv("BLAZE_JOIN_CORE", env)
    assert joins._join_core_choice(backend) == want


def test_group_core_auto_stays_sort_on_a_tpu(monkeypatch):
    from blaze_tpu.config import get_config

    monkeypatch.delenv("BLAZE_GROUP_CORE", raising=False)
    cfg = get_config().group_core
    assert resolve_core_choice("BLAZE_GROUP_CORE", cfg, backend="tpu") \
        == "sort"
    assert resolve_core_choice("BLAZE_GROUP_CORE", cfg, backend="cpu") \
        == "scatter"


_RESOLVE = joins._join_core_choice


def steer(monkeypatch, choice):
    """Resolve the join core as `auto` does on a TPU ("direct"), or pin
    it ("sort")."""
    monkeypatch.delenv("BLAZE_JOIN_CORE", raising=False)
    monkeypatch.setattr(
        joins, "_join_core_choice",
        partial(_RESOLVE, backend="tpu") if choice == "direct"
        else (lambda backend=None: choice))


def scan(cols):
    cb = ColumnBatch.from_arrow(pa.record_batch(cols))
    return MemoryScanExec([[cb]], cb.schema)


PROBE = {"p": np.array([7, 3, 3, 1 << 24, 8, -2, 0], dtype=np.int32),
         "y": np.arange(7, dtype=np.int32)}


def builds():
    """(build key column, what the chip's core indexes it as)."""
    i32 = partial(np.array, dtype=np.int32)
    return {
        # TPC-DS surrogate keys far sparser than 8x the rows: on the CPU
        # the key|row table, on a TPU the direct array
        "unique_sparse": (pa.array(i32([3, 7, 100_000, 8, -2])),
                          "table_direct"),
        "unique_int64": (pa.array(np.array([3, 7, 1 << 20, 8, -2],
                                           dtype=np.int64)),
                         "table_direct"),
        # a NULL key is left out of the array, as it never matches
        "null_key": (pa.array(i32([3, 7, 0, 8, -2]),
                              mask=np.array([0, 0, 1, 0, 0], bool)),
                     "table_direct"),
        "duplicate_key": (pa.array(i32([3, 7, 7, 8, -2])), "sorted"),
        "span_over_cap": (pa.array(i32([3, 7, 1 << 24, 8, -2])),
                          "sorted"),
        "float_key": (pa.array(np.array([3, 7, 9, 8, -2],
                                        dtype=np.float32)), "sorted"),
    }


def expected(keys, probe_keys, jt):
    """The join by definition over python values: (k, x, p, y) rows."""
    bk = keys.to_pylist()
    rows = []
    matched = set()
    for j, p in enumerate(probe_keys.tolist()):
        hit = [i for i, k in enumerate(bk) if k is not None and k == p]
        matched.update(hit)
        rows += [(bk[i], 10 * i, p, j) for i in hit]
    if jt is JoinType.LEFT:
        rows += [(bk[i], 10 * i, None, None)
                 for i in range(len(bk)) if i not in matched]
    return sorted(rows, key=lambda r: tuple((v is None, v) for v in r))


def joined(op, ctx):
    with task_scope(ctx):
        tabs = [ensure_compacted(b).to_arrow() for b in op.execute(0, ctx)]
    rows = [r for t in tabs
            for r in zip(*[c.to_pylist() for c in t.columns])]
    return sorted(rows, key=lambda r: tuple((v is None, v) for v in r))


@pytest.mark.parametrize("jt", [JoinType.INNER, JoinType.LEFT])
@pytest.mark.parametrize("name", list(builds()))
def test_chip_core_indexes_and_answers(monkeypatch, name, jt):
    """The index the chip's core builds for each build relation, and the
    join's answer through `HashJoinExec`: the direct array where the
    build qualifies, else the sort core, both exact."""
    steer(monkeypatch, "direct")
    keys, mode = builds()[name]
    probe = PROBE["p"].astype(keys.type.to_pandas_dtype()) \
        if pa.types.is_floating(keys.type) else PROBE["p"]
    build = {"k": keys, "x": 10 * np.arange(5, dtype=np.int32)}
    core = joins._JoinCore(
        ColumnBatch.from_arrow(pa.record_batch(build)), [0])
    core.index_build()
    # no table of the CPU's other kinds is ever taken
    assert core._index[0] == mode
    ctx = ExecContext()
    got = joined(HashJoinExec(scan(build), scan(dict(PROBE, p=probe)),
                              ["k"], ["p"], jt), ctx)
    assert got == expected(keys, probe, jt)
    m = ctx.metrics.counters
    assert m["join_probe_batches"] == 1
    assert m["join_direct_batches"] == (mode == "table_direct")
    assert m["join_pair_syncs"] == (mode == "sorted")


BUILD = {"k": np.array([1, 2, 3, 5, 7], dtype=np.int32),
         "x": np.array([10, 20, 30, 50, 70], dtype=np.int32)}
PROBE_PARTS = [{"b": np.array([2, 2, 9], dtype=np.int32),
                "y": np.array([200, 201, 900], dtype=np.int32)},
               {"b": np.array([3, 11, 7], dtype=np.int32),
                "y": np.array([300, 1100, 700], dtype=np.int32)}]


def probe_scan():
    cbs = [ColumnBatch.from_arrow(pa.record_batch(p)) for p in PROBE_PARTS]
    return MemoryScanExec([[cb] for cb in cbs], cbs[0].schema)


@pytest.mark.parametrize("jt", [
    JoinType.LEFT, JoinType.FULL, JoinType.LEFT_SEMI, JoinType.LEFT_ANTI,
    JoinType.RIGHT, JoinType.INNER])
def test_matched_build_under_the_direct_array(monkeypatch, jt):
    """The build-emitting joins read `matched_build`, which the direct
    array's emission folds only for them: over two probe partitions their
    answer is the sort core's."""
    def run(choice):
        steer(monkeypatch, choice)
        op = HashJoinExec(scan(BUILD), probe_scan(), ["k"], ["b"], jt)
        ctx = ExecContext()
        rows = joined(op, ctx)
        with task_scope(ctx):
            rows += [r for b in op.execute(1, ctx)
                     for t in [ensure_compacted(b).to_arrow()]
                     for r in zip(*[c.to_pylist() for c in t.columns])]
        return (sorted(rows, key=lambda r: tuple((v is None, v)
                                                 for v in r)),
                ctx.metrics.counters["join_direct_batches"])

    want, none = run("sort")
    got, direct = run("direct")
    assert none == 0 and direct == 2
    assert got == want
    if jt is JoinType.LEFT_SEMI:
        assert got == [(2, 20), (3, 30), (7, 70)]


@pytest.mark.parametrize("jt", [JoinType.RIGHT, JoinType.FULL,
                                JoinType.LEFT, JoinType.LEFT_SEMI])
def test_sort_merge_join_under_the_direct_array(monkeypatch, jt):
    """The materializing sort-merge join builds on its right side and
    reads `matched_build` for RIGHT and FULL only."""
    left = {"a": np.array([1, 2, 3, 5, 5], dtype=np.int32),
            "x": np.arange(5, dtype=np.int32)}
    right = {"b": np.array([2, 4, 5, 9], dtype=np.int32),
             "y": np.arange(4, dtype=np.int32)}

    def run(choice):
        steer(monkeypatch, choice)
        return joined(SortMergeJoinExec(scan(left), scan(right), ["a"],
                                        ["b"], jt), ExecContext())

    assert run("direct") == run("sort")
