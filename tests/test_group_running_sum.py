"""Integer sums on the sort core are read off a running sum at the
groups' boundaries (`ops/hash_aggregate.py: _SegOps.sum`), no scatter:
the same bits as `segment_sum`'s, which the scatter core still computes.
Each case runs one plan on both cores (pinned; `auto` is the sort core
on a TPU and the scatter core here) against a reference in numpy and
Python integers; and the blocked scan both group-bys share
(`ops/running.py`) against numpy's."""

import collections
import decimal

import numpy as np
import pyarrow as pa
import pytest

import jax.numpy as jnp
from jax import lax

from blaze_tpu import ColumnBatch
from blaze_tpu.exprs import AggExpr, AggFn, Col
from blaze_tpu.ops import AggMode, HashAggregateExec, MemoryScanExec
from blaze_tpu.ops.base import ExecContext
from blaze_tpu.runtime import dispatch

from tests.test_group_tiers import launches, pinned  # noqa: F401

CAP = 4096
# tiers [256, 1024, None] over a 4,096-row bucket
BUCKET = dict(batch_size=CAP, shape_buckets=(CAP,),
              agg_group_capacity=1024)
KEYS = [(Col("k"), "k"), (Col("j"), "j")]
AGGS = [(AggExpr(AggFn.SUM, Col("v")), "s"),
        (AggExpr(AggFn.COUNT, Col("v")), "c"),
        (AggExpr(AggFn.COUNT_STAR, None), "n")]


def _wrap(x: int) -> int:
    """A Python integer as the i64 it wraps to."""
    return (x + (1 << 63)) % (1 << 64) - (1 << 63)


class Case:
    """One batch (or several) of two nullable int keys and a value, and
    how it is aggregated. `null` is the share of NULLs in every column;
    `keep` marks the selected rows, None for all."""

    def __init__(self, rows, groups, vtype="int64", null=0.05,
                 select=None, batches=1, lo=-1000, hi=1000, seed=34):
        rng = np.random.default_rng(seed)
        n = rows * batches
        self.rows, self.vtype = rows, vtype
        self.k = rng.integers(0, max(groups, 1), n)
        self.j = rng.integers(0, 3, n) if groups > 1 else np.zeros(n, int)
        if groups == n:  # as many groups as rows
            self.k, self.j = np.arange(n), np.zeros(n, int)
        self.v = rng.integers(lo, hi, n, dtype=np.int64)
        self.nulls = {c: rng.random(n) < null for c in "kjv"}
        self.keep = (None if select is None
                     else rng.random(n) < select)

    def batches(self):
        def column(name, typ):
            vals = getattr(self, name)
            if typ == "decimal":
                vals = [decimal.Decimal(int(x)).scaleb(-2) for x in vals]
                return pa.array(vals, pa.decimal128(7, 2),
                                mask=self.nulls[name])
            return pa.array(vals.astype(typ), mask=self.nulls[name])

        table = pa.table({"k": column("k", "int32"),
                          "j": column("j", "int32"),
                          "v": column("v", self.vtype)})
        out = []
        for i, rb in enumerate(table.to_batches(max_chunksize=self.rows)):
            cb = ColumnBatch.from_arrow(rb, capacity=CAP)
            if self.keep is not None:
                keep = np.zeros(CAP, bool)
                part = self.keep[i * self.rows:(i + 1) * self.rows]
                keep[:len(part)] = part
                # dead rows past num_rows selected too: num_rows masks them
                keep[len(part):] = True
                cb.selection = jnp.asarray(keep)
            out.append(cb)
        return out

    def reference(self):
        """{(k, j): (sum, count, rows)}: NULL keys are groups, a sum
        over no value is NULL, an integer sum wraps as i64 does and a
        decimal sum is exact."""
        acc = collections.defaultdict(lambda: [0, 0, 0])
        live = (np.ones(len(self.k), bool) if self.keep is None
                else self.keep)
        for i in np.flatnonzero(live):
            key = tuple(None if self.nulls[c][i] else int(getattr(self, c)[i])
                        for c in "kj")
            a = acc[key]
            a[2] += 1
            if not self.nulls["v"][i]:
                a[0] += int(self.v[i])
                a[1] += 1
        if self.vtype == "decimal":
            total = lambda s: decimal.Decimal(s).scaleb(-2)
        else:
            total = _wrap
        return {key: (total(s) if c else None, c, n)
                for key, (s, c, n) in acc.items()}


def complete(batches):
    (cb,) = batches
    op = HashAggregateExec(MemoryScanExec([[cb]], cb.schema),
                           keys=KEYS, aggs=AGGS, mode=AggMode.COMPLETE)
    return [op._aggregate_batch(cb)]


def partial_then_final(batches):
    """Per-batch partial states (a decimal's four i64 limbs), merged by
    a FINAL aggregate over all partial rows."""
    schema = batches[0].schema
    partial = HashAggregateExec(MemoryScanExec([batches], schema),
                                keys=KEYS, aggs=AGGS, mode=AggMode.PARTIAL)
    states = list(partial.execute(0, ExecContext()))
    final = HashAggregateExec(MemoryScanExec([states], partial.schema),
                              keys=KEYS, aggs=AGGS, mode=AggMode.FINAL)
    return list(final.execute(0, ExecContext()))


def answer(out_batches):
    """The groups as an Arrow table sorted by key, and as the
    reference's dict."""
    if not out_batches:
        return None, {}
    table = pa.Table.from_batches([b.to_arrow() for b in out_batches])
    table = table.sort_by([("k", "ascending"), ("j", "ascending")])
    rows = table.to_pydict()
    return table, {(k, j): (s, c, n) for k, j, s, c, n in
                   zip(*(rows[x] for x in "kjscn"))}


CASES = [
    pytest.param(Case(3000, 200, "int32"), complete, id="int32"),
    pytest.param(Case(3000, 200, "int64"), complete, id="int64"),
    pytest.param(Case(3000, 200, "decimal", lo=-99999, hi=9999999),
                 complete, id="decimal_7_2"),
    pytest.param(Case(3000, 40, null=0.4), complete,
                 id="null_keys_and_all_null_sums"),
    pytest.param(Case(3000, 200, select=0.5), complete, id="selection"),
    pytest.param(Case(CAP, 200, select=0.3), complete,
                 id="selection_of_a_full_batch"),
    pytest.param(Case(700, 50), complete, id="rows_under_capacity"),
    pytest.param(Case(3000, 200, select=0.0), complete, id="no_live_row"),
    pytest.param(Case(3000, 1, null=0.0), complete, id="one_group"),
    pytest.param(Case(CAP, CAP, null=0.0), complete,
                 id="as_many_groups_as_rows"),
    pytest.param(Case(3000, 5, lo=1 << 61, hi=(1 << 62) + 1), complete,
                 id="sums_that_wrap_i64"),
    pytest.param(Case(1000, 300, "decimal", batches=3, lo=-99999,
                      hi=9999999), partial_then_final,
                 id="partial_then_final_decimal_limbs"),
    pytest.param(Case(1000, 300, "int64", batches=3, lo=1 << 61,
                      hi=(1 << 62) + 1), partial_then_final,
                 id="partial_then_final_wrapping"),
    pytest.param(Case(3000, 30), complete, id="cut_at_the_first_tier"),
    pytest.param(Case(3000, 200), complete, id="cut_at_the_second_tier"),
    pytest.param(Case(4000, 900, null=0.0), complete, id="uncut"),
]


@pytest.mark.parametrize("case,run", CASES)
def test_sort_core_sums_equal_reference_and_scatter_core(case, run,
                                                         launches):
    ctx = ExecContext()
    with pinned(tier1=256, group_core="sort", **BUCKET), \
            dispatch.task_scope(ctx):
        sort_table, got = answer(run(case.batches()))
    exp = case.reference()
    assert got == exp
    # every grouping program of the task read its integer sums off a
    # running sum, and none ran twice for a tier
    n_programs = sum(launches.values())
    assert n_programs >= 1
    assert ctx.metrics.counters["agg_running_sum_launches"] == n_programs
    assert ctx.metrics.counters["agg_tier_retries"] == 0
    ctx = ExecContext()
    with pinned(tier1=256, group_core="scatter", **BUCKET), \
            dispatch.task_scope(ctx):
        scatter_table, other = answer(run(case.batches()))
    assert other == exp
    assert ctx.metrics.counters["agg_running_sum_launches"] == 0
    if exp:
        assert sort_table.schema == scatter_table.schema
        assert sort_table.equals(scatter_table)


@pytest.mark.parametrize("groups,slots", [(30, 256), (200, 1024),
                                          (900, CAP)])
def test_each_cut_holds_what_a_kernel_built_at_it_returns(groups, slots):
    """`_cut_tiers`' promise under the new sums: slots at and past the
    group count read 0, as an empty segment did, in every cut."""
    case = Case(4000, groups, null=0.0)
    with pinned(tier1=256, group_core="sort", **BUCKET):
        (out,) = complete(case.batches())
    n = len(case.reference())
    assert next(t for t in (256, 1024, CAP) if n <= t) == slots
    for col in out.columns[2:]:
        vals = np.asarray(col.values)
        assert vals.shape[0] == slots
        assert not vals[n:].any()


def test_a_keyless_or_scan_task_has_no_counter():
    ctx = ExecContext()
    with pinned(group_core="sort", **BUCKET), dispatch.task_scope(ctx):
        (cb,) = Case(3000, 10).batches()
        HashAggregateExec(MemoryScanExec([[cb]], cb.schema), keys=[],
                          aggs=AGGS, mode=AggMode.COMPLETE
                          )._aggregate_batch(cb)
    assert "agg_running_sum_launches" not in ctx.metrics.counters


# ---- the shared blocked scan ---------------------------------------------

@pytest.mark.parametrize("n", [1, 511, 512, 513, 16384, 737280])
@pytest.mark.parametrize("dtype", [np.int32, np.int64])
def test_running_scan_equals_numpy(n, dtype):
    from blaze_tpu.ops.running import running_scan

    rng = np.random.default_rng(n)
    info = np.iinfo(dtype)
    # i64 values a few of which pass the type's range when summed
    x = rng.integers(info.min // 4, info.max // 4, n, dtype=dtype)
    with np.errstate(over="ignore"):
        want = np.cumsum(x, dtype=dtype)
    got = np.asarray(running_scan(jnp.asarray(x), lax.cumsum))
    assert got.dtype == dtype and (got == want).all()
    if n > 8:
        assert (np.abs(np.cumsum(x.astype(object))) > info.max).any()
    # the running maximum is taken of row numbers: never negative
    pos = np.where(rng.random(n) < 0.3, np.arange(n), 0).astype(dtype)
    got = np.asarray(running_scan(jnp.asarray(pos), lax.cummax))
    assert (got == np.maximum.accumulate(pos)).all()


def test_the_mesh_group_by_uses_the_same_scan():
    import inspect

    from blaze_tpu.ops import hash_aggregate, running
    from blaze_tpu.parallel import sharded

    assert sharded._running is running.running_scan
    assert hash_aggregate.running_scan is running.running_scan
    assert "def _running" not in inspect.getsource(sharded)
