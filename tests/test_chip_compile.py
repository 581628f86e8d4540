"""Compile checks for the branches only the chip takes.

tier-1 runs on the CPU (conftest.py) and the engine asks
`jax.default_backend()`, so the TPU's own branches - the murmur3 Pallas
kernel in the shuffle writer, the (hi, lo) f64 pack route, the
segmented-reduce Pallas kernel - are never walked there, and interpret
mode cannot see what the Mosaic compiler refuses (block tiling, X64
rewrites). The TPU compiler is installed here and compiles for a chip
that is DESCRIBED, not attached: each test lowers one kernel or jitted
step for one device of a `v5e:2x2` topology at the size the chip smoke
runs (8,388,608 rows). A compile that passes is not a chip run.

The topology is described inside a module-scoped fixture (never at
import: only one process may load the TPU library, and every xdist
worker imports every test file), everything compiles in the test's own
process, and the persistent compile cache is off around the compiles
(an entry written without a chip cannot be read back).
"""

import os
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROWS = 8 << 20
N_PARTS = 200


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    """Lower + compile `fn` for the described chip from shapes alone;
    raises what the chip's compiler would raise."""
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [16384, ROWS])
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.int64])
def test_murmur3_partition_ids(one_chip, dtype, rows):
    """The one Pallas kernel on the default chip path
    (ops/shuffle_writer.py selects it when the backend is "tpu"), at
    the serve batch capacity and at the whole fact table."""
    from blaze_tpu.ops.kernels import murmur3_pallas as mp

    fn = (mp.partition_ids_int32 if dtype == jnp.int32
          else mp.partition_ids_int64)
    compiled = _compile(
        partial(fn, n_parts=N_PARTS, interpret=False), one_chip,
        ((rows,), dtype),
    )
    assert _has_kernel(compiled)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_masked_stats(one_chip, dtype):
    """stats_pallas has no call site yet; it is the murmur3 layout and
    the compiler accepts it."""
    from blaze_tpu.ops.kernels import stats_pallas as sp

    assert sp.supports(ROWS, dtype)
    compiled = _compile(
        partial(sp.masked_stats, interpret=False), one_chip,
        ((ROWS,), dtype), ((ROWS,), jnp.bool_),
    )
    assert _has_kernel(compiled)


# one batch's worth of every engine dtype, f64 included
_PACK_SHAPES = (
    ((ROWS,), jnp.int32), ((ROWS,), jnp.int64), ((ROWS,), jnp.float32),
    ((ROWS,), jnp.float64), ((ROWS,), jnp.bool_),
)


def test_pack_f64_pairs(one_chip):
    """D2H pack with f64 as (hi, lo) f32 pairs - the route
    runtime/pack.py takes on any non-CPU backend."""
    from blaze_tpu.runtime.pack import _build_pack

    pack = _build_pack(None, f64_pairs=True)
    _compile(lambda *bufs: pack(list(bufs)), one_chip, *_PACK_SHAPES)


def test_pack_f64_direct_bitcast_is_refused(one_chip):
    """Why the pair route exists: the TPU compiler's X64 rewrite does
    not implement bitcast-convert from f64. If this starts compiling,
    `_f64_pairs()` can go."""
    from blaze_tpu.runtime.pack import _build_pack

    pack = _build_pack(None, f64_pairs=False)
    with pytest.raises(Exception, match="(?i)x64|bitcast"):
        _compile(lambda b: pack([b]), one_chip, ((ROWS,), jnp.float64))


def test_unpack_f64_pairs(one_chip):
    from blaze_tpu.runtime.pack import _build_unpack, _packed_nbytes

    metas = tuple(
        (str(np.dtype(dt)), shape) for shape, dt in _PACK_SHAPES
    )
    total = sum(
        # f64 travels as two f32: same 8 bytes per value
        _packed_nbytes(shape, np.dtype(dt)) for shape, dt in _PACK_SHAPES
    )
    _compile(
        _build_unpack(metas, f64_pairs=True), one_chip,
        ((total,), jnp.uint8),
    )


def test_q6_step(one_chip):
    """`__graft_entry__.entry()`: the q6 filter/project/aggregate step."""
    import __graft_entry__ as graft

    step, args = graft.entry()
    shapes = [((ROWS,), a.dtype) for a in args[:3]] + [((), args[3].dtype)]
    _compile(step, one_chip, *shapes)


@pytest.fixture(scope="module")
def sales_parquet(tmp_path_factory):
    """Two batches of the two columns query 9's subqueries read, typed
    as the benchmark's store_sales has them: a nullable int32 and a
    nullable decimal(7,2) stored as parquet INT32."""
    import decimal

    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(27)
    n = 2 * 16384
    cents = rng.integers(0, 3_000_000, n)
    path = str(tmp_path_factory.mktemp("q9") / "sales.parquet")
    pq.write_table(pa.table({
        "ss_quantity": pa.array(rng.integers(1, 101, n).astype(np.int32),
                                mask=rng.random(n) < 0.045),
        "ss_net_paid": pa.array(
            [decimal.Decimal(int(c)).scaleb(-2) for c in cents],
            pa.decimal128(7, 2), mask=rng.random(n) < 0.045),
    }), path, store_decimal_as_integer=True)
    return path


@pytest.mark.parametrize("with_carry", [False, True],
                         ids=["first_batch", "carry"])
@pytest.mark.parametrize("agg", ["count", "avg"])
def test_keyless_carry_kernel(one_chip, sales_parquet, agg, with_carry):
    """The per-batch program of `FusedAggregateExec._execute_keyless_carry`
    (unpack of the packed wire buffer, the filter, the keyless partial
    aggregate with its i64 decimal limbs and count, the merge into the
    carry, `pack_in_kernel`) as query 9's scalar subqueries build it:
    the cell `q9_scalar.s4` launches it 170 times a task."""
    from blaze_tpu.batch import packed_view
    from blaze_tpu.exprs import AggExpr, AggFn, Col
    from blaze_tpu.ops import (
        AggMode, ExecContext, FilterExec, HashAggregateExec,
    )
    from blaze_tpu.ops.fused import (
        _build_carry_kernel, _keyless_merge_plan, fuse_pipelines,
    )
    from blaze_tpu.ops.parquet_scan import FileRange, ParquetScanExec

    columns = ["ss_quantity"] + (["ss_net_paid"] if agg == "avg" else [])
    fused = fuse_pipelines(HashAggregateExec(
        FilterExec(
            ParquetScanExec([[FileRange(sales_parquet)]],
                            projection=columns),
            (Col("ss_quantity") >= 21) & (Col("ss_quantity") <= 40)),
        keys=[],
        aggs=[(AggExpr(AggFn.COUNT_STAR, None), "cnt") if agg == "count"
              else (AggExpr(AggFn.AVG, Col("ss_net_paid")), "avg")],
        mode=AggMode.COMPLETE,
    )).children[0]
    cb = next(iter(fused.children[0].execute(0, ExecContext())))
    pv = packed_view(cb)
    assert pv is not None and cb.capacity == 16384
    plan = _keyless_merge_plan(fused.agg.aggs, fused.agg.schema.fields)
    inner = fused._build_kernel_packed(pv, group_cap=1)
    buf = jax.ShapeDtypeStruct(pv.buf.shape, pv.buf.dtype,
                               sharding=one_chip)
    first = _build_carry_kernel(inner, plan, False)
    if not with_carry:
        jax.jit(lambda b: first(b, None, None)).lower(buf).compile()
        return
    states, _packed = jax.eval_shape(lambda b: first(b, None, None), buf)
    carry = [
        tuple(None if x is None else jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip) for x in pair)
        for pair in states
    ]
    merge = _build_carry_kernel(inner, plan, True)
    jax.jit(lambda b, c: merge(b, None, None, c)).lower(buf, carry) \
        .compile()


@pytest.fixture(scope="module")
def returns_parquet(tmp_path_factory):
    """One batch of the four columns query 1's CTE reads from
    `store_returns` (a date, two nullable `int` keys, a nullable
    decimal(7,2) stored as parquet INT32) and the amount as a `double`."""
    import decimal

    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(34)
    n = 16384

    def ints(hi):
        return pa.array(rng.integers(1, hi, n).astype(np.int32),
                        mask=rng.random(n) < 0.045)

    cents = rng.integers(0, 3_000_000, n)
    path = str(tmp_path_factory.mktemp("q1") / "returns.parquet")
    pq.write_table(pa.table({
        "sr_returned_date_sk": pa.array(
            rng.integers(2451545, 2451911, n).astype(np.int32)),
        "sr_customer_sk": ints(12_000_000),
        "sr_store_sk": ints(1002),
        "sr_return_amt": pa.array(
            [decimal.Decimal(int(c)).scaleb(-2) for c in cents],
            pa.decimal128(7, 2), mask=rng.random(n) < 0.045),
        "amt_double": pa.array(cents / 100.0, mask=rng.random(n) < 0.045),
    }), path, store_decimal_as_integer=True)
    return path


@pytest.mark.parametrize("amount,scatters", [
    pytest.param("sr_return_amt", 0, id="decimal_sum"),
    pytest.param("amt_double", 1, id="double_sum"),
])
def test_q1_batch_grouping_program(one_chip, returns_parquet, amount,
                                   scatters):
    """Query 1's per-batch grouping program on the sort core, what
    `auto` resolves to on the chip (pinned here, where it resolves to
    the scatter core): unpack of the packed wire buffer, the date
    filter, the sort by the keys' hash, boundaries, and the states cut
    at the first tier; `q1_group.s4` launches it 64 times a task. Its
    integer sums (a decimal's four i64 limbs, the any-valid count) are
    read off a running sum and each group's first row comes from a sort,
    so it holds no scatter: an `i64` scatter of 16,384 updates is 1.2 ms
    on the chip where the sort of the same rows is microseconds
    (PERF.md, PR 30). A `double` sum stays a segment sum, the one
    scatter: a difference of running sums would round differently. The
    merge over 737,280 partial rows is compiled on the chip, not here."""
    import dataclasses

    from blaze_tpu.batch import packed_view
    from blaze_tpu.config import get_config, set_config
    from blaze_tpu.exprs import AggExpr, AggFn, Col
    from blaze_tpu.ops import (
        AggMode, ExecContext, FilterExec, HashAggregateExec,
    )
    from blaze_tpu.ops.fused import fuse_pipelines
    from blaze_tpu.ops.hash_aggregate import _with_cuts
    from blaze_tpu.ops.parquet_scan import FileRange, ParquetScanExec

    date = Col("sr_returned_date_sk")
    fused = fuse_pipelines(HashAggregateExec(
        FilterExec(
            ParquetScanExec(
                [[FileRange(returns_parquet)]],
                projection=["sr_returned_date_sk", "sr_customer_sk",
                            "sr_store_sk", amount]),
            (date >= 2451545) & (date <= 2451910)),
        keys=[(Col("sr_customer_sk"), "ctr_customer_sk"),
              (Col("sr_store_sk"), "ctr_store_sk")],
        aggs=[(AggExpr(AggFn.SUM, Col(amount)), "ctr_total_return")],
        mode=AggMode.COMPLETE,
    )).children[0]
    cb = next(iter(fused.children[0].execute(0, ExecContext())))
    pv = packed_view(cb)
    assert pv is not None and cb.capacity == 16384
    prior = get_config()
    set_config(dataclasses.replace(prior, group_core="sort"))
    try:
        kernel = _with_cuts(fused._build_kernel_packed(pv), (4096,))
        buf = jax.ShapeDtypeStruct(pv.buf.shape, pv.buf.dtype,
                                   sharding=one_chip)
        text = jax.jit(lambda b: kernel(b, None, None)).lower(buf) \
            .compile().as_text()
    finally:
        set_config(prior)
    assert " sort(" in text
    assert text.count(" scatter(") == scatters


def test_q1_merge_concat(one_chip):
    """The FINAL merge's materialization in `q1_group.s4`: 64 per-batch
    partial states of 16,384 rows into the 737,280-row bucket
    (`ops/util.py: _concat_many`), two nullable `int` keys with validity
    on some parts, `i64` sums. Each part is written whole at its offset,
    so it holds no scatter: in the scatter form the 256 scatters of
    737,281 rows were 0.331 s of the task's 1.109 s on a TPU v5e
    (PERF.md, section 5)."""
    from blaze_tpu.ops.util import _concat_many

    n_parts, rows, cap = 64, 16384, 737280

    def part(dtype):
        return jax.ShapeDtypeStruct((rows,), dtype, sharding=one_chip)

    values = [[part(dt) for _ in range(n_parts)]
              for dt in (jnp.int32, jnp.int32, jnp.int64, jnp.int64)]
    masks = [[part(jnp.bool_) if i % 2 else None for i in range(n_parts)],
             [part(jnp.bool_) for _ in range(n_parts)], None, None]
    lengths = jax.ShapeDtypeStruct((n_parts,), jnp.int32,
                                   sharding=one_chip)
    text = _concat_many.lower(
        values, masks, lengths, cap=cap, any_mask=(True, True, False, False)
    ).compile().as_text()
    assert " dynamic-update-slice(" in text
    assert " scatter(" not in text


def _steer_join_to_the_chip(monkeypatch):
    """The join core resolved as `auto` resolves it on a TPU, the sort
    grouping core with it."""
    from blaze_tpu.ops import joins

    monkeypatch.delenv("BLAZE_JOIN_CORE", raising=False)
    monkeypatch.setenv("BLAZE_GROUP_CORE", "sort")
    monkeypatch.setattr(joins, "_join_core_choice",
                        partial(joins._join_core_choice, backend="tpu"))


def _on_chip(args, one_chip):
    """The shapes of a program's arguments, placed on the described chip."""
    def leaf(x):
        a = jnp.asarray(x)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    return jax.tree_util.tree_map(leaf, args)


def _broadcast(slots, rows=6000, bcap=8192):
    """A broadcast relation of `rows` unique `int` keys whose span takes a
    direct array of `slots` slots, with an `int` and a string column, in
    a batch of capacity `bcap`."""
    import pyarrow as pa

    from blaze_tpu import ColumnBatch
    from blaze_tpu.ops.hash_table import direct_table_size

    step = (slots // 2 + 1) // rows + 1
    keys = 2415022 + step * np.arange(rows, dtype=np.int32)
    assert direct_table_size(int(keys[-1] - keys[0]) + 1) == slots
    return ColumnBatch.from_arrow(pa.record_batch({
        "k": keys, "year": (keys % 200).astype(np.int32),
        "brand": pa.array([f"brand #{i % 97}" for i in range(rows)]),
    }), capacity=bcap)


@pytest.mark.parametrize("slots", [131072, 524288])
def test_join_direct_programs(one_chip, monkeypatch, slots):
    """The programs of a broadcast hash join on the direct key->row
    array, as `_JoinCore` builds them on the chip (`auto`): the key span,
    the array's one scatter, the lookup and the inner join's emission at
    a probe batch of 16,384 nullable keys. None holds a `while` loop (the
    sort core's binary searches, about 0.9 ms each on a v5e), and the
    lookup and the emission hold no scatter: an inner
    join reads no matched-build flags, so its emission folds none."""
    import pyarrow as pa

    from blaze_tpu import ColumnBatch
    from blaze_tpu.ops import joins

    _steer_join_to_the_chip(monkeypatch)
    programs = {}
    real = joins.cached_kernel

    def recording(key, build, **kw):
        fn = real(key, build, **kw)
        body = build()

        def call(*args):
            programs[key[0]] = (body, args)
            return fn(*args)

        return call

    monkeypatch.setattr(joins, "cached_kernel", recording)
    build = _broadcast(slots)
    rng = np.random.default_rng(38)
    probe = ColumnBatch.from_arrow(pa.record_batch({
        "p": pa.array(rng.integers(2415022, 2415022 + slots, 16384)
                      .astype(np.int32), mask=rng.random(16384) < 0.045),
        "v": rng.integers(0, 1000, 16384).astype(np.int32)}))
    core = joins._JoinCore(build, [0])
    core.index_build()
    assert core._index[0] == "table_direct"
    assert core._index[1][0].shape == (slots,)
    state = core.probe(probe, [0])
    core.emit_pairs(state, list(build.columns), list(probe.columns),
                    build_first=True, fold_build=False)
    assert set(programs) == {"join_keyspan", "join_table_direct",
                             "join_lookup", "join_emit_table"}
    texts = {
        name: jax.jit(body).lower(*_on_chip(args, one_chip))
        .compile().as_text()
        for name, (body, args) in programs.items()
    }
    for name, text in texts.items():
        assert " while(" not in text, name
    assert " scatter(" in texts["join_table_direct"]
    assert " scatter(" not in texts["join_lookup"]
    assert " scatter(" not in texts["join_emit_table"]


def test_join_direct_fused_aggregate(one_chip, monkeypatch, sales_parquet):
    """The fused join+aggregate program (`FusedAggregateExec.
    _build_join_probe_kernel`, through its packed-input form): the scan's
    filter, the lookup in a direct array of 131,072 slots, the build
    side's gather and a grouped SUM, one program a probe batch."""
    from blaze_tpu.batch import packed_view
    from blaze_tpu.exprs import AggExpr, AggFn, Col
    from blaze_tpu.ops import (
        AggMode, ExecContext, FilterExec, HashAggregateExec, HashJoinExec,
        JoinType, MemoryScanExec,
    )
    from blaze_tpu.ops.fused import fuse_pipelines
    from blaze_tpu.ops.joins import _eq_layout, _flatten_cols
    from blaze_tpu.ops.parquet_scan import FileRange, ParquetScanExec

    _steer_join_to_the_chip(monkeypatch)
    build = _broadcast(131072, bcap=16384)
    join = HashJoinExec(
        MemoryScanExec([[build]], build.schema),
        FilterExec(
            ParquetScanExec([[FileRange(sales_parquet)]],
                            projection=["ss_quantity", "ss_net_paid"]),
            Col("ss_quantity") >= 21),
        ["k"], ["ss_quantity"], JoinType.INNER)
    fused = fuse_pipelines(HashAggregateExec(
        join, keys=[(Col("year"), "year")],
        aggs=[(AggExpr(AggFn.SUM, Col("ss_net_paid")), "s")],
        mode=AggMode.COMPLETE,
    )).children[0]
    pleaf, ppipe = join._fused_probe
    build_cb, core = join.build_side(ExecContext(), shared=True)
    mode, tab = core.table_state_static(join.right_keys, ppipe.schema)
    assert mode == "table_direct"
    raw = next(iter(pleaf.execute(0, ExecContext())))
    pv = packed_view(raw)
    assert pv is not None and raw.capacity == 16384
    b_eq = _flatten_cols([build_cb.columns[i] for i in join.left_keys])
    kernel = fused._build_join_probe_kernel_packed(
        pv, mode, build_cb.layout(), _eq_layout(
            [build_cb.columns[i] for i in join.left_keys]),
        tuple(join.right_keys), ppipe)
    args = _on_chip((build_cb.device_buffers(), pv.buf, b_eq, tab),
                    one_chip)
    text = jax.jit(lambda b, buf, k, t: kernel(b, buf, k, t, None, None)) \
        .lower(*args).compile().as_text()
    assert " while(" not in text


def test_join_kr_lookup_is_refused(one_chip):
    """Why the chip takes only the direct array of the table core: the
    key|row table's lookup (`hash_table.lookup_kr`) runs out of vector
    memory in the compacted tail's prefix sum (`jnp.nonzero`), for the
    table a 6,000-row broadcast gets and a 16,384-row probe batch."""
    from blaze_tpu.ops import hash_table as ht

    tsize = ht.probe_table_size(6000)
    with pytest.raises(Exception) as info:
        _compile(ht.lookup_kr, one_chip, ((tsize,), jnp.uint64),
                 ((16384,), jnp.uint32), ((16384,), jnp.uint32),
                 ((16384,), jnp.bool_))
    assert "RESOURCE_EXHAUSTED" in str(info.value)


def _shuffle_batch(table):
    """One 16,384-row batch shaped as the benchmark's shuffle cells
    scan it (`store_sales`: 23 columns, nullable `int` keys, decimal(7,2)
    money, a `bigint` ticket; `inventory`: four `int`s, one nullable)."""
    import decimal

    import pyarrow as pa

    from blaze_tpu import ColumnBatch

    rng = np.random.default_rng(32)
    n = 16384

    def ints(nullable):
        return pa.array(rng.integers(1, 300_000, n).astype(np.int32),
                        mask=rng.random(n) < 0.045 if nullable else None)

    if table == "inventory":
        cols = {"inv_date_sk": ints(False), "inv_item_sk": ints(False),
                "inv_warehouse_sk": ints(False),
                "inv_quantity_on_hand": ints(True)}
        key = "inv_item_sk"
    else:
        money = pa.array(
            [decimal.Decimal(int(c)).scaleb(-2)
             for c in rng.integers(0, 3_000_000, n)],
            pa.decimal128(7, 2), mask=rng.random(n) < 0.045)
        cols = {"ss_item_sk": ints(False),
                "ss_ticket_number": pa.array(np.arange(n, dtype=np.int64))}
        cols.update({f"ss_key_{i}": ints(True) for i in range(9)})
        cols.update({f"ss_money_{i}": money for i in range(12)})
        key = "ss_key_0"
    return ColumnBatch.from_arrow(pa.RecordBatch.from_pydict(cols)), key


@pytest.mark.parametrize("table", ["inventory", "store_sales"])
def test_shuffle_partition_programs(one_chip, table):
    """The two jitted programs of the stage `shuffle_partition`
    (ops/shuffle_writer.py) at the serve batch's capacity: the murmur3
    chain of a nullable key, and the sort by partition with the gather
    of every buffer and the 200 counts."""
    from blaze_tpu.exprs import ir
    from blaze_tpu.ops.shuffle_writer import (
        _build_partition_ids, _build_sort_by_partition,
    )

    cb, key = _shuffle_batch(table)
    assert cb.capacity == 16384
    bufs = [jax.ShapeDtypeStruct(b.shape, b.dtype, sharding=one_chip)
            for b in cb.device_buffers()]
    i = cb.schema.index_of(key)
    dt = cb.schema.fields[i].dtype
    ids = _build_partition_ids(cb.schema, cb.layout(),
                               (ir.BoundCol(i, dt),), [dt], N_PARTS)
    jax.jit(ids).lower(bufs).compile()
    pids = jax.ShapeDtypeStruct((16384,), jnp.int32, sharding=one_chip)
    rows = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(_build_sort_by_partition(N_PARTS)).lower(
        pids, rows, bufs).compile()
    assert "sort" in compiled.as_text()


# ---- kernels with no path from blaze_tpu/: the refusal, on record ----
# Interpret mode passes all of these (tests/test_pallas_kernels.py);
# the v5e compiler does not. The BLAZE_SEGREDUCE selector that reached
# segreduce_pallas is gone and compact_pallas never had a call site
# (ROADMAP D7 decides their fate). A test here that starts FAILING means
# the kernel now compiles: move it up among the checks above.

def _refused(one_chip, fn, *shapes):
    with pytest.raises(Exception) as info:
        _compile(fn, one_chip, *shapes)
    return str(info.value)


@pytest.mark.parametrize("k", [1024, 4096])
def test_segreduce_sum_is_refused(one_chip, monkeypatch, k):
    from blaze_tpu.ops.kernels import segreduce_pallas as sr

    # the kernel asks the backend whether to interpret: steer it here
    monkeypatch.setattr(sr, "_interpret", lambda: False)
    msg = _refused(
        one_chip, lambda gid, v: sr._call(sr._sum_kernel, gid, v, k),
        ((ROWS,), jnp.int32), ((ROWS,), jnp.float32),
    )
    # the (4, 128) output block: _K_BLK // 128 is not a multiple of 8
    assert "divisible by 8 and 128" in msg


@pytest.mark.parametrize("k", [1024, 4096])
def test_segreduce_minmax_is_refused(one_chip, monkeypatch, k):
    from blaze_tpu.ops.kernels import segreduce_pallas as sr

    monkeypatch.setattr(sr, "_interpret", lambda: False)
    msg = _refused(
        one_chip,
        lambda gid, v: sr._call(
            partial(sr._minmax_kernel, is_min=True), gid, v, k),
        ((ROWS,), jnp.int32), ((ROWS,), jnp.float32),
    )
    assert "divisible by 8 and 128" in msg


def test_compact_perm_is_refused(one_chip, monkeypatch):
    from blaze_tpu.ops.kernels import compact_pallas as cp

    monkeypatch.setattr(cp, "_interpret", lambda: False)
    # the undecorated function, freshly jitted: the module's own jit
    # may hold an interpret-mode trace from another test in this worker
    msg = _refused(
        one_chip, lambda keep: cp.compact_perm.__wrapped__(keep),
        ((ROWS,), jnp.bool_),
    )
    # outputs[1], the (1, 1) SMEM count block
    assert "divisible by 8 and 128" in msg


# ---- the mesh group-by, for the four chips of the described host -------

@pytest.fixture(scope="module")
def four_chips(one_chip):
    """The described host's four devices as the mesh the group-by is
    given (`one_chip` has loaded the TPU library and set the cache)."""
    from jax.experimental import topologies
    from jax.sharding import Mesh

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    return Mesh(np.array(topo.devices), ("data",))


def test_mesh_groupby_programs(four_chips):
    """Query 1's mesh program (two nullable `int` keys, a nullable
    `decimal(7,2)` sum, the date filter) and the program that appends a
    dealt batch to the chips' column buffers compile for the four chips
    (at 4,096 rows a shard: XLA's time over the sorts grows with their
    length, 62 s on the chip's host at the cell's 262,144). The mesh
    program holds all-to-alls and sorts and no scatter: a scatter of
    `i64` is what three quarters of `q1_group.s4`'s device time are."""
    import types

    from jax.sharding import NamedSharding, PartitionSpec as P

    from blaze_tpu.exprs import Col
    from blaze_tpu.exprs.ir import AggFn
    from blaze_tpu.parallel import mesh_ops
    from blaze_tpu.parallel.sharded import DistAgg, DistributedGroupBy
    from blaze_tpu.runtime.pack import _aligned_metas
    from blaze_tpu.types import DataType, Field, Schema

    n_dev, cap, run_cap = 4, 4096, 1024
    shard = NamedSharding(four_chips, P("data"))

    def stack(dtype, *shape):
        return jax.ShapeDtypeStruct((n_dev,) + shape, dtype,
                                    sharding=shard)

    schema = Schema([Field("d", DataType.int32(), True),
                     Field("c", DataType.int32(), True),
                     Field("s", DataType.int32(), True),
                     Field("a", DataType.decimal(7, 2), True)])
    dtypes = (jnp.int32, jnp.int32, jnp.int32, jnp.int64)
    gb = DistributedGroupBy(
        four_chips, schema, keys=[Col("c"), Col("s")],
        aggs=[DistAgg(AggFn.SUM, Col("a"))],
        filter_pred=(Col("d") >= 2451545) & (Col("d") <= 2451910),
        slack=1.5)
    compiled = gb._compile().lower(
        stack(jnp.bool_, cap), [stack(dt, cap) for dt in dtypes],
        [stack(jnp.bool_, cap) for _ in dtypes]).compile()
    text = compiled.as_text()
    assert " all-to-all(" in text and " sort(" in text
    assert " scatter(" not in text

    # a dealt batch as the scan packs it: the run's rows, the date
    # (the scan's filter leaves it no NULL), three nullable columns
    entries = [(np.zeros(1, np.int32), 4, 0),
               (np.zeros(1, np.int32), run_cap, 0)]
    for dt in (np.int32, np.int32, np.int64):
        entries += [(np.zeros(1, dt), run_cap, 0),
                    (np.zeros(1, np.bool_), run_cap, 1)]
    metas, nbytes = _aligned_metas(entries)
    batch = types.SimpleNamespace(
        metas=metas, pairs=True, run_cap=run_cap,
        col_meta=[(None, False, None, True)]
        + [(None, True, None, True)] * 3)
    acc = (stack(jnp.int32), stack(jnp.bool_, cap),
           [stack(dt, cap) for dt in dtypes],
           [stack(jnp.bool_, cap) for _ in dtypes])
    appended = jax.jit(
        mesh_ops._build_deal_append(four_chips, batch),
        donate_argnums=(0,),
    ).lower(acc, stack(jnp.uint8, nbytes)).compile()
    # the buffers are updated where they lie
    assert appended.memory_analysis().alias_size_in_bytes \
        >= n_dev * cap * (4 + 4 + 4 + 8) // n_dev
