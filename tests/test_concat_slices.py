"""`concat_batches` writes each compacted part whole at its offset
(`ops/util.py: _concat_many`): the live rows of every part, in order,
then rows that read 0 and invalid up to the bucket - for every column
type the engine puts on the device, parts of any capacity, validity on
all, some or none of a column's parts, and one compile for every mix of
row counts with the same shapes."""

import zlib

import numpy as np
import pyarrow as pa
import pytest

import jax

from blaze_tpu.batch import Column, ColumnBatch
from blaze_tpu.config import get_config
from blaze_tpu.ops.base import ExecContext
from blaze_tpu.ops.util import _concat_many, concat_batches
from blaze_tpu.runtime import dispatch
from blaze_tpu.types import DataType, Field, Schema

SCHEMA = Schema([
    Field("i32", DataType.int32()),
    Field("i64", DataType.int64()),
    Field("f32", DataType.float32()),
    Field("b", DataType.bool_()),
    Field("wide", DataType.decimal(38, 2)),  # (capacity, 2) limb pairs
    Field("s", DataType.utf8()),  # dictionary codes, a dictionary a part
])

# (capacity, live rows, with a pending selection) for each part
LAYOUTS = {
    "one_capacity": [(1024, 1000, False), (1024, 17, False),
                     (1024, 512, False)],
    "mixed_capacities": [(256, 200, False), (16384, 3000, False),
                         (1024, 1024, False), (4096, 4000, False)],
    # a few live rows in parts far wider than the 256-row bucket
    "wider_than_bucket": [(16384, 3, False), (16384, 1, False),
                          (16384, 7, False), (16384, 2, False)],
    # 4,096 rows into 4,096: the last part starts at 3,072 and is 4,096
    # wide, so a slice start clamped to fit the bucket would be wrong
    "ends_at_cap": [(1024, 1024, False), (4096, 2048, False),
                    (4096, 1024, False)],
    "single_part": [(16384, 300, False)],
    "selected_parts": [(1024, 900, True), (4096, 2500, False),
                       (1024, 1024, True)],
    "many_parts": [(1024, n, False) for n in
                   (1024, 1, 700, 333, 1000, 64, 1023, 512) * 8],
}


def _part(rng, cap, n, select, masks):
    """One batch of `cap` rows, `n` of them live, padding filled with
    garbage (a compacted part's tail is whatever the gather left);
    `masks[c]` says whether column c carries validity."""
    words = [f"w{k}" for k in rng.choice(40, size=6, replace=False)]
    vals = [
        rng.integers(-2**31, 2**31, cap).astype(np.int32),
        rng.integers(-2**62, 2**62, cap),
        rng.standard_normal(cap).astype(np.float32),
        rng.random(cap) < 0.5,
        rng.integers(-2**62, 2**62, (cap, 2)),
        rng.integers(0, len(words), cap).astype(np.int32),
    ]
    cols = []
    for f, v, m in zip(SCHEMA, vals, masks):
        cols.append(Column(
            f.dtype, jax.numpy.asarray(v),
            jax.numpy.asarray(rng.random(cap) < 0.8) if m else None,
            pa.array(words) if f.dtype.is_dictionary_encoded else None))
    sel = rng.random(cap) < 0.6 if select else None
    cb = ColumnBatch(SCHEMA, cols, n,
                     None if sel is None else jax.numpy.asarray(sel))
    return cb, sel


def _expected(parts):
    """Each column's live values and validity as numpy concatenates
    them; strings decoded through each part's own dictionary."""
    want_v, want_m = [], []
    for ci in range(len(SCHEMA)):
        vs, ms = [], []
        for cb, sel in parts:
            c = cb.columns[ci]
            live = np.arange(cb.capacity) < cb.num_rows
            if sel is not None:
                live &= sel
            v = np.asarray(c.values)[live]
            if c.dictionary is not None:
                v = np.asarray(c.dictionary.to_pylist(), dtype=object)[v]
            vs.append(v)
            ms.append(np.asarray(c.valid_mask())[live])
        want_v.append(np.concatenate(vs))
        want_m.append(np.concatenate(ms))
    return want_v, want_m


@pytest.mark.parametrize("validity", ["all", "some", "none"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_equals_numpy_concatenation(layout, validity):
    rng = np.random.default_rng(zlib.crc32(f"{layout}/{validity}".encode()))
    parts = []
    for i, (cap, n, select) in enumerate(LAYOUTS[layout]):
        # "some": validity on every other part of every column
        has = {"all": True, "some": i % 2 == 1, "none": False}[validity]
        parts.append(_part(rng, cap, n, select, [has] * len(SCHEMA)))
    want_v, want_m = _expected(parts)
    total = len(want_v[0])

    out = concat_batches([cb for cb, _ in parts])

    cap = get_config().bucket_for(total)
    assert out.num_rows == total and out.capacity == cap
    assert out.selection is None
    any_mask = validity == "all" or (validity == "some" and len(parts) > 1)
    for ci, f in enumerate(SCHEMA):
        c = out.columns[ci]
        got = np.asarray(c.values)
        assert got.shape[0] == cap and got.dtype == f.dtype.physical_dtype()
        live = got[:total]
        if c.dictionary is not None:
            live = np.asarray(c.dictionary.to_pylist(), dtype=object)[live]
        np.testing.assert_array_equal(live, want_v[ci], err_msg=f.name)
        # the tail reads 0, bit for bit (-0.0 would not pass)
        assert not got[total:].view(np.uint8).any(), f.name
        if not any_mask:
            assert c.validity is None, f.name
            continue
        m = np.asarray(c.validity)
        np.testing.assert_array_equal(m[:total], want_m[ci], err_msg=f.name)
        assert not m[total:].any(), f.name


def test_one_compile_for_every_row_count_mix():
    """The row counts are traced: two mixes of the same shapes share one
    program, and the task counts every part the launch wrote."""
    rng = np.random.default_rng(36)
    caps = (1024, 256, 4096, 1024, 256)
    masks = [True, False, False, True, False, False]

    def parts(lengths):
        return [_part(rng, cap, n, False, masks)[0]
                for cap, n in zip(caps, lengths)]

    first = parts((1000, 3, 2000, 17, 256))  # 3,276 rows into 4,096
    concat_batches(first)
    compiled = _concat_many._cache_size()
    ctx = ExecContext()
    with dispatch.task_scope(ctx):
        second = parts((5, 256, 1, 1000, 9))
        out = concat_batches(second)
    assert _concat_many._cache_size() == compiled
    assert out.num_rows == 1271 and out.capacity == 4096
    assert ctx.metrics.counters["concat_slice_parts"] == len(caps)
    want_v, _ = _expected([(cb, None) for cb in second])
    np.testing.assert_array_equal(
        np.asarray(out.columns[1].values)[:out.num_rows], want_v[1])


def test_a_part_already_at_its_bucket_is_no_launch():
    rng = np.random.default_rng(7)
    cb, _ = _part(rng, 1024, 1000, False, [True] * len(SCHEMA))
    ctx = ExecContext()
    with dispatch.task_scope(ctx):
        out = concat_batches([cb])
    # the same buffers back (only the codes were remapped)
    assert out.columns[1].values is cb.columns[1].values
    assert "concat_slice_parts" not in ctx.metrics.counters
