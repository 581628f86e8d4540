"""Pallas kernel tests, interpret mode on the CPU test mesh: semantics
only. Whether the TPU compiler accepts a kernel is another matter -
tests/test_chip_compile.py compiles each for a described v5e (murmur3
and stats are accepted, segreduce and compact are refused), and only
murmur3 has run on a chip (chip_smoke.py)."""

import numpy as np
import jax.numpy as jnp
import pytest

from blaze_tpu.exprs.hashing import hash_int_host, hash_long_host
from blaze_tpu.ops.kernels.murmur3_pallas import (
    partition_ids_int32,
    partition_ids_int64,
    supports,
)


def exp_pid(h, n=200):
    r = np.int32(np.uint32(h & 0xFFFFFFFF)) % n
    return int(r + n if r < 0 else r)


def test_pallas_partition_ids_int32_bit_exact():
    rng = np.random.default_rng(1)
    cap = 2048
    vals = rng.integers(-(2**31), 2**31, cap).astype(np.int32)
    got = np.asarray(
        partition_ids_int32(jnp.asarray(vals), 200, interpret=True)
    )
    exp = np.array([exp_pid(hash_int_host(int(v))) for v in vals[:256]])
    np.testing.assert_array_equal(got[:256], exp)


def test_pallas_partition_ids_int64_bit_exact():
    rng = np.random.default_rng(2)
    cap = 2048
    vals = rng.integers(-(2**63), 2**63 - 1, cap, dtype=np.int64)
    got = np.asarray(
        partition_ids_int64(jnp.asarray(vals), 31, interpret=True)
    )
    exp = np.array(
        [exp_pid(hash_long_host(int(v)), 31) for v in vals[:256]]
    )
    np.testing.assert_array_equal(got[:256], exp)


def test_supports():
    assert supports("int64", 4096)
    assert supports("int32", 1024)
    assert not supports("utf8", 4096)
    assert not supports("int64", 1000)  # not block-aligned


def test_masked_stats_interpret():
    """Fused sum/min/max/count over a masked column == numpy, incl. the
    all-masked empty selection (identities + count 0)."""
    import numpy as np
    import jax.numpy as jnp

    from blaze_tpu.ops.kernels import stats_pallas as sp

    rng = np.random.default_rng(17)
    n = 4096
    vals = (rng.random(n).astype(np.float32) - 0.5) * 1000
    mask = (rng.random(n) < 0.7)

    assert sp.supports(n, jnp.float32)
    out = np.asarray(sp.masked_stats(
        jnp.asarray(vals), jnp.asarray(mask), interpret=True))
    sel = vals[mask]
    np.testing.assert_allclose(out[0], sel.sum(), rtol=1e-5)
    assert out[1] == sel.min()
    assert out[2] == sel.max()
    assert out[3] == len(sel)

    empty = np.asarray(sp.masked_stats(
        jnp.asarray(vals), jnp.zeros(n, dtype=bool), interpret=True))
    assert empty[0] == 0.0 and empty[3] == 0.0
    assert np.isinf(empty[1]) and np.isinf(empty[2])

    # int32 values path + multi-chunk shape (> _CHUNK_ROWS)
    big_n = 1 << 20
    ivals = rng.integers(-1000, 1000, big_n).astype(np.int32)
    imask = rng.random(big_n) < 0.5
    got = np.asarray(sp.masked_stats(
        jnp.asarray(ivals), jnp.asarray(imask), interpret=True))
    isel = ivals[imask]
    np.testing.assert_allclose(got[0], isel.sum(), rtol=1e-4)
    assert got[1] == isel.min() and got[2] == isel.max()
    assert got[3] == len(isel)


def test_pallas_segment_sum_interpret():
    from blaze_tpu.ops.kernels import segreduce_pallas as sr

    rng = np.random.default_rng(5)
    cap, k = 4096, 512
    gid = rng.integers(0, k, cap).astype(np.int32)
    # park some rows out of range: they must contribute nowhere
    gid[::97] = k + 3
    v = (rng.random(cap) * 100 - 50).astype(np.float32)
    assert sr.supports(cap, k)
    got = np.asarray(sr.segment_sum(jnp.asarray(gid), jnp.asarray(v), k))
    exp = np.zeros(k, np.float64)
    for g, x in zip(gid, v):
        if g < k:
            exp[g] += x
    np.testing.assert_allclose(got, exp, rtol=1e-4, atol=1e-3)


def test_pallas_segment_minmax_interpret():
    from blaze_tpu.ops.kernels import segreduce_pallas as sr

    rng = np.random.default_rng(6)
    cap, k = 2048, 512
    gid = rng.integers(0, k, cap).astype(np.int32)
    v = (rng.random(cap) * 1000 - 500).astype(np.float32)
    lo = np.asarray(
        sr.segment_minmax(jnp.asarray(gid), jnp.asarray(v), k, True)
    )
    hi = np.asarray(
        sr.segment_minmax(jnp.asarray(gid), jnp.asarray(v), k, False)
    )
    for g in range(k):
        sel = v[gid == g]
        if len(sel):
            assert lo[g] == sel.min()
            assert hi[g] == sel.max()
        else:
            assert lo[g] == np.inf and hi[g] == -np.inf


def test_pallas_compact_interpret():
    from blaze_tpu.ops.kernels import compact_pallas as cp

    rng = np.random.default_rng(7)
    cap = 4096
    v = (rng.random(cap) * 100 - 50).astype(np.float32)
    keep = rng.random(cap) < 0.37
    assert cp.supports(cap)
    out, n = cp.compact_column_f32(jnp.asarray(v), jnp.asarray(keep))
    out = np.asarray(out)
    n = int(n)
    exp = v[keep]
    assert n == len(exp)
    np.testing.assert_array_equal(out[:n], exp)
    assert (out[n:] == 0).all()


def test_pallas_compact_i32_exact_full_range():
    from blaze_tpu.ops.kernels import compact_pallas as cp

    rng = np.random.default_rng(8)
    cap = 2048
    v = rng.integers(-(2**31), 2**31, cap).astype(np.int32)
    keep = rng.random(cap) < 0.5
    out, n = cp.compact_column_i32(jnp.asarray(v), jnp.asarray(keep))
    out = np.asarray(out)
    n = int(n)
    np.testing.assert_array_equal(out[:n], v[keep])


def test_pallas_segment_sum_matches_engine_segops():
    """Parity with the aggregate's XLA segment path (the operator-suite
    cross-check VERDICT r3 asked for)."""
    import jax

    from blaze_tpu.ops.kernels import segreduce_pallas as sr

    rng = np.random.default_rng(9)
    cap, k = 8192, 1024
    gid = jnp.asarray(rng.integers(0, k, cap).astype(np.int32))
    v = jnp.asarray((rng.random(cap) * 10).astype(np.float32))
    xla = jax.ops.segment_sum(v, gid, num_segments=k)
    pls = sr.segment_sum(gid, v, k)
    np.testing.assert_allclose(
        np.asarray(pls), np.asarray(xla), rtol=1e-4, atol=1e-3
    )


def test_pallas_segment_sum_nonfinite_isolated():
    """ADVICE r4: a NaN/inf value anywhere in a 1024-row block must
    poison ONLY its own segment, never the whole block's segments
    (IEEE 0*NaN=NaN would leak through a raw one-hot contraction)."""
    from blaze_tpu.ops.kernels import segreduce_pallas as sr

    rng = np.random.default_rng(10)
    cap, k = 2048, 512
    gid = rng.integers(0, k, cap).astype(np.int32)
    v = (rng.random(cap) * 10).astype(np.float32)
    gid[7], v[7] = 3, np.nan           # NaN lands in segment 3
    gid[1500], v[1500] = 5, np.inf     # +inf lands in segment 5
    gid[11], v[11] = k + 2, np.nan     # dead NaN row: contributes nowhere
    got = np.asarray(sr.segment_sum(jnp.asarray(gid), jnp.asarray(v), k))
    exp = np.zeros(k, np.float64)
    for g, x in zip(gid, v):
        if g < k:
            exp[g] += np.float64(x)
    assert np.isnan(got[3]) and np.isnan(exp[3])
    assert got[5] == np.inf
    fin = np.isfinite(exp)
    assert fin.sum() == k - 2
    np.testing.assert_allclose(got[fin], exp[fin], rtol=1e-4, atol=1e-3)


def test_pallas_compact_preserves_nonfinite():
    """ADVICE r4: compacting a float column containing NaN/inf (kept or
    dropped) must move every surviving value bit-exactly."""
    from blaze_tpu.ops.kernels import compact_pallas as cp

    rng = np.random.default_rng(11)
    cap = 2048
    v = (rng.random(cap) * 100 - 50).astype(np.float32)
    v[3] = np.nan
    v[4] = np.inf
    v[5] = -np.inf
    v[1024] = np.nan          # dropped NaN in the second block
    keep = rng.random(cap) < 0.5
    keep[3] = keep[4] = keep[5] = True
    keep[1024] = False
    out, n = cp.compact_column_f32(jnp.asarray(v), jnp.asarray(keep))
    out = np.asarray(out)
    n = int(n)
    exp = v[keep]
    assert n == len(exp)
    np.testing.assert_array_equal(
        out[:n].view(np.uint32), exp.view(np.uint32)
    )
    assert (out[n:] == 0).all()
