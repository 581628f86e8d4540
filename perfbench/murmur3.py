"""Spark's Murmur3_x86_32 over fixed-width integers, in numpy.

The benchmark's own: `ShuffleWriterExec`'s partition of a row is
`pmod(hash(key), n)` with Spark's seed 42, and the reference must not
borrow the program's host murmur3 (`ops/shuffle_writer._chain_fixed`)
to say what that is. Written from the published algorithm
(`org.apache.spark.unsafe.hash.Murmur3_x86_32`): `hashLong` mixes the low
then the high 32-bit word and finalises with length 8, `hashInt` mixes
one word and finalises with length 4.
"""

from __future__ import annotations

import numpy as np

SPARK_SEED = 42
_C1 = np.uint32(0xCC9E2D51)
_C2 = np.uint32(0x1B873593)


def _rotl(x: np.ndarray, r: int) -> np.ndarray:
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def _mix_k1(k1: np.ndarray) -> np.ndarray:
    return _rotl(k1 * _C1, 15) * _C2


def _mix_h1(h1: np.ndarray, k1: np.ndarray) -> np.ndarray:
    h1 = _rotl(h1 ^ k1, 13)
    return h1 * np.uint32(5) + np.uint32(0xE6546B64)


def _fmix(h1: np.ndarray, length: int) -> np.ndarray:
    h1 = h1 ^ np.uint32(length)
    h1 = h1 ^ (h1 >> np.uint32(16))
    h1 = h1 * np.uint32(0x85EBCA6B)
    h1 = h1 ^ (h1 >> np.uint32(13))
    h1 = h1 * np.uint32(0xC2B2AE35)
    return h1 ^ (h1 >> np.uint32(16))


def hash_int(values, seed: int = SPARK_SEED) -> np.ndarray:
    """Spark `hash(int)`: int32 hashes of 32-bit integers."""
    v = np.asarray(values).astype(np.int32).view(np.uint32)
    with np.errstate(over="ignore"):
        h1 = _mix_h1(np.full(v.shape, seed, np.uint32), _mix_k1(v))
        return _fmix(h1, 4).view(np.int32)


def hash_long(values, seed: int = SPARK_SEED) -> np.ndarray:
    """Spark `hash(bigint)`: int32 hashes of 64-bit integers."""
    v = np.asarray(values).astype(np.int64).view(np.uint64)
    low = (v & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    high = (v >> np.uint64(32)).astype(np.uint32)
    with np.errstate(over="ignore"):
        h1 = _mix_h1(np.full(v.shape, seed, np.uint32), _mix_k1(low))
        h1 = _mix_h1(h1, _mix_k1(high))
        return _fmix(h1, 8).view(np.int32)


def pmod(hashes: np.ndarray, n: int) -> np.ndarray:
    """Spark's `pmod`: the non-negative remainder (numpy's `%` on signed
    integers already takes the divisor's sign)."""
    return (hashes.astype(np.int64) % n).astype(np.int32)
