"""ctypes bindings to the C++ host runtime (cpp/blaze_host.cpp).

The shared library builds lazily on first use (g++ -O3 -march=native,
linked against the system libzstd) and is cached next to the source with a
content hash, so a source change rebuilds automatically. Falls back to pure
Python (zstandard module + numpy murmur3) if the toolchain is unavailable -
the engine stays functional, just slower on host-side byte crunching.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import tempfile
from typing import Optional

import numpy as np

log = logging.getLogger("blaze_tpu.native")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_CPP_SRC = os.path.join(_REPO_ROOT, "cpp", "blaze_host.cpp")

_lib: Optional[ctypes.CDLL] = None
_lib_tried = False


def _build_lib() -> Optional[str]:
    with open(_CPP_SRC, "rb") as f:
        src = f.read()
    tag = hashlib.sha256(src).hexdigest()[:16]
    cache_dir = os.path.join(
        tempfile.gettempdir(), "blaze_tpu_native"
    )
    os.makedirs(cache_dir, exist_ok=True)
    so_path = os.path.join(cache_dir, f"libblaze_host_{tag}.so")
    if os.path.exists(so_path):
        return so_path
    cmd = [
        "g++", "-O3", "-march=native", "-shared", "-fPIC", "-std=c++17",
        _CPP_SRC, "-o", so_path + ".tmp", "-lzstd",
    ]
    try:
        subprocess.run(
            cmd, check=True, capture_output=True, timeout=120
        )
        os.replace(so_path + ".tmp", so_path)
        return so_path
    except Exception as e:  # toolchain missing / compile error
        log.warning("native host lib build failed, using Python fallback: %s",
                    e)
        return None


def get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _lib_tried
    if _lib is not None or _lib_tried:
        return _lib
    _lib_tried = True
    if os.environ.get("BLAZE_DISABLE_NATIVE"):
        return None
    path = _build_lib()
    if path is None:
        return None
    lib = ctypes.CDLL(path)
    c = ctypes
    i64, i32, u8p, i64p, i32p, u32p = (
        c.c_int64, c.c_int32, c.POINTER(c.c_uint8), c.POINTER(c.c_int64),
        c.POINTER(c.c_int32), c.POINTER(c.c_uint32),
    )
    lib.blz_zstd_compress_bound.restype = i64
    lib.blz_zstd_compress_bound.argtypes = [i64]
    lib.blz_zstd_compress.restype = i64
    lib.blz_zstd_compress.argtypes = [u8p, i64, u8p, i64, c.c_int]
    lib.blz_zstd_decompress.restype = i64
    lib.blz_zstd_decompress.argtypes = [u8p, i64, u8p, i64]
    lib.blz_zstd_frame_content_size.restype = i64
    lib.blz_zstd_frame_content_size.argtypes = [u8p, i64]
    lib.blz_zstd_decompress_stream.restype = i64
    lib.blz_zstd_decompress_stream.argtypes = [u8p, i64, u8p, i64]
    lib.blz_murmur3_strings_chain.restype = None
    lib.blz_murmur3_strings_chain.argtypes = [u8p, i32p, u8p, i64, u32p]
    lib.blz_murmur3_dict_strings_chain.restype = None
    lib.blz_murmur3_dict_strings_chain.argtypes = [
        u8p, i32p, i32p, u8p, i64, u32p
    ]
    lib.blz_murmur3_i32_chain.restype = None
    lib.blz_murmur3_i32_chain.argtypes = [i32p, u8p, i64, u32p]
    lib.blz_murmur3_i64_chain.restype = None
    lib.blz_murmur3_i64_chain.argtypes = [i64p, u8p, i64, u32p]
    lib.blz_pmod.restype = None
    lib.blz_pmod.argtypes = [u32p, i64, i32, i32p]
    lib.blz_shuffle_assemble.restype = i64
    lib.blz_shuffle_assemble.argtypes = [
        c.c_char_p, c.c_char_p, u8p, i64p, i32,
        c.POINTER(c.c_char_p), i32, i64p,
    ]
    _lib = lib
    return _lib


def _as(ptr_type, arr: np.ndarray):
    return arr.ctypes.data_as(ptr_type)


# ---------------------------------------------------------------------------
# zstd with Python fallback
# ---------------------------------------------------------------------------

# Last-resort frame marker when NO zstd implementation exists in the
# environment (neither the C++ host lib nor the `zstandard` module -
# toolchain-less containers). Frames start with these 8 bytes followed by
# the raw payload; a real zstd frame starts with magic 28 B5 2F FD, so
# the two can never be confused. Wire bit-compat with the reference is
# only claimed when a zstd tier exists - this keeps the shuffle/cluster
# machinery functional (self-consistent) instead of crashing.
_RAW_FRAME_MAGIC = b"BLZRAW\x00\x01"


def _py_zstd():
    try:
        import zstandard

        return zstandard
    except ImportError:
        return None


def zstd_compress(data: bytes, level: int = 1) -> bytes:
    lib = get_lib()
    if lib is None:
        zstandard = _py_zstd()
        if zstandard is None:
            return _RAW_FRAME_MAGIC + data

        return zstandard.ZstdCompressor(level=level).compress(data)
    src = np.frombuffer(data, dtype=np.uint8)
    bound = lib.blz_zstd_compress_bound(len(data))
    dst = np.empty(bound, dtype=np.uint8)
    n = lib.blz_zstd_compress(
        _as(ctypes.POINTER(ctypes.c_uint8), src), len(data),
        _as(ctypes.POINTER(ctypes.c_uint8), dst), bound, level,
    )
    if n < 0:
        raise IOError("zstd compression failed")
    return dst[:n].tobytes()


def zstd_decompress(data: bytes, hint: Optional[int] = None) -> bytes:
    if data[:8] == _RAW_FRAME_MAGIC:
        # raw fallback frame (zstd-less writer); readable regardless of
        # which zstd tier THIS process has
        return data[8:]
    lib = get_lib()
    if lib is None:
        zstandard = _py_zstd()
        if zstandard is None:
            raise IOError(
                "zstd frame received but no zstd implementation is "
                "available (install zstandard or the C++ host lib)"
            )

        return zstandard.ZstdDecompressor().decompressobj().decompress(data)
    src = np.frombuffer(data, dtype=np.uint8)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    size = lib.blz_zstd_frame_content_size(_as(u8p, src), len(data))
    if size >= 0:
        dst = np.empty(size, dtype=np.uint8)
        n = lib.blz_zstd_decompress(
            _as(u8p, src), len(data), _as(u8p, dst), size
        )
        if n < 0:
            raise IOError("zstd decompression failed")
        return dst[:n].tobytes()
    # unknown content size (streaming frames): grow-and-retry
    cap = hint or max(len(data) * 8, 1 << 20)
    while True:
        dst = np.empty(cap, dtype=np.uint8)
        n = lib.blz_zstd_decompress_stream(
            _as(u8p, src), len(data), _as(u8p, dst), cap
        )
        if n == -3:
            cap *= 4
            continue
        if n < 0:
            raise IOError("zstd stream decompression failed")
        return dst[:n].tobytes()


# ---------------------------------------------------------------------------
# murmur3 chains with Python fallback
# ---------------------------------------------------------------------------

def murmur3_strings_chain(arr, hashes: np.ndarray) -> np.ndarray:
    """Chain a pyarrow StringArray into running per-row hashes (uint32,
    modified in place and returned). NULL rows keep their seed."""
    import pyarrow as pa

    lib = get_lib()
    n = len(arr)
    if lib is None:
        from blaze_tpu.exprs.hashing import hash_bytes_host

        vals = arr.to_pylist()
        for i, s in enumerate(vals):
            if s is None:
                continue
            b = s.encode("utf-8") if isinstance(s, str) else s
            hashes[i] = np.uint32(hash_bytes_host(b, int(hashes[i])))
        return hashes
    arr = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
    if pa.types.is_dictionary(arr.type):
        arr = arr.dictionary_decode()
    if pa.types.is_large_string(arr.type):
        # the C walk reads int32 offsets; large_string carries int64
        arr = arr.cast(pa.string())
    if arr.offset != 0:
        arr = pa.concat_arrays([arr])  # re-materialize at offset 0
    bufs = arr.buffers()
    validity_np = None
    if arr.null_count > 0:
        validity_np = np.asarray(arr.is_valid()).astype(np.uint8)
    offsets = np.frombuffer(bufs[1], dtype=np.int32)[: n + 1]
    data = (
        np.frombuffer(bufs[2], dtype=np.uint8)
        if bufs[2] is not None
        else np.zeros(1, dtype=np.uint8)
    )
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.blz_murmur3_strings_chain(
        _as(u8p, data),
        _as(ctypes.POINTER(ctypes.c_int32),
            np.ascontiguousarray(offsets)),
        _as(u8p, validity_np) if validity_np is not None else None,
        n,
        _as(ctypes.POINTER(ctypes.c_uint32), hashes),
    )
    return hashes


def murmur3_dict_strings_chain(dictionary, codes: np.ndarray,
                               validity: Optional[np.ndarray],
                               hashes: np.ndarray) -> np.ndarray:
    """Chain a dictionary-encoded string column into running per-row hashes
    (uint32, in place). `dictionary` is a pyarrow StringArray; codes int32."""
    import pyarrow as pa

    lib = get_lib()
    n = len(codes)
    if lib is None or len(dictionary) == 0:
        from blaze_tpu.exprs.hashing import hash_bytes_host

        vals = dictionary.to_pylist()
        for i in range(n):
            if validity is not None and not validity[i]:
                continue
            s = vals[int(codes[i])] if vals else ""
            b = s.encode("utf-8") if isinstance(s, str) else (s or b"")
            hashes[i] = np.uint32(hash_bytes_host(b, int(hashes[i])))
        return hashes
    d = dictionary
    if isinstance(d, pa.ChunkedArray):
        d = d.combine_chunks()
    d = d.cast(pa.utf8())
    if d.offset != 0:
        d = pa.concat_arrays([d])
    bufs = d.buffers()
    offsets = np.frombuffer(bufs[1], dtype=np.int32)[: len(d) + 1]
    data = (
        np.frombuffer(bufs[2], dtype=np.uint8)
        if bufs[2] is not None
        else np.zeros(1, dtype=np.uint8)
    )
    u8p = ctypes.POINTER(ctypes.c_uint8)
    validity_np = (
        np.ascontiguousarray(validity).astype(np.uint8)
        if validity is not None
        else None
    )
    lib.blz_murmur3_dict_strings_chain(
        _as(u8p, data),
        _as(ctypes.POINTER(ctypes.c_int32),
            np.ascontiguousarray(offsets)),
        _as(ctypes.POINTER(ctypes.c_int32),
            np.ascontiguousarray(codes.astype(np.int32))),
        _as(u8p, validity_np) if validity_np is not None else None,
        n,
        _as(ctypes.POINTER(ctypes.c_uint32), hashes),
    )
    return hashes


def pmod_np(hashes: np.ndarray, num_partitions: int) -> np.ndarray:
    lib = get_lib()
    n = len(hashes)
    out = np.empty(n, dtype=np.int32)
    if lib is None:
        h = hashes.view(np.int32)
        r = h % np.int32(num_partitions)
        return np.where(r < 0, r + num_partitions, r).astype(np.int32)
    lib.blz_pmod(
        _as(ctypes.POINTER(ctypes.c_uint32), hashes), n,
        num_partitions, _as(ctypes.POINTER(ctypes.c_int32), out),
    )
    return out


def shuffle_assemble(data_path: str, index_path: str,
                     partition_buffers, num_partitions: int,
                     spills=None) -> None:
    """Write the .data/.index pair from per-partition segment buffers plus
    spill files (reference shuffle_writer_exec.rs:437-506 format)."""
    spills = spills or []
    lib = get_lib()
    if lib is None:
        _shuffle_assemble_py(
            data_path, index_path, partition_buffers, num_partitions, spills
        )
        return
    blob = b"".join(partition_buffers)
    offs = np.zeros(num_partitions + 1, dtype=np.int64)
    pos = 0
    for i, b in enumerate(partition_buffers):
        offs[i] = pos
        pos += len(b)
    offs[num_partitions] = pos
    blob_np = (
        np.frombuffer(blob, dtype=np.uint8)
        if blob
        else np.zeros(1, dtype=np.uint8)
    )
    n_spills = len(spills)
    spill_paths = (ctypes.c_char_p * max(n_spills, 1))()
    spill_offs = np.zeros(
        (max(n_spills, 1), num_partitions + 1), dtype=np.int64
    )
    for i, (path, so) in enumerate(spills):
        spill_paths[i] = path.encode()
        spill_offs[i, :] = so
    rc = lib.blz_shuffle_assemble(
        data_path.encode(), index_path.encode(),
        _as(ctypes.POINTER(ctypes.c_uint8), blob_np),
        _as(ctypes.POINTER(ctypes.c_int64), offs),
        num_partitions, spill_paths, n_spills,
        _as(ctypes.POINTER(ctypes.c_int64),
            np.ascontiguousarray(spill_offs)),
    )
    if rc != 0:
        raise IOError(f"shuffle assemble failed: {rc}")


def _shuffle_assemble_py(data_path, index_path, partition_buffers,
                         num_partitions, spills):
    offsets = [0] * (num_partitions + 1)
    with open(data_path, "wb") as out:
        pos = 0
        for p in range(num_partitions):
            offsets[p] = pos
            # spills first, oldest first, then what was still in memory:
            # a partition's parts stay in the order they were written
            for path, so in spills:
                length = so[p + 1] - so[p]
                if length > 0:
                    with open(path, "rb") as f:
                        f.seek(so[p])
                        out.write(f.read(length))
                    pos += length
            buf = partition_buffers[p]
            out.write(buf)
            pos += len(buf)
        offsets[num_partitions] = pos
    with open(index_path, "wb") as idx:
        for off in offsets:
            idx.write(int(off).to_bytes(8, "little"))
