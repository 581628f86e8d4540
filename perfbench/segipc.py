"""Reader of the shuffle files a `ShuffleWriterExec` task leaves behind.

The benchmark's own, written from the format's description and not from
the program's reader (`blaze_tpu/io/ipc.py`):

    index := (partitions + 1) little-endian int64 start offsets
    data  := one segment per partition, concatenated
    segment := part*, part := [u64 LE length][zstd(Arrow IPC stream)]

Zero-length parts are skipped, as the Spark-side reader does.
"""

from __future__ import annotations

import struct

import numpy as np
import pyarrow as pa
import zstandard


def read_index(path: str) -> np.ndarray:
    with open(path, "rb") as f:
        raw = f.read()
    if len(raw) % 8:
        raise ValueError(f"{path}: {len(raw)} bytes is not a whole number "
                         "of int64 offsets")
    return np.frombuffer(raw, dtype="<i8")


def read_segment(buf: memoryview) -> list:
    """RecordBatches of one partition's segment."""
    out, pos, n = [], 0, len(buf)
    dctx = zstandard.ZstdDecompressor()
    while pos + 8 <= n:
        (length,) = struct.unpack_from("<Q", buf, pos)
        pos += 8
        if length == 0:
            continue
        if pos + length > n:
            raise ValueError("part runs past the end of its segment")
        raw = dctx.stream_reader(bytes(buf[pos:pos + length])).read()
        pos += length
        with pa.ipc.open_stream(raw) as reader:
            out.extend(rb for rb in reader if rb.num_rows)
    if pos != n:
        raise ValueError(f"{n - pos} stray bytes at the end of a segment")
    return out


def read_partitions(data_path: str, index_path: str) -> list:
    """One pyarrow Table (or None, when empty) per partition."""
    offs = read_index(index_path)
    with open(data_path, "rb") as f:
        data = memoryview(f.read())
    if len(offs) < 2 or offs[0] != 0 or offs[-1] != len(data) \
            or np.any(np.diff(offs) < 0):
        raise ValueError(f"{index_path}: offsets do not tile {data_path}")
    tables = []
    for a, b in zip(offs[:-1], offs[1:]):
        rbs = read_segment(data[a:b])
        tables.append(pa.Table.from_batches(rbs) if rbs else None)
    return tables
