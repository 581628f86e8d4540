"""Host-tier shuffle writer: same files, no device.

Reference counterpart: the JVM fallback row-shuffle writers
(ArrowShuffleWriter301.java:74, ArrowBypassMergeSortShuffleWriter301.
java:81) - when a shuffle's input was never native, rows are serialized
host-side into the SAME segmented-IPC `.data`/`.index` format the native
writer produces, so the read side never knows which tier wrote a block.
This module is that second producer: pyarrow batches in, bit-exact
Spark murmur3/pmod partition ids computed with the numpy/C++ host
hashing tier (no HBM touch), rows staged per partition and frozen to
zstd IPC parts through the shared PartitionBuffers ladder.

Used by host-fallback subtrees feeding an exchange, and as the format
witness: tests assert host-written and device-written shuffles are
interchangeable under the native readers.
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Sequence

import numpy as np
import pyarrow as pa

from blaze_tpu.config import get_config
from blaze_tpu.types import from_arrow_schema
from blaze_tpu.io.ipc import encode_ipc_segment
from blaze_tpu.ops.shuffle_writer import PartitionBuffers, _chain_fixed
from blaze_tpu.runtime import native


def host_partition_ids(rb: pa.RecordBatch,
                       key_names: Sequence[str],
                       num_partitions: int) -> np.ndarray:
    """Bit-exact Spark murmur3(seed 42)/pmod ids for one host batch -
    the same chain the device/C++ tiers compute (spark_hash.rs:221
    semantics), evaluated with numpy + the C++ string kernel only."""
    schema = from_arrow_schema(rb.schema)
    h = np.full(rb.num_rows, 42, dtype=np.uint32)
    for name in key_names:
        idx = rb.schema.get_field_index(name)
        col = rb.column(idx)
        dt = schema.fields[idx].dtype
        if pa.types.is_dictionary(col.type):
            col = col.cast(col.type.value_type)
        if pa.types.is_string(col.type) or pa.types.is_large_string(
            col.type
        ):
            h = native.murmur3_strings_chain(col, h)
        else:
            validity = (
                np.asarray(col.is_valid())
                if col.null_count else None
            )
            vals = col.to_numpy(zero_copy_only=False)
            h = _chain_fixed(vals, validity, dt, h)
    return native.pmod_np(h, num_partitions)


def host_shuffle_write(batches: Iterable[pa.RecordBatch],
                       key_names: Sequence[str],
                       num_partitions: int,
                       data_file: str,
                       index_file: str,
                       spill_dir: Optional[str] = None,
                       compression_level: int = 1) -> List[int]:
    """Hash-partition host batches and write one map output in the
    shared shuffle format. Returns per-partition byte lengths (what the
    index file records; the reference's writeIndexFileAndCommit input,
    ArrowShuffleExchangeExec301.scala:572-585)."""
    import tempfile

    bufs = PartitionBuffers(
        num_partitions, spill_dir or tempfile.gettempdir(),
        get_config().batch_size, compression_level,
    )
    for rb in batches:
        if rb.num_rows == 0:
            continue
        if num_partitions == 1:
            bufs.append(0, encode_ipc_segment(rb, compression_level))
            continue
        pids = host_partition_ids(rb, key_names, num_partitions)
        order = np.argsort(pids, kind="stable")
        rb_sorted = rb.take(pa.array(order))
        bufs.stage(
            rb_sorted, np.bincount(pids, minlength=num_partitions)
        )
    return bufs.finalize(data_file, index_file)
