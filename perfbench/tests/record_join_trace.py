#!/usr/bin/env python3
"""Record the small trace that `test_q3_join_cell.py` checks the join's
roofline reader on.

    python3 perfbench/tests/record_join_trace.py OUT_DIR

Run on a machine with a TPU, in one process, which holds the chip
itself: a broadcast relation of 6,000 `int` keys indexed once, then a
16,384-row probe batch joined against it a few times through `ops/joins.py: _JoinCore` (the core `auto` takes on
the chip: the counting program `join_probe`, the pair count read back,
the emission program `join_emit`), under `jax.profiler` with the options
the launcher uses and a pause between joins. Writes
`join_small.xplane.pb` and `join_small.json` (what was launched, how
often) into the directory given. Run again only when the runtime's trace
format or the join's program names change; the recorded files are kept
in `perfbench/tests/data/`.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import time

LAUNCHES = 5
ROWS = 16384
BUILD_ROWS = 6000


def main(out_dir: str) -> int:
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    import jax
    import numpy as np
    import pyarrow as pa

    from blaze_tpu.batch import ColumnBatch
    from blaze_tpu.ops.joins import _join_core_choice, _JoinCore

    dev = jax.devices()[0]
    rng = np.random.default_rng(37)
    keys = np.arange(BUILD_ROWS, dtype=np.int32) * 3
    build = ColumnBatch.from_arrow(pa.record_batch({
        "k": pa.array(keys), "v": pa.array(keys + 1)}))
    probe = ColumnBatch.from_arrow(pa.record_batch({
        "p": pa.array(rng.integers(0, 3 * BUILD_ROWS, ROWS)
                      .astype(np.int32))}))
    core = _JoinCore(build, [0])
    core.index_build()

    def join():
        state = core.probe(probe, [0])
        _cols, valid, _cap, _ = core.emit_pairs(
            state, list(build.columns), list(state[1].columns), True)
        return int(np.asarray(valid).sum())

    pairs = join()  # compiles
    tmp = os.path.join(out_dir, "raw")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=options)
    for _ in range(LAUNCHES):
        join()
        time.sleep(0.02)
    jax.profiler.stop_trace()
    (pb,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    shutil.copy(pb, os.path.join(out_dir, "join_small.xplane.pb"))
    shutil.rmtree(tmp)
    with open(os.path.join(out_dir, "join_small.json"), "w") as f:
        json.dump({
            "device": {"platform": dev.platform, "kind": dev.device_kind},
            "core": _join_core_choice(), "launches": LAUNCHES,
            "rows": ROWS, "build_rows": BUILD_ROWS, "pairs": pairs,
            "pause_s": 0.02,
        }, f, indent=1)
    print(os.path.getsize(os.path.join(out_dir, "join_small.xplane.pb")),
          "bytes of trace")
    return 0 if dev.platform == "tpu" else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
