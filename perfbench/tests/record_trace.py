#!/usr/bin/env python3
"""Record the small trace that `test_xplane.py` checks the reduction on.

    chiprun -- python3 perfbench/tests/record_trace.py chiprun_out/small

One process, which holds the chip itself: a few launches of a small jitted
sum and of the program's Pallas murmur3 kernel over 16,384 int64 keys,
under `jax.profiler` with the options the launcher uses, with a pause
between the launches so that the device is seen idle. Writes
`small.xplane.pb` and `small.json` (what was launched, how often) into the
directory given. Run again only when the runtime's trace format changes;
the recorded files are kept in `perfbench/tests/data/`.
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


def main(out_dir: str) -> int:
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from blaze_tpu.ops.kernels.murmur3_pallas import partition_ids_int64

    dev = jax.devices()[0]
    keys = jnp.asarray(np.arange(ROWS, dtype=np.int64) * 7919)
    vals = jnp.asarray(np.linspace(0, 1, ROWS * 64, dtype=np.float32))
    total = jax.jit(lambda v: jnp.sum(v * v))
    total(vals).block_until_ready()
    ids = np.asarray(partition_ids_int64(keys, 200))
    tmp = os.path.join(out_dir, "raw")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=options)
    for _ in range(LAUNCHES):
        total(vals).block_until_ready()
        time.sleep(0.02)
        partition_ids_int64(keys, 200).block_until_ready()
        time.sleep(0.02)
    jax.profiler.stop_trace()
    (pb,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    shutil.copy(pb, os.path.join(out_dir, "small.xplane.pb"))
    shutil.rmtree(tmp)
    with open(os.path.join(out_dir, "small.json"), "w") as f:
        json.dump({
            "device": {"platform": dev.platform, "kind": dev.device_kind},
            "launches": LAUNCHES, "rows": ROWS, "partitions": 200,
            "pause_s": 0.02,
            "first_ids": [int(x) for x in ids[:8]],
        }, f, indent=1)
    print(os.path.getsize(os.path.join(out_dir, "small.xplane.pb")),
          "bytes of trace")
    return 0 if dev.platform == "tpu" else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
