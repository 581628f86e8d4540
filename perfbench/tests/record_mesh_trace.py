#!/usr/bin/env python3
"""Record the small four-device trace that `test_mesh_readers.py` checks
the mesh readers on.

    chiprun --chips 4 -- python3 perfbench/tests/record_mesh_trace.py \
        chiprun_out/mesh_small

One process, which holds the four chips itself: a few launches of the
program's mesh group-by (`parallel/sharded.py: DistributedGroupBy`, two
nullable `int` keys and a nullable `bigint` sum, 4,096 rows a device)
under `jax.profiler` with the options the launcher uses, with a pause
between the launches so that the devices are seen idle. Writes
`mesh_small.xplane.pb` and `mesh_small.json` (what was launched, how
often, the groups that came out) into the directory given. Run again only
when the runtime's trace format or the program's name changes; the
recorded files are kept in `perfbench/tests/data/`.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import time

LAUNCHES = 3
CAP = 4096


def main(out_dir: str) -> int:
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    import jax
    import numpy as np

    from blaze_tpu.exprs import Col
    from blaze_tpu.exprs.ir import AggFn
    from blaze_tpu.parallel.mesh import get_mesh
    from blaze_tpu.parallel.mesh_exec import to_mesh
    from blaze_tpu.parallel.sharded import DistAgg, DistributedGroupBy
    from blaze_tpu.types import DataType, Field, Schema

    n_dev = len(jax.devices())
    mesh = get_mesh((n_dev,))
    rng = np.random.default_rng(33)
    schema = Schema([Field("c", DataType.int32(), True),
                     Field("s", DataType.int32(), True),
                     Field("a", DataType.int64(), True)])
    gb = DistributedGroupBy(
        mesh, schema, keys=[Col("c"), Col("s")],
        aggs=[DistAgg(AggFn.SUM, Col("a"))], slack=1.5)
    shape = (n_dev, CAP)
    cols = [rng.integers(0, 3000, shape).astype(np.int32),
            rng.integers(0, 7, shape).astype(np.int32),
            rng.integers(0, 10**6, shape).astype(np.int64)]
    valids = [rng.random(shape) > 0.045 for _ in cols]
    rows = np.full(n_dev, CAP, np.int32)
    args = ([to_mesh(c, mesh) for c in cols], to_mesh(rows, mesh),
            [to_mesh(v, mesh) for v in valids])
    first = jax.device_get(gb.run(*args))
    tmp = os.path.join(out_dir, "raw")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=options)
    for _ in range(LAUNCHES):
        jax.block_until_ready(gb.run(*args))
        time.sleep(0.02)
    jax.profiler.stop_trace()
    (pb,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    shutil.copy(pb, os.path.join(out_dir, "mesh_small.xplane.pb"))
    shutil.rmtree(tmp)
    dev = jax.devices()[0]
    with open(os.path.join(out_dir, "mesh_small.json"), "w") as f:
        json.dump({
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": n_dev},
            "launches": LAUNCHES, "rows_a_device": CAP,
            "groups": [int(n) for n in first.counts],
            "overflow": bool(first.overflow.any()),
            "jax": jax.__version__,
        }, f, indent=1)
    print(json.dumps({"ok": True, "bytes": os.path.getsize(
        os.path.join(out_dir, "mesh_small.xplane.pb"))}))
    return 0


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    sys.exit(main(sys.argv[1]))
