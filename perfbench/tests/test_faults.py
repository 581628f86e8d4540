"""The harness with the timed path broken underneath has to say `correct`
false. Each case drives a whole run (serving child, warm-up, window,
comparison) on the CPU at the configuration's rehearsal size. The driver
process replaces the harness's look for a chip (`run.chip_found`), and the
serving child finds the fault planted in the program by a `sitecustomize`
on its PYTHONPATH: an answer altered where it is produced. No option or
parameter of the benchmark's own code is there for this. The other faults
the builder's list names (a step that returns its state unchanged, half of
a batch left out, the exchange between chips left out) have no counterpart
in a one-chip query engine's cells. A sound run of the same cell comes out
correct, so the false is the fault's doing.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

FAULTS = {
    # the read-back of every result: the first key of a frame is one more
    "key_altered": """
        import pyarrow as pa, pyarrow.compute as pc
        from blaze_tpu import batch
        sound = batch.ColumnBatch.to_arrow
        def to_arrow(self):
            rb = sound(self)
            if not rb.num_rows or not pa.types.is_int32(rb.column(0).type):
                return rb
            first = rb.column(0).to_pylist()
            first[0] = (first[0] or 0) + 1
            cols = [pa.array(first, pa.int32())] + rb.columns[1:]
            return pa.RecordBatch.from_arrays(cols, schema=rb.schema)
        batch.ColumnBatch.to_arrow = to_arrow
    """,
    # the partition of the first row of every batch: one over
    "row_misplaced": """
        import numpy as np
        from blaze_tpu.ops import shuffle_writer
        sound = shuffle_writer.spark_partition_ids
        def ids(cb, key_exprs, n):
            out = np.array(sound(cb, key_exprs, n))
            out[0] = (out[0] + 1) % n
            return out
        shuffle_writer.spark_partition_ids = ids
    """,
}

CASES = [
    ("q6_scan.s4", None, True),
    ("q6_scan.s4", "key_altered", False),
    ("q1_group.s4", None, True),
    ("q1_group.s4", "key_altered", False),
    ("repart200.s4", None, True),
    ("repart200.s4", "row_misplaced", False),
]


@pytest.mark.parametrize("cell, fault, want", CASES)
def test_fault_is_seen(tmp_path, cell, fault, want):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    if fault:
        # only the serving child has the program on its path when Python
        # starts, so only there is the fault planted
        with open(tmp_path / "sitecustomize.py", "w") as f:
            f.write("try:\n    import blaze_tpu\nexcept ImportError:\n"
                    "    blaze_tpu = None\nif blaze_tpu:\n"
                    + textwrap.indent(textwrap.dedent(FAULTS[fault]),
                                      "    "))
        env["PYTHONPATH"] = str(tmp_path)
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {ROOT!r})
        from perfbench import run
        run.chip_found = lambda device, cell: True
        sys.exit(run.main(["--workload", {cell!r}, "--seed", "2147483659",
                           "--seconds", "3", "--trace", "0", "--rehearse"]))
    """)
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is want, done.stderr[-3000:]
    assert (done.returncode == 0) is want
    assert result["attempted"] > 0 and result["failed"] == 0
    over = [k for k, v in result["compared"].items()
            if isinstance(v, dict) and v["value"] > v["limit"]]
    assert bool(over) is not want, result["compared"]
