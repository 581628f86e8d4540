"""Seeded tables of a configuration, in memory and as parquet.

The configuration's file defines the data: `generator` names a module of
`perfbench/generators/` (found by name, as templates and layer metrics
are), and `data` is what that module reads: tables, columns with their
types, cardinalities, split sizes. A new table shape is a new generator
module and a configuration that names it; nothing here changes.

The in-memory frames are the plain reference's input and are always made
anew from the seed; the parquet files are what the serving child reads,
written once per (configuration, seed) under `perfbench/.data/` and found
again by later runs of the same checkout.
"""

from __future__ import annotations

import importlib
import json
import os
import shutil
from concurrent.futures import ThreadPoolExecutor

import numpy as np

KEEP_SEEDS = 4  # table sets kept per configuration, newest first


def gen_tables(data_cfg: dict, generator: str, seed: int) -> dict:
    """{table: [frame per split]}; see the generator for what a frame is."""
    module = importlib.import_module(f"perfbench.generators.{generator}")
    return module.generate(data_cfg, seed)


def arrow_type(t: str):
    import pyarrow as pa

    if t.startswith("decimal("):
        p, s = t[8:-1].split(",")
        return pa.decimal128(int(p), int(s))
    return {"int32": pa.int32(), "int64": pa.int64()}[t]


def to_arrow(frame: dict):
    """The frame as a pyarrow Table: decimals from their unscaled int64."""
    import pyarrow as pa

    arrays, n = [], frame["rows"]
    for name, t in frame["types"].items():
        v, valid = frame["values"][name], frame["valid"][name]
        mask = None if valid is None else ~valid
        if t.startswith("decimal("):
            wide = np.empty((n, 2), np.int64)
            wide[:, 0] = v
            wide[:, 1] = v >> 63
            bitmap = None if mask is None else pa.array(valid).buffers()[1]
            arrays.append(pa.Array.from_buffers(
                arrow_type(t), n, [bitmap, pa.py_buffer(wide)],
                null_count=-1 if mask is not None else 0))
        else:
            arrays.append(pa.array(v, type=arrow_type(t), mask=mask))
    return pa.table(arrays, names=list(frame["types"]))


def from_arrow(table, types: dict) -> dict:
    """A pyarrow Table (what a client fetched, or a shuffle file read
    back) as a frame's `values` and `valid`, decimals as unscaled int64.
    A column whose Arrow type is not the one `types` names is left out, so
    the comparison counts it as wrong."""
    import pyarrow as pa

    values, valid = {}, {}
    for name in table.column_names:
        col = table.column(name).combine_chunks()
        if name not in types or col.type != arrow_type(types[name]):
            continue
        ok = None
        if col.null_count:
            ok = np.asarray(col.is_valid())
        if pa.types.is_decimal(col.type):
            raw = np.frombuffer(col.buffers()[1], np.int64)
            v = raw[2 * col.offset:2 * (col.offset + len(col)):2].copy()
        else:
            v = col.fill_null(0).to_numpy(zero_copy_only=False)
        if ok is not None:
            v = np.where(ok, v, 0)  # what lies under a NULL is nobody's
        values[name], valid[name] = v, ok
    return {"rows": table.num_rows, "values": values, "valid": valid}


def _write(frame: dict, path: str, parquet_cfg: dict) -> None:
    import pyarrow.parquet as pq

    pq.write_table(to_arrow(frame), path, **parquet_cfg)


def ensure_parquet(data_root: str, config: dict, data_cfg: dict, seed: int,
                   tables: dict) -> dict:
    """{table: [path per split]} of (configuration, seed), writing the
    files if this checkout has not yet. A finished set is marked by
    `done.json`, so a run that was cut while writing is written again."""
    cfg_dir = os.path.join(data_root, config["name"])
    d = os.path.join(cfg_dir, f"seed_{seed}")
    mark = os.path.join(d, "done.json")
    want = json.dumps([config["generator"], data_cfg, config["parquet"]],
                      sort_keys=True)
    paths = {name: [os.path.join(d, f"{name}_{k:03d}.parquet")
                    for k in range(len(frames))]
             for name, frames in tables.items()}
    fresh = True
    if os.path.exists(mark):
        with open(mark) as f:
            fresh = f.read() != want
    if fresh:
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d)
        jobs = [(frame, p, config["parquet"])
                for name, frames in tables.items()
                for frame, p in zip(frames, paths[name])]
        with ThreadPoolExecutor(len(jobs)) as pool:
            list(pool.map(lambda job: _write(*job), jobs))
        with open(mark, "w") as f:
            f.write(want)
    os.utime(d)
    olds = sorted(
        (e for e in os.scandir(cfg_dir) if e.is_dir()),
        key=lambda e: e.stat().st_mtime, reverse=True,
    )
    for e in olds[KEEP_SEEDS:]:
        shutil.rmtree(e.path, ignore_errors=True)
    return paths
