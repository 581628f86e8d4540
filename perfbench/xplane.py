"""From a profiler trace (`.xplane.pb`) to device busy time, idle share,
per-operation time and named idle gaps.

Read with `jax.profiler.ProfileData`, which needs nothing but JAX. What
counts as a device plane, as a line of device operations and as a kernel
is looked up in `trace_patterns.json`. Busy time is the union of the
intervals in which an operation ran on the device, so overlapping
operations are not counted twice; the window is the span the trace itself
covers, first event to last, on the profiler's own clock.
"""

from __future__ import annotations

import json
import os
import re

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_patterns(path: str = os.path.join(HERE, "trace_patterns.json")):
    with open(path) as f:
        return json.load(f)


def union_seconds(starts: np.ndarray, ends: np.ndarray):
    """(total length, merged starts, merged ends) of a set of intervals."""
    if len(starts) == 0:
        return 0.0, starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.concatenate(([True], s[1:] > reach[:-1]))
    ms = s[new]
    me = np.concatenate((reach[:-1][new[1:]], reach[-1:]))
    return float((me - ms).sum()), ms, me


def _events(lines) -> tuple:
    names, starts, ends = [], [], []
    for line in lines:
        for ev in line.events:
            names.append(ev.name)
            starts.append(ev.start_ns)
            ends.append(ev.start_ns + ev.duration_ns)
    return (np.array(names, dtype=object),
            np.array(starts, dtype=np.float64) / 1e9,
            np.array(ends, dtype=np.float64) / 1e9)


def read_planes(path: str, patterns: dict) -> dict:
    """{"devices": {plane: {"ops": events, "lines": {line: events}}},
    "host": events}; events are (names, starts, ends) with times in
    seconds on the profiler's clock. "ops" joins the lines of device
    operations, "lines" keeps every line a kernel pattern asks for."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    dev_re = re.compile(patterns["device_plane"])
    host_re = re.compile(patterns["host_plane"])
    op_res = [re.compile(p) for p in patterns["op_lines"]]
    kernel_lines = [re.compile(k["line"])
                    for k in patterns["kernels"].values()]
    devices, host_lines = {}, []
    for plane in data.planes:
        if dev_re.search(plane.name):
            lines = list(plane.lines)
            devices[plane.name] = {
                "ops": _events(ln for ln in lines
                               if any(r.search(ln.name) for r in op_res)),
                "lines": {ln.name: _events([ln]) for ln in lines
                          if any(r.search(ln.name) for r in kernel_lines)},
            }
        elif host_re.search(plane.name):
            host_lines.extend(plane.lines)
    return {"devices": devices, "host": _events(host_lines)}


def name_gaps(ga: np.ndarray, gb: np.ndarray, host) -> np.ndarray:
    """What the host was doing in each idle gap: the name of the shortest
    host span that covers the gap's middle. Spans are painted over the
    gaps' middles longest first, so the shortest stays on top."""
    names, hs, he = host
    out = np.full(len(ga), "unnamed: no host span on the profiler's clock",
                  dtype=object)
    if len(ga) == 0 or len(names) == 0:
        return out
    mid = 0.5 * (ga + gb)
    order = np.argsort(mid, kind="stable")
    smid = mid[order]
    painted = np.full(len(ga), -1, dtype=np.int64)
    lo = np.searchsorted(smid, hs, side="left")
    hi = np.searchsorted(smid, he, side="right")
    for k in np.argsort(-(he - hs), kind="stable"):
        if hi[k] > lo[k]:
            painted[lo[k]:hi[k]] = k
    got = painted >= 0
    out[order[got]] = names[painted[got]]
    return out


def short(name: str, limit: int = 100) -> str:
    """An operation's name as the trace gives it can be a whole HLO
    instruction: keep its head."""
    name = str(name)
    return name if len(name) <= limit else name[:limit - 3] + "..."


def reduce_planes(planes: dict, patterns: dict) -> dict:
    """The numbers the per-layer readers and the result line take."""
    devices, host = planes["devices"], planes["host"]
    if not devices:
        return {"devices": 0}
    all_ops = [d["ops"] for d in devices.values()]
    lo = min([s.min() for _, s, _ in all_ops if len(s)]
             + ([host[1].min()] if len(host[1]) else []), default=0.0)
    hi = max([e.max() for _, _, e in all_ops if len(e)]
             + ([host[2].max()] if len(host[2]) else []), default=0.0)
    window = hi - lo
    busy, by_name, gaps = [], {}, []
    kernels = {k: 0.0 for k in patterns["kernels"]}
    kernel_events = {k: 0 for k in patterns["kernels"]}
    launches = {}
    for dev in devices.values():
        names, starts, ends = dev["ops"]
        total, ms, me = union_seconds(starts, ends)
        busy.append(total)
        if len(names):
            uniq, inv = np.unique(names.astype(str), return_inverse=True)
            sums = np.bincount(inv, weights=ends - starts,
                               minlength=len(uniq))
            for n, s in zip(uniq, sums):
                by_name[n] = by_name.get(n, 0.0) + float(s)
        for kn, _, _ in dev["lines"].values():
            for n in kn:
                n = str(n).split("(")[0]
                launches[n] = launches.get(n, 0) + 1
        for key, pat in patterns["kernels"].items():
            for line, (kn, ks, ke) in dev["lines"].items():
                if not re.search(pat["line"], line):
                    continue
                hit = np.array([bool(re.search(pat["name"], str(n)))
                                for n in kn], dtype=bool)
                kernels[key] += float((ke - ks)[hit].sum())
                kernel_events[key] += int(hit.sum())
        edges_a = np.concatenate(([lo], me))
        edges_b = np.concatenate((ms, [hi]))
        keep = edges_b > edges_a
        gaps.extend(zip(edges_a[keep], edges_b[keep]))
    n_dev = len(devices)
    ga = np.array([g[0] for g in gaps], dtype=np.float64)
    gb = np.array([g[1] for g in gaps], dtype=np.float64)
    gap_by = {}
    for name, length in zip(name_gaps(ga, gb, host), gb - ga):
        name = short(name)
        gap_by[name] = gap_by.get(name, 0.0) + float(length) / n_dev
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    return {
        "devices": n_dev,
        "busy_s": float(np.mean(busy)),
        "window_s": float(window),
        "idle_share": 1.0 - float(np.mean(busy)) / window if window else None,
        "op_events": int(sum(len(s) for _, s, _ in all_ops)),
        "device_ops": [[short(n), s / n_dev] for n, s in top],
        "idle_gaps": [[n, s] for n, s in sorted(
            gap_by.items(), key=lambda kv: -kv[1])[:10]],
        "kernel_s": {k: v / n_dev for k, v in kernels.items()},
        "kernel_events": kernel_events,
        "launches": dict(sorted(launches.items(),
                                key=lambda kv: -kv[1])[:12]),
        "longest_gap_s": float((gb - ga).max()) if len(ga) else 0.0,
    }


def reduce_file(path: str, patterns: dict | None = None) -> dict:
    patterns = patterns or load_patterns()
    return reduce_planes(read_planes(path, patterns), patterns)
