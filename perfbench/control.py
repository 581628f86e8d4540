#!/usr/bin/env python3
"""The control of a cell's comparison: the plain reference put in the
program's place, computed in the nearest precision below the one the
configuration states, has to come out as not correct.

    python3 perfbench/control.py --workload CELL --seeds 1,2,3 [--requests N]

For each seed: the configuration's tables, the first N requests the
cell's traffic would send, each answered by the template's `control`
(a guarantee of the configuration broken: a NULL's stored value let
through a filter, a NULL key hashed as 0, cents summed in float32) and compared with the reference by the same
`compare` and the same limits as a run's answers. Prints every reading
beside its limit, the smallest reading of each number over all seeds (the
upper reading a limit is set under), and exits 0 only if on every seed
the control failed at least one number. Needs no chip and starts no
server; the benchmark's own runs never call it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def control_readings(workload: str, seed: int, n_requests: int,
                     rehearse: bool = False) -> dict:
    """{number: [reading per request]} of the control on one seed."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from perfbench import datagen, run, traffic

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = run.Cell(json.load(f), workload, rehearse)
    frames = datagen.gen_tables(cell.data_cfg, cell.config["generator"],
                                seed)[cell.table]
    gen = traffic.Traffic(cell.traffic_spec, seed, len(frames))
    streams = [gen.stream(k) for k in range(gen.streams)]
    out = {}
    for i in range(n_requests):
        req = next(streams[i % len(streams)])
        tmpl = cell.template(req["template"])
        frame = frames[req["split"]]
        readings = tmpl.compare(tmpl.reference(frame, req["params"]),
                                tmpl.control(frame, req["params"]))
        for name, value in readings.items():
            out.setdefault(name, []).append((value, tmpl.LIMITS[name]))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    all_failed, least = True, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        readings = control_readings(args.workload, seed, args.requests,
                                    args.rehearse)
        failed = False
        for name, pairs in readings.items():
            values = [v for v, _ in pairs]
            limit = pairs[0][1]
            # a run's number is the worst over its answers, so the
            # control's is too
            worst = max(values)
            failed = failed or worst > limit
            least[name] = min(least.get(name, worst), worst)
            print(f"seed {seed} {name}: worst {worst:.6g} least "
                  f"{min(values):.6g} limit {limit:g} over {len(values)} "
                  "answers")
        print(f"seed {seed}: control "
              f"{'fails, as it must' if failed else 'PASSES'}")
        all_failed = all_failed and failed
    for name, value in least.items():
        print(f"upper reading {name}: {value:.6g}")
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
