"""The one traffic generator: a closed loop of `streams` clients, each
playing blocks of requests described by a data file under `traffic/`.

A block lists entries with counts; every stream plays the block over and
over, each time in an order drawn from the seed, so every seed sends the
same set of work in another order. An entry is a template with parameter
draws of its own (`fixed`, or `uniform` / `integers` over a range).

`input` says what a request scans: `next_split` is a task, the next split
of the stage's `table`, in an order drawn from the seed, under a path of
its own.
"""

from __future__ import annotations

import itertools
import json
import os
import threading

import numpy as np


def load(path: str) -> dict:
    with open(path) as f:
        spec = json.load(f)
    if spec.get("loop") != "closed":
        raise ValueError(f"{path}: only closed loops are generated here")
    return spec


def _draw(rng, params: dict) -> dict:
    out = {}
    for name, how in params.items():
        if "fixed" in how:
            out[name] = how["fixed"]
        elif "uniform" in how:
            lo, hi = how["uniform"]
            out[name] = float(rng.uniform(lo, hi))
        elif "integers" in how:
            lo, hi = how["integers"]
            out[name] = int(rng.integers(lo, hi + 1))
        else:
            raise ValueError(f"parameter {name}: no draw in {how}")
    return out


class Traffic:
    def __init__(self, spec: dict, seed: int, n_splits: int):
        self.spec = spec
        self.seed = int(seed)
        self.streams = int(spec["streams"])
        self.n_splits = int(n_splits)
        if spec["input"] != "next_split":
            raise ValueError(f"input {spec['input']!r}")
        self._tasks = itertools.count()
        self._lock = threading.Lock()
        self._orders = {}
        self._warm_rng = np.random.default_rng([self.seed, 0x3A93])

    def _next_task(self):
        """(task number, split): tasks walk the splits in rounds, each
        round in an order of its own drawn from the seed."""
        with self._lock:
            task = next(self._tasks)
            rnd, k = divmod(task, self.n_splits)
            if rnd not in self._orders:
                self._orders[rnd] = np.random.default_rng(
                    [self.seed, 0x5917, rnd]).permutation(self.n_splits)
            return task, int(self._orders[rnd][k])

    def _request(self, rng, entry: dict) -> dict:
        task, split = self._next_task()
        return {"template": entry["template"],
                "params": _draw(rng, entry["params"]),
                "split": split, "task": task}

    def _play(self, rng):
        block = [e for e in self.spec["block"]
                 for _ in range(int(e.get("count", 1)))]
        while True:
            for k in rng.permutation(len(block)):
                yield self._request(rng, block[int(k)])

    def stream(self, k: int):
        """Requests of client `k`, without end."""
        return self._play(np.random.default_rng([self.seed, 0x57AE, k]))

    def entries(self) -> list:
        return list(self.spec["block"])

    def warm_request(self, entry: dict) -> dict:
        """A request like the window's, from draws the window never
        makes."""
        return self._request(self._warm_rng, entry)


def path_of(directory: str, name: str) -> str:
    return os.path.join(directory, "traffic", name + ".json")
