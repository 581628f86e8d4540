"""Mesh stage anatomy: sub-phase attribution for the mesh dispatch
overhead.

BENCH_r10 measured `mesh_groupby_d8` at 6.57 s against 0.11 s for the
same 1M rows single-device - a ~60x per-stage overhead - and the
ROADMAP's multi-host tier (open item 2) is explicitly gated on saying
WHERE that time goes. Until this module, the whole stage was one
opaque `mesh_execute` span: host staging, single-flight serialization,
program re-trace, launch, and result fetch were indistinguishable.
Flare (PAPERS.md) lives or dies on where compilation cost lands
relative to execution; Data Path Fusion on host<->device movement
dominating analytical dispatch - this is the instrument that separates
those hypotheses for the mesh tier.

Every mesh stage is split into named sub-phases:

  mesh_lower     the planner pass (lower_plan_to_mesh) that decided to
                 lower this op - recorded at plan time, replayed into
                 the stage's span tree
  mesh_trace     jit/shard_map trace + XLA compile (AOT lower+compile
                 where the installed jax supports it; otherwise the
                 first launch folds the trace and this phase is ~0)
  mesh_stage_in  stack_partitions: host materialize + pad/stack +
                 device_put, with bytes staged
  mesh_launch    the compiled program call (the chaos `mesh.exchange`
                 seam fires at the top of this phase, so an injected
                 STALL lands here - it models exchange-fabric latency)
  mesh_sync      block_until_ready on the program outputs
  mesh_gather    the batched device_get at the mesh boundary

Design points (the trace.ACTIVE / chaos.ACTIVE discipline, adapted):

  * The sub-phase ROLLUP is ALWAYS ON, like the dispatch counters: a
    mesh stage is milliseconds-to-seconds of work and the cost here is
    a dozen monotonic clock reads, so there is no armed/off mode to
    keep byte-identical - the timing code is pure host control flow
    and cannot dispatch by construction
    (tests/test_dispatch_budget.py pins the budgets anyway).
  * Span emission stays gated on `trace.ACTIVE` + a live recorder:
    sub-phases land as child spans under `mesh_execute` on their own
    synthetic tid (validate_chrome-clean - they are sequential, so the
    per-track nesting sweep sees well-formed B/E pairs).
  * Re-trace detection is a process-wide seen-key registry:
    `note_trace` increments `blaze_mesh_trace_total{op}` on the first
    trace of a logical program and `blaze_mesh_retrace_total{op}` when
    the SAME logical program (op kind + structural expressions + arg
    signature) is traced again from a fresh op instance - the silent
    cache-key churn ISSUE 19 calls the likeliest hidden chunk of the
    60x. A warm repeat on one instance reuses its executable and
    records neither (the warm-repeat pin).
  * Bounded memory: at most `_MAX_OPS` op classes, fixed ring sizes,
    a capped trace-key registry.

Surfaces: `snapshot()` is the `meshprof` STATS section on both tiers;
a registered METRICS collector renders
`blaze_mesh_subphase_seconds_{sum,count}{op,subphase}` plus the stage
wall; `python -m blaze_tpu mesh-attr` drives `run_attr_probe` at d1
vs dN in fresh subprocesses and emits the versioned MESHATTR_r*.json
artifact whose sub-phase p50s must reconcile to the measured stage
wall (`build_doc` computes the gap attribution and the written
verdict ROADMAP item 2 needs).
"""

from __future__ import annotations

import collections
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, Hashable, List, Optional, Tuple

# canonical sub-phase order (artifact + rendering stability).
SUBPHASES = (
    "mesh_lower",     # planner pass (outside the stage wall)
    "mesh_trace",
    "mesh_stage_in",
    "mesh_launch",
    "mesh_sync",
    "mesh_dcn",       # fleet tier: DCN exchange round trips
    "mesh_gather",
)

# the sub-phases INSIDE the stage wall (stage_in start -> gather end):
# these are what must reconcile - sum to the measured wall within
# tolerance. mesh_lower happens at plan time, before the wall opens.
STAGE_SUBPHASES = (
    "mesh_trace", "mesh_stage_in", "mesh_launch", "mesh_sync",
    "mesh_dcn", "mesh_gather",
)

_MAX_OPS = 16
_SAMPLES = 128
_MAX_TRACE_KEYS = 4096

# synthetic tid for the sub-phase track in exported traces (the mesh
# stage track is 999, per-device tracks 1000+; see parallel/mesh_exec)
MESH_SUB_TID = 998


class MeshStageRollup:
    """Bounded per-(op, sub-phase) duration rings + stage-wall ring +
    bytes-staged totals. Thread-safe; observed at stage end, never on
    a per-row path."""

    def __init__(self, max_ops: int = _MAX_OPS,
                 samples: int = _SAMPLES):
        self.max_ops = int(max_ops)
        self.samples = int(samples)
        self._lock = threading.Lock()
        # op -> {"wall": deque, "bytes": int, "stages": int,
        #        "sub": {subphase: deque}}
        self._ops: "collections.OrderedDict[str, Dict[str, Any]]" = (
            collections.OrderedDict()
        )

    def _slot(self, op: str) -> Dict[str, Any]:
        slot = self._ops.get(op)
        if slot is None:
            slot = self._ops[op] = {
                "wall": collections.deque(maxlen=self.samples),
                "bytes": 0, "stages": 0, "sub": {},
            }
            while len(self._ops) > self.max_ops:
                self._ops.popitem(last=False)
        self._ops.move_to_end(op)
        return slot

    def observe_stage(self, op: str, wall_s: float,
                      phases: List[Tuple[str, float, float]],
                      nbytes: int = 0) -> None:
        """Fold one finished mesh stage: its wall, each sub-phase
        duration, and the bytes staged in."""
        with self._lock:
            slot = self._slot(op)
            slot["wall"].append(float(wall_s))
            slot["bytes"] += int(nbytes)
            slot["stages"] += 1
            for name, p0, p1 in phases:
                if p1 < p0:
                    continue
                dq = slot["sub"].get(name)
                if dq is None:
                    dq = slot["sub"][name] = collections.deque(
                        maxlen=self.samples
                    )
                dq.append(p1 - p0)

    @staticmethod
    def _stats(xs: List[float]) -> Dict[str, Any]:
        xs = sorted(xs)

        def pct(q: float) -> float:
            idx = min(len(xs) - 1,
                      max(0, int(round(q * (len(xs) - 1)))))
            return xs[idx]

        return {
            "n": len(xs),
            "p50": round(pct(0.5), 6),
            "p95": round(pct(0.95), 6),
            "mean": round(sum(xs) / len(xs), 6),
        }

    def snapshot(self) -> Dict[str, Any]:
        """{op: {stages, bytes_staged, stage_wall: {n,p50,p95,mean},
        subphases: {name: {...}}}} - the `meshprof` STATS section and
        the attr-probe measurement form. Empty dict when no mesh
        stage ran."""
        with self._lock:
            ops = {
                op: {
                    "wall": list(slot["wall"]),
                    "bytes": slot["bytes"],
                    "stages": slot["stages"],
                    "sub": {n: list(dq)
                            for n, dq in slot["sub"].items() if dq},
                }
                for op, slot in self._ops.items()
            }
        out: Dict[str, Any] = {}
        for op, slot in ops.items():
            entry: Dict[str, Any] = {
                "stages": slot["stages"],
                "bytes_staged": slot["bytes"],
            }
            if slot["wall"]:
                entry["stage_wall"] = self._stats(slot["wall"])
            subs = {}
            for name in SUBPHASES:
                xs = slot["sub"].get(name)
                if xs:
                    subs[name] = self._stats(xs)
            if subs:
                entry["subphases"] = subs
            out[op] = entry
        return out

    def metrics_samples(self):
        """Prometheus samples: per-(op, subphase) seconds sum/count
        plus the stage wall - the METRICS-tier rendering of the same
        rings (collector surface: the stage hot path never touches
        the registry lock)."""
        with self._lock:
            ops = {
                op: {
                    "wall": list(slot["wall"]),
                    "sub": {n: list(dq)
                            for n, dq in slot["sub"].items()},
                }
                for op, slot in self._ops.items()
            }
        for op, slot in ops.items():
            if slot["wall"]:
                yield ("blaze_mesh_stage_wall_seconds_sum",
                       {"op": op}, round(sum(slot["wall"]), 6),
                       "counter")
                yield ("blaze_mesh_stage_wall_seconds_count",
                       {"op": op}, len(slot["wall"]), "counter")
            for name, xs in slot["sub"].items():
                if not xs:
                    continue
                yield ("blaze_mesh_subphase_seconds_sum",
                       {"op": op, "subphase": name},
                       round(sum(xs), 6), "counter")
                yield ("blaze_mesh_subphase_seconds_count",
                       {"op": op, "subphase": name}, len(xs),
                       "counter")

    def _reset_for_tests(self) -> None:
        with self._lock:
            self._ops.clear()


# the process-wide rollup every mesh stage folds into (swappable via
# capture() for probe/bench measurement windows)
ROLLUP = MeshStageRollup()


@contextmanager
def capture():
    """Route stage folds into a PRIVATE rollup for the duration (the
    attr probe's and bench's measurement window), so a probe inside a
    live process neither pollutes nor reads production rollup state.
    Not re-entrant across threads: the swap is module-global."""
    global ROLLUP
    prev = ROLLUP
    ROLLUP = MeshStageRollup()
    try:
        yield ROLLUP
    finally:
        ROLLUP = prev


def _collector():
    return ROLLUP.metrics_samples()


def _register_collector() -> None:
    # keyed + idempotent, re-asserted on every stage finish: the test
    # registry reset clears collectors, and a stage is seconds of work
    # against one dict set
    from blaze_tpu.obs.metrics import REGISTRY

    REGISTRY.register_collector("meshprof", _collector)


# ---------------------------------------------------------------------------
# re-trace detection: first-trace vs avoidable re-trace
# ---------------------------------------------------------------------------

_trace_keys: set = set()
_tk_lock = threading.Lock()


def note_trace(op: str, key: Hashable) -> bool:
    """Record that `op`'s program was traced+compiled under logical
    identity `key` (op kind + structural expression trees + argument
    shape/dtype signature). Returns True - and increments
    `blaze_mesh_retrace_total{op}` - when this process already traced
    that identity (an AVOIDABLE re-trace: a fresh op instance re-paid
    compilation for a program the process had already built, i.e.
    cache-key churn). First traces count `blaze_mesh_trace_total{op}`.
    Call ONLY when a trace actually ran - a warm executable reuse
    records neither, which is exactly the warm-repeat delta-0 pin."""
    from blaze_tpu.obs.metrics import REGISTRY

    with _tk_lock:
        retrace = key in _trace_keys
        if not retrace:
            if len(_trace_keys) >= _MAX_TRACE_KEYS:
                _trace_keys.clear()  # bounded; worst case undercounts
            _trace_keys.add(key)
    REGISTRY.inc("blaze_mesh_trace_total", op=op)
    if retrace:
        REGISTRY.inc("blaze_mesh_retrace_total", op=op)
    return retrace


def arg_signature(*arrays) -> Tuple:
    """(shape, dtype) signature over a flat sequence of arrays (lists
    flatten one level) - the shape half of a trace key."""
    sig = []
    for a in arrays:
        if isinstance(a, (list, tuple)):
            sig.extend((tuple(x.shape), str(x.dtype)) for x in a)
        else:
            sig.append((tuple(a.shape), str(a.dtype)))
    return tuple(sig)


# ---------------------------------------------------------------------------
# the per-stage stopwatch
# ---------------------------------------------------------------------------


class _PhaseCtx:
    __slots__ = ("_stage", "_name", "_t0")

    def __init__(self, stage: "MeshStage", name: str):
        self._stage = stage
        self._name = name

    def __enter__(self):
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        self._stage.phases.append(
            (self._name, self._t0, time.monotonic())
        )
        return False


class MeshStage:
    """One mesh stage's sub-phase stopwatch. Always-on (see module
    docstring); `finish()` folds into the process rollup, span
    emission happens in mesh_exec.record_mesh_run where the tracer
    lives. The planner's mesh_lower window (stamped on the lowered op
    by lower_plan_to_mesh) replays into the phase list so it lands in
    the same span tree and rollup."""

    __slots__ = ("op", "n_dev", "t0", "t1", "phases", "bytes_staged")

    def __init__(self, op: str, n_dev: int,
                 lower_window: Optional[Tuple[float, float]] = None):
        self.op = op
        self.n_dev = n_dev
        self.t0 = time.monotonic()
        self.t1: Optional[float] = None
        self.phases: List[Tuple[str, float, float]] = []
        self.bytes_staged = 0
        if lower_window is not None:
            self.phases.append(
                ("mesh_lower", lower_window[0], lower_window[1])
            )

    def phase(self, name: str) -> _PhaseCtx:
        return _PhaseCtx(self, name)

    def add_bytes(self, n: int) -> None:
        self.bytes_staged += int(n)

    def finish(self, t1: Optional[float] = None) -> float:
        """Close the stage wall and fold into the process rollup.
        Returns the end timestamp (monotonic seconds)."""
        self.t1 = time.monotonic() if t1 is None else t1
        ROLLUP.observe_stage(
            self.op, self.t1 - self.t0, self.phases,
            nbytes=self.bytes_staged,
        )
        _register_collector()
        return self.t1


def stage(op: str, n_dev: int, lower_window=None) -> MeshStage:
    """Open one mesh stage's stopwatch (mesh_exec call sites)."""
    return MeshStage(op, n_dev, lower_window=lower_window)


def snapshot() -> Dict[str, Any]:
    """The `meshprof` STATS section (both tiers serve it)."""
    return ROLLUP.snapshot()


def _reset_for_tests() -> None:
    ROLLUP._reset_for_tests()
    with _tk_lock:
        _trace_keys.clear()


# ---------------------------------------------------------------------------
# the attribution probe (`mesh-attr` child) + MESHATTR doc builder
# ---------------------------------------------------------------------------


def run_attr_probe(n_dev: int, rows: int = 1 << 20,
                   iters: int = 4) -> Dict[str, Any]:
    """One device-count measurement for `mesh-attr`: build the bench
    `mesh_groupby` shape (8-partition MemoryScan under a FINAL /
    exchange / PARTIAL sandwich), lower it with mode="on", and run
    1 cold + `iters` warm rounds, collecting the sub-phase rollup,
    stage walls, trace/retrace counters, bytes staged, and the mesh
    single-flight lock's wait:hold. At 1 device the planner refuses
    to lower and the rounds time the file-shuffle sandwich instead -
    the single-device baseline wall the gap attribution needs.

    Expects the process device count to already match `n_dev` (the
    parent forces it via XLA_FLAGS before any backend init); runs
    against a PRIVATE rollup (capture()) plus contention accounting
    scoped to the probe window."""
    import tempfile

    import numpy as np

    import jax

    from blaze_tpu.batch import ColumnBatch
    from blaze_tpu.exprs import AggExpr, AggFn, Col
    from blaze_tpu.obs import contention
    from blaze_tpu.obs.metrics import REGISTRY
    from blaze_tpu.ops import (
        AggMode,
        HashAggregateExec,
        MemoryScanExec,
    )
    from blaze_tpu.planner.distribute import (
        insert_exchanges,
        lower_plan_to_mesh,
    )
    from blaze_tpu.runtime.executor import run_plan

    import pyarrow as pa

    assert len(jax.devices()) == n_dev, (
        f"expected {n_dev} devices, saw {len(jax.devices())} "
        "(the device count freezes at first backend init - run the "
        "probe in a fresh subprocess)"
    )
    n_parts = 8
    per = max(1, rows // n_parts)
    rng = np.random.default_rng(17)
    parts, schema = [], None
    for _ in range(n_parts):
        k = rng.integers(0, 4096, per).astype(np.int64)
        v = rng.integers(0, 1000, per).astype(np.int64)
        cb = ColumnBatch.from_arrow(pa.record_batch({"k": k, "v": v}))
        schema = cb.schema
        parts.append([cb])
    shuffle_dir = tempfile.mkdtemp(prefix="blaze_mesh_attr_")

    def sandwich():
        return insert_exchanges(
            HashAggregateExec(
                MemoryScanExec(parts, schema),
                keys=[(Col("k"), "k")],
                aggs=[(AggExpr(AggFn.SUM, Col("v")), "s"),
                      (AggExpr(AggFn.COUNT_STAR, None), "n")],
                mode=AggMode.COMPLETE,
            ),
            n_parts, shuffle_dir=shuffle_dir,
        )

    lowered = lower_plan_to_mesh(sandwich(), mode="on")
    mesh_lowered = type(lowered).__name__ == "MeshGroupByExec"
    op_key = "mesh.groupby"
    trace0 = REGISTRY.get("blaze_mesh_trace_total", op=op_key)
    retrace0 = REGISTRY.get("blaze_mesh_retrace_total", op=op_key)

    def run_once():
        if mesh_lowered:
            lowered._result = None  # fresh execution, warm program
            return run_plan(lowered)
        return run_plan(sandwich())

    doc: Dict[str, Any] = {
        "n_devices": n_dev, "rows": per * n_parts, "iters": iters,
        "mesh_lowered": mesh_lowered,
    }
    contention.enable()
    try:
        with capture() as cold_rollup:
            t0 = time.perf_counter()
            run_once()  # cold: pays trace+compile
            cold_wall = time.perf_counter() - t0
        cold_snap = cold_rollup.snapshot().get(op_key, {})
        doc["cold"] = {
            "wall": round(cold_wall, 4),
            "subphases": {
                name: st["p50"] for name, st in
                (cold_snap.get("subphases") or {}).items()
            },
        }
        walls = []
        with capture() as rol:
            for _ in range(max(1, iters)):
                t0 = time.perf_counter()
                run_once()
                walls.append(time.perf_counter() - t0)
        warm_trace = REGISTRY.get("blaze_mesh_trace_total", op=op_key)
        warm_retrace = REGISTRY.get(
            "blaze_mesh_retrace_total", op=op_key
        )
        # re-trace demonstration: a FRESH lowering of the SAME logical
        # plan. Since the program cache (fleet/program_cache.py) it
        # finds the traced holder and `retrace_total` stays 0; a
        # count here is the cache-key churn the counter exists to
        # expose
        if mesh_lowered:
            relowered = lower_plan_to_mesh(sandwich(), mode="on")
            t0 = time.perf_counter()
            run_plan(relowered)
            doc["retrace_demo_wall"] = round(
                time.perf_counter() - t0, 4
            )
    finally:
        contention.disable()
    walls.sort()
    median = walls[len(walls) // 2]
    doc["wall"] = {
        "median": round(median, 4),
        "spread": round(
            (walls[-1] - walls[0]) / median, 3
        ) if median > 0 else 0.0,
        "k": len(walls),
    }
    snap = rol.snapshot().get(op_key)
    if mesh_lowered and snap:
        doc["subphases"] = snap.get("subphases") or {}
        doc["bytes_staged"] = snap.get("bytes_staged", 0)
        wall_stat = snap.get("stage_wall") or {}
        wall_p50 = wall_stat.get("p50", 0.0)
        sub_sum = sum(
            doc["subphases"].get(n, {}).get("p50", 0.0)
            for n in STAGE_SUBPHASES
        )
        doc["reconcile"] = {
            "wall_p50": round(wall_p50, 6),
            "subphase_sum": round(sub_sum, 6),
            "coverage": round(sub_sum / wall_p50, 4)
            if wall_p50 > 0 else 0.0,
        }
    doc["trace_total"] = int(
        REGISTRY.get("blaze_mesh_trace_total", op=op_key) - trace0
    )
    doc["retrace_total"] = int(
        REGISTRY.get("blaze_mesh_retrace_total", op=op_key) - retrace0
    )
    # warm-repeat pin data: trace delta across the warm rounds alone
    doc["warm_trace_delta"] = int(
        warm_trace - trace0
        - (1 if mesh_lowered else 0)  # the cold round's first trace
    )
    doc["warm_retrace_delta"] = int(warm_retrace - retrace0)
    lock = contention.snapshot().get("mesh_groupby")
    if lock:
        doc["lock"] = lock
    return doc


def build_doc(d1: Dict[str, Any], dn: Dict[str, Any]) -> Dict[str, Any]:
    """Fold the two child measurements into the MESHATTR_r*.json doc:
    per-sub-phase p50s (in regress-snapshot shape so `regress --bench`
    can diff consecutive rounds), the (dN - d1) stage-wall gap
    attribution, and the written verdict - which sub-phase dominates -
    that ROADMAP item 2 records."""
    n_dev = int(dn.get("n_devices", 0))
    d1_wall = float((d1.get("wall") or {}).get("median", 0.0))
    dn_wall = float((dn.get("wall") or {}).get("median", 0.0))
    subs = dn.get("subphases") or {}
    gap = dn_wall - d1_wall
    sub_sum = sum(
        subs.get(n, {}).get("p50", 0.0) for n in STAGE_SUBPHASES
    )
    # the stage's sub-phases cover the dN wall; the single-device wall
    # is the equivalent-work baseline, so the portion of the GAP the
    # named sub-phases explain is what they cover beyond that baseline
    attributed = max(0.0, min(sub_sum, dn_wall) - d1_wall)
    shares = {
        n: round(subs.get(n, {}).get("p50", 0.0) / dn_wall, 4)
        if dn_wall > 0 else 0.0
        for n in STAGE_SUBPHASES if n in subs
    }
    doc: Dict[str, Any] = {
        "format": "blaze-meshattr-v1",
        "rows": dn.get("rows"),
        "rounds": {"d1": d1, f"d{n_dev}": dn},
        "gap": {
            "d1_wall": round(d1_wall, 4),
            f"d{n_dev}_wall": round(dn_wall, 4),
            "gap_s": round(gap, 4),
            "ratio": round(dn_wall / d1_wall, 1)
            if d1_wall > 0 else None,
            "attributed_s": round(attributed, 4),
            "attributed_frac": round(attributed / gap, 4)
            if gap > 0 else None,
            "subphase_share_of_wall": shares,
        },
        # regress-snapshot shape: {class: {phase: {n, p50, ...}}} -
        # `regress --bench` diffs two rounds of THIS
        "phases": {"snapshot": {"_all": {
            **{n: st for n, st in subs.items()},
            **({"stage_wall": dn["reconcile"] and {
                "n": (dn.get("wall") or {}).get("k", 0),
                "p50": dn["reconcile"]["wall_p50"],
                "p95": dn["reconcile"]["wall_p50"],
                "mean": dn["reconcile"]["wall_p50"],
            }} if dn.get("reconcile") else {}),
        }}},
    }
    if subs and dn_wall > 0:
        ranked = sorted(
            ((n, subs[n]["p50"]) for n in STAGE_SUBPHASES
             if n in subs),
            key=lambda kv: -kv[1],
        )
        top, top_s = ranked[0]
        lock = dn.get("lock") or {}
        parts = [
            f"{top} dominates the d{n_dev} stage: "
            f"{top_s:.2f}s of the {dn_wall:.2f}s wall "
            f"({100 * top_s / dn_wall:.0f}%)"
        ]
        rest = ", ".join(
            f"{n} {100 * s / dn_wall:.0f}%" for n, s in ranked[1:]
        )
        if rest:
            parts.append(rest)
        parts.append(
            "warm re-trace "
            + ("avoided (delta 0)"
               if not dn.get("warm_retrace_delta")
               else f"x{dn['warm_retrace_delta']} - cache-key churn")
        )
        wh = lock.get("wait_hold_ratio")
        if wh is not None:
            parts.append(f"lock wait:hold {wh}")
        doc["verdict"] = "; ".join(parts)
    return doc


def next_round_path(dirpath: str) -> str:
    """Next MESHATTR_rNN.json in the versioned-artifact convention
    (MULTICHIP_r*/BENCH_r* siblings)."""
    import glob
    import os
    import re

    n = 0
    for p in glob.glob(os.path.join(dirpath, "MESHATTR_r*.json")):
        m = re.search(r"MESHATTR_r(\d+)\.json$", p)
        if m:
            n = max(n, int(m.group(1)))
    return os.path.join(dirpath, f"MESHATTR_r{n + 1:02d}.json")
