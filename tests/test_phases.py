"""Per-phase rollup + regression detection (ISSUE 6 tentpole):
PhaseRollup bounds/percentiles, the trace-driven fold, compare()'s
noise-band semantics, the regress CLI plumbing, and the acceptance
pin - a chaos STALL at parquet.decode (a synthetic decode regression)
is DETECTED by the per-phase diff while the e2e median stays inside
its own noise band, i.e. the regression BENCH-style e2e tracking
would have missed."""

import json
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from blaze_tpu.exprs import AggExpr, AggFn, Col
from blaze_tpu.obs import phases
from blaze_tpu.obs.phases import (
    ALL_CLASS,
    SPAN_PHASE,
    PhaseRollup,
    class_key,
    compare,
    fold_span_dicts,
    run_probe,
)
from blaze_tpu.ops import AggMode, FilterExec, HashAggregateExec
from blaze_tpu.ops.parquet_scan import FileRange, ParquetScanExec
from blaze_tpu.plan.serde import task_to_proto
from blaze_tpu.service import QueryService
from blaze_tpu.testing import chaos
from blaze_tpu.testing.chaos import Fault


# ---------------------------------------------------------------------------
# rollup units
# ---------------------------------------------------------------------------


def test_rollup_percentiles_and_aggregate_class():
    r = PhaseRollup()
    for i in range(1, 11):
        r.observe("arrow_decode", i / 100.0, klass="abc")
    snap = r.snapshot()
    assert snap["abc"]["arrow_decode"]["n"] == 10
    assert snap["abc"]["arrow_decode"]["p50"] == pytest.approx(0.05, rel=0.3)
    # every observation also lands in the _all aggregate
    assert snap[ALL_CLASS]["arrow_decode"]["n"] == 10


def test_rollup_bounded_rings_and_class_lru():
    r = PhaseRollup(max_classes=3, samples_per_phase=4)
    for i in range(10):
        r.observe("e2e", 0.01, klass=f"c{i}")
    snap = r.snapshot()
    # _all survives eviction; ring caps samples
    assert ALL_CLASS in snap
    assert snap[ALL_CLASS]["e2e"]["n"] == 4
    assert len(snap) <= 3


def test_rollup_negative_and_unknown_phase_dropped():
    r = PhaseRollup()
    r.observe("arrow_decode", -1.0)
    r.fold_phases({"not_a_phase": 1.0, "arrow_decode": None})
    assert r.snapshot() == {}


def test_class_key_digests_not_prefixes():
    a = class_key("HashAggregateExec(x)")
    b = class_key("HashAggregateExec(y)")
    assert a != b  # a readable-prefix key would collide these
    assert class_key(None) == "unstable"
    assert class_key("abc", stable=False) == "unstable"


def test_fold_span_dicts_sums_per_phase():
    spans = [
        {"name": "parquet_decode", "start_ns": 0, "end_ns": 10_000_000},
        {"name": "parquet_decode", "start_ns": 0, "end_ns": 5_000_000},
        {"name": "h2d", "start_ns": 0, "end_ns": 2_000_000},
        {"name": "attempt", "start_ns": 0, "end_ns": 9_000_000},  # structure
        {"name": "router_stream", "start_ns": 0, "end_ns": 9},  # passthrough
        {"name": "parquet_decode", "start_ns": 5, "end_ns": None},  # open
    ]
    out = fold_span_dicts(spans)
    assert out == {
        "arrow_decode": pytest.approx(0.015),
        "h2d": pytest.approx(0.002),
    }


# ---------------------------------------------------------------------------
# compare() semantics
# ---------------------------------------------------------------------------


def _cell(p50, n=5):
    return {"n": n, "p50": p50, "p95": p50, "mean": p50}


def test_compare_flags_creep_beyond_band_only():
    base = {"_all": {"arrow_decode": _cell(0.1), "e2e": _cell(1.0)}}
    live = {"_all": {"arrow_decode": _cell(0.4), "e2e": _cell(1.1)}}
    regs = compare(live, base, rel_band=0.5, abs_floor_s=0.01)
    assert [r["phase"] for r in regs] == ["arrow_decode"]
    assert regs[0]["ratio"] == pytest.approx(4.0)


def test_compare_min_samples_and_missing_cells():
    base = {"_all": {"arrow_decode": _cell(0.1, n=2)},
            "only_base": {"e2e": _cell(0.1)}}
    live = {"_all": {"arrow_decode": _cell(10.0, n=2)},
            "only_live": {"e2e": _cell(9.0)}}
    # too few samples -> ignored; classes present on one side -> ignored
    assert compare(live, base) == []


def test_compare_per_phase_band_overrides():
    base = {"_all": {"arrow_decode": _cell(0.1), "e2e": _cell(0.2)}}
    live = {"_all": {"arrow_decode": _cell(0.25), "e2e": _cell(0.5)}}
    regs = compare(
        live, base, rel_band=0.3, abs_floor_s=0.01,
        bands={"e2e": (5.0, 0.5)},  # e2e explicitly slack
    )
    assert [r["phase"] for r in regs] == ["arrow_decode"]


# ---------------------------------------------------------------------------
# service integration: the terminal hook feeds the process rollup
# ---------------------------------------------------------------------------


@pytest.fixture
def agg_blob(tmp_path):
    rng = np.random.default_rng(3)
    p = str(tmp_path / "ph.parquet")
    pq.write_table(
        pa.table({
            "k": pa.array(rng.integers(0, 16, 4000), pa.int32()),
            "v": pa.array(rng.random(4000), pa.float64()),
        }),
        p,
    )
    plan = HashAggregateExec(
        FilterExec(ParquetScanExec([[FileRange(p)]]),
                   Col("v") > 0.5),
        keys=[(Col("k"), "k")],
        aggs=[(AggExpr(AggFn.SUM, Col("v")), "s")],
        mode=AggMode.COMPLETE,
    )
    return task_to_proto(plan, 0)


def test_terminal_hook_folds_phases_into_global_rollup(agg_blob):
    phases.ROLLUP._reset_for_tests()
    with QueryService(max_concurrency=1, enable_cache=False,
                      enable_trace=True) as svc:
        for _ in range(3):
            q = svc.submit_task(agg_blob, use_cache=False)
            assert q.wait(60.0) and q.state.value == "DONE"
        snap = phases.ROLLUP.snapshot()
        assert snap[ALL_CLASS]["e2e"]["n"] == 3
        # the keyed aggregate's launches land in `dispatch`, from the
        # task's launch counter (the per-launch span and the `group`
        # phase are gone)
        for ph in ("queue_wait", "execute", "arrow_decode", "dispatch"):
            assert ph in snap[ALL_CLASS], snap[ALL_CLASS].keys()
        # the fingerprint class rode along (stable plan)
        fp_classes = [k for k in snap if k not in (ALL_CLASS,)]
        assert fp_classes, snap.keys()
        # and STATS serves the same snapshot shape
        st = svc.stats()
        assert ALL_CLASS in st["phases"]


def test_obs_off_service_still_folds_lifecycle_phases(agg_blob):
    phases.ROLLUP._reset_for_tests()
    with QueryService(max_concurrency=1, enable_cache=False,
                      enable_trace=False) as svc:
        q = svc.submit_task(agg_blob, use_cache=False)
        assert q.wait(60.0) and q.state.value == "DONE"
    snap = phases.ROLLUP.snapshot()
    # no trace -> no decode/dispatch detail, but the lifecycle phases
    # (timings-driven) still roll up
    assert "e2e" in snap[ALL_CLASS]
    assert "execute" in snap[ALL_CLASS]
    assert "arrow_decode" not in snap[ALL_CLASS]


# ---------------------------------------------------------------------------
# the acceptance pin: a decode regression invisible to e2e medians
# ---------------------------------------------------------------------------


def test_regress_detects_stalled_decode_under_flat_e2e():
    """Chaos STALL at parquet.decode slows ONLY the decode phase by a
    fixed 80ms - a fraction of the probe query's e2e (which stays
    inside a generous e2e noise band, exactly the regression
    BENCH-style e2e medians shrug off) - and the per-phase diff flags
    decode anyway."""
    rows = 1 << 17
    baseline = run_probe(rounds=3, rows=rows)
    with chaos.active([
        Fault(site="parquet.decode", klass="STALL", times=0,
              stall_s=0.12),
    ], seed=61):
        live = run_probe(rounds=3, rows=rows)
    # e2e noise band: up to 2.5x + 0.15s (the BENCH-median analog)
    bands = {"e2e": (1.5, 0.15)}
    regs = compare(live, baseline, rel_band=0.3, abs_floor_s=0.02,
                   bands=bands, min_samples=3)
    flagged = {r["phase"] for r in regs}
    assert "arrow_decode" in flagged, (regs, live, baseline)
    assert "e2e" not in flagged, (regs, live, baseline)
    # the decode creep is a multiple, not jitter
    dec = next(r for r in regs if r["phase"] == "arrow_decode"
               and r["class"] == ALL_CLASS)
    assert dec["ratio"] > 1.5


# ---------------------------------------------------------------------------
# CLI plumbing
# ---------------------------------------------------------------------------


def test_regress_cli_baseline_roundtrip(tmp_path, capsys):
    """emit-baseline -> --against on the same host inside the smoke's
    generous band exits 0; a poisoned baseline (phases 100x faster
    than reality) exits 1 with the regression named. In-process
    cli_main: a subprocess per invocation would pay three jax imports
    for zero extra coverage."""
    from blaze_tpu.__main__ import main as cli_main

    base_path = str(tmp_path / "base.json")
    rc = cli_main(["regress", "--emit-baseline", base_path,
                   "--rounds", "3", "--rows", str(1 << 16)])
    assert rc == 0, capsys.readouterr()
    capsys.readouterr()
    doc = json.load(open(base_path))
    assert doc["format"] == "blaze-phase-baseline-v1"
    assert "e2e" in doc["phases"][ALL_CLASS]

    rc = cli_main(["regress", "--against", base_path,
                   "--rounds", "3", "--rows", str(1 << 16),
                   "--noise", "3.0", "--abs-floor", "0.25"])
    assert rc == 0, capsys.readouterr()
    capsys.readouterr()

    # poison: divide every p50 by 100 -> everything regresses
    for klass in doc["phases"].values():
        for cell in klass.values():
            cell["p50"] = cell["p50"] / 100.0
    poisoned = str(tmp_path / "poisoned.json")
    json.dump(doc, open(poisoned, "w"))
    rc = cli_main(["regress", "--against", poisoned,
                   "--rounds", "3", "--rows", str(1 << 16),
                   "--noise", "0.5", "--abs-floor", "0.001"])
    captured = capsys.readouterr()
    assert rc == 1, captured
    assert "REGRESSION" in captured.err
    assert json.loads(captured.out)["regressions"]


def test_regress_bench_artifact_diff(tmp_path, capsys):
    """--bench OLD NEW: per-phase p50s recorded in the
    `{"phases": {"snapshot": ...}}` shape diff across rounds; wrapper artifacts ({n, cmd,
    rc, tail}) and bare battery results both parse."""
    from blaze_tpu.__main__ import main as cli_main

    def artifact(path, decode_p50, wrap):
        snap = {ALL_CLASS: {
            "arrow_decode": _cell(decode_p50),
            "e2e": _cell(1.0),
        }}
        result = {"queries": {"phases": {"median": 1.0, "spread": 0.1,
                                         "k": 5, "snapshot": snap}}}
        doc = ({"n": 9, "cmd": "bench", "rc": 0,
                "tail": "noise\n" + json.dumps(result)}
               if wrap else result)
        with open(path, "w") as f:
            json.dump(doc, f)
        return str(path)

    old = artifact(tmp_path / "old.json", 0.01, wrap=True)
    new = artifact(tmp_path / "new.json", 0.2, wrap=False)
    rc = cli_main(["regress", "--bench", old, new,
                   "--noise", "0.5", "--abs-floor", "0.01"])
    captured = capsys.readouterr()
    assert rc == 1, captured
    report = json.loads(captured.out)
    assert [r["phase"] for r in report["regressions"]] == ["arrow_decode"]
    # reversed direction is clean (improvements never fail CI)
    rc = cli_main(["regress", "--bench", new, old,
                   "--noise", "0.5", "--abs-floor", "0.01"])
    capsys.readouterr()
    assert rc == 0


def test_regress_bench_missing_phases_is_usage_error(
    tmp_path, capsys,
):
    from blaze_tpu.__main__ import main as cli_main

    p = str(tmp_path / "old.json")
    json.dump({"queries": {}}, open(p, "w"))
    rc = cli_main(["regress", "--bench", p, p])
    capsys.readouterr()
    assert rc == 2
    # unreadable / corrupt inputs are usage errors (2), never the
    # regression-detected code (1)
    rc = cli_main(["regress", "--bench", p,
                   str(tmp_path / "nope.json")])
    capsys.readouterr()
    assert rc == 2
    bad = str(tmp_path / "bad.json")
    open(bad, "w").write("{truncated")
    rc = cli_main(["regress", "--against", bad,
                   "--rounds", "1", "--rows", "1024"])
    capsys.readouterr()
    assert rc == 2


def test_regress_bench_emit_baseline_refreshes_from_new_round(
    tmp_path, capsys,
):
    from blaze_tpu.__main__ import main as cli_main

    snap = {ALL_CLASS: {"e2e": _cell(1.0)}}
    art = str(tmp_path / "round.json")
    json.dump({"queries": {"phases": {"snapshot": snap}}},
              open(art, "w"))
    out_baseline = str(tmp_path / "fresh_baseline.json")
    rc = cli_main(["regress", "--bench", art, art,
                   "--emit-baseline", out_baseline])
    capsys.readouterr()
    assert rc == 0
    doc = json.load(open(out_baseline))
    assert doc["phases"] == snap
    assert doc["meta"]["source"] == art


def test_probe_service_stays_out_of_global_rollup():
    """run_probe inside a live serving process must not skew the
    process-global rollup (fold_phases=False isolation)."""
    phases.ROLLUP._reset_for_tests()
    run_probe(rounds=1, rows=1 << 14)
    assert phases.ROLLUP.snapshot() == {}


# ---------------------------------------------------------------------------
# stream phase folds at FETCH time (wire tier)
# ---------------------------------------------------------------------------


def test_stream_phase_folds_on_wire_fetch(agg_blob):
    from blaze_tpu.runtime.gateway import TaskGatewayServer
    from blaze_tpu.service import ServiceClient

    phases.ROLLUP._reset_for_tests()
    with QueryService(max_concurrency=1, enable_cache=False) as svc:
        with TaskGatewayServer(service=svc) as srv:
            host, port = srv.address
            with ServiceClient(host, port) as c:
                st = c.submit(agg_blob)
                assert c.fetch(st["query_id"])
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                snap = phases.ROLLUP.snapshot()
                if "stream" in snap.get(ALL_CLASS, {}):
                    break
                time.sleep(0.01)
    assert "stream" in phases.ROLLUP.snapshot()[ALL_CLASS]


def test_compare_router_stream_phases_get_widened_default_bands():
    """ISSUE 11 satellite: the hop phases (router/stream) measure
    millisecond p50s that wobble by integer factors under CI load -
    compare() widens their bands by default (max of the caller band
    and the built-in widener), so a 3ms->8ms jitter passes while a
    real execute regression of the same ratio still fails."""
    base = {"_all": {"router": _cell(0.003), "stream": _cell(0.004),
                     "execute": _cell(1.0)}}
    live = {"_all": {"router": _cell(0.008), "stream": _cell(0.010),
                     "execute": _cell(2.7)}}
    regs = compare(live, base, rel_band=0.5, abs_floor_s=0.01)
    # execute (2.7x) regresses; router/stream ride the widened band
    assert [r["phase"] for r in regs] == ["execute"]
    # a genuine hop blowup still fails: beyond 3x + the 50ms floor
    live2 = {"_all": {"router": _cell(0.25)}}
    regs2 = compare(live2, {"_all": {"router": _cell(0.003)}},
                    rel_band=0.5, abs_floor_s=0.01)
    assert [r["phase"] for r in regs2] == ["router"]
    # an EXPLICIT per-phase band wins outright over the widener
    regs3 = compare(
        {"_all": {"router": _cell(0.008)}},
        {"_all": {"router": _cell(0.003)}},
        rel_band=0.5, abs_floor_s=0.01,
        bands={"router": (0.1, 0.001)},
    )
    assert [r["phase"] for r in regs3] == ["router"]


def test_phase_totals_matches_fold_span_dicts():
    """The allocation-free terminal-hook fold
    (TraceRecorder.phase_totals) must agree exactly with the
    dict-materializing fold it replaced - same span-name map, same
    totals - or the rollup baselines would shift under a pure
    optimization."""
    from blaze_tpu.obs import trace

    rec = trace.TraceRecorder("fold-parity")
    t0 = time.monotonic()
    rec.record_span("queue_wait", t0, t0 + 0.010)
    rec.record_span("parquet_decode", t0, t0 + 0.020)
    rec.record_span("parquet_decode", t0 + 0.020, t0 + 0.050)
    rec.record_span("plan_decode", t0, t0 + 0.001)
    rec.record_span("attempt", t0, t0 + 0.5)  # structural: unmapped
    unfinished = rec.begin("h2d")  # open span: excluded by both
    assert unfinished is not None
    rec.finish(state="DONE")
    fast = rec.phase_totals(SPAN_PHASE)
    slow = fold_span_dicts(rec.to_dicts())
    assert fast == slow
    assert fast["arrow_decode"] == pytest.approx(0.050, abs=1e-6)
    assert "h2d" not in fast and "attempt" not in fast
