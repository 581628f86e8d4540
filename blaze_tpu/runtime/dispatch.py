"""Device-dispatch accounting and the global kernel cache.

Why this exists (reference parity + TPU reality): the reference engine's
hot loop is one native call per *task* (exec.rs:196-255) - operators fuse
into a single streamed program, so per-query overhead is O(1) calls. An
XLA engine pays per *dispatch* (jit call, eager op, H2D/D2H transfer);
when the chip is network-attached each dispatch costs a round trip, so
dispatch count IS the end-to-end performance model for small/medium
queries. This module makes that count observable (per-query logging in
benchmarks, regression tests) and provides the process-wide kernel cache
so freshly-built plans (a new plan object per query, as in the reference's
per-task plan decode) reuse compiled executables instead of re-tracing.

Counters are process-global and thread-safe-enough (GIL increments); the
scheduler's worker threads all contribute to the same totals, which is
what a per-query report wants.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Callable, Dict, Tuple

import jax

_lock = threading.Lock()
_counts: Dict[str, int] = {}

# process-wide compiled-kernel cache: structural key -> wrapped jit fn.
# Keys must capture everything that changes the traced program: op kind,
# bound expression trees (ir.Expr is structurally hashable), schema dtype
# descriptors, buffer layout, static config (capacities, modes).
# LRU-bounded: a long-lived worker seeing many structurally distinct
# queries must not accumulate executables forever (per-plan caches used
# to die with the plan object; this is the global replacement).
_KERNELS: "collections.OrderedDict[Tuple, Callable]" = (
    collections.OrderedDict()
)
# Bounded for executable memory. Entries evicted LRU recompile
# transparently. BLAZE_KERNEL_CACHE_CAP overrides (0 = unbounded).
import os as _os

_KERNEL_CACHE_CAP = int(
    _os.environ.get("BLAZE_KERNEL_CACHE_CAP", 256)
) or (1 << 30)

# ---------------------------------------------------------------------------
# Per-kernel XLA:CPU runtime selection.
#
# jaxlib's default CPU runtime (the "thunk" runtime) serializes scatter
# updates through a slow per-element path: an 8M-row segment_sum costs
# ~457ms vs ~33ms under the legacy runtime (measured on this host,
# jaxlib 0.4.36) - a 14x gap that dominates every scatter-core grouped
# aggregate and hash-table insert. The legacy runtime, in turn, sorts
# ~6x SLOWER, so the selection must be per-kernel: scatter-dominated
# kernels (grouped aggregation, join table inserts, the fused
# join+aggregate program) opt in via `cached_kernel(...,
# scatter_class=True)`; sort-dominated kernels (window, lexsort
# grouping, the sorted join core) keep the default runtime.
#
# CPU-only: on TPU (and any non-CPU backend) the hint is a no-op. The
# option is probed once with a throwaway compile so an incompatible
# jaxlib silently falls back to the default runtime.
# BLAZE_CPU_RUNTIME_SPLIT=0 disables the split entirely.
_SCATTER_JIT_KWARGS: Dict[str, Any] = None


def _scatter_jit_kwargs() -> Dict[str, Any]:
    global _SCATTER_JIT_KWARGS
    if _SCATTER_JIT_KWARGS is not None:
        return _SCATTER_JIT_KWARGS
    kwargs: Dict[str, Any] = {}
    if _os.environ.get("BLAZE_CPU_RUNTIME_SPLIT", "1") != "0":
        try:
            if jax.default_backend() == "cpu":
                opts = {"xla_cpu_use_thunk_runtime": False}
                # probe compile: rejects on jaxlibs without the flag
                jax.jit(
                    lambda x: x + 1, compiler_options=opts
                )(0)
                kwargs = {"compiler_options": opts}
        except Exception:
            kwargs = {}
    _SCATTER_JIT_KWARGS = kwargs
    return kwargs


def record(kind: str, n: int = 1) -> None:
    with _lock:
        _counts[kind] = _counts.get(kind, 0) + n


# the task this thread launches for (`.ctx`, an ExecContext): the
# executor's scope around each pull of a partition's stream, handed on
# to prefetch workers. `_wrap_dispatch` counts each cached kernel's
# launch on it (`task_dispatches`, POLL) and `_launch` every program's
# (`launches`), whatever else runs in the process and whether or not
# tracing is on
_task = threading.local()


def current_task():
    return getattr(_task, "ctx", None)


class task_scope:
    """`with task_scope(ctx):` - `ctx` is this thread's task inside
    (re-enterable: the executor enters it once a pull)."""

    __slots__ = ("_ctx", "_outer")

    def __init__(self, ctx):
        self._ctx = ctx

    def __enter__(self):
        self._outer = current_task()
        _task.ctx = self._ctx

    def __exit__(self, *exc):
        _task.ctx = self._outer
        return False


def count(name: str, k: int) -> None:
    """Add `k` to the counter `name` of this thread's task's metrics
    (POLL carries it), where the thread works for a task."""
    ctx = current_task()
    if ctx is not None:
        ctx.metrics.add(name, k)


def snapshot() -> Dict[str, int]:
    with _lock:
        return dict(_counts)


def reset() -> Dict[str, int]:
    """Return current counts and zero them (per-query measurement)."""
    global _counts
    with _lock:
        out = _counts
        _counts = {}
        return out


class counting:
    """Context manager: `with counting() as c: ...; c.counts` gives the
    dispatch/transfer counts attributable to the block (delta of the
    global counters; concurrent tasks in other threads also land here)."""

    def __enter__(self):
        self._start = snapshot()
        self.counts: Dict[str, int] = {}
        return self

    def __exit__(self, *exc):
        end = snapshot()
        self.counts = {
            k: v - self._start.get(k, 0)
            for k, v in end.items()
            if v - self._start.get(k, 0)
        }
        return False


def _launch(ctx, fn: Callable, args, kw):
    """Call a compiled program, and where this thread launches for a
    task, count the launch on it: `launches`, the thread's wall time in
    the call (`launch_ns`) and the arrays it handed back
    (`launch_buffers`). The call is where a launch costs its thread,
    about 0.2 ms plus 40 us an output buffer on a v5e (ROADMAP S6);
    dispatch is async, so this is not the device's time."""
    if ctx is None:
        return fn(*args, **kw)
    t0 = time.perf_counter_ns()
    out = fn(*args, **kw)
    dt = time.perf_counter_ns() - t0
    n = len(jax.tree_util.tree_leaves(out))
    with _lock:  # the prefetch worker launches for its consumer's task
        ctx.launches += 1
        ctx.launch_ns += dt
        ctx.launch_buffers += n
    return out


def launch(fn: Callable, *args, **kw):
    """`fn(*args, **kw)` for a plain `jax.jit` entry point on a served
    path, counted on this thread's task as `cached_kernel`'s are."""
    return _launch(getattr(_task, "ctx", None), fn, args, kw)


def _wrap_dispatch(fn: Callable, kind: str) -> Callable:
    from blaze_tpu.testing import chaos

    def wrapped(*args, **kw):
        if chaos.ACTIVE:
            # chaos seam: a compiled-kernel invocation that throws
            # (device reset, interconnect error) - off path is one
            # module-attribute load
            chaos.fire("kernel.dispatch", kind=kind)
        ctx = getattr(_task, "ctx", None)
        with _lock:
            _counts[kind] = _counts.get(kind, 0) + 1
            if ctx is not None:
                ctx.task_dispatches += 1
        return _launch(ctx, fn, args, kw)

    return wrapped


def cached_kernel(key: Tuple, build: Callable[[], Callable],
                  scatter_class: bool = False,
                  **jit_kwargs) -> Callable:
    """Process-wide compiled-kernel lookup.

    `build()` returns the python function to jit; it runs only on cache
    miss. Each invocation of the returned callable records one
    "dispatches" count (steady state: one XLA execution per call).

    `scatter_class=True` marks a scatter-dominated kernel: on the CPU
    backend it compiles under the legacy (non-thunk) XLA:CPU runtime
    (see _scatter_jit_kwargs)."""
    with _lock:
        fn = _KERNELS.get(key)
        if fn is not None:
            _KERNELS.move_to_end(key)
            # cache-hit accounting (vs kernel_builds): a steady-state
            # query stream should be all hits - tests pin this
            _counts["kernel_hits"] = _counts.get("kernel_hits", 0) + 1
    if fn is None:
        if scatter_class:
            jit_kwargs = {**_scatter_jit_kwargs(), **jit_kwargs}
        with _lock:
            fn = _KERNELS.get(key)
            if fn is None:
                # inline count: record() would re-take the
                # non-reentrant lock
                _counts["kernel_builds"] = (
                    _counts.get("kernel_builds", 0) + 1
                )
                fn = _wrap_dispatch(
                    jax.jit(build(), **jit_kwargs), "dispatches"
                )
                _KERNELS[key] = fn
                while len(_KERNELS) > _KERNEL_CACHE_CAP:
                    _KERNELS.popitem(last=False)
    return fn


def kernel_cache_size() -> int:
    return len(_KERNELS)


def clear_kernel_cache() -> None:
    _KERNELS.clear()


def task_threads(n_tasks: int, cap: int = 4) -> int:
    """Concurrency for device-dispatching task pools (exchange map
    stages, the scheduler). One process shares one device, so threads
    buy IO/encode overlap, not compute throughput. BLAZE_TASK_THREADS
    overrides (set to 1 to serialize every device-touching task - the
    workaround for jaxlib CPU-client races under concurrent
    compilation, see tests/conftest.py)."""
    import os

    env = os.environ.get("BLAZE_TASK_THREADS")
    if env:
        cap = max(1, int(env))
    return min(cap, max(1, n_tasks))


def device_get(tree: Any) -> Any:
    """One batched D2H fetch (counted once - the transfers pipeline)."""
    record("d2h_fetches")
    return jax.device_get(tree)


def host_int(x) -> int:
    """Blocking scalar readback (a full device round trip)."""
    record("d2h_syncs")
    return int(x)
