"""Memory budget + spill ladder.

Reference counterpart: DataFusion's MemoryConsumer/try_grow/spill protocol
wired through MemoryManagerConfig {max_memory, memory_fraction}
(exec.rs:79-94; spill path shuffle_writer_exec.rs:570-623). The TPU engine
extends the ladder one level: device HBM -> host RAM -> disk (SURVEY 7
"spill & memory ladder") - operators materialize on device, overflow to
host buffers tracked here, and spill those to disk under pressure.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List

from blaze_tpu.config import get_config
from blaze_tpu.testing import chaos


class MemoryPool:
    """Tracks host-side buffered bytes; triggers consumer spills when the
    budget (max_memory * memory_fraction) is exceeded. Spill order is
    largest-consumer-first like DataFusion's."""

    def __init__(self, budget: int = None):
        cfg = get_config()
        self.budget = budget if budget is not None else int(
            cfg.max_memory * cfg.memory_fraction
        )
        self._used: Dict[int, int] = {}
        self._spill_fns: Dict[int, Callable[[], int]] = {}
        self._lock = threading.Lock()
        self.spill_count = 0
        self.spilled_bytes = 0

    def register(self, consumer_id: int, spill: Callable[[], int]) -> None:
        with self._lock:
            self._used.setdefault(consumer_id, 0)
            self._spill_fns[consumer_id] = spill

    def unregister(self, consumer_id: int) -> None:
        with self._lock:
            self._used.pop(consumer_id, None)
            self._spill_fns.pop(consumer_id, None)

    def total_used(self) -> int:
        with self._lock:
            return sum(self._used.values())

    def grow(self, consumer_id: int, nbytes: int) -> None:
        """Account nbytes to the consumer; spill others (or it) if needed."""
        with self._lock:
            self._used[consumer_id] = self._used.get(consumer_id, 0) + nbytes
            over = sum(self._used.values()) - self.budget
            victims: List[int] = []
            if over > 0:
                victims = sorted(
                    self._used, key=lambda c: -self._used[c]
                )
        if over > 0:
            freed = 0
            for v in victims:
                fn = self._spill_fns.get(v)
                if fn is None:
                    continue
                released = fn()
                with self._lock:
                    if v in self._used:  # not finalized meanwhile
                        self._used[v] = max(0, self._used[v] - released)
                self.spill_count += 1
                self.spilled_bytes += released
                freed += released
                if freed >= over:
                    break

    def shrink(self, consumer_id: int, nbytes: int) -> None:
        with self._lock:
            self._used[consumer_id] = max(
                0, self._used.get(consumer_id, 0) - nbytes
            )


class DeviceMemoryTracker:
    """Live DEVICE (HBM) bytes per operator - the accounting the spill
    ladder's top rung runs on. Materializing operators (joins,
    aggregates, sorts) register what they hold resident; sizing
    decisions (external bucket counts, materialize-vs-stream) read the
    budget headroom instead of guessing (reference role:
    MemoryManagerConfig feeding DataFusion consumers, exec.rs:79-94)."""

    def __init__(self, budget: int = None):
        self._budget_override = budget
        self._used: Dict[int, int] = {}
        self._lock = threading.Lock()
        self.high_water = 0

    @property
    def budget(self) -> int:
        if self._budget_override is not None:
            return self._budget_override
        # live read: the process-global tracker must follow config swaps
        return int(get_config().device_memory_budget)

    def track(self, op_id: int, nbytes: int) -> None:
        if chaos.ACTIVE:
            # chaos seam: device-memory-pressure at the HBM accounting
            # boundary (a RESOURCE_EXHAUSTED fault here drives the
            # host-engine degradation path)
            chaos.fire("device.memory", op_id=op_id, nbytes=nbytes)
        with self._lock:
            self._used[op_id] = self._used.get(op_id, 0) + nbytes
            self.high_water = max(self.high_water, self.total_unlocked())

    def release(self, op_id: int, nbytes: int = None) -> None:
        with self._lock:
            if nbytes is None:
                self._used.pop(op_id, None)
            else:
                self._used[op_id] = max(
                    0, self._used.get(op_id, 0) - nbytes
                )

    def total_unlocked(self) -> int:
        return sum(self._used.values())

    def total_used(self) -> int:
        with self._lock:
            return self.total_unlocked()

    def headroom(self) -> int:
        return max(0, self.budget - self.total_used())


def batch_device_bytes(cb) -> int:
    """Bytes a ColumnBatch holds resident on device (values + validity)."""
    total = 0
    for c in cb.columns:
        v = c.values
        total += int(getattr(v, "nbytes", 0) or 0)
        if c.validity is not None:
            total += int(getattr(c.validity, "nbytes", 0) or 0)
    return total


def choose_external_bucket_count(est_bytes: int, config=None,
                                 headroom: int = None) -> int:
    """Bucket count for grace (external) execution such that one bucket's
    materialization fits comfortably in the CURRENT device headroom
    (budget minus what other live operators have tracked): each bucket
    gets at most a quarter of it. Grows in powers of two from the
    configured floor (capped at 1024 buckets - past that, per-bucket
    file overhead dominates)."""
    cfg = config or get_config()
    if headroom is None:
        headroom = get_device_tracker().headroom()
    per_bucket = max(1, int(headroom * cfg.memory_fraction) // 4)
    n = max(2, cfg.external_buckets)
    import math

    need = max(1, math.ceil(est_bytes / per_bucket))
    while n < need and n < 1024:
        n *= 2
    return n


_POOL = None
_DEVICE_TRACKER = None


def get_pool() -> MemoryPool:
    global _POOL
    if _POOL is None:
        _POOL = MemoryPool()
    return _POOL


def get_device_tracker() -> DeviceMemoryTracker:
    global _DEVICE_TRACKER
    if _DEVICE_TRACKER is None:
        _DEVICE_TRACKER = DeviceMemoryTracker()
    return _DEVICE_TRACKER
