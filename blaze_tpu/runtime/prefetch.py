"""Double-buffered host pipeline.

The reference's hot loop is a tokio stream pumping batches through a
rendezvous queue (exec.rs:196-255); the TPU-first equivalent (SURVEY 7
"streaming model") overlaps host-side work (parquet decode, IPC decode,
dictionary encoding, H2D issue) with device compute by running the
producer iterator on a worker thread ahead of the consumer, bounded by a
small queue. JAX dispatch is async already, so two stages of lookahead
keep both the host decoder and the device busy.
"""

from __future__ import annotations

import queue
import threading
from typing import Iterator, TypeVar

from blaze_tpu.obs import trace as obs_trace
from blaze_tpu.runtime import dispatch

T = TypeVar("T")

_SENTINEL = object()


def _wait(name: str, rec):
    """The span of a call that is about to block (obs/trace.py
    WAIT_SPANS), on the task's own recorder."""
    if obs_trace.ACTIVE and rec is not None:
        return obs_trace.span(name, rec=rec)
    return obs_trace.NULL


def prefetch(it: Iterator[T], depth: int = 2) -> Iterator[T]:
    """Run `it` on a background thread with `depth` items of lookahead.
    Exceptions propagate to the consumer at the point of consumption;
    early consumer exit stops the producer. A call that blocks is a
    wait span: `wait_batch` where the consumer waits for the producer,
    `wait_room` where the producer waits for the consumer. A call that
    does not block reads no clock."""
    q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
    stop = threading.Event()
    task = dispatch.current_task()  # the consumer's: the worker's too
    rec = getattr(task, "tracer", None)

    def put(item):
        try:
            q.put_nowait(item)
        except queue.Full:
            with _wait("wait_room", rec):
                q.put(item)

    def worker():
        try:
            with dispatch.task_scope(task):
                for item in it:
                    if stop.is_set():
                        return
                    put(item)
            put(_SENTINEL)
        except BaseException as e:  # noqa: BLE001 - forwarded to consumer
            q.put(e)

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    try:
        while True:
            try:
                item = q.get_nowait()
            except queue.Empty:
                with _wait("wait_batch", rec):
                    item = q.get()
            if item is _SENTINEL:
                return
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        stop.set()
        # drain so a blocked producer can observe the stop flag
        try:
            while True:
                q.get_nowait()
        except queue.Empty:
            pass


class PrefetchExec:
    """Operator wrapper adding producer-side lookahead to any child."""

    def __init__(self, child, depth: int = 2):
        self.children = [child]
        self.depth = depth

    @property
    def schema(self):
        return self.children[0].schema

    @property
    def partition_count(self):
        return self.children[0].partition_count

    def describe(self):
        return f"PrefetchExec(depth={self.depth})"

    def display(self, indent: int = 0) -> str:
        lines = ["  " * indent + self.describe()]
        for c in self.children:
            lines.append(c.display(indent + 1))
        return "\n".join(lines)

    def fingerprint(self):
        return self.children[0].fingerprint()

    def execute(self, partition: int, ctx):
        return prefetch(
            self.children[0].execute(partition, ctx), self.depth
        )
