"""The serving child: `python -m blaze_tpu serve`, unchanged, with a side
thread that answers the benchmark's questions about the process that
holds the chip. Nobody else can: only this process can read the device's
memory peak, hear JAX's compile events or trace the chip.

    python perfbench/launcher.py <serve arguments...>

The side thread listens on a loopback port (printed as
`perfbench launcher ctl PORT`) for one JSON object per line:

    {"op": "stats"}        compile events so far, each with its time
    {"op": "memory"}       peak bytes in use, per device
    {"op": "trace_start", "dir": D}   start jax.profiler into D
    {"op": "trace_stop"}   stop it and say where the .xplane.pb is

It starts nothing until asked: a `--trace 0` run costs the serving
process one idle thread and a listener of compile events.
"""

from __future__ import annotations

import glob
import json
import os
import socket
import sys
import threading
import time

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class Control:
    def __init__(self):
        self.builds = []      # (unix time, seconds) of each program build
        self.cache_hits = []  # unix time of each persistent-cache hit
        self._lock = threading.Lock()
        self._tracing = None

    def listen(self) -> None:
        import jax.monitoring as monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        sock = socket.socket()
        sock.bind(("127.0.0.1", 0))
        sock.listen(4)
        print(f"perfbench launcher ctl {sock.getsockname()[1]}", flush=True)
        threading.Thread(target=self._accept, args=(sock,), daemon=True,
                         name="perfbench-ctl").start()

    def _on_duration(self, event, seconds, **kw) -> None:
        if event == BACKEND_COMPILE:
            with self._lock:
                self.builds.append((time.time(), seconds))

    def _on_event(self, event, **kw) -> None:
        if event == CACHE_HIT:
            with self._lock:
                self.cache_hits.append(time.time())

    def _accept(self, sock) -> None:
        while True:
            conn, _ = sock.accept()
            threading.Thread(target=self._serve, args=(conn,), daemon=True,
                             name="perfbench-ctl-conn").start()

    def _serve(self, conn) -> None:
        with conn, conn.makefile("rw") as f:
            for line in f:
                try:
                    reply = self._handle(json.loads(line))
                except Exception as e:  # noqa: BLE001 - report to the asker
                    reply = {"error": f"{type(e).__name__}: {e}"}
                f.write(json.dumps(reply) + "\n")
                f.flush()

    def _handle(self, msg: dict) -> dict:
        import jax

        op = msg.get("op")
        if op == "stats":
            with self._lock:
                return {"builds": list(self.builds),
                        "cache_hits": list(self.cache_hits),
                        "time": time.time()}
        if op == "memory":
            per = []
            for d in jax.local_devices():
                stats = d.memory_stats() or {}
                per.append(stats.get("peak_bytes_in_use"))
            return {"peak_bytes_in_use": per}
        if op == "trace_start":
            if self._tracing:
                raise RuntimeError("a trace is already running")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # host spans of the runtime only
            options.host_tracer_level = 2
            os.makedirs(msg["dir"], exist_ok=True)
            jax.profiler.start_trace(msg["dir"], profiler_options=options)
            self._tracing = (msg["dir"], time.time())
            return {"started": self._tracing[1]}
        if op == "trace_stop":
            if not self._tracing:
                raise RuntimeError("no trace is running")
            directory, started = self._tracing
            stopping = time.time()
            jax.profiler.stop_trace()
            self._tracing = None
            files = sorted(glob.glob(os.path.join(
                directory, "plugins", "profile", "*", "*.xplane.pb")))
            return {"started": started, "stopped": stopping,
                    "written": time.time(), "xplane": files[-1:]}
        raise ValueError(f"unknown op {op!r}")


def main(argv) -> int:
    from blaze_tpu.__main__ import main as blaze_main

    Control().listen()
    return blaze_main(["serve", *argv])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
