#!/usr/bin/env python3
"""One run of one cell of the benchmark: the served path, on the chip.

    python3 perfbench/run.py --workload CELL --seed N --seconds S --trace 0|1

Starts the serving child (`perfbench/launcher.py`: `python -m blaze_tpu
serve --port 0` with its shipped defaults, alone on the chip; this parent
is pinned to the CPU before `blaze_tpu` is imported), makes the
configuration's tables from the seed, warms up the cell's own shapes,
then drives the cell's closed loop through `ServiceClient` for S seconds.
When the window has closed it reads the device's memory peak, stops the
server, and compares what the clients fetched (and, for a shuffle write,
the files the tasks wrote) with the plain reference. The last line of
standard output is the result object; PERF.md says what is in it.

`--rehearse` runs every phase at the configuration's
`rehearsal_split_rows` on whatever backend there is and prints its numbers
under `rehearsal.*` names; off a TPU `correct` is false (`not_on_tpu`) and
the exit code 1: it shows that the phases run, never a device number.
Without it, a serving process that is not on a TPU ends the run with exit
code 3 and no result.
"""

from __future__ import annotations

import argparse
import copy
import importlib
import importlib.util
import json
import os
import re
import shutil
import signal
import socket
import statistics
import subprocess
import sys
import threading
import time
import traceback

T0 = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WARM_LIMIT_S = 340.0    # a run has 360 s
COLD_LIMIT_S = 1150.0   # a cell's first run in a checkout compiles: 1200 s
WARMUP_MAX_SENDS = 6
CLIENT_TIMEOUT_S = 300.0
TRACE_SECONDS = 8.0     # the traced slice, from 30% of the window


def log(msg: str) -> None:
    print(f"[perfbench {time.time() - T0:7.2f}s] {msg}", file=sys.stderr,
          flush=True)


# ---- the serving child --------------------------------------------------

class Server:
    """The launcher as a child; its output goes to a log file (a pipe
    nobody drains would block it)."""

    def __init__(self, env: dict, workdir: str, flags):
        self.log_path = os.path.join(workdir, "serve.log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-u", os.path.join(HERE, "launcher.py"),
             "--port", "0", *flags],
            stdout=self._log, stderr=subprocess.STDOUT, env=env, cwd=ROOT,
        )
        self._ctl = None

    def log_tail(self, n=4000) -> str:
        with open(self.log_path, "rb") as f:
            return f.read()[-n:].decode("utf-8", "replace")

    def _await(self, pattern: str, timeout_s: float):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            m = re.search(pattern, self.log_tail(1 << 20))
            if m:
                return m
            if self.proc.poll() is not None:
                break
            time.sleep(0.05)
        raise RuntimeError(
            f"serve did not start (rc={self.proc.poll()}):\n"
            + self.log_tail())

    def address(self, timeout_s=300.0):
        m = self._await(r"listening on \('([^']+)', (\d+)\)", timeout_s)
        return m.group(1), int(m.group(2))

    def ask(self, **msg) -> dict:
        """One question to the launcher's side thread."""
        if self._ctl is None:
            port = int(self._await(
                r"perfbench launcher ctl (\d+)", 300.0).group(1))
            sock = socket.create_connection(("127.0.0.1", port), timeout=120)
            self._ctl = sock.makefile("rw")
            self._ctl_sock = sock
        self._ctl.write(json.dumps(msg) + "\n")
        self._ctl.flush()
        reply = json.loads(self._ctl.readline())
        if "error" in reply:
            raise RuntimeError(f"launcher: {reply['error']}")
        return reply

    def stop(self) -> int:
        """SIGTERM drain; the exit code is part of the result."""
        if self._ctl is not None:
            self._ctl.close()
            self._ctl_sock.close()
            self._ctl = None
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()
        return self.proc.returncode

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()


# ---- one request --------------------------------------------------------

class Cell:
    """What a run knows: the cell's files, its tables and where things
    live on disk."""

    def __init__(self, bench: dict, workload: str, rehearse: bool):
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
        self.entry = cells[workload]
        self.name = workload
        cfg_entry = {c["name"]: c for c in bench["configs"]}[
            self.entry["config"]]
        with open(os.path.join(ROOT, cfg_entry["file"])) as f:
            self.config = json.load(f)
        from perfbench import traffic

        self.traffic_spec = traffic.load(
            traffic.path_of(HERE, self.entry["traffic"]))
        # the table the cell's tasks scan, and no other, is made
        self.table = self.traffic_spec["table"]
        self.data_cfg = copy.deepcopy(self.config["data"])
        self.table_cfg = self.data_cfg["tables"][self.table]
        self.data_cfg["tables"] = {self.table: self.table_cfg}
        if rehearse:
            self.table_cfg["split_rows"] = self.config[
                "rehearsal_split_rows"]
        self.types = {c["name"]: c["type"]
                      for c in self.table_cfg["columns"]}
        self.end_to_end = [
            m for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])]
        self.per_layer = [
            m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload])]
        self.workdir = os.path.join(HERE, ".work", workload)
        self._templates = {}

    def template(self, name: str):
        if name not in self._templates:
            self._templates[name] = importlib.import_module(
                f"perfbench.templates.{name}")
        return self._templates[name]


def task_paths(cell: Cell, paths: dict, req: dict) -> tuple:
    """(what the request scans, where a shuffle write goes). A task reads
    its split through a hard link of its own, so that its scan has a path
    (and its plan a fingerprint) that no other task has."""
    stem = os.path.join(cell.workdir, "tasks", f"t{req['task']:06d}")
    scan = stem + ".parquet"
    if not os.path.exists(scan):
        os.link(paths[cell.table][req["split"]], scan)
    return scan, {"data": stem + ".data", "index": stem + ".index"}


def send(client, cell: Cell, paths: dict, req: dict) -> dict:
    """Build, submit, fetch, poll. The latency is submit to last frame
    fetched, on this client's clock; building the blob and the POLL that
    follows are outside it."""
    tmpl = cell.template(req["template"])
    rec = dict(req, out={}, ok=False, error=None, poll={}, answer=None,
               rows_out=0, latency_s=0.0, t_submit=time.time())
    t0 = None
    try:
        scan, rec["out"] = task_paths(cell, paths, req)
        blob = tmpl.build(scan, req["params"], rec["out"])
        rec["t_submit"] = time.time()
        t0 = time.perf_counter()
        st = client.submit(blob)
        batches = client.fetch(st["query_id"])
        rec["latency_s"] = time.perf_counter() - t0
        rec["poll"] = client.poll(st["query_id"])
        rec["rows_out"] = sum(b.num_rows for b in batches)
        rec["answer"] = tmpl.answer(batches, rec["out"])
    except Exception as e:  # noqa: BLE001 - a failed send is a result
        if t0 is not None and not rec["latency_s"]:
            rec["latency_s"] = time.perf_counter() - t0
        rec["error"] = f"{type(e).__name__}: {e}"
        log(f"send failed: {rec['error']}\n{traceback.format_exc()}")
    rec["t_done"] = rec["t_submit"] + rec["latency_s"]
    p = rec["poll"]
    done = p.get("state") == "DONE"
    clean = done and not (p.get("degraded") or p.get("retries")
                          or p.get("attempts"))
    rec["device_run"] = bool(clean and p.get("dispatches", 0) > 0
                             and not p.get("cache_hits"))
    # every task has to run on the device, with nothing hidden
    rec["ok"] = rec["error"] is None and rec["device_run"]
    return rec


# ---- phases -------------------------------------------------------------

def warm_up(cell: Cell, server: Server, make_client, traffic, paths) -> list:
    """Each template the window will send, on the cell's own shapes, until
    a send builds no more programs than the one before it."""
    records = []
    with make_client() as client:
        for entry in traffic.entries():
            last = None
            for _ in range(WARMUP_MAX_SENDS):
                before = len(server.ask(op="stats")["builds"])
                rec = send(client, cell, paths, traffic.warm_request(entry))
                built = len(server.ask(op="stats")["builds"]) - before
                records.append(rec)
                log(f"warm-up {entry['template']}: {rec['latency_s']:.3f}s "
                    f"builds={built} "
                    f"dispatches={rec['poll'].get('dispatches')} "
                    f"ok={rec['ok']}")
                if not rec["ok"] or (last is not None and built <= last):
                    break
                last = built
    return records


def window(cell: Cell, make_client, traffic, paths, seconds: float,
           on_start=None) -> tuple:
    """The closed loop: every stream sends its next request when its last
    one is fetched, and stops sending after `seconds`. Returns the records
    and the window's first and last instants (unix time)."""
    records, lock = [], threading.Lock()
    clients = [make_client() for _ in range(traffic.streams)]
    barrier = threading.Barrier(traffic.streams + 1)
    t_end = [None]

    def run(k: int):
        gen = traffic.stream(k)
        barrier.wait()
        failures = 0
        # a stream whose sends fail in a row has lost its server: stop it,
        # the run is incorrect already
        while time.time() < t_end[0] and failures < 3:
            rec = send(clients[k], cell, paths, next(gen))
            rec["stream"] = k
            failures = 0 if rec["error"] is None else failures + 1
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=run, args=(k,), name=f"stream-{k}")
               for k in range(traffic.streams)]
    for t in threads:
        t.start()
    t_start = time.time()
    t_end[0] = t_start + seconds
    barrier.wait()
    if on_start:
        on_start(t_start)
    for t in threads:
        t.join()
    for c in clients:
        c.close()
    t_last = max((r["t_done"] for r in records), default=time.time())
    return records, t_start, t_last


def judge(cell: Cell, tables: dict, records: list, seed: int) -> dict:
    """The comparison that decides `correct`: every answer of the window,
    or a sample of them drawn from the seed, against the plain reference.
    Returns {number: (worst reading, limit)}."""
    import numpy as np

    k = int(cell.traffic_spec.get("compare_sample", 0))
    chosen = list(range(len(records)))
    if k and len(records) > k:
        rng = np.random.default_rng([seed, 0xC0DE])
        longest = max(chosen, key=lambda i: records[i]["latency_s"])
        rest = [i for i in chosen if i != longest]
        chosen = [longest] + [int(i) for i in
                              rng.choice(rest, size=k - 1, replace=False)]
    wants, worst, lines = {}, {}, []
    for i in chosen:
        rec = records[i]
        tmpl = cell.template(rec["template"])
        key = (rec["template"], rec["split"],
               json.dumps(rec["params"], sort_keys=True))
        if key not in wants:
            wants[key] = tmpl.reference(
                tables[cell.table][rec["split"]], rec["params"])
        answer = rec["answer"]
        if callable(answer):
            answer = answer(cell.types)
        readings = tmpl.compare(wants[key], answer)
        lines.append((max(v / lim if lim else v
                          for v, lim in ((v, tmpl.LIMITS[n])
                                         for n, v in readings.items())),
                      f"request {i}: {rec['template']} "
                      f"{json.dumps(rec['params'])} split {rec['split']} "
                      f"latency {rec['latency_s']:.3f}s "
                      f"dispatches {rec['poll'].get('dispatches')} "
                      f"readings {json.dumps(readings)}"))
        for name, value in readings.items():
            limit = tmpl.LIMITS[name]
            if name not in worst or value > worst[name][0]:
                worst[name] = (value, limit)
    # the requests that read worst, for whoever has to find out why
    for _, line in sorted(lines, key=lambda x: -x[0])[:6]:
        log(line)
    return worst, len(chosen)


# ---- the run ------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    return run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                    args.rehearse)


def chip_found(device: dict, cell: Cell) -> bool:
    """The harness's look for a chip: the serving process reports a TPU
    with as many chips as the cell asks for."""
    return (device["platform"] == "tpu"
            and device["count"] >= cell.entry["chips"])


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearse: bool) -> int:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)

    # one process per chip: the child keeps the environment's backend,
    # this parent is pinned to the CPU before blaze_tpu imports jax
    child_env = dict(os.environ)
    child_env["PYTHONPATH"] = ROOT + os.pathsep + child_env.get(
        "PYTHONPATH", "")
    child_env.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(ROOT, "benchmarks", ".jax_cache"))
    os.environ["JAX_PLATFORMS"] = "cpu"
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    if importlib.util.find_spec("blaze_tpu") is None:
        raise SystemExit("perfbench measures the program: no blaze_tpu "
                         f"package under {ROOT}")
    cell = Cell(bench, workload, rehearse)
    # a cell that has not yet warmed up in this checkout may have to
    # compile, whatever other cells left in the cache
    cold = not os.path.exists(cell.workdir + ".warm")
    shutil.rmtree(cell.workdir, ignore_errors=True)
    os.makedirs(os.path.join(cell.workdir, "tasks"))
    server = Server(child_env, cell.workdir, cell.config["serve"]["flags"])

    def give_up():
        # the whole run is bounded: a send that met a surprise compile
        # ends as a failed run, not as a hang
        log("run limit reached: killing the server, no result\n"
            + server.log_tail())
        server.kill()
        os._exit(4)

    limit = COLD_LIMIT_S if cold else WARM_LIMIT_S
    watchdog = threading.Timer(limit - (time.time() - T0), give_up)
    watchdog.daemon = True
    watchdog.start()
    try:
        # the program builds its native host library on first use, through
        # one temporary name: parent and child building at once lose it,
        # and the loser runs its Python tier for the rest of its life. So
        # this parent builds it now, long before the child's first request
        # asks for it
        from blaze_tpu.runtime import native

        native.get_lib()
        return _run(cell, server, seed, seconds, trace, rehearse, cold)
    finally:
        watchdog.cancel()
        server.kill()


def _run(cell, server, seed, seconds, trace, rehearse, cold) -> int:
    from blaze_tpu.service.wire import ServiceClient

    from perfbench import datagen
    from perfbench.traffic import Traffic

    log(f"cell {cell.name} seed {seed} seconds {seconds:g} trace {trace} "
        f"{'first run of the cell here' if cold else 'warmed before'}")
    # the tables are made while the child starts
    tables = datagen.gen_tables(cell.data_cfg, cell.config["generator"],
                                seed)
    paths = datagen.ensure_parquet(
        os.path.join(HERE, ".data"), cell.config, cell.data_cfg, seed,
        tables)
    log(f"tables: {cell.table}, {len(paths[cell.table])} splits of "
        f"{cell.table_cfg['split_rows']} rows")
    host, port = server.address()

    def make_client():
        return ServiceClient(host, port, timeout=CLIENT_TIMEOUT_S)

    with make_client() as c:
        device = c.stats()["service"]["device"]
    log(f"device: {json.dumps(device)}")
    on_chip = chip_found(device, cell)
    if not on_chip and not rehearse:
        log(f"the serving process is on {device['platform']} x"
            f"{device['count']}, the cell asks for tpu x"
            f"{cell.entry['chips']}: no result")
        server.stop()
        return 3

    traffic = Traffic(cell.traffic_spec, seed, len(paths[cell.table]))
    warm = warm_up(cell, server, make_client, traffic, paths)
    setup_failed = sum(not r["ok"] for r in warm)
    if not setup_failed:
        with open(cell.workdir + ".warm", "w"):
            pass

    tracer = {}

    def trace_later(t_start: float):
        """A steady slice inside the window, from a thread of its own."""
        span = min(TRACE_SECONDS, 0.5 * seconds)

        def go():
            try:
                time.sleep(max(0.0, t_start + 0.3 * seconds - time.time()))
                server.ask(op="trace_start",
                           dir=os.path.join(cell.workdir, "trace"))
                time.sleep(span)
                tracer.update(server.ask(op="trace_stop"))
            except Exception as e:  # noqa: BLE001 - reported in the result
                tracer["error"] = f"{type(e).__name__}: {e}"

        tracer["thread"] = threading.Thread(target=go, name="tracer")
        tracer["thread"].start()

    setup_s = time.time() - T0
    records, t_start, t_last = window(
        cell, make_client, traffic, paths, seconds,
        on_start=trace_later if trace else None)
    if "thread" in tracer:
        tracer.pop("thread").join()
        log(f"trace: {json.dumps(tracer)}")
    whole_s = t_last - t_start
    log(f"window: {len(records)} requests in {whole_s:.2f}s")

    stats = server.ask(op="stats")
    peaks = [p for p in server.ask(op="memory")["peak_bytes_in_use"]
             if p is not None]
    rc = server.stop()
    log(f"server: SIGTERM drain rc={rc}")

    # the reference runs when the server is gone and its memory read
    worst, compared = judge(cell, tables, records, seed)
    failed = sum(not r["ok"] for r in records)
    worst["requests_failed"] = (failed, 0)
    worst["setup_sends_failed"] = (setup_failed, 0)
    worst["server_exit_code"] = (abs(rc), 0)
    worst["not_on_tpu"] = (0 if on_chip else 1, 0)
    correct = all(v <= lim for v, lim in worst.values()) and bool(records)
    compared_line = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in worst.items()}
    compared_line["answers_compared"] = compared

    lat_ms = [1e3 * r["latency_s"] for r in records if r["ok"]]
    done_ok = len(lat_ms)
    run = {
        "cell": cell, "records": records, "t_start": t_start,
        "t_last": t_last, "whole_s": whole_s, "stats": stats,
        "device": device, "trace": None,
    }
    values = {
        "setup_s": setup_s,
        "queries_per_s": done_ok / whole_s if whole_s > 0 else None,
        "latency_p50_ms": statistics.median(lat_ms) if lat_ms else None,
    }
    out_device = dict(device,
                      memory_peak_bytes=max(peaks) if peaks else None)
    breakdown = None
    if trace:
        from perfbench import layers

        values, breakdown = layers.read_all(run, tracer, out_device)
        if run["trace"] and run["trace"].get("devices"):
            log("trace: launches by program "
                f"{json.dumps(run['trace']['launches'])}, matched "
                f"{json.dumps(run['trace']['kernel_events'])}")
        wanted = cell.per_layer
    else:
        wanted = cell.end_to_end
    prefix = "rehearsal." if rehearse else ""
    metrics = {
        prefix + m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in wanted if values.get(m["name"]) is not None
    }
    result = {
        "correct": bool(correct),
        "attempted": len(records),
        "failed": failed,
        "metrics": metrics,
        "device": out_device,
    }
    if rehearse:
        result["rehearsal"] = True
    if breakdown:
        result["breakdown"] = breakdown
    result["compared"] = compared_line
    shutil.rmtree(cell.workdir, ignore_errors=True)
    print(json.dumps(result), flush=True)
    for k, v in compared_line.items():
        print(f"compared {k}: {json.dumps(v)}", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
