"""`chip_smoke.py` on the CPU: every phase runs, and the run still fails.

The smoke is the driver's proof that the served path starts on the
chip, so the one thing it must never do is pass without one. Here
(`JAX_PLATFORMS=cpu`) it walks all 12 sends through a real
`serve` child, matches each against its oracle, sees device dispatches
on each - and exits non-zero with `"ok": false, "platform": "cpu"`.
"""

import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cpu_run_walks_every_phase_and_fails():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # the smoke is one device; conftest's eight virtual ones are not
    # part of what it rehearses
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--rows", "65536"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    out = proc.stdout
    assert proc.returncode != 0, out + proc.stderr
    lines = out.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is False
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    sends = [ln for ln in lines if ln.startswith("send ")]
    assert len(sends) == 12, out + proc.stderr
    for ln in sends:
        assert " matched " in ln, ln
        assert int(re.search(r"dispatches=(\d+)", ln).group(1)) > 0, ln
        assert "cache_hits=0 " in ln and "device_ok=True" in ln, ln
    assert "server: SIGTERM drain rc=0" in out
