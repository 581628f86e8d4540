#!/usr/bin/env python
"""Standalone repro: jaxlib 0.9.0 CPU-client segfault under cumulative
compilation volume of LARGE MANY-OUTPUT programs in one process.

History (rounds 2-3 of this build): the full TPC-DS differential suite
run in a single process reliably dies with SIGSEGV inside
`backend_compile_and_load` after a few hundred query compilations. The
round-3 bisect (docs/JAXLIB_SEGFAULT.md) excluded:
  - thread concurrency        (BLAZE_TASK_THREADS=1 still crashes)
  - the engine's C++ tier     (BLAZE_DISABLE_NATIVE=1 still crashes)
  - executable eviction       (cache cap 0 + no clears still crash)
  - the legacy thunk runtime  (crashes later, same signature)
and a 3000-compile loop of SMALL programs survives - the trigger is
specifically large programs with MANY OUTPUTS (the engine's fused
aggregate kernels return dozens of state arrays) compiled at volume.

This script is that observation distilled: it compiles structurally
distinct many-output programs (default 96 outputs each, ~150 fused ops)
in a loop, printing progress per compile so the crash point is visible.
On this environment's jaxlib it is expected to die with SIGSEGV
(rc -11) before reaching the target count; on a fixed jaxlib it exits 0.

Usage:
    python benchmarks/jaxlib_segfault_repro.py [n_programs] [n_outputs]
    # defaults: 600 programs x 96 outputs; ~20-40 min on one core.
    # Survives? Raise n_programs; the suite crashed between ~200 and
    # ~500 structurally-distinct compilations.

Upgrade test: this image forbids pip installs, so "try jaxlib HEAD" is
documented as the exit rather than executed here. To run it elsewhere:
    python -m venv /tmp/v && . /tmp/v/bin/activate
    pip install -U jax jaxlib
    python benchmarks/jaxlib_segfault_repro.py
If a newer jaxlib survives, drop the cache clear of tests/conftest.py
and the child process of tests/test_tpcds_exchange.py (OWN_PROCESS),
and see what the gate's time becomes.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def build_program(seed: int, n_outputs: int):
    """One structurally distinct many-output program shaped like the
    engine's fused aggregate kernels: elementwise chains + segment
    reductions fanning out to dozens of state arrays."""
    import jax
    import jax.numpy as jnp

    def fn(x, g):
        outs = []
        y = x
        for i in range(n_outputs):
            # vary structure per seed AND per output so nothing hits
            # the compilation cache
            k = (seed * 131 + i * 17) % 7
            y = y * (1.0 + 0.001 * k) + jnp.float32(i)
            if k % 3 == 0:
                y = jnp.where(y > 50.0, y - 25.0, y)
            s = jax.ops.segment_sum(
                y, g, num_segments=256 + (seed % 13)
            )
            outs.append(s)
            if k % 2 == 0:
                outs.append(jnp.sum(y) * jnp.float32(seed + 1))
        return outs

    return jax.jit(fn)


def main() -> int:
    n_programs = int(sys.argv[1]) if len(sys.argv) > 1 else 600
    n_outputs = int(sys.argv[2]) if len(sys.argv) > 2 else 96

    import numpy as np

    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")
    print(
        f"jax {jax.__version__} jaxlib "
        f"{getattr(jax, 'lib', None) and jax.lib.__version__}; "
        f"{n_programs} programs x {n_outputs} outputs",
        flush=True,
    )
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.random(1 << 16).astype(np.float32))
    g = jnp.asarray(
        rng.integers(0, 256, 1 << 16).astype(np.int32)
    )
    for i in range(n_programs):
        fn = build_program(i, n_outputs)
        out = fn(x, g)
        jax.block_until_ready(out)
        del fn, out
        print(f"compiled {i + 1}/{n_programs}", flush=True)
    print("SURVIVED: no segfault at this volume", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
