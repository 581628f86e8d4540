"""Root pytest config: force an 8-device virtual CPU mesh for all tests.

Multi-chip TPU hardware is not available in this environment; sharding and
collective paths are validated on XLA's host platform with 8 virtual devices
(the driver separately dry-runs the multi-chip path via __graft_entry__).

The platform choice freezes at first backend init, so it is pinned here,
in the environment and in jax's config, before any computation runs.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
