"""Test configuration: run JAX on a simulated 8-device CPU mesh.

The reference has no multi-node surface to test (SURVEY.md §4); our mesh
merges are tested without TPU hardware by forcing the CPU backend to expose
8 virtual devices, so shard_map/psum paths execute for real in CI.  The
driver runs the suite with ``JAX_PLATFORMS=cpu``; the config pin below keeps
a bare ``pytest`` off an attached chip too.  The chip itself is driven by
``chip_smoke.py``, never by the test suite.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
