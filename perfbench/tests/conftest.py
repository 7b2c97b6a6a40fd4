"""These tests run by hand and in rehearsal, on the CPU: four forced host
devices, set before jax is first imported (as tests/conftest.py does for
the program's own suite)."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4"
    ).strip()
