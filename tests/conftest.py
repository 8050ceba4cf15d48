"""Test config: run on CPU with 8 virtual devices so multi-chip sharding
logic is exercised without TPU hardware (the reference could only test
multi-node on a real cluster; XLA's host-platform device simulation does
better). Both variables must be set before the CPU client initializes.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

assert len(jax.devices()) == 8, jax.devices()
