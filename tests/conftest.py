"""Test configuration: force a deterministic 8-device virtual CPU mesh.

Multi-chip sharding is validated on a virtual CPU mesh
(xla_force_host_platform_device_count), per the TPU-rebuild test strategy.
On the CPU ops/dispatch.py takes the lax references, so what the chip is
asked to compile is guarded separately (tests/test_chip_kernels.py) and
proven by ``python chip_smoke.py`` on the TPU, not here.

The platform is pinned twice, through the environment and through
jax.config, so a JAX_PLATFORMS inherited from the machine cannot send
the suite to an accelerator.
"""

import os

# must be set before jax is imported anywhere in the test session
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
