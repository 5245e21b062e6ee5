"""Test configuration: an 8-device virtual CPU platform.

Tests never touch an accelerator: this sandbox has none, and sharding
correctness is tested on a virtual CPU mesh. `JAX_PLATFORMS=cpu` and the
XLA host-device-count flag are set here, before JAX is first imported, so
a bare `pytest` behaves like the driver's command. The chip is reached
only through `python chip_smoke.py` (see .claude/skills/verify/SKILL.md);
`tests/test_chip_compile.py` asks the TPU *compiler* about the kernels
without a chip, from a fixture of its own.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
