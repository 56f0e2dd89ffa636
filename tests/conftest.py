"""Test harness configuration.

Tests run on CPU with a virtual 8-device platform so multi-chip sharding
compiles and executes without TPU hardware.  These env vars must be set
before JAX is imported anywhere in the test process.
"""

import os

# Tests always run on the CPU, whatever the environment preselects:
# they validate multi-chip sharding on the virtual 8-device mesh, and a
# chip belongs to one process at a time — it is reserved for
# chip_smoke.py and benchmarks/run.py, run through the chip tool.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# JAX reads JAX_PLATFORMS when it is imported; should a plugin have
# imported it before this file ran, the config value still decides.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
os.environ.setdefault("TOKENIZERS_PARALLELISM", "false")
