import os

# Tests run on the CPU backend with four virtual devices, so the mesh-sharded
# planes (tests/test_mesh_shard.py) run in every test lane.  Everything else
# places on device 0 exactly as on a one-device host.  Must be set before jax
# initializes its backends; a device count the caller forced already wins.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               "--xla_force_host_platform_device_count=4"
                               ).strip()

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)
