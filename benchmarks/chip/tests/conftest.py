import os
import sys
from pathlib import Path

# the CPU backend with four virtual devices, as in the repo's tests/, so
# the four-chip cell's mesh path runs here too; set before jax initializes
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               "--xla_force_host_platform_device_count=4"
                               ).strip()

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
