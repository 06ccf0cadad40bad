import os

# Tests run on the CPU backend with 8 virtual devices (the mesh and shard
# tests need more than one); the chip is exercised by chip_smoke.py.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
