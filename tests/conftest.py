import os

# Tests run on the CPU backend with 8 virtual devices (the mesh and shard
# tests need more than one); the chip is exercised by chip_smoke.py.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest


@pytest.fixture(autouse=True)
def layout_manifest(monkeypatch):
    """Every test process shares the checkout's ``.xla_cache``, and with it the
    layout manifest beside it: a test that builds an evaluator on a table some
    other test has served would find that table's layouts loaded ahead of its
    own flights, and count other compiles, hits and jit-cache keys than it
    made. So in this process nothing is recorded and nothing is loaded; a
    module that tests the manifest overrides this fixture with one that points
    it at a directory of its own (tests/test_layout_manifest.py). Servers
    that tests start as processes of their own keep theirs, as deployed."""
    from cerbos_tpu.tpu import layoutmanifest

    monkeypatch.setattr(layoutmanifest, "path", lambda: None)
