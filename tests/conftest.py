"""Test harness: force an 8-device virtual CPU mesh so every multi-chip code
path (shard_map over jax.sharding.Mesh) compiles and runs without TPU hardware,
mirroring how the driver's dryrun validates sharding.  Pallas kernels run in
interpret mode here; tests/test_tpu_compile.py compiles them for a described
v5e chip instead.

jax reads JAX_PLATFORMS when it is imported, so mutating os.environ here is
too late for the platform choice — use jax.config instead.  XLA_FLAGS is read
lazily at CPU client creation, so setting it here still works.  The
persistent compilation cache stays off: a CLI test that places it
(util/compile_cache.py) must not make later tests read or write it.
"""

import os

import jax
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "live: opt-in integration tests against REAL store/sink "
        "endpoints (env-gated; see tests/test_live_drivers.py and "
        "deploy/README.md)")
    config.addinivalue_line(
        "markers",
        "tpu: opt-in byte-identity gate on the REAL TPU chip "
        "(SEAWEED_TEST_TPU=1; see tests/test_real_tpu.py)")
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 gate (-m 'not slow'); run "
        "explicitly with -m slow")


def pytest_collection_modifyitems(config, items):
    # SEAWEED_TEST_TPU=1 disables the CPU pin process-wide, so running
    # anything BUT the tpu-marked tests in that mode would put the whole
    # suite on the wrong platform (1 chip instead of the 8-device
    # virtual mesh).  Fail fast instead of flaking later.
    if os.environ.get("SEAWEED_TEST_TPU") == "1":
        stray = [i.nodeid for i in items
                 if not i.get_closest_marker("tpu")]
        if stray:
            raise pytest.UsageError(
                "SEAWEED_TEST_TPU=1 runs ONLY tests/test_real_tpu.py "
                f"(-m tpu); collected non-tpu tests: {stray[:3]}...")

if os.environ.get("SEAWEED_TEST_TPU") == "1":
    # opt-in real-chip gate (tests/test_real_tpu.py): keep JAX's
    # default platform (the TPU) instead of pinning the virtual CPU
    # mesh.  Run this mode as a dedicated
    # process on ONLY the tpu-marked file — the rest of the suite
    # expects the 8-device CPU mesh.
    pass
else:
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_compilation_cache", False)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
