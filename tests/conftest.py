"""Test harness: an 8-device virtual CPU mesh.

This is the "fake backend" the reference never had (SURVEY.md §4): XLA's
host-platform device-count flag gives 8 independent CPU devices, so every
mesh/sharding/collective path is exercised without TPU hardware. Must run
before jax is imported anywhere.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
# Force CPU: the session env may pin JAX_PLATFORMS to a real TPU backend,
# but the test suite always runs on the virtual 8-device CPU mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
# The CLIs' persistent compile cache is on by default, and the child
# processes the suite starts share it (<checkout>/.jax_cache): ~100 s off
# the tier-1 wall. XLA:CPU logs a multi-KB ERROR line on every cache hit
# ("Loading XLA:CPU AOT result ... machine feature"), hundreds per run,
# which fills the pipe of any child whose stderr a test does not drain
# (test_obs's SIGTERM drill hung on it); XLA's own log level keeps the
# children quiet. Python errors and tracebacks are unaffected.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
# Determinism and small-host friendliness.
os.environ.setdefault("TPUDIST_TEST", "1")

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs
