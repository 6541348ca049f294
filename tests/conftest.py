"""Test bootstrap: fake 8-device CPU mesh.

Must run before `jax` is first imported anywhere in the test process.  This is
JAX's standard fake-multi-device mechanism (SURVEY.md §4): the TPU-world
equivalent of a fake distributed backend, letting every sharding/collective
path compile and execute on CI hardware.  The real-chip path is exercised by
`chip_smoke.py` at the repo root, through the chip tool.
"""

import os

# Force CPU even on a machine with a chip: unit tests always run on the fake
# 8-device CPU mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, "conftest failed to fake 8 CPU devices"
    return devs[:8]
