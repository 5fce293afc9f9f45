"""Test fixtures. Tests must see the real single CPU device: a test that
needs several CPU devices starts a subprocess with the device-count flag
(``tests/test_multidevice.py``)."""
import os

import jax
import pytest

assert "xla_force_host_platform_device_count" not in \
    os.environ.get("XLA_FLAGS", ""), \
    "tests must not run with placeholder devices"


@pytest.fixture(scope="session")
def single_device():
    return jax.devices()[0]
