"""On-device test lane (run on real TPU hardware; see scripts/run_tpu_tests.sh).

Unlike tests/conftest.py this does NOT force the CPU platform — the whole
point of this lane is to exercise the Pallas kernels on the hardware that
runs them in production. The lane is only ever run on purpose, so a
session that finds no TPU FAILS at collection: a skip would read as green.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import pytest  # noqa: E402

from distributedtraining_tpu.utils.platform import (  # noqa: E402
    enable_compile_cache)

enable_compile_cache()


def pytest_sessionstart(session):
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise pytest.UsageError(
            f"tests_tpu/ needs TPU hardware; jax found platform="
            f"{dev.platform!r} device_kind={dev.device_kind!r}")
    print(f"tests_tpu: platform={dev.platform} "
          f"device_kind={dev.device_kind} count={len(jax.devices())}")


@pytest.fixture(scope="session")
def tpu_device():
    return jax.devices()[0]
