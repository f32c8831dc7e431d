"""Test harness: force an 8-device virtual CPU mesh (SURVEY.md §4 template —
the TPU analog of the reference's localhost multi-process NCCL tests).

The suite runs on the CPU backend whatever the machine holds. JAX_PLATFORMS is an
ordinary environment variable that jax honours: it is set here for every child
process a test starts, and the config update pins this process too in case a pytest
plugin imported jax before this file ran. (The chip is exercised by chip_smoke.py,
not by pytest; tests/test_chip_compile.py compiles for a described TPU without one.)
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_tape():
    """Isolate the global autograd tape between tests."""
    from paddle_tpu.core.tape import global_tape

    global_tape().clear()
    yield
    global_tape().clear()


@pytest.fixture
def seed():
    import numpy as np

    import paddle_tpu as paddle

    np.random.seed(0)
    paddle.seed(0)
    return 0


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process/subprocess tests (seconds-scale)")
