"""Test environment: CPU backend with 8 virtual devices.

Mirrors the reference's no-real-cluster trick (SURVEY.md §4): every
parallelism test runs on a simulated 8-device CPU mesh, exactly like the
reference's gloo/CPU backend parameterization
(test/auto_parallel/test_semi_auto_parallel_basic.py:27).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle

    paddle.seed(1234)
    np.random.seed(1234)
    yield
