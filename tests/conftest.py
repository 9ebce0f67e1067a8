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


@pytest.fixture(autouse=True, scope="module")
def _no_mesh_from_another_file():
    """A test file starts with no global mesh, whichever file the worker
    ran before it: ``topology`` keeps the mesh in a module global, and a
    file whose last engine was built at ``mp=2`` used to hand it on (the
    next file's engines then read ``mp=2``)."""
    from paddle_tpu.distributed import topology

    topology.set_mesh(None)
    yield


@pytest.fixture(scope="session")
def hold_intake():
    """``hold_intake(replica)`` keeps a fleet replica's engine thread from
    taking anything in until the returned event is set.  What it then
    finds queued it meets in one piece and in submission order, so the
    step, launch and cache counts of a fixed stream do not depend on how
    the submitting thread and the engine threads interleave."""
    import threading

    def hold(replica):
        gate = threading.Event()

        def held(real=replica._drain_submissions):
            gate.wait()
            real()

        replica._drain_submissions = held
        return gate

    return hold
