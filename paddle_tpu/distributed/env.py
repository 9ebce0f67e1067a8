"""Process/topology environment.

Single-controller JAX replaces the reference's per-rank process model
(SURVEY.md §7 hard part (f)): one Python process drives all local devices;
multi-host runs have one controller per host coordinated by
``jax.distributed``.  "rank" maps to ``jax.process_index()`` and data-parallel
shard index; the reference's env vars (PADDLE_TRAINER_ID...) are honored when
set by the launcher.
"""

from __future__ import annotations

import os

import jax

_initialized = False


def init_parallel_env(strategy=None):
    """``paddle.distributed.init_parallel_env`` (parallel.py:943 analog).

    Multi-host: uses jax.distributed.initialize (coordination service =
    TCPStore analog, tcp_store.h:121). Single-host: no-op.

    ``PADDLE_TPU_CPU_SIM=<n>`` (set by the cpu-sim launcher/spawn path,
    next to ``JAX_PLATFORMS=cpu``): this worker is a simulated CPU "host"
    with ``n`` virtual devices.
    """
    global _initialized
    if _initialized:
        return
    sim = os.environ.get("PADDLE_TPU_CPU_SIM")
    if sim:
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={int(sim)}")
    coord = os.environ.get("PADDLE_MASTER") or os.environ.get("MASTER_ADDR")
    nprocs = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    pid = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    if coord and nprocs > 1 and not jax.distributed.is_initialized():
        port = os.environ.get("MASTER_PORT", "8476")
        jax.distributed.initialize(
            coordinator_address=f"{coord.split(':')[0]}:{port}",
            num_processes=nprocs,
            process_id=pid,
        )
    _initialized = True


def get_rank(group=None) -> int:
    return jax.process_index()


def get_world_size(group=None) -> int:
    # world size in paddle terms = number of devices participating in DP;
    # for the single-controller runtime this is the process count unless a
    # mesh is active (then the dp axis size).
    from .topology import get_hybrid_communicate_group

    hcg = get_hybrid_communicate_group()
    if hcg is not None:
        return hcg.get_data_parallel_world_size()
    return jax.process_count()


def is_initialized() -> bool:
    return _initialized
