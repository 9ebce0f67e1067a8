"""``paddle.distributed.spawn`` analog (``spawn.py:450``): fork N worker
processes running ``func`` with rendezvous env injected.

TPU-first note: on a real pod you launch one controller per host (use
``paddle_tpu.distributed.launch``); ``spawn`` exists for the CPU-simulation
path and API parity — each child is an independent CPU "host" with
``sim_devices`` virtual devices (default 1, the reference's per-GPU fork
semantics)."""

from __future__ import annotations

import multiprocessing as mp
import os
import socket
from typing import Optional, Tuple


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _worker(func, rank: int, nprocs: int, master: str, args: Tuple,
            sim_devices: int):
    os.environ.update({
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(nprocs),
        "PADDLE_MASTER": master,
        "MASTER_ADDR": master.split(":")[0],
        "MASTER_PORT": master.split(":")[1],
        # consumed by init_parallel_env: virtual device count
        "PADDLE_TPU_CPU_SIM": str(sim_devices),
    })
    func(*args)


def spawn(func, args=(), nprocs: int = 1, join: bool = True,
          daemon: bool = False, **options):
    """Run ``func(*args)`` in ``nprocs`` processes; returns the context.

    ``sim_devices=<n>`` (option): virtual CPU devices per worker in the
    CPU-simulation path (default 1 — the reference's per-GPU fork shape)."""
    master = options.get("master") or f"127.0.0.1:{_free_port()}"
    sim_devices = int(options.get("sim_devices", 1))
    # jax reads JAX_PLATFORMS when it is imported, which in a child happens
    # while _worker is being unpickled: the variable has to be in the
    # environment the children start with (this process has imported jax
    # already and is not affected)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    ctx = mp.get_context("spawn")
    procs = []
    for rank in range(nprocs):
        p = ctx.Process(target=_worker,
                        args=(func, rank, nprocs, master, tuple(args),
                              sim_devices),
                        daemon=daemon)
        p.start()
        procs.append(p)

    class Context:
        def __init__(self, procs):
            self.processes = procs

        def join(self, timeout: Optional[float] = None):
            for p in self.processes:
                p.join(timeout)
            bad = [p.exitcode for p in self.processes if p.exitcode]
            if bad:
                raise RuntimeError(f"spawned worker failed: exit {bad[0]}")

    c = Context(procs)
    if join:
        c.join()
    return c
