"""Launcher implementation (launch/main.py + controllers/collective.py analog)."""

from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional

ELASTIC_EXIT_CODE = 101  # fleet/elastic/manager.py:32 analog


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _local_addresses() -> set:
    """Every address this host answers to (names + resolved IPs)."""
    addrs = {"127.0.0.1", "localhost", socket.gethostname()}
    try:
        _, aliases, ips = socket.gethostbyname_ex(socket.gethostname())
        addrs.update(aliases)
        addrs.update(ips)
    except OSError:
        pass
    return addrs


def _is_local_host(host: str) -> bool:
    if host in _local_addresses():
        return True
    try:
        return socket.gethostbyname(host) in _local_addresses()
    except OSError:
        return False


def _routable_ip(master_host: str) -> str:
    """The local IP a peer would reach us on (UDP-connect trick)."""
    try:
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            s.connect((master_host, 1))
            return s.getsockname()[0]
    except OSError:
        return "127.0.0.1"


def _build_env(rank: int, nprocs: int, master: str, base: Dict[str, str],
               cpu_sim: bool, log_dir: Optional[str],
               sim_devices: int = 1) -> Dict[str, str]:
    env = dict(base)
    env.update({
        # paddle-compat names (launch/controllers/collective.py env set)
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(nprocs),
        "PADDLE_MASTER": master,
        "MASTER_ADDR": master.split(":")[0],
        "MASTER_PORT": master.split(":")[1],
        "PADDLE_RANK_IN_NODE": str(rank),
        # jax.distributed picks these up via init_parallel_env
        "PADDLE_TPU_LAUNCHED": "1",
    })
    if cpu_sim:
        # each simulated worker is an independent CPU "host" with
        # ``sim_devices`` virtual devices (init_parallel_env reads the
        # count from PADDLE_TPU_CPU_SIM)
        env["PADDLE_TPU_CPU_SIM"] = str(sim_devices)
        env["JAX_PLATFORMS"] = "cpu"
        flags = env.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={sim_devices}")
    return env


class Pod:
    """A set of local worker processes (launch/job/pod.py analog)."""

    def __init__(self):
        self.procs: List[subprocess.Popen] = []
        self.logs: List[Optional[object]] = []

    def spawn(self, cmd: List[str], envs: List[Dict[str, str]],
              log_dir: Optional[str]):
        if log_dir:
            os.makedirs(log_dir, exist_ok=True)
        for rank, env in enumerate(envs):
            out = None
            if log_dir:
                out = open(os.path.join(log_dir, f"workerlog.{rank}"), "w")
            self.logs.append(out)
            self.procs.append(subprocess.Popen(
                cmd, env=env, stdout=out or None, stderr=out or None))

    def poll(self) -> Optional[int]:
        """None while running; 0 when all exited cleanly; first bad code
        (rest killed) on failure."""
        codes = [p.poll() for p in self.procs]
        if all(c == 0 for c in codes):
            return 0
        bad = [c for c in codes if c not in (None, 0)]
        if bad:
            self.terminate()
            return bad[0]
        return None

    def close_logs(self):
        for f in self.logs:
            if f:
                f.close()
        self.logs = []

    def watch(self, tick=None) -> int:
        """Block until all exit (0) or any fails (its code); kill the rest.
        ``tick()`` runs each poll interval — the elastic watcher hook; a
        non-None return terminates the pod with that code."""
        try:
            while True:
                code = self.poll()
                if code is not None:
                    return code
                if tick is not None:
                    t = tick()
                    if t is not None:
                        self.terminate()
                        return t
                time.sleep(0.2)
        finally:
            self.close_logs()

    def terminate(self):
        for p in self.procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.time() + 5
        for p in self.procs:
            try:
                p.wait(max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                p.kill()


def launch(script: str, script_args: List[str] = (), nproc_per_node: int = 1,
           master: Optional[str] = None, log_dir: Optional[str] = None,
           cpu_sim: bool = False, max_restarts: int = 0,
           elastic: bool = False, np_min: int = 1,
           np_max: Optional[int] = None, elastic_ttl: float = 6.0,
           sim_devices: int = 1) -> int:
    """Programmatic launch (spawn.py:450-style entry); returns exit code.

    ``max_restarts`` > 0 enables elastic behavior: workers exiting with
    ``ELASTIC_EXIT_CODE`` (or crashing) are relaunched with a fresh
    rendezvous, up to the limit (fleet/elastic/manager.py:126 analog).

    ``elastic=True`` additionally runs TTL-heartbeat membership over the
    rendezvous TCPStore: this node registers a lease and watches for
    joined/dead peers; a membership change (within ``[np_min, np_max]``)
    triggers a relaunch with refreshed endpoints — the reference's etcd
    watcher semantics, without the etcd dependency.
    """
    master = master or f"127.0.0.1:{_free_port()}"
    cmd = [sys.executable, "-u", script, *script_args]

    from .. import elastic as elastic_mod

    manager = None
    if elastic:
        from ..store import TCPStore

        host, port = master.split(":")
        store_port = int(port) + 1  # heartbeat store next to rendezvous
        is_master = _is_local_host(host)
        try:
            store = TCPStore(host, store_port, is_master=is_master)
        except OSError:
            store = TCPStore(host, store_port, is_master=False)
        local_ip = _routable_ip(host)
        manager = elastic_mod.ElasticManager(
            store, node_id=f"{local_ip}:{os.getpid()}",
            endpoint=f"{local_ip}:{store_port}",
            np_min=np_min, np_max=np_max, ttl=elastic_ttl)
        manager.register()

    def elastic_tick():
        if manager is None:
            return None
        status = manager.watch()
        if status == elastic_mod.ElasticStatus.RESTART:
            print("[launch] membership changed; endpoints now "
                  f"{manager.endpoints()}", file=sys.stderr)
            return ELASTIC_EXIT_CODE
        return None

    restarts = 0
    try:
        while True:
            envs = [
                _build_env(r, nproc_per_node, master, dict(os.environ),
                           cpu_sim, log_dir, sim_devices=sim_devices)
                for r in range(nproc_per_node)
            ]
            if manager is not None:
                eps = manager.endpoints()
                for e in envs:
                    e["DISTRIBUTED_TRAINER_ENDPOINTS"] = eps
                manager.snapshot()
            pod = Pod()
            pod.spawn(cmd, envs, log_dir)
            code = pod.watch(tick=elastic_tick)
            if code == 0:
                return 0
            if manager is not None and code == ELASTIC_EXIT_CODE:
                # membership change: relaunch with refreshed endpoints —
                # scale events never consume the crash-restart budget
                master_host = master.split(":")[0]
                master = f"{master_host}:{_free_port()}"
                continue
            if restarts >= max_restarts:
                return code
            restarts += 1
            master_host = master.split(":")[0]
            master = f"{master_host}:{_free_port()}"  # rendezvous regen
            print(f"[launch] worker failed (exit {code}); elastic restart "
                  f"{restarts}/{max_restarts}", file=sys.stderr)
    finally:
        if manager is not None:
            manager.deregister()


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="Launch distributed training "
                    "(paddle.distributed.launch analog)")
    p.add_argument("--nnodes", type=int, default=1,
                   help="number of hosts (informational on TPU pods)")
    p.add_argument("--nproc_per_node", "--devices", dest="nproc_per_node",
                   type=lambda v: len(v.split(",")) if "," in str(v) else int(v),
                   default=1, help="worker processes on this host "
                   "(CPU-sim) — on TPU keep 1 per host")
    p.add_argument("--master", default=None, help="rendezvous addr host:port")
    p.add_argument("--log_dir", default=None)
    p.add_argument("--backend", default=None,
                   help="'cpu' forces CPU-simulation workers")
    p.add_argument("--sim_devices", type=int, default=1,
                   help="virtual CPU devices per cpu-sim worker "
                        "(>1 implies --backend cpu)")
    p.add_argument("--max_restarts", type=int, default=0)
    p.add_argument("--elastic", action="store_true",
                   help="TTL-heartbeat membership over the TCPStore")
    p.add_argument("--np_min", type=int, default=1)
    p.add_argument("--np_max", type=int, default=None)
    p.add_argument("--elastic_ttl", type=float, default=6.0)
    p.add_argument("script")
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)

    return launch(
        args.script, args.script_args,
        nproc_per_node=args.nproc_per_node, master=args.master,
        log_dir=args.log_dir,
        cpu_sim=(args.backend == "cpu" or args.sim_devices > 1),
        max_restarts=args.max_restarts, elastic=args.elastic,
        np_min=args.np_min, np_max=args.np_max,
        elastic_ttl=args.elastic_ttl, sim_devices=args.sim_devices)


if __name__ == "__main__":
    sys.exit(main())
