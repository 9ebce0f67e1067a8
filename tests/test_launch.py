"""Launcher tests: multi-process CPU-sim pod, env injection, elastic restart
(the reference's CommunicationTestDistBase / elastic pattern, SURVEY.md §4)."""

import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.launch.main import ELASTIC_EXIT_CODE, launch


def _write(tmp_path, name, body):
    p = tmp_path / name
    p.write_text(textwrap.dedent(body))
    return str(p)


def test_launch_two_workers_env(tmp_path):
    script = _write(tmp_path, "worker.py", f"""
        import os
        rank = os.environ["PADDLE_TRAINER_ID"]
        assert os.environ["PADDLE_TRAINERS_NUM"] == "2"
        assert os.environ["MASTER_ADDR"] == "127.0.0.1"
        open(r"{tmp_path}/rank_" + rank, "w").write("ok")
    """)
    code = launch(script, nproc_per_node=2, cpu_sim=True,
                  log_dir=str(tmp_path / "logs"))
    assert code == 0
    assert (tmp_path / "rank_0").exists()
    assert (tmp_path / "rank_1").exists()
    assert (tmp_path / "logs" / "workerlog.0").exists()


def test_launch_failure_propagates(tmp_path):
    script = _write(tmp_path, "bad.py", """
        import sys
        sys.exit(3)
    """)
    assert launch(script, nproc_per_node=2, cpu_sim=True) == 3


def test_elastic_restart(tmp_path):
    marker = tmp_path / "attempted"
    script = _write(tmp_path, "flaky.py", f"""
        import os, sys
        m = r"{marker}"
        if not os.path.exists(m):
            open(m, "w").write("1")
            sys.exit({ELASTIC_EXIT_CODE})   # simulated preemption
        # second attempt succeeds
    """)
    code = launch(script, nproc_per_node=1, cpu_sim=True, max_restarts=2)
    assert code == 0
    assert marker.exists()


def test_cli_entry(tmp_path):
    script = _write(tmp_path, "hello.py", """
        import os
        print("rank", os.environ["PADDLE_TRAINER_ID"])
    """)
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--backend", "cpu", script],
        capture_output=True, text=True, cwd="/root/repo",
        env={**os.environ,
             "JAX_PLATFORMS": "cpu",  # tests never take the chip
             "PYTHONPATH": "/root/repo:" + os.environ.get("PYTHONPATH", "")})
    assert out.returncode == 0, out.stderr


class TestElasticMembership:
    """TTL-heartbeat membership (fleet/elastic/manager.py:126 analog)."""

    def test_lease_expiry_marks_dead(self):
        from paddle_tpu.distributed import elastic as em

        store = em.LocalStore()
        a = em.ElasticManager(store, "nodeA", ttl=0.5,
                              heartbeat_interval=0.1)
        b = em.ElasticManager(store, "nodeB", ttl=0.5,
                              heartbeat_interval=0.1)
        a.register()
        b.register()
        try:
            time.sleep(0.3)
            assert sorted(a.alive_nodes()) == ["nodeA", "nodeB"]
            b.deregister()  # stop B's lease renewal
            time.sleep(0.8)
            assert a.alive_nodes() == ["nodeA"]
        finally:
            a.deregister()
            b.deregister()

    def test_watch_detects_change_and_holds_below_min(self):
        from paddle_tpu.distributed import elastic as em

        store = em.LocalStore()
        a = em.ElasticManager(store, "nodeA", np_min=1, ttl=0.5,
                              heartbeat_interval=0.1)
        a.register()
        try:
            a.snapshot()
            assert a.watch() == em.ElasticStatus.COMPLETED
            b = em.ElasticManager(store, "nodeB", ttl=0.5,
                                  heartbeat_interval=0.1)
            b.register()
            time.sleep(0.2)
            assert a.watch() == em.ElasticStatus.RESTART  # scale-up seen
            assert a.watch() == em.ElasticStatus.COMPLETED  # new baseline
            b.deregister()
            time.sleep(0.8)
            assert a.watch() == em.ElasticStatus.RESTART  # scale-down seen
        finally:
            a.deregister()

        # below np_min -> HOLD (fresh store: one live node, min two)
        store = em.LocalStore()
        strict = em.ElasticManager(store, "nodeC", np_min=2, ttl=0.5,
                                   heartbeat_interval=0.1)
        strict.register()
        try:
            time.sleep(0.2)
            assert strict.watch() == em.ElasticStatus.HOLD
        finally:
            strict.deregister()

    def test_endpoints_lists_live(self):
        from paddle_tpu.distributed import elastic as em

        store = em.LocalStore()
        a = em.ElasticManager(store, "host1:1", ttl=5.0)
        b = em.ElasticManager(store, "host2:1", ttl=5.0)
        a.register()
        b.register()
        try:
            assert a.endpoints() == "host1:1,host2:1"
        finally:
            a.deregister()
            b.deregister()

    def test_launcher_restarts_on_membership_change(self, tmp_path):
        """End-to-end: a second node joining triggers a pod relaunch."""
        from paddle_tpu.distributed import elastic as em
        from paddle_tpu.distributed.launch.main import Pod

        store = em.LocalStore()
        mgr = em.ElasticManager(store, "self", ttl=1.0,
                                heartbeat_interval=0.2)
        mgr.register()
        script = tmp_path / "sleepy.py"
        script.write_text("import time; time.sleep(30)")
        try:
            mgr.snapshot()
            pod = Pod()
            pod.spawn([sys.executable, str(script)],
                      [dict(os.environ)], None)

            joined = em.ElasticManager(store, "joiner", ttl=1.0,
                                       heartbeat_interval=0.2)
            joined.register()

            def tick():
                if mgr.watch() == em.ElasticStatus.RESTART:
                    return 101
                return None

            code = pod.watch(tick=tick)
            assert code == 101  # membership change terminated the pod
            joined.deregister()
        finally:
            mgr.deregister()


class TestElasticAtomicRegistry:
    def test_concurrent_first_beats_not_lost(self):
        """Reviewer-reproduced lost-update: concurrent registrations must
        all survive (atomic add-allocated slots, no shared-list RMW)."""
        import threading

        from paddle_tpu.distributed import elastic as em

        store = em.LocalStore()
        mgrs = [em.ElasticManager(store, f"n{i}", ttl=5.0) for i in range(8)]
        threads = [threading.Thread(target=m._beat_once) for m in mgrs]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sorted(mgrs[0].alive_nodes()) == [f"n{i}" for i in range(8)]

    def test_endpoints_are_routable_not_pids(self):
        from paddle_tpu.distributed import elastic as em

        store = em.LocalStore()
        a = em.ElasticManager(store, "hostA:12345", ttl=5.0,
                              endpoint="10.0.0.1:6001")
        b = em.ElasticManager(store, "hostB:99", ttl=5.0,
                              endpoint="10.0.0.2:6001")
        a._beat_once()
        b._beat_once()
        assert a.endpoints() == "10.0.0.1:6001,10.0.0.2:6001"

    def test_elastic_restart_does_not_consume_crash_budget(self, tmp_path):
        """A membership-triggered ELASTIC_EXIT_CODE relaunches even with
        max_restarts=0 (scale events are not crashes)."""
        import importlib
        from unittest import mock

        lm = importlib.import_module("paddle_tpu.distributed.launch.main")

        calls = {"n": 0}

        class FakePod:
            def __init__(self):
                pass

            def spawn(self, cmd, envs, log_dir):
                pass

            def watch(self, tick=None):
                calls["n"] += 1
                # first launch: membership change; second: clean exit
                return lm.ELASTIC_EXIT_CODE if calls["n"] == 1 else 0

        class FakeManager:
            def endpoints(self):
                return "127.0.0.1:1"

            def snapshot(self):
                pass

            def register(self):
                pass

            def deregister(self):
                pass

            def watch(self):
                return "completed"

        fake_store = mock.MagicMock()
        with mock.patch.object(lm, "Pod", FakePod), \
             mock.patch("paddle_tpu.distributed.store.TCPStore",
                        return_value=fake_store), \
             mock.patch("paddle_tpu.distributed.elastic.ElasticManager",
                        return_value=FakeManager()):
            rc = lm.launch("noscript.py", elastic=True, max_restarts=0)
        assert rc == 0
        assert calls["n"] == 2  # relaunched once despite max_restarts=0


class TestDistributedApiTail:
    """r4 parity tail for paddle.distributed (env classes, object
    collectives single-process forms, split, datasets; the cross-process
    forms run inside tests/mp_proof_worker.py)."""

    def test_env_and_introspection(self):
        import paddle_tpu.distributed as dist

        env = dist.ParallelEnv()
        assert env.rank == 0 and env.world_size == 1
        assert dist.is_available()
        assert dist.get_backend().startswith("xla:")
        assert dist.get_group(0).world_size >= 1
        assert dist.ParallelMode.SHARDING_PARALLEL == 3
        assert dist.ReduceType.kRedSum == 0

    def test_object_collectives_single_process(self):
        import paddle_tpu.distributed as dist

        out = []
        dist.all_gather_object(out, {"a": 1})
        assert out == [{"a": 1}]
        lst = [1, 2, 3]
        dist.broadcast_object_list(lst, src=0)
        assert lst == [1, 2, 3]
        res = []
        dist.scatter_object_list(res, ["only"], src=0)
        assert res == ["only"]
        gl = []
        dist.gather(paddle.to_tensor(np.arange(3.0, dtype=np.float32)), gl)
        assert len(gl) == 1

    def test_split_linear_and_embedding(self):
        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed import topology

        topology.init_mesh(mp=4)
        try:
            paddle.seed(0)
            x = paddle.to_tensor(
                np.random.default_rng(0).normal(size=(2, 8)).astype("float32"))
            y = dist.split(x, (8, 12), operation="linear", axis=1)
            assert tuple(y.shape) == (2, 12)
            e = dist.split(paddle.to_tensor(np.array([[1, 2]], np.int64)),
                           (32, 16), operation="embedding")
            assert tuple(e.shape) == (1, 2, 16)
            with pytest.raises(ValueError):
                dist.split(x, (8, 8), operation="conv")
        finally:
            topology._global_mesh = None
            topology._global_hcg = None

    def test_datasets_and_entries(self, tmp_path):
        import paddle_tpu.distributed as dist

        f = tmp_path / "data.txt"
        f.write_text("a 1\nb 2\nc 3\n")
        ds = dist.InMemoryDataset()
        ds.init(batch_size=2)
        ds.set_filelist([str(f)])
        ds.load_into_memory()
        assert ds.get_memory_data_size() == 3
        assert [len(b) for b in ds] == [2, 1]
        ds.local_shuffle(seed=1)
        ds.release_memory()
        assert ds.get_memory_data_size() == 0
        q = dist.QueueDataset()
        q.init(batch_size=3)
        q.set_filelist([str(f)])
        assert [len(b) for b in q] == [3]
        assert "5" in dist.CountFilterEntry(5)._to_attr()
        assert "show" in dist.ShowClickEntry()._to_attr()

    @pytest.mark.slow
    def test_dist_model_trains(self):
        import paddle_tpu.distributed as dist
        from paddle_tpu import nn

        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        model = dist.to_static(net, loss=nn.MSELoss(), optimizer=opt)
        model.train()
        rng = np.random.default_rng(0)
        W = rng.normal(size=(8, 1)).astype(np.float32)
        first = last = None
        for _ in range(40):
            xb = rng.normal(size=(16, 8)).astype(np.float32)
            l = model(paddle.to_tensor(xb), paddle.to_tensor(xb @ W))
            first = first if first is not None else float(l)
            last = float(l)
        assert last < 0.1 * first, (first, last)
        model.eval()
        assert np.isfinite(float(model(paddle.to_tensor(xb),
                                       paddle.to_tensor(xb @ W))))
