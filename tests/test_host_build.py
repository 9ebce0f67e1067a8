"""host_build: off-device model init + one batched transfer.

Parameter init runs on the host CPU backend and the built tensors move to
the device (or straight to their mesh shards) in one ``device_put`` (see
paddle_tpu/utils/host_build.py).
These tests pin the contract on the CPU backend: identical numerics to an
on-device build, tensors rebound in place, Layers found in tuple returns.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import topology
from paddle_tpu.jit import to_static
from paddle_tpu.models import (
    LlamaConfig,
    LlamaForCausalLM,
    LlamaPretrainingCriterion,
)
from paddle_tpu.utils import host_build


@pytest.fixture(autouse=True)
def _no_leaked_mesh():
    """Earlier suite tests leave a global mesh; these tests pin both the
    no-mesh (single device) and explicit-mesh placement paths."""
    prev = topology.get_mesh()
    topology.set_mesh(None)
    yield
    topology.set_mesh(prev)


def _build(cfg):
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    crit = LlamaPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-3, parameters=model.parameters())

    @to_static
    def step(ids):
        loss = crit(model(ids), ids)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return model, step


class TestHostBuild:
    def test_training_matches_plain_build(self):
        cfg = LlamaConfig.tiny()
        logs = []
        model, step = host_build(lambda: _build(cfg), log=logs.append)
        assert any("transferring" in m for m in logs)
        ids = paddle.to_tensor(
            np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16)),
            dtype="int32")
        losses = [float(step(ids)) for _ in range(4)]
        assert losses[-1] < losses[0]

        _, step_plain = _build(cfg)  # same seed stream -> same init
        plain = [float(step_plain(ids)) for _ in range(4)]
        np.testing.assert_allclose(losses, plain, rtol=0, atol=0)

    def test_rebinds_in_place_and_returns_output(self):
        cfg = LlamaConfig.tiny()
        out = host_build(lambda: (LlamaForCausalLM(cfg), "tag"))
        model, tag = out
        assert tag == "tag"
        ids = paddle.to_tensor(np.zeros((1, 4), dtype="int32"))
        logits = model(ids)
        assert logits.shape == [1, 4, cfg.vocab_size]

    def test_non_layer_output_passthrough(self):
        with pytest.warns(RuntimeWarning, match="nothing was transferred"):
            assert host_build(lambda: 42) == 42

    def test_layer_nested_in_dict_is_found(self):
        # ADVICE r4: a Layer inside a dict (or deeper nesting) must be
        # transferred, not silently left on the host CPU
        cfg = LlamaConfig.tiny()
        logs = []
        out = host_build(
            lambda: {"bundle": [LlamaForCausalLM(cfg)],
                     "extra": paddle.to_tensor(np.ones(3, np.float32))},
            log=logs.append)
        assert any("transferring" in m for m in logs)
        model = out["bundle"][0]
        ids = paddle.to_tensor(np.zeros((1, 4), dtype="int32"))
        assert model(ids).shape == [1, 4, cfg.vocab_size]

    def test_active_mesh_shards_instead_of_committing(self):
        # with a live mesh, host init must place tensors by PartitionSpec
        # (replicated default) instead of committing them to device 0 —
        # single-device commitment conflicts with GSPMD constraints in
        # the forward (mp/vocab-parallel layers)
        topology.init_mesh(dp=2, mp=4)
        try:
            cfg = LlamaConfig.tiny()
            logs = []
            model, _ = host_build(lambda: _build(cfg), log=logs.append)
            assert any("mesh" in m for m in logs)
            n_dev = len(next(iter(
                model.parameters()))._value.sharding.device_set)
            assert n_dev == 8
            ids = paddle.to_tensor(np.zeros((2, 8), dtype="int32"))
            logits = model(ids)  # sharding_constraint path must not raise
            assert logits.shape == [2, 8, cfg.vocab_size]
        finally:
            topology.set_mesh(None)
