"""BERT + MoE-Llama model family tests (capability rungs #3/#5)."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.distributed import topology
from paddle_tpu.jit import to_static
from paddle_tpu.models import (
    BertConfig,
    BertForQuestionAnswering,
    BertForSequenceClassification,
    BertModel,
    LlamaConfig,
    LlamaForCausalLM,
    LlamaPretrainingCriterion,
)
from paddle_tpu.parallel.utils import apply_param_shardings, param_spec


@pytest.fixture
def ep_mesh():
    m = topology.init_mesh(dp=2, sep=4)
    yield m
    topology._global_mesh = None
    topology._global_hcg = None


def _ids(cfg, batch=2, seq=16, seed=0, low=1):
    rng = np.random.default_rng(seed)
    return paddle.to_tensor(
        rng.integers(low, cfg.vocab_size, (batch, seq)).astype("int64"))


class TestBert:
    def test_shapes(self):
        cfg = BertConfig.tiny()
        m = BertModel(cfg)
        seq, pooled = m(_ids(cfg))
        assert seq.shape == [2, 16, cfg.hidden_size]
        assert pooled.shape == [2, cfg.hidden_size]

    def test_padding_mask_isolates_pad_tokens(self):
        cfg = BertConfig.tiny()
        m = BertModel(cfg)
        m.eval()
        ids = _ids(cfg, batch=1)
        base, _ = m(ids)
        # changing content of a PADDED position must not affect real tokens
        padded = ids.numpy().copy()
        padded[0, -4:] = cfg.pad_token_id
        out1, _ = m(paddle.to_tensor(padded))
        changed = padded.copy()
        changed[0, -1] = 7  # still masked out in out1's mask? no — mask is
        # computed from ids, so instead compare two pad-content variants with
        # an explicit mask
        mask = np.ones((1, 16), "float32")
        mask[0, -4:] = 0.0
        o1, _ = m(paddle.to_tensor(padded), attention_mask=paddle.to_tensor(mask))
        changed[0, -2] = 9
        o2, _ = m(paddle.to_tensor(changed), attention_mask=paddle.to_tensor(mask))
        np.testing.assert_allclose(o1.numpy()[0, :12], o2.numpy()[0, :12],
                                   atol=1e-5)

    def test_qa_head(self):
        cfg = BertConfig.tiny()
        m = BertForQuestionAnswering(cfg)
        s, e = m(_ids(cfg))
        assert s.shape == [2, 16] and e.shape == [2, 16]

    @pytest.mark.slow
    def test_finetune_step_learns(self):
        cfg = BertConfig.tiny(hidden_dropout_prob=0.0,
                              attention_probs_dropout_prob=0.0)
        paddle.seed(0)
        m = BertForSequenceClassification(cfg, num_classes=2)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=m.parameters())
        loss_fn = nn.CrossEntropyLoss()
        # learnable rule: label = (first token > vocab/2)
        rng = np.random.default_rng(0)
        ids = rng.integers(1, cfg.vocab_size, (16, 12)).astype("int64")
        labels = (ids[:, 0] > cfg.vocab_size // 2).astype("int64")

        @to_static
        def step(x, y):
            loss = loss_fn(m(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        losses = [float(step(paddle.to_tensor(ids), paddle.to_tensor(labels)))
                  for _ in range(25)]
        assert losses[-1] < losses[0] * 0.5, losses


class TestMoELlama:
    def test_moe_block_wired(self):
        cfg = LlamaConfig.tiny_moe()
        m = LlamaForCausalLM(cfg)
        from paddle_tpu.models import LlamaMoEBlock

        assert isinstance(m.llama.layers[0].mlp, LlamaMoEBlock)
        # expert-stacked weights are EP-annotated on dim 0
        w = m.llama.layers[0].mlp.moe.experts.w_in
        assert param_spec(w)[0] == "sep"

    @pytest.mark.slow
    def test_aux_loss_present_and_grads(self):
        cfg = LlamaConfig.tiny_moe()
        m = LlamaForCausalLM(cfg)
        crit = LlamaPretrainingCriterion(cfg)
        ids = _ids(cfg, low=0)
        loss = crit(m(ids), ids) + cfg.aux_loss_weight * m.aux_loss
        loss.backward()
        missing = [n for n, p in m.named_parameters() if p.grad is None]
        assert missing == []

    @pytest.mark.slow
    def test_ep_train_step_loss_decreases(self, ep_mesh):
        cfg = LlamaConfig.tiny_moe()
        paddle.seed(0)
        m = LlamaForCausalLM(cfg)
        apply_param_shardings(m)
        crit = LlamaPretrainingCriterion(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=m.parameters())

        @to_static
        def step(ids):
            loss = crit(m(ids), ids) + cfg.aux_loss_weight * m.aux_loss
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        ids = paddle.to_tensor(
            np.random.default_rng(0).integers(0, cfg.vocab_size, (4, 16))
            .astype("int32"))
        losses = [float(step(ids)) for _ in range(4)]
        assert losses[-1] < losses[0]

    def test_switch_top1_variant(self):
        cfg = LlamaConfig.tiny_moe(num_experts_per_tok=1)
        m = LlamaForCausalLM(cfg)
        gate = m.llama.layers[0].mlp.moe.gate
        assert gate.top_k == 1
        # Switch semantics: raw softmax prob as the gate weight —
        # _topk_gating never renormalizes k=1 (a single surviving gate
        # would be pinned to exactly 1.0)
        import jax.numpy as jnp

        from paddle_tpu.parallel.moe import _topk_gating

        logits = jnp.array([[2.0, 0.0, -1.0, 0.5]], jnp.float32)
        combine, _, _ = _topk_gating(logits, capacity=4, k=1, normalize=True)
        w = float(jnp.sum(combine))
        assert 0.0 < w < 0.999  # raw prob, not renormalized to 1.0
        ids = _ids(cfg, low=0)
        assert m(ids).shape == [2, 16, cfg.vocab_size]

    @pytest.mark.parametrize("k,normalize", [(1, False), (2, True),
                                             (3, False), (6, False),
                                             (8, True)])
    def test_topk_gating_matches_unrolled_reference(self, k, normalize):
        """The vectorized top_k/closed-form-offset gating (ADVICE r4)
        must reproduce the k-unrolled argmax/cumsum formulation exactly,
        including capacity drops and slot positions."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.parallel.moe import _one_hot, _topk_gating

        def reference(logits, capacity, k, normalize):
            normalize = normalize and k > 1
            T, E = logits.shape
            probs = jax.nn.softmax(logits, axis=-1)
            remaining = probs
            masks, gates = [], []
            for _ in range(k):
                idx = jnp.argmax(remaining, axis=-1)
                m = _one_hot(idx, E)
                masks.append(m)
                gates.append(jnp.sum(probs * m, axis=-1))
                remaining = remaining * (1.0 - m)
            density = jnp.mean(masks[0], axis=0)
            aux = jnp.sum(density * jnp.mean(probs, axis=0)) * E
            offset = jnp.zeros((1, E), probs.dtype)
            kept, pos = [], []
            for m in masks:
                p = (jnp.cumsum(m, axis=0) + offset) * m - 1.0
                m = m * (p < capacity)
                offset = offset + jnp.sum(m, axis=0, keepdims=True)
                kept.append(m)
                pos.append(p)
            gates = [g * jnp.sum(m, axis=-1) for g, m in zip(gates, kept)]
            if normalize:
                denom = sum(gates)
                denom = jnp.where(denom > 0, denom, 1.0)
                gates = [g / denom for g in gates]
            combine = jnp.zeros((T, E, capacity), probs.dtype)
            for g, m, p in zip(gates, kept, pos):
                pi = jnp.sum(p * m, axis=-1).astype(jnp.int32)
                combine = combine + (g[:, None, None] * m[:, :, None]
                                     * _one_hot(pi, capacity)[:, None, :])
            return combine, combine > 0.0, aux

        rng = np.random.default_rng(k)
        # tight capacity on a skewed distribution to force real drops
        # (even for k=1: 64 tokens / 8 experts averages 8 > capacity 6)
        logits = jnp.asarray(
            rng.standard_normal((64, 8)).astype(np.float32) * 2.0)
        capacity = 6
        c1, d1, a1 = _topk_gating(logits, capacity, k, normalize)
        c2, d2, a2 = reference(logits, capacity, k, normalize)
        # the comparison must exercise the drop path: fewer kept slots
        # than routed (k per token) proves capacity pruning engaged
        assert int(jnp.sum(d2)) < k * 64
        np.testing.assert_allclose(np.asarray(c1), np.asarray(c2),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
        np.testing.assert_allclose(float(a1), float(a2), rtol=1e-6)


class TestVisionModels:
    @pytest.mark.slow
    def test_mobilenet_v2_forward_backward(self):
        import paddle_tpu as paddle
        from paddle_tpu.vision.models import mobilenet_v2

        paddle.seed(0)
        m = mobilenet_v2(num_classes=10)
        x = paddle.to_tensor(np.random.randn(2, 3, 32, 32).astype("float32"))
        out = m(x)
        assert out.shape == [2, 10]
        out.sum().backward()
        convs = [p for n, p in m.named_parameters() if "conv" in n.lower() or "weight" in n]
        assert any(p.grad is not None for p in convs)

    @pytest.mark.slow
    def test_vit_forward_backward(self):
        from paddle_tpu.vision.models import VisionTransformer

        paddle.seed(0)
        m = VisionTransformer(img_size=32, patch_size=8, embed_dim=32,
                              depth=2, num_heads=2, class_num=5)
        x = paddle.to_tensor(np.random.randn(2, 3, 32, 32).astype("float32"))
        out = m(x)
        assert out.shape == [2, 5]
        out.sum().backward()
        assert m.pos_embed.grad is not None
        assert m.cls_token.grad is not None

    @pytest.mark.slow
    def test_vgg_forward(self):
        from paddle_tpu.vision.models import vgg11

        m = vgg11(num_classes=7)
        x = paddle.to_tensor(np.random.randn(1, 3, 224, 224).astype("float32"))
        assert m(x).shape == [1, 7]


class TestGPT:
    """GPT family (PaddleNLP gpt/modeling.py analog): pre-LN, learned
    positions, GELU, tied head, same TP/pipeline substrate as Llama."""

    def _model(self):
        from paddle_tpu.models import GPTConfig, GPTForCausalLM

        paddle.seed(0)
        cfg = GPTConfig.tiny()
        return cfg, GPTForCausalLM(cfg)

    def test_forward_shape_and_tied_head(self):
        cfg, m = self._model()
        ids = _ids(cfg)
        out = m(ids)
        assert tuple(out.shape) == (2, 16, cfg.vocab_size)
        assert m.lm_head is None  # GPT ties embeddings by default
        names = [n for n, _ in m.named_parameters()]
        assert sum("embed_tokens" in n for n in names) == 1

    def test_causality(self):
        cfg, m = self._model()
        ids = _ids(cfg)
        base = m(ids).numpy()
        pert = ids.numpy().copy()
        pert[:, 10] = (pert[:, 10] + 1) % cfg.vocab_size
        got = m(paddle.to_tensor(pert)).numpy()
        np.testing.assert_allclose(base[:, :10], got[:, :10], rtol=1e-5,
                                   atol=1e-6)
        assert not np.allclose(base[:, 10:], got[:, 10:])

    @pytest.mark.slow
    def test_train_step_learns(self):
        from paddle_tpu.jit import to_static
        from paddle_tpu.models import GPTPretrainingCriterion

        cfg, m = self._model()
        crit = GPTPretrainingCriterion(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=3e-3,
                                     parameters=m.parameters())

        @to_static
        def step(x):
            loss = crit(m(x), x)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        data = paddle.to_tensor(
            np.tile(np.arange(16, dtype=np.int64) % 7, (4, 1)))
        first = float(step(data))
        for _ in range(25):
            last = float(step(data))
        assert last < 0.5 * first, (first, last)

    @pytest.mark.slow
    def test_tp_matches_single_device(self):
        from paddle_tpu.models import GPTForCausalLM

        cfg, m = self._model()
        ids = _ids(cfg)
        ref = m(ids).numpy()
        topology.init_mesh(mp=4)
        try:
            paddle.seed(0)
            m2 = GPTForCausalLM(cfg)
            apply_param_shardings(m2)
            np.testing.assert_allclose(m2(ids).numpy(), ref,
                                       rtol=2e-4, atol=2e-4)
        finally:
            topology._global_mesh = None
            topology._global_hcg = None


    @pytest.mark.slow
    def test_recompute_flag_matches_plain_forward(self):
        from paddle_tpu.jit import to_static
        from paddle_tpu.models import (
            GPTConfig,
            GPTForCausalLM,
            GPTPretrainingCriterion,
        )

        paddle.seed(0)
        cfg = GPTConfig.tiny(recompute=True)
        m = GPTForCausalLM(cfg)
        crit = GPTPretrainingCriterion(cfg)
        ids = _ids(cfg)
        m.train()

        @to_static
        def loss_fn(x):
            loss = crit(m(x), x)
            loss.backward()
            g = m.gpt.layers[0].attn.qkv_proj.weight.grad
            m.clear_gradients()
            return loss, g

        loss_r, grad_r = loss_fn(ids)
        m.config.recompute = False
        loss_p = crit(m(ids), ids)
        loss_p.backward()
        grad_p = m.gpt.layers[0].attn.qkv_proj.weight.grad
        np.testing.assert_allclose(float(loss_r), float(loss_p), rtol=1e-5)
        np.testing.assert_allclose(grad_r.numpy(), grad_p.numpy(),
                                   rtol=1e-4, atol=1e-6)

    def test_seed_controls_position_embeddings(self):
        from paddle_tpu.models import GPTConfig, GPTModel

        paddle.seed(1)
        a = GPTModel(GPTConfig.tiny()).position_embeddings.numpy()
        paddle.seed(2)
        b = GPTModel(GPTConfig.tiny()).position_embeddings.numpy()
        paddle.seed(1)
        c = GPTModel(GPTConfig.tiny()).position_embeddings.numpy()
        assert not np.allclose(a, b)
        np.testing.assert_array_equal(a, c)


class TestNamedMoEConfigs:
    def test_deepseek_and_qwen2_shapes(self):
        c = LlamaConfig.deepseek_moe_16b()
        assert (c.num_experts, c.num_experts_per_tok,
                c.num_shared_experts) == (64, 6, 2)
        assert c.hidden_size == 2048 and c.num_hidden_layers == 28
        q = LlamaConfig.qwen2_moe_a14b()
        assert (q.num_experts, q.num_experts_per_tok) == (64, 8)
        assert q.num_attention_heads // q.num_key_value_heads == 7


class TestErnie:
    def test_classification_learns(self):
        from paddle_tpu.jit import to_static
        from paddle_tpu.models import ErnieConfig, ErnieForSequenceClassification
        from paddle_tpu.nn import functional as F

        cfg = ErnieConfig.tiny()
        paddle.seed(0)
        m = ErnieForSequenceClassification(cfg, num_classes=2)
        rng = np.random.default_rng(0)
        ids = paddle.to_tensor(rng.integers(1, cfg.vocab_size, (4, 12)),
                               dtype="int64")
        labels = paddle.to_tensor(rng.integers(0, 2, (4,)), dtype="int64")
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=m.parameters())

        @to_static
        def step(x, y):
            loss = F.cross_entropy(m(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        losses = [float(step(ids, labels)) for _ in range(5)]
        assert losses[-1] < losses[0]

    def test_task_type_default_zero_added(self):
        from paddle_tpu.models import ErnieConfig, ErnieModel

        cfg = ErnieConfig.tiny()
        paddle.seed(0)
        m = ErnieModel(cfg)
        m.eval()
        ids = paddle.to_tensor(
            np.random.default_rng(1).integers(1, cfg.vocab_size, (1, 8)),
            dtype="int64")
        seq_none, _ = m(ids)
        task0 = paddle.to_tensor(np.zeros((1, 8), np.int64))
        seq_zero, _ = m(ids, task_type_ids=task0)
        np.testing.assert_allclose(seq_none.numpy(), seq_zero.numpy(),
                                   rtol=1e-6, atol=1e-6)
