"""MFU accounting: the training FLOPs-per-token formula, pinned against
hand-computed values (one accounting for the whole repo:
``distributed/auto_tuner.train_flops_per_token``, which the auto-tuner
cost model and ``observability.telemetry`` use).
"""

import numpy as np

from paddle_tpu.distributed.auto_tuner import train_flops_per_token


class TestMfuFormula:
    def test_flops_per_token_hand_computed(self):
        # 6N = 600,000,000;  12*L*S*H = 12*6*2048*1024 = 150,994,944
        got = train_flops_per_token(100_000_000, 6, 2048, 1024)
        assert got == 600_000_000 + 150_994_944

    def test_end_to_end_mfu(self):
        """FLOPs/token x tokens/s over the chip's peak, and the same
        against a 40% MFU target."""
        flops_tok = train_flops_per_token(100_000_000, 6, 2048, 1024)
        tok_per_s = 50_000.0
        peak = 197e12  # TPU v5e, bf16
        mfu = flops_tok * tok_per_s / peak
        np.testing.assert_allclose(mfu, 0.19061, atol=1e-4)
        np.testing.assert_allclose(mfu / 0.40, 0.47653, atol=1e-4)

    def test_model_params_match_formula_inputs(self):
        """The N fed to the formula is the real parameter count of the
        model (pinned on the tiny config)."""
        import paddle_tpu as paddle
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

        paddle.seed(0)
        cfg = LlamaConfig.tiny()
        model = LlamaForCausalLM(cfg)
        n_params = sum(p.size for p in model.parameters())
        # hand count: embed V*H (+ untied head H*V) + per-layer
        # (q/o full mats + GQA-narrow k/v + 3 mlp mats + 2 norms) + norm
        V, H, I, L = (cfg.vocab_size, cfg.hidden_size,
                      cfg.intermediate_size, cfg.num_hidden_layers)
        kv_dim = H * cfg.num_key_value_heads // cfg.num_attention_heads
        expect = V * H + (H * V if not cfg.tie_word_embeddings else 0)
        expect += L * (2 * H * H + 2 * H * kv_dim + 3 * H * I + 2 * H) + H
        assert n_params == expect
