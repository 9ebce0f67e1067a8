"""MFU accounting: trace-breakdown + FLOPs-formula fixtures (VERDICT r4
item #8 / missing #8).

When the first on-chip number lands, the two things that will be contested
are (a) the step-time category breakdown from the XPlane/chrome trace and
(b) the MFU formula.  Both are pinned here against hand-computed values:
the committed fixture (``tests/fixtures/mfu_trace``, generated by
``tools/make_mfu_fixture.py``) has sequential non-overlapping device
events whose bucket totals are computed by hand below.

Reference analog: ``python/paddle/profiler/profiler_statistic.py``
(sortable per-category summaries over the reference's trace tree).
"""

import os
import sys

import numpy as np
import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "tools"))

import analyze_trace  # noqa: E402  (tools/analyze_trace.py)
from bench import _peak_flops, train_flops_per_token  # noqa: E402

_FIXTURE = os.path.join(_HERE, "fixtures", "mfu_trace")


class TestTraceBreakdown:
    def test_fixture_breakdown_matches_hand_computed(self):
        res = analyze_trace.analyze(_FIXTURE, n_steps=1)
        # hand-computed from tools/make_mfu_fixture.py EVENTS:
        assert dict(res["by_cat"]) == {
            "matmul/conv (MXU)": 300,
            "fusion (mixed)": 200,
            "pallas": 150,            # pallas_call 125 + custom-call 25
            "copy/transpose": 50,
            "collectives": 75,
            "dynamic-update/scatter": 60,
            "other": 40,
        }
        # sequential events: wall == summed busy time == 875 us
        assert res["wall"] == 875
        assert res["busy"] == 875
        # the 5000-us host-lane event must not leak into the device buckets
        assert res["device_pids"] == {1}
        assert "python_dispatch" not in res["by_name"]

    def test_bucket_rules(self):
        b = analyze_trace._bucket
        assert b("dot_general.77") == "matmul/conv (MXU)"
        assert b("convolution.3") == "matmul/conv (MXU)"
        assert b("fusion.2") == "fusion (mixed)"
        assert b("loop_fusion.9") == "fusion (mixed)"
        assert b("custom-call.1") == "pallas"
        assert b("mosaic_kernel") == "pallas"
        assert b("copy.1") == "copy/transpose"
        assert b("all-reduce.4") == "collectives"
        assert b("reduce-scatter.2") == "collectives"
        assert b("dynamic-update-slice") == "dynamic-update/scatter"
        assert b("exp.3") == "other"

    def test_analyzer_reads_a_real_jax_trace(self, tmp_path):
        """Format compatibility with what jax.profiler actually writes."""
        import jax
        import jax.numpy as jnp

        f = jax.jit(lambda a, b: jnp.tanh(a @ b).sum())
        x = jnp.ones((128, 128), jnp.float32)
        f(x, x).block_until_ready()  # compile outside the trace
        with jax.profiler.trace(str(tmp_path)):
            for _ in range(3):
                f(x, x).block_until_ready()
        res = analyze_trace.analyze(str(tmp_path), n_steps=3)
        assert res["busy"] > 0
        assert res["wall"] > 0
        assert sum(res["by_cat"].values()) == res["busy"]


class TestMfuFormula:
    def test_flops_per_token_hand_computed(self):
        # 6N = 600,000,000;  12*L*S*H = 12*6*2048*1024 = 150,994,944
        got = train_flops_per_token(100_000_000, 6, 2048, 1024)
        assert got == 600_000_000 + 150_994_944

    def test_peak_flops_table(self):
        assert _peak_flops("TPU v5 lite") == 197e12
        assert _peak_flops("TPU v5e") == 197e12
        assert _peak_flops("TPU v5p") == 459e12
        assert _peak_flops("TPU v4") == 275e12
        assert _peak_flops("TPU v6e (Trillium)") == 918e12
        with pytest.raises(KeyError):  # unknown device: error, not 0
            _peak_flops("cpu")

    def test_end_to_end_mfu(self):
        """The exact arithmetic bench.py reports as vs_baseline."""
        flops_tok = train_flops_per_token(100_000_000, 6, 2048, 1024)
        tok_per_s = 50_000.0
        peak = _peak_flops("TPU v5 lite")
        mfu = flops_tok * tok_per_s / peak
        np.testing.assert_allclose(mfu, 0.19061, atol=1e-4)
        np.testing.assert_allclose(mfu / 0.40, 0.47653, atol=1e-4)

    def test_model_params_match_formula_inputs(self):
        """The N the bench feeds the formula is the real parameter count
        of the model it times (pinned on the tiny config)."""
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
