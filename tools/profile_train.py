"""Capture a profiler trace of the flagship train step on the live chip.

Usage: ``python tools/profile_train.py [outdir]`` — runs the same compiled
Llama train step as ``bench.py`` and records an XPlane/perfetto trace via
``paddle.profiler`` (N34 analog) for the MFU gap analysis.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(outdir: str = "prof_trace") -> None:
    import jax

    from paddle_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.jit import to_static
    from paddle_tpu.models import (
        LlamaConfig,
        LlamaForCausalLM,
        LlamaPretrainingCriterion,
    )

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        # EXACT bench.py config — same program, so the trace describes the
        # benchmarked step and hits the bench-warmed compile cache
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=6, num_attention_heads=8,
            num_key_value_heads=8, max_position_embeddings=2048,
            rope_theta=10000.0, dtype="bfloat16", scan_layers=True)
        batch, seq = 8, 2048
        paddle.set_default_dtype("bfloat16")
    else:
        cfg = LlamaConfig.tiny()
        batch, seq = 4, 64

    def build(cfg):
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        criterion = LlamaPretrainingCriterion(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters())

        @to_static
        def train_step(ids):
            loss = criterion(model(ids), ids)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        return model, train_step

    from paddle_tpu.utils import host_build

    def build_off_device(cfg):
        # same init as bench.py: host CPU init + one batched transfer
        _, step = host_build(
            lambda: build(cfg),
            log=lambda m: print(m, file=sys.stderr))
        return step

    train_step = (build_off_device if on_tpu else lambda c: build(c)[1])(cfg)

    ids = paddle.to_tensor(
        np.random.default_rng(0).integers(
            0, cfg.vocab_size, (batch, seq)), dtype="int32")
    float(train_step(ids))  # compile (cache-warm)
    print(f"profiling batch={batch} seq={seq}", file=sys.stderr)
    float(train_step(ids))  # settle

    jax.profiler.start_trace(outdir)
    for _ in range(3):
        loss = train_step(ids)
    float(loss)
    jax.profiler.stop_trace()
    from paddle_tpu.ops import flash_attention as fa

    print(f"trace written to {outdir}; attention path: {fa.last_path}")


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "prof_trace")
