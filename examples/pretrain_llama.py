"""Llama pretraining driver (PaddleNLP ``llm/run_pretrain.py`` analog) —
capability-ladder config #4: TP+PP+sharding hybrid parallel.

Run (CPU simulation, 8 virtual devices):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/pretrain_llama.py --cpu --dp 2 --pp 2 --mp 2 \
        --model tiny --steps 20

On a TPU pod, drop --cpu and pick the mesh to match the slice.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import json
import os
import time


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="tiny", choices=["tiny", "llama3_8b"])
    p.add_argument("--dp", type=int, default=1)
    p.add_argument("--mp", type=int, default=1)
    p.add_argument("--pp", type=int, default=1)
    p.add_argument("--sharding", type=int, default=1)
    p.add_argument("--micro_batches", type=int, default=2)
    p.add_argument("--batch_size", type=int, default=4)
    p.add_argument("--seq_len", type=int, default=128)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--sequence_parallel", action="store_true")
    p.add_argument("--recompute", action="store_true")
    p.add_argument("--scan_layers", action="store_true",
                   help="compile the decoder stack as ONE lax.scan body "
                        "(L-times faster cold compile, same math)")
    p.add_argument("--auto", action="store_true",
                   help="pick dp/mp/pp/sharding with the cost-model planner")
    p.add_argument("--save_dir", default=None)
    p.add_argument("--resume", default=None)
    p.add_argument("--cpu", action="store_true")
    args = p.parse_args()

    if args.cpu:
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import checkpoint as dist_ckpt
    from paddle_tpu.distributed import fleet
    from paddle_tpu.jit import to_static
    from paddle_tpu.models import (
        LlamaConfig,
        LlamaForCausalLM,
        LlamaPretrainingCriterion,
    )

    paddle.seed(42)

    mk = (LlamaConfig.tiny if args.model == "tiny" else LlamaConfig.llama3_8b)
    cfg = mk(sequence_parallel=args.sequence_parallel,
             recompute=args.recompute, scan_layers=args.scan_layers)

    # fleet API end to end (fleet/fleet.py:167 usage pattern): one strategy
    # object wires mesh + placements + pipeline schedule + sharded optimizer
    if args.auto:
        # cost-model planner (engine.py:61 capability): describe the
        # workload, let the tuner choose dp/mp/pp/sharding/micro-batch
        from paddle_tpu.distributed.auto_tuner import ModelSpec

        n_params = (cfg.vocab_size * cfg.hidden_size
                    + cfg.num_hidden_layers
                    * (4 * cfg.hidden_size ** 2
                       + 3 * cfg.hidden_size * cfg.intermediate_size))
        strategy = fleet.auto_tune_strategy(ModelSpec(
            num_params=n_params, num_layers=cfg.num_hidden_layers,
            num_heads=cfg.num_attention_heads, hidden=cfg.hidden_size,
            seq_len=args.seq_len, global_batch=args.batch_size))
        h = strategy.hybrid_configs
        args.dp, args.mp = h["dp_degree"], h["mp_degree"]
        args.pp, args.sharding = h["pp_degree"], h["sharding_degree"]
        args.micro_batches = strategy.pipeline_configs["accumulate_steps"]
        print("auto-tuned parallel plan (best first):")
        print(strategy.auto_tune_plan.report())
    else:
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {
            "dp_degree": args.dp, "mp_degree": args.mp, "pp_degree": args.pp,
            "sharding_degree": args.sharding,
            "pp_configs": {"accumulate_steps": args.micro_batches},
        }
    strategy.sequence_parallel = args.sequence_parallel
    if args.recompute:
        strategy.recompute = True
    fleet.init(is_collective=True, strategy=strategy)
    model = fleet.distributed_model(LlamaForCausalLM(cfg))
    criterion = LlamaPretrainingCriterion(cfg)
    sched = paddle.optimizer.lr.CosineAnnealingDecay(
        learning_rate=args.lr, T_max=args.steps)
    opt = fleet.distributed_optimizer(
        paddle.optimizer.AdamW(learning_rate=sched,
                               parameters=model.parameters(),
                               weight_decay=0.01))
    if args.resume:
        sd = model.state_dict()
        dist_ckpt.load_state_dict(sd, args.resume)

    if args.pp > 1:
        @to_static
        def train_step(ids):
            return model.train_batch([ids, ids], opt)
    else:
        @to_static
        def train_step(ids):
            logits = model(ids)
            loss = criterion(logits, ids)
            aux = getattr(model, "aux_loss", None)
            if aux is not None:
                loss = loss + cfg.aux_loss_weight * aux
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

    rng = np.random.default_rng(0)

    def batch():
        # synthetic corpus: shifted arithmetic sequences (learnable quickly)
        start = rng.integers(0, 17, (args.batch_size, 1))
        seq = (start + np.arange(args.seq_len)) % 17
        return paddle.to_tensor(seq.astype("int32"))

    t0 = time.time()
    for step in range(args.steps):
        loss = train_step(batch())
        sched.step()
        if step % 5 == 0 or step == args.steps - 1:
            tok_s = (args.batch_size * args.seq_len * (step + 1) /
                     max(time.time() - t0, 1e-9))
            print(f"step {step:4d} loss {float(loss):.4f} "
                  f"lr {sched.last_lr:.2e} tokens/s {tok_s:,.0f}")

    if args.save_dir:
        dist_ckpt.save_state_dict(model.state_dict(), args.save_dir)
        print("saved distributed checkpoint to", args.save_dir)

    print(json.dumps({"final_loss": float(loss)}))


if __name__ == "__main__":
    main()
