"""BERT-base SQuAD-style fine-tune, DP over 8 chips — ladder config #3.

The capability-ladder rung the reference covers with PaddleNLP's
``run_squad.py``: BertForQuestionAnswering span head, AdamW with linear
warmup, data parallelism over the full mesh (batch sharded over ``dp``;
gradient reduction is in-program GSPMD).  Synthetic SQuAD-shaped data
(the answer span is marked in the input with sentinel tokens, so span
accuracy is meaningfully learnable).

Run: python examples/finetune_bert_squad.py --cpu --dp 8 --steps 20
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="tiny", choices=["tiny", "base"])
    p.add_argument("--dp", type=int, default=8)
    p.add_argument("--batch_size", type=int, default=16)
    p.add_argument("--seq_len", type=int, default=48)
    p.add_argument("--steps", type=int, default=80)
    p.add_argument("--lr", type=float, default=2e-3)
    p.add_argument("--warmup", type=int, default=10)
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
    from paddle_tpu.distributed import fleet
    from paddle_tpu.jit import to_static
    from paddle_tpu.models import BertConfig, BertForQuestionAnswering
    from paddle_tpu.nn import functional as F

    paddle.seed(42)

    cfg = (BertConfig.tiny() if args.model == "tiny" else BertConfig())

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": args.dp, "mp_degree": 1,
                               "pp_degree": 1, "sharding_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    model = fleet.distributed_model(BertForQuestionAnswering(cfg))

    sched = paddle.optimizer.lr.LinearWarmup(
        paddle.optimizer.lr.PolynomialDecay(
            learning_rate=args.lr, decay_steps=args.steps, end_lr=0.0),
        warmup_steps=args.warmup, start_lr=0.0, end_lr=args.lr)
    opt = fleet.distributed_optimizer(
        paddle.optimizer.AdamW(learning_rate=sched,
                               parameters=model.parameters(),
                               weight_decay=0.01))

    @to_static
    def train_step(ids, start, end):
        s_logits, e_logits = model(ids)
        loss = (F.cross_entropy(s_logits, start)
                + F.cross_entropy(e_logits, end)) / 2.0
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    rng = np.random.default_rng(0)
    S = args.seq_len
    SENT_L, SENT_R = 2, 3  # sentinel tokens marking the span boundaries

    def make_split(n):
        # SQuAD-shaped synthetic split: random context; the answer span
        # is bracketed by sentinel tokens, so span-pointing is learnable
        ids = rng.integers(4, cfg.vocab_size, (n, S))
        start = rng.integers(1, S - 4, (n,))
        length = rng.integers(1, 3, (n,))
        end = np.minimum(start + length, S - 2)
        ids[np.arange(n), start] = SENT_L   # span starts AT the marker
        ids[np.arange(n), end] = SENT_R
        return ids.astype("int64"), start.astype("int64"), end.astype("int64")

    # finite train set iterated in epochs — finetune semantics, like the
    # reference's run_squad loop (not fresh random data every step)
    n_train = args.batch_size * 16
    train = make_split(n_train)
    dev = make_split(args.batch_size)

    t0 = time.time()
    step = 0
    while step < args.steps:
        perm = rng.permutation(n_train)
        for lo in range(0, n_train, args.batch_size):
            if step >= args.steps:
                break
            sel = perm[lo:lo + args.batch_size]
            loss = train_step(*(paddle.to_tensor(a[sel]) for a in train))
            sched.step()
            if step % 5 == 0 or step == args.steps - 1:
                ex_s = (args.batch_size * (step + 1)
                        / max(time.time() - t0, 1e-9))
                print(f"step {step:4d} loss {float(loss):.4f} "
                      f"lr {float(sched.get_lr()):.2e} "
                      f"examples/s {ex_s:,.1f}")
            step += 1

    # span accuracy on the held-out dev split (eval mode: dropout off)
    model.eval()
    ids, start, end = (paddle.to_tensor(a) for a in dev)
    with paddle.no_grad():
        s_logits, e_logits = model(ids)
    s_pred = s_logits.numpy().argmax(-1)
    e_pred = e_logits.numpy().argmax(-1)
    s_acc = float((s_pred == start.numpy()).mean())
    e_acc = float((e_pred == end.numpy()).mean())
    em = float(((s_pred == start.numpy())
                & (e_pred == end.numpy())).mean())
    print(json.dumps({"final_loss": float(loss), "start_acc": s_acc,
                      "end_acc": e_acc, "exact_match": em}))


if __name__ == "__main__":
    main()
