"""Store-activations vs recompute 1F1B at the flagship 8B config — the
no-hardware version of the VERDICT r3 weak-#2 comparison.

The r3 round made activation recompute a *choice* with store-activations
the default, picked without a measured step.  Until a chip is available,
this quantifies the trade analytically with the same memory model the
planner uses (``distributed/auto_tuner.py``), at the real Llama-3-8B
v5p-64 target:

- store-activations: 1F1B keeps ≤ pp microbatches of full stage
  activations alive (Megatron ~34·b·s·h bytes per layer, mp-sharded);
  zero extra FLOPs.
- recompute: buffers only stage inputs (2·b·s·h bytes per in-flight
  microbatch) and re-runs the stage forward in backward: ≈ +1/3 step
  FLOPs (fwd 2N, bwd 4N, recompute adds another fwd 2N → 8N/6N).

Writes the table to stdout; ``--doc`` appends it to ``AOT_8B.md``.
"""

from __future__ import annotations

import argparse
import os

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Llama-3-8B / v5p-64 flagship (BASELINE.json configs[3])
N_PARAMS = 8.03e9
LAYERS, HIDDEN, SEQ = 32, 4096, 4096
HBM = 95e9
BYTES = 2  # bf16


def act_bytes_store(micro_batch: int, pp: int, mp: int) -> float:
    """Peak per-device activation bytes, store-activations 1F1B: the depth-d
    stage holds (pp - d) ≤ pp in-flight microbatches of its layers' full
    activations (Megatron 34·b·s·h per layer, activations mp-sharded)."""
    per_layer = 34 * micro_batch * SEQ * HIDDEN / mp
    return pp * per_layer * (LAYERS / pp)


def act_bytes_recompute(micro_batch: int, pp: int, mp: int) -> float:
    """Recompute buffers only the stage INPUT per in-flight microbatch
    (+ one microbatch of live activations while recomputing)."""
    stage_input = BYTES * micro_batch * SEQ * HIDDEN / mp
    live = 34 * micro_batch * SEQ * HIDDEN / mp * (LAYERS / pp)
    return pp * stage_input + live


def fixed_bytes(pp: int, mp: int, sharding: int) -> float:
    p = N_PARAMS * BYTES / (mp * pp)
    g = p
    o = N_PARAMS * BYTES * 6 / (mp * pp * sharding)
    return p + g + o


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--doc", action="store_true",
                    help="append the table to AOT_8B.md")
    args = ap.parse_args()

    rows = []
    for (mp, pp, sharding, mb) in [(2, 4, 8, 1), (2, 4, 8, 2), (4, 4, 4, 1),
                                   (2, 8, 4, 1), (4, 8, 2, 2), (8, 4, 2, 4)]:
        fixed = fixed_bytes(pp, mp, sharding)
        store = fixed + act_bytes_store(mb, pp, mp)
        reco = fixed + act_bytes_recompute(mb, pp, mp)
        rows.append((mp, pp, sharding, mb, store / 1e9, reco / 1e9,
                     store <= HBM))
    lines = [
        "| mp | pp | shard | micro | store GB/dev | recompute GB/dev | "
        "store fits 95GB |",
        "|---|---|---|---|---|---|---|",
    ]
    for mp, pp, sh, mb, s, r, fits in rows:
        lines.append(f"| {mp} | {pp} | {sh} | {mb} | {s:.1f} | {r:.1f} | "
                     f"{'yes' if fits else 'NO'} |")
    verdict = (
        "Every pipeline-feasible 8B layout fits v5p HBM comfortably in "
        "store-activations mode, so the r3 default (store, zero extra "
        "FLOPs) is the right call on this hardware: recompute's ~+33% "
        "step FLOPs (fwd 2N + bwd 4N + recomputed fwd 2N) would cost "
        "~25% throughput for memory headroom the chip does not need. "
        "Recompute becomes the right default only when micro-batch·seq "
        "grows ~6-8x (long-context or small-mp layouts pushing the "
        "activation term toward the HBM line). To be re-validated with "
        "measured steps on the chip.")
    table = "\n".join(lines)
    print(table)
    print()
    print(verdict)
    if args.doc:
        with open(os.path.join(_HERE, "AOT_8B.md"), "a") as f:
            f.write("\n## 1F1B mode choice at 8B (analytical, "
                    "tools/analyze_1f1b_modes.py)\n\n")
            f.write(table + "\n\n" + verdict + "\n")
        print("\n[appended to AOT_8B.md]")


if __name__ == "__main__":
    main()
