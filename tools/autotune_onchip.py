"""On-chip Pallas flash block autotune sweep (first-contact item 4).

Measures every admissible (block_q, block_k) candidate for the bench
attention shape on the live chip (fwd+bwd, ``ops/autotune.py`` machinery),
prints the winner vs the (128, 128) default, and appends the result to
``AUTOTUNE_ONCHIP.json``.  Compiles are cached persistently, so a re-run
is cheap.
"""

from __future__ import annotations

import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _HERE)


def main() -> None:
    import jax

    from paddle_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        raise SystemExit("needs the live chip")

    from paddle_tpu.ops import autotune
    from paddle_tpu.ops.pallas_flash import flash_attention

    rng = np.random.default_rng(0)
    # three training attention shapes, small to large;
    # (batch, seq, q_heads, kv_heads, head_dim) — C is GQA 16q/8kv
    shapes = [
        (8, 2048, 8, 8, 128),   # B_flagship
        (8, 1024, 8, 8, 64),    # A_small
        (4, 2048, 16, 8, 128),  # C_large
    ]
    summaries = []
    for B, S, H, Hkv, D in shapes:
        q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.bfloat16)

        rows = []
        for bq, bk in autotune.candidates(S, S, D):
            try:
                def step(q_, k_, v_):
                    out, vjp = jax.vjp(
                        lambda a, b, c: flash_attention(a, b, c, True,
                                                        bq, bk),
                        q_, k_, v_)
                    return out, vjp(out)

                jitted = jax.jit(step)
                jax.block_until_ready(jitted(q, k, v))
                t0 = time.perf_counter()
                for _ in range(5):
                    r = jitted(q, k, v)
                jax.block_until_ready(r)
                dt = (time.perf_counter() - t0) / 5
                rows.append({"block_q": bq, "block_k": bk,
                             "ms": round(dt * 1e3, 3)})
                print(json.dumps(rows[-1]))
            except Exception as e:
                rows.append({"block_q": bq, "block_k": bk,
                             "error": str(e)[-300:]})
                print(json.dumps(rows[-1]))

        ok = [r for r in rows if "ms" in r]
        if not ok:
            continue
        best = min(ok, key=lambda r: r["ms"])
        default = next((r for r in ok
                        if r["block_q"] == 128 and r["block_k"] == 128),
                       None)
        summaries.append({"device": jax.devices()[0].device_kind,
                          "shape": [B, S, H, Hkv, D], "best": best,
                          "default_128_128": default, "rows": rows})
        print(json.dumps({"shape": [B, S, H, Hkv, D], "best": best,
                          "default": default}))
        # feed the call-time cache: committed=True writes the repo-root
        # AUTOTUNE.json that cached_flash_blocks() consults by default
        autotune.record((B, S, H, D), (B, S, Hkv, D), "bfloat16", True,
                        (best["block_q"], best["block_k"]), committed=True)
        # checkpoint after EVERY shape: a timeout kill mid-sweep must not
        # lose the shapes that completed (same design as bench phases)
        with open(os.path.join(_HERE, "AUTOTUNE_ONCHIP.json"), "w") as f:
            json.dump(summaries, f, indent=1)


if __name__ == "__main__":
    main()
