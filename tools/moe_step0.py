"""Step 0 of ISSUE 51: ONE routed-expert layer alone on the chip,
``jax.lax.ragged_dot`` against ``ops.pallas_moe.grouped_matmul``, at the
decode shapes of the four cells with routed experts and at their prefill
shapes; the sweep that ``pallas_moe.PASS_ROWS`` and
``STREAM_ROWS_PER_EXPERT`` are read from.  The benchmark does not run it.

    chiprun -- python tools/moe_step0.py [--only latent,delta,...] [--quick]

A line of JSON a measurement, to ``chiprun_out/moe_step0.jsonl`` as it comes
and to standard output.  ``ms`` is the median of ``--repeats`` timed groups
of ``--calls`` launches of BOTH products and the SwiGLU between them;
``roofline`` the touched experts' bytes over 819 GB/s over that time.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402
import numpy as np                                              # noqa: E402

import paddle_tpu                                     # noqa: E402,F401
from paddle_tpu.ops import pallas_moe                           # noqa: E402

HBM_BYTES_PER_S = 819e9     # TPU v5e (Google Cloud, "TPU v5e")
OUT = "chiprun_out/moe_step0.jsonl"

#: name -> (experts held, H, F): w_gate_up [E, H, 2F], w_down [E, F, H]
MODELS = {
    "latent": (64, 2048, 1536),       # glm-4.7-flash
    "delta": (16, 7168, 2048),        # gigachat3.5-432b-a28b, 16 of 64 held
    "cmd": (16, 4096, 4096),          # command-a-plus-05-2026, 16 held
    "xing": (64, 3584, 1024),         # xing4.0-29b-a4b
}


def routing(rng, kind: str, rows: int, experts: int, real=None, touched=None,
            skew=None):
    """``sizes`` [experts]: ``real`` pairs (all ``rows`` by default) over
    ``touched`` experts; ``even`` splits them equally, ``uniform`` draws a
    multinomial, ``skew`` draws one whose fullest expert holds ``skew``
    times the mean."""
    real = rows if real is None else real
    touched = experts if touched is None else touched
    held = np.sort(rng.permutation(experts)[:touched])
    if kind == "even":
        part = np.full(touched, real // touched)
        part[:real - part.sum()] += 1
    else:
        p = np.ones(touched)
        if kind == "skew":
            # one geometric profile, its ratio searched so that the expected
            # fullest share is skew / touched
            lo, hi = 0.5, 1.0
            for _ in range(40):
                r = (lo + hi) / 2
                p = r ** np.arange(touched)
                if p.max() / p.mean() > skew:
                    lo = r
                else:
                    hi = r
            p = rng.permutation(p)
        part = rng.multinomial(real, p / p.sum())
    sizes = np.zeros(experts, np.int32)
    sizes[held] = part
    return sizes


def layer(product, rows, wgu, wd, sizes):
    f = wd.shape[1]
    h = product(rows, wgu, sizes)
    h = jax.nn.silu(h[:, :f]) * h[:, f:]
    return product(h, wd, sizes)


def timed(fn, args, calls: int, repeats: int):
    out = fn(*args)
    out.block_until_ready()
    fn(*args).block_until_ready()
    ms = []
    for _ in range(repeats):
        t = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        out.block_until_ready()
        ms.append((time.perf_counter() - t) * 1e3 / calls)
    return statistics.median(ms), out


def emit(**line):
    text = json.dumps(line)
    print(text, flush=True)
    with open(OUT, "a") as f:
        f.write(text + "\n")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="latent,delta,cmd,xing")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--quick", action="store_true",
                    help="the default tiles alone, no sweep")
    ap.add_argument("--cross", action="store_true",
                    help="the crossing alone: 128 to 1,024 rows an expert, "
                         "passes of 32 to 256 rows")
    a = ap.parse_args()
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    dev = jax.devices()[0]
    emit(device=dev.platform, kind=dev.device_kind, jax=jax.__version__)
    if dev.platform != "tpu":
        raise SystemExit("no TPU: a time from another backend is no "
                         "measurement")
    rng = np.random.default_rng(51)
    bf16 = jnp.bfloat16

    # (model, case, rows, routing kwargs); the decode shapes first
    cases = [
        ("latent", "decode even", 512, dict(kind="even")),
        ("latent", "decode uniform", 512, dict(kind="uniform")),
        ("latent", "decode skew 3.16", 512, dict(kind="skew", skew=2.7)),
        ("delta", "decode 54 real, 9 touched", 1024,
         dict(kind="uniform", real=54, touched=9)),
        ("delta", "decode 256 real, 16 touched", 1024,
         dict(kind="uniform", real=256)),
        ("cmd", "decode 30 real", 256, dict(kind="uniform", real=30,
                                            touched=12)),
        ("cmd", "decode 64 real", 256, dict(kind="uniform", real=64)),
    ]
    # the crossing: rows a held expert from 8 to 256, all of them real
    for per in (16, 32, 64, 128, 256):
        cases.append(("latent", f"{per} rows an expert", 64 * per,
                      dict(kind="uniform")))
    for per in (64, 128, 256):
        cases.append(("xing", f"prefill {per} rows an expert", 64 * per,
                      dict(kind="uniform")))
    for per in (32, 64):
        cases.append(("cmd", f"{per} rows an expert", 16 * per,
                      dict(kind="uniform")))
        cases.append(("delta", f"{per} rows an expert", 16 * per,
                      dict(kind="uniform")))

    if a.cross:
        cases = [(m, f"{per} rows an expert", MODELS[m][0] * per,
                  dict(kind="uniform"))
                 for m in MODELS for per in (128, 256, 512, 1024)]

    for model in a.only.split(","):
        E, H, F = MODELS[model]
        key = jax.random.PRNGKey(E + H)
        k1, k2 = jax.random.split(key)
        wgu = (jax.random.normal(k1, (E, H, 2 * F), bf16) * H ** -0.5)
        wd = (jax.random.normal(k2, (E, F, H), bf16) * F ** -0.5)
        per_expert = (H * 2 * F + F * H) * 2
        for name, case, rows, kw in cases:
            if name != model:
                continue
            sizes_np = routing(rng, rows=rows, experts=E, **kw)
            sizes = jnp.asarray(sizes_np)
            x = jnp.asarray(rng.standard_normal((rows, H)), bf16)
            real, touched = int(sizes_np.sum()), int((sizes_np > 0).sum())
            floor_ms = touched * per_expert / HBM_BYTES_PER_S * 1e3
            base = dict(model=model, case=case, rows=rows, real=real,
                        touched=touched, max_over_mean=round(
                            float(sizes_np.max() * E / max(real, 1)), 2),
                        floor_ms=round(floor_ms, 4))
            ragged = jax.jit(functools.partial(layer, jax.lax.ragged_dot))
            ms, want = timed(ragged, (x, wgu, wd, sizes), a.calls, a.repeats)
            emit(**base, path="ragged_dot", ms=round(ms, 4),
                 roofline=round(100 * floor_ms / ms, 2))
            want = np.asarray(want[:real], np.float32)
            sweeps = [dict()]
            if a.cross:
                sweeps += [dict(tm=tm) for tm in (64, 128, 256)]
            elif not a.quick and "decode" in case:
                sweeps += [dict(tm=tm) for tm in (16, 64, 128)]
                sweeps += [dict(tn=tn) for tn in (256, 512, 1024)]
            for sweep in sweeps:
                product = functools.partial(pallas_moe.grouped_matmul,
                                            **sweep)
                fn = jax.jit(functools.partial(layer, product))
                try:
                    ms, got = timed(fn, (x, wgu, wd, sizes), a.calls,
                                    a.repeats)
                except Exception as e:      # a tile the compiler refuses
                    emit(**base, path="kernel", **sweep,
                         error=str(e).splitlines()[0][:300])
                    continue
                got = np.asarray(got[:real], np.float32)
                emit(**base, path="kernel", **sweep, ms=round(ms, 4),
                     roofline=round(100 * floor_ms / ms, 2),
                     max_abs_diff=float(np.abs(got - want).max()),
                     finite=bool(np.isfinite(got).all()))
        del wgu, wd


if __name__ == "__main__":
    main()
