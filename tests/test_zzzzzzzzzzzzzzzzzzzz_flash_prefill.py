"""The expanded latent prefill's flash kernel (``pallas_flash.flash_prefill``)
against its oracle (the XLA form kept in ``ops/paged_attention.py``), how
``latent_expanded_attention`` chooses between them, and both latent model
families served with the kernel in their prefill programs against the plain
references.  Interpret mode on the CPU, tiny lengths, the cells' head
sizes."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import harness
from paddle_tpu.ops import paged_attention as ops
from paddle_tpu.ops import pallas_flash

RANK = 32


def operands(B, S, M, heads, nope, vd, dtype, rope=64, seed=0):
    rng = np.random.default_rng(seed)

    def rand(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    w = (rand(heads, RANK, nope, scale=RANK ** -0.5),
         rand(heads, RANK, vd, scale=RANK ** -0.5))
    return (rand(B, S, heads, nope + rope), rand(B, M, RANK + rope), w,
            (nope + rope) ** -0.5)


def both_forms(q, lat, w, scale, q_start, lens, blocks, monkeypatch):
    monkeypatch.setattr(pallas_flash, "prefill_blocks", lambda S, M: blocks)
    got = ops.latent_expanded_attention(q, lat, w, RANK, scale, q_start, lens,
                                        use_pallas=True)
    assert ops.last_latent_prefill_path == "pallas"
    want = ops.latent_expanded_attention(q, lat, w, RANK, scale, q_start,
                                         lens, use_pallas=False)
    assert ops.last_latent_prefill_path == "xla"
    shape = q.shape[:3] + (-1,)
    return (np.asarray(got, np.float32).reshape(shape),
            np.asarray(want, np.float32).reshape(shape))


# (heads, nope, v) at the two cells' head sizes; S < M puts the queries at
# q_start > 0 (a chunk after its prefix); blocks of 16 make two or more
# query and key blocks, so that blocks past the diagonal are skipped, blocks
# under it run unmasked and the running max and sum are rescaled
CASES = [
    # heads, nope, vd, S, M, q_start, lens, blocks, dtype
    (1, 128, 128, 32, 32, 0, None, (16, 16), jnp.float32),
    (3, 128, 128, 32, 32, 0, None, (16, 16), jnp.bfloat16),
    (1, 192, 256, 32, 32, 0, None, (16, 16), jnp.bfloat16),
    (2, 192, 256, 32, 32, 0, None, (16, 16), jnp.float32),
    (2, 128, 128, 32, 64, 32, None, (16, 16), jnp.float32),
    (2, 192, 256, 16, 64, 17, None, (16, 32), jnp.bfloat16),
    (2, 128, 128, 32, 64, 20, 52, (16, 16), jnp.float32),
    (2, 192, 256, 32, 64, 0, 21, (16, 32), jnp.bfloat16),
    (2, 128, 128, 32, 32, 0, None, (32, 16), jnp.bfloat16),
    (2, 128, 128, 32, 32, 0, None, None, jnp.float32),     # one block
]


@pytest.mark.parametrize(
    "heads,nope,vd,S,M,q_start,lens,blocks,dtype", CASES,
    ids=[f"h{c[0]}-{c[1]}+64-{c[2]}-S{c[3]}-M{c[4]}-at{c[5]}-len{c[6]}-"
         f"{c[7]}-{jnp.dtype(c[8]).name}" for c in CASES])
def test_the_kernel_agrees_with_its_oracle(heads, nope, vd, S, M, q_start,
                                           lens, blocks, dtype, monkeypatch):
    q, lat, w, scale = operands(1, S, M, heads, nope, vd, dtype)
    n = None if lens is None else jnp.asarray([lens], jnp.int32)
    got, want = both_forms(q, lat, w, scale, jnp.int32(q_start), n, blocks,
                           monkeypatch)
    real = S if lens is None else max(0, min(S, lens - q_start))
    assert real > 0
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got[:, :real], want[:, :real], atol=tol,
                               rtol=tol)
    assert np.isfinite(got).all()       # the padding rows too


def test_rows_of_a_batch_take_their_own_start_and_length(monkeypatch):
    """``q_start`` and ``lens`` a row of the batch (the chunk programs'
    operands); a row of length 0 holds zeros, which nothing reads."""
    q, lat, w, scale = operands(3, 32, 64, 2, 128, 128, jnp.float32, seed=1)
    starts = jnp.asarray([32, 5, 0], jnp.int32)
    lens = jnp.asarray([64, 30, 0], jnp.int32)
    got, want = both_forms(q, lat, w, scale, starts, lens, (16, 16),
                           monkeypatch)
    np.testing.assert_allclose(got[0], want[0], atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(got[1, :25], want[1, :25], atol=2e-5,
                               rtol=2e-5)
    assert not got[2].any()


@pytest.mark.parametrize("S,M,blocks", [
    (4096, 4096, (1024, 1024)), (2048, 2048, (1024, 1024)),
    (1024, 1024, (1024, 1024)), (512, 512, (512, 512)),
    (512, 1536, (512, 512)), (384, 384, (128, 128)),
    (100, 128, None), (128, 100, None), (64, 64, None)])
def test_blocks_follow_the_launch(S, M, blocks):
    assert pallas_flash.prefill_blocks(S, M) == blocks


def primitives(jaxpr):
    """The primitives of a jaxpr and of the jits it calls; a kernel's own
    body is not looked into."""
    names = set()
    for e in jaxpr.eqns:
        names.add(e.primitive.name)
        if e.primitive.name == "jit":
            names |= primitives(e.params["jaxpr"].jaxpr)
    return names


@pytest.mark.parametrize("on_tpu,S,use_pallas,killed,path", [
    (False, 128, None, False, "xla"),       # the CPU
    (True, 128, None, False, "pallas"),     # a TPU, whole blocks
    (True, 96, None, False, "xla"),         # a length that is no whole block
    (True, 128, False, False, "xla"),       # pinned
    (False, 96, True, False, "pallas"),     # forced past both
    (True, 128, None, True, "xla"),         # the operator's kill switch
    (False, 96, True, True, "xla"),         # ... wins over the force too
])
def test_shape_and_platform_choose_the_form(on_tpu, S, use_pallas, killed,
                                            path, monkeypatch):
    monkeypatch.setattr(ops, "_on_tpu", lambda: on_tpu)
    if killed:
        monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "1")
    q, lat, w, scale = operands(1, S, S, 1, 128, 128, jnp.float32)
    made = jax.make_jaxpr(lambda q, lat: ops.latent_expanded_attention(
        q, lat, w, RANK, scale, use_pallas=use_pallas))(q, lat)
    assert ops.last_latent_prefill_path == path
    made = primitives(made.jaxpr)
    assert ("pallas_call" in made) == (path == "pallas")
    # beside the kernel: no loop over query blocks, no softmax of scores
    assert path == "xla" or not made & {"while", "scan", "exp", "reduce_max"}


# --- both latent families served with the kernel in their prefill programs ------------

FAMILIES = {
    "moe_mla": ("glm_moe_mla", "moe_mla_decoder",
                "tests.test_zzzzzzzzzzzzzz_moe_mla"),
    "hc_moe_mla": ("hc_moe_mla", "hc_moe_mla_decoder",
                   "tests.test_zzzzzzzzzzzzzzzzzzz_hc_moe_mla"),
}


@pytest.fixture
def forced(monkeypatch):
    """The kernel wherever the models call for the expanded attention, in
    blocks of 32 queries by 16 keys (a 64-token bucket is 2 by 4 of them)."""
    from paddle_tpu.models import moe_mla

    monkeypatch.setattr(pallas_flash, "PREFILL_BLOCKS_Q", (32,))
    monkeypatch.setattr(pallas_flash, "PREFILL_BLOCKS_K", (16,))
    always = functools.partial(ops.latent_expanded_attention,
                               use_pallas=True)
    monkeypatch.setattr(moe_mla, "latent_expanded_attention", always)
    monkeypatch.setattr(ops, "latent_expanded_attention", always)  # chunks


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_served_with_the_kernel_forced_agrees_with_the_reference(
        family, forced):
    import importlib

    from paddle_tpu.serving import SchedulerConfig

    build, reference, helpers = FAMILIES[family]
    t = importlib.import_module(helpers)
    builder = harness.load_module("models", build)
    ref = harness.load_module("reference", reference)
    model = builder.build(t.TINY, 7, dtype="float32")

    def check(rows, req, prompt, steps):
        got = np.stack([l if l.ndim == 1 else l[0] for _, l, _ in rows])
        ids = prompt + [int(tok) for tok in req.output_tokens[:steps]]
        full = ref.reference_logits(builder.reference_weights(model),
                                    t.TINY, ids)
        return ref.compare(got, full[len(prompt) - 1:], t.ATOL, t.RMS_REL)

    # one-shot: a 37-token prompt in its 64-token bucket
    eng = t.make_engine(model)
    rows = t.capture(eng)
    prompt = t.prompt_of(37)
    req = t.serve(eng, prompt, 3)
    assert [p for p, _, _ in rows] == ["prefill"] + ["decode"] * 3
    assert eng.attention_paths["prefill"] == "pallas"
    assert eng._flash_rows == {("prefill", 64): 32}
    res = check(rows, req, prompt, 3)
    assert res["ok"] and res["rows_compared"] == 4, res
    assert res["max_abs_diff"] < 5e-6

    # chunks of 16 tokens over the pages: q_start > 0, lens under M
    eng = t.make_engine(model, prefix_cache=True, scheduler=SchedulerConfig(
        max_num_seqs=8, max_prefill_tokens_per_step=16))
    rows = t.capture(eng)
    prompt = t.prompt_of(45, seed=1)
    req = t.serve(eng, prompt, 2)
    programs = [p for p, _, _ in rows]
    assert programs.count("chunk") >= 2
    assert eng.attention_paths["chunk"] == "pallas"
    last_chunk = max(i for i, p in enumerate(programs) if p == "chunk")
    res = check(rows[last_chunk:], req, prompt, 2)
    assert res["ok"] and res["rows_compared"] == 3, res


def test_a_prefill_dispatch_carries_the_kernels_query_rows(forced,
                                                           monkeypatch):
    """``flash_block_q`` rides ``engine.dispatch`` of a prefill launch: what
    the program's trace wrote (so a bucket's first call still says 0), and 0
    on every launch whose program holds the XLA form."""
    import importlib

    from paddle_tpu.observability import tracer as tracer_mod

    t = importlib.import_module(FAMILIES["moe_mla"][2])
    model = harness.load_module("models", "glm_moe_mla").build(
        t.TINY, 7, dtype="float32")
    seen = []

    class Annotation:          # stands in for the profiler's annotation
        def __init__(self, name, **ints):
            if name == "engine.dispatch":
                seen.append(ints)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tracer_mod, "TraceAnnotation", Annotation)
    eng = t.make_engine(model)
    for seed in (0, 1):
        t.serve(eng, t.prompt_of(37, seed=seed), 1)
    prefills = [i["flash_block_q"] for i in seen if i["bucket"] == 64]
    assert prefills == [0, 32]
    assert all(type(i["flash_block_q"]) is int for i in seen)
    assert {i["flash_block_q"] for i in seen if i["bucket"] != 64} == {0}
