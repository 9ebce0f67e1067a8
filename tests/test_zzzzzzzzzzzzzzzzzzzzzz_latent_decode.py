"""The absorbed latent decode's page walk
(``pallas_paged.latent_decode_attention``) against its oracle (the XLA
gather form kept in ``ops/paged_attention.py``), how
``latent_paged_decode_attention`` chooses between them, the trace a row
bucket shares, and both latent model families served with the kernel in
their decode programs against the plain references.  Interpret mode on the
CPU, tiny lengths, the cells' head sizes."""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import harness
from paddle_tpu.ops import paged_attention as ops
from paddle_tpu.ops import pallas_paged
from tests.test_zzzzzzzzzzzzzzzzzzzz_flash_prefill import FAMILIES, primitives

GLM = dict(heads=20, rank=512, rope=64, nope=192, vd=256)     # glm-4.7-flash
XING = dict(heads=32, rank=512, rope=64, nope=128, vd=128)    # xing4.0-29b-a4b
SMALL = dict(heads=4, rank=24, rope=8, nope=16, vd=20)


def operands(lens, width, heads, rank, rope, nope, vd, bs=16, blocks=24,
             dtype=jnp.bfloat16, pool_dtype=None, resident=True, seed=0):
    """A launch of ``len(lens)`` rows behind tables of ``width`` entries.
    ``resident``: the pool as the engine holds it (rows in whole lane
    tiles); else ``[blocks, bs, 1, rank + rope]``."""
    rng = np.random.default_rng(seed)

    def rand(*shape, scale=1.0, dt=dtype):
        return jnp.asarray(rng.standard_normal(shape) * scale, dt)

    rows = rand(blocks, bs, 1, rank + rope, dt=pool_dtype or dtype)
    pool = rows
    if resident:
        pool = jnp.zeros(ops.latent_pool_shape(blocks, bs, (1, rank + rope)),
                         rows.dtype)
        pool = pool.at[..., :rank + rope].set(rows[:, :, 0])
    w = (rand(heads, rank, nope, scale=rank ** -0.5),
         rand(heads, rank, vd, scale=rank ** -0.5))
    tables = jnp.asarray(rng.integers(1, blocks, (len(lens), width)),
                         jnp.int32)
    return (rand(len(lens), heads, nope + rope), pool, w, tables,
            jnp.asarray(lens, jnp.int32), (nope + rope) ** -0.5)


def both_forms(q, pool, w, tables, lens, scale, rank, oracle_pool=None):
    got = ops.latent_paged_decode_attention(q, pool, w, tables, lens, rank,
                                            scale, use_pallas=True)
    assert ops.last_path == "pallas"
    want = ops.latent_paged_decode_attention(
        q, pool if oracle_pool is None else oracle_pool, w, tables, lens,
        rank, scale, use_pallas=False)
    assert ops.last_path == "xla"
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


# step: tokens a group of the walk holds (P pages of bs); lengths around a
# whole number of groups, one token, none, and more than the table holds;
# tables of 5 and 7 entries are no whole number of groups of 2 or 4 pages
CASES = [
    # name, head set, step, bs, width, lens, dtype, pool dtype, resident
    ("glm-around-two-groups", GLM, 32, 16, 5, [63, 64, 65], "bfloat16", None,
     True),
    ("xing-around-two-groups", XING, 32, 16, 5, [63, 64, 65], "bfloat16",
     None, True),
    ("glm-float32", GLM, 32, 16, 5, [31, 33, 80], "float32", None, True),
    ("xing-float32-pool-bf16-q", XING, 64, 16, 7, [100, 17], "bfloat16",
     "float32", True),
    ("small-one-none-over-the-table", SMALL, 8, 4, 7, [1, 0, 33, 28, 0, 9],
     "float32", None, False),
    ("small-around-three-groups", SMALL, 8, 4, 7, [23, 24, 25], "float32",
     None, True),
    ("glm-one-row-the-checks-launch", GLM, 32, 16, 5, [37], "bfloat16", None,
     True),
    ("xing-a-bucket-with-empty-rows", XING, 32, 16, 5, [0, 70, 0, 0, 16],
     "bfloat16", None, True),
    ("small-one-page-a-group", SMALL, 4, 4, 7, [5, 26, 4], "float32", None,
     True),
]


@pytest.mark.parametrize(
    "name,head_set,step,bs,width,lens,dtype,pool_dtype,resident", CASES,
    ids=[c[0] for c in CASES])
def test_the_walk_agrees_with_the_xla_form(name, head_set, step, bs, width,
                                           lens, dtype, pool_dtype, resident,
                                           monkeypatch):
    """Every case also plants NaN in the blocks no row's pages name and in
    the entries of a table past its row's last page: the walk reads neither
    (the oracle, which gathers the padded context, gets the clean pool)."""
    monkeypatch.setattr(pallas_paged, "LATENT_STEP_TOKENS", step)
    q, pool, w, tables, n, scale = operands(
        lens, width, bs=bs, dtype=jnp.dtype(dtype),
        pool_dtype=pool_dtype and jnp.dtype(pool_dtype), resident=resident,
        **head_set)
    assert pallas_paged.latent_pages_per_step(
        bs, pool.shape[-1], pool.dtype.itemsize, width) == step // bs
    held = np.minimum(-(-np.asarray(lens) // bs), width)
    tables = np.array(tables)
    poisoned = pool.shape[0] - 1          # a block no row holds
    tables[tables == poisoned] = 1
    for row, pages in enumerate(held):
        tables[row, pages:] = poisoned
    dirty = pool.at[poisoned].set(jnp.nan).at[0].set(jnp.nan)
    tables = jnp.asarray(tables)
    got, want = both_forms(q, dirty, w, tables, n, scale, head_set["rank"],
                           oracle_pool=pool)
    live = np.asarray(lens) > 0
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(got[live], want[live], atol=tol, rtol=tol)
    assert np.isfinite(got).all()
    assert not got[~live].any()         # a row of length 0 yields zeros


def test_pages_a_step_follow_the_shapes():
    """``P``: ``LATENT_STEP_TOKENS`` tokens' worth of pages, held to the
    table's width and to two buffers inside the VMEM budget."""
    tokens = pallas_paged.LATENT_STEP_TOKENS
    per = pallas_paged.latent_pages_per_step
    assert per(16, 640, 2, 512) == tokens // 16       # the cells' pools
    assert per(16, 640, 2, 8) == 8                    # a narrow table
    assert per(16, 640, 4, 512) == tokens // 16       # float32
    assert per(2 * tokens, 640, 2, 512) == 1          # a page a step
    budget = pallas_paged.PAGE_BUFFER_BYTES
    wide = budget // (2 * 16 * 2 * 4)                 # four pages fit twice
    assert per(16, wide, 2, 512) == 4
    pool = jax.ShapeDtypeStruct((64, 16, 640), jnp.bfloat16)
    assert pallas_paged.latent_kernel_pages(pool, 512) == tokens // 16


def test_traced_once_a_row_bucket(monkeypatch):
    """Tables narrower than ``TABLE_WIDTH`` go in padded to it and launches
    under ``ROWS_MIN`` rows get empty rows: programs that differ in their
    table width alone, or in a row bucket under 8, share ONE trace."""
    monkeypatch.setattr(pallas_paged, "LATENT_STEP_TOKENS", 8)
    traced = pallas_paged._latent_decode._cache_size
    before = traced()
    scale = 0.123           # this test's own: the scale is part of a trace

    def launch(rows, width):
        q, pool, w, tables, n, _ = operands(
            [9] * rows, width, bs=4, dtype=jnp.float32, **SMALL)
        ops.latent_paged_decode_attention(q, pool, w, tables, n, 24, scale,
                                          use_pallas=True)

    for rows, width in ((1, 5), (2, 7), (4, 5), (8, 9)):
        launch(rows, width)
    assert traced() == before + 1
    launch(16, 5)
    assert traced() == before + 2


class _TwoShards:
    axis_names = ("dp", "mp")
    shape = {"dp": 1, "mp": 2}


@pytest.mark.parametrize("on_tpu,pool_kw,use_pallas,killed,mesh,path", [
    (False, {}, None, False, None, "xla"),          # the CPU
    (True, {}, None, False, None, "pallas"),        # a TPU, the resident pool
    (True, dict(rows=1), None, False, None, "pallas"),   # ... at ONE row too
    (True, dict(resident=False), None, False, None, "xla"),  # rows of 576
    (True, dict(bs=8), None, False, None, "xla"),   # half a 16-bit tile a page
    (True, dict(pool_dtype=jnp.float32, bs=8), None, False, None, "pallas"),
    (True, {}, False, False, None, "xla"),          # pinned
    (False, dict(resident=False), True, False, None, "pallas"),  # forced
    (True, {}, None, True, None, "xla"),            # the operator's switch
    (False, {}, True, True, None, "xla"),           # ... wins over the force
    (True, {}, None, False, _TwoShards, "xla"),     # a mesh with mp > 1
])
def test_shape_and_platform_choose_the_form(on_tpu, pool_kw, use_pallas,
                                            killed, mesh, path, monkeypatch):
    from paddle_tpu.distributed import topology

    monkeypatch.setattr(ops, "_on_tpu", lambda: on_tpu)
    monkeypatch.setattr(topology, "get_mesh", lambda: mesh)
    if killed:
        monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "1")
    kw = dict(pool_kw)
    q, pool, w, tables, n, scale = operands([40] * kw.pop("rows", 3), 5,
                                            **kw, **GLM)
    made = jax.make_jaxpr(lambda q, pool: ops.latent_paged_decode_attention(
        q, pool, w, tables, n, 512, scale, use_pallas=use_pallas))(q, pool)
    assert ops.last_path == path
    made = primitives(made.jaxpr)
    assert ("pallas_call" in made) == (path == "pallas")
    # beside the kernel nothing is gathered and no score is written
    assert path == "xla" or not made & {"gather", "exp", "reduce_max"}


# --- both latent families served with the kernel in their decode programs ------------

@pytest.fixture
def forced(monkeypatch):
    """The kernel wherever the models call for the absorbed decode, in
    groups of two 4-token pages (a 40-token row is five of them)."""
    from paddle_tpu.models import moe_mla

    def always(*args, use_pallas=None, **kw):      # a pin still pins
        return ops.latent_paged_decode_attention(
            *args, use_pallas=use_pallas is None or use_pallas, **kw)

    monkeypatch.setattr(pallas_paged, "LATENT_STEP_TOKENS", 8)
    monkeypatch.setattr(moe_mla, "latent_paged_decode_attention", always)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_served_with_the_kernel_forced_agrees_with_the_reference(
        family, forced):
    build, reference, helpers = FAMILIES[family]
    t = importlib.import_module(helpers)
    builder = harness.load_module("models", build)
    ref = harness.load_module("reference", reference)
    model = builder.build(t.TINY, 7, dtype="float32")
    eng = t.make_engine(model)
    rows = t.capture(eng)
    prompt = t.prompt_of(37)
    req = t.serve(eng, prompt, 6)
    assert [p for p, _, _ in rows] == ["prefill"] + ["decode"] * 6
    assert eng.attention_paths["decode"] == "pallas"
    assert set(eng._kernel_pages.values()) == {2}
    got = np.stack([l if l.ndim == 1 else l[0] for _, l, _ in rows])
    ids = prompt + [int(tok) for tok in req.output_tokens[:6]]
    full = ref.reference_logits(builder.reference_weights(model), t.TINY,
                                ids)
    res = ref.compare(got, full[len(prompt) - 1:], t.ATOL, t.RMS_REL)
    assert res["ok"] and res["rows_compared"] == 7, res
    # the same tokens as the XLA form serves
    assert req.output_tokens == t.serve(
        t.make_engine(model, use_pallas_paged=False), prompt,
        6).output_tokens


def test_a_batch_of_rows_of_unlike_lengths_is_served_the_same(forced):
    """Rows that join and leave a decode launch (lengths apart, a bucket
    with padding rows) get the tokens each gets alone on the XLA form."""
    t = importlib.import_module(FAMILIES["moe_mla"][2])
    model = harness.load_module("models", "glm_moe_mla").build(
        t.TINY, 7, dtype="float32")
    from paddle_tpu.serving.request import SamplingParams

    prompts = [t.prompt_of(n, seed=n) for n in (5, 37, 18)]
    eng = t.make_engine(model)
    reqs = [eng.add_request(p, SamplingParams(max_new_tokens=4 + 3 * i,
                                              temperature=0.0))
            for i, p in enumerate(prompts)]
    for _ in range(80):
        if all(r.finished for r in reqs):
            break
        eng.step()
    assert eng.attention_paths["decode"] == "pallas"
    alone = t.make_engine(model, use_pallas_paged=False)
    for i, (p, r) in enumerate(zip(prompts, reqs)):
        assert r.output_tokens == t.serve(alone, p, 3 + 3 * i).output_tokens
