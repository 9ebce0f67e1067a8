"""No full-vocabulary sort in a step program unless a row samples (ISSUE 28).

The contract under test:

* ``logit_stats`` takes its top-2 margin from two max-reductions: its
  ``[rows, 3]`` output equals, bit for bit, the ``jax.lax.top_k``
  formulation it replaces (kept here as the reference), and it lowers to
  neither a ``sort`` nor a ``top_k``;
* ``sample_tokens`` keeps its sort, masks and draw behind one ``lax.cond``
  on ``any(temps > 0)``: its tokens equal, row for row and for the same
  keys, those of the unconditional pipeline it replaces (kept here as the
  reference), under ``jit`` and inside a ``fori_loop`` as ``decode_burst``
  calls it;
* in every step-program family no ``sort`` or ``top_k`` primitive stands
  outside a ``cond``;
* the engine counts its launches by the branch their sampler takes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability.audit import logit_stats
from paddle_tpu.ops.decode_burst import _step_keys
from paddle_tpu.ops.sampling import _NEG, _gumbel_from_keys, sample_tokens
from paddle_tpu.serving import (
    EngineConfig,
    EngineCore,
    SamplingParams,
    SchedulerConfig,
)

V = 257     # odd, so no reduction tiles evenly


def _bits(a):
    a = np.asarray(a)
    return a.view({4: np.uint32, 2: np.uint16}[a.dtype.itemsize])


# --- logit_stats against the top_k formulation --------------------------------

def _logit_stats_top_k(logits):
    """``logit_stats`` as it stood before ISSUE 28: the margin from
    ``jax.lax.top_k(safe, 2)``."""
    l = logits.astype(jnp.float32)
    if l.ndim == 1:
        l = l[None, :]
    finite = jnp.isfinite(l)
    nonfinite = jnp.sum(~finite, axis=-1).astype(jnp.float32)
    safe = jnp.where(finite, l, 0.0)
    absmax = jnp.max(jnp.abs(safe), axis=-1)
    top2 = jax.lax.top_k(safe, 2)[0]
    margin = top2[:, 0] - top2[:, 1]
    return jnp.stack([nonfinite, absmax, margin], axis=-1)


def _stats_rows(kind, rows, seed=0):
    rng = np.random.default_rng(seed)
    x = (4.0 * rng.standard_normal((rows, V))).astype(np.float32)
    if kind == "tie_at_top":
        # the maximum twice in every row, at lanes that differ by row
        top = np.abs(x).max(axis=-1) + 1.0
        for r in range(rows):
            x[r, (3 * r) % V] = top[r]
            x[r, (3 * r + 101) % V] = top[r]
    elif kind == "all_equal":
        x[:] = np.float32(-2.5)
    elif kind == "nonfinite":
        # an inf ABOVE every finite entry, a nan and a -inf: masked to 0
        x[:, 5] = np.inf
        x[:, 17] = np.nan
        x[::2, 40] = -np.inf
    elif kind == "nonfinite_row":
        # nothing finite in row 0: every lane masks to 0, margin 0
        x[0, :] = np.nan
    elif kind == "negative":
        # every finite entry below the 0 a masked lane reads
        x = -np.abs(x) - 1.0
        x[:, 9] = np.inf
    return x


class TestLogitStatsIdentity:
    @pytest.mark.parametrize("rows", [1, 8, 64])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("kind", ["random", "tie_at_top", "all_equal",
                                      "nonfinite", "nonfinite_row",
                                      "negative"])
    def test_equals_top_k_formulation(self, kind, dtype, rows):
        x = jnp.asarray(_stats_rows(kind, rows)).astype(dtype)
        got = jax.jit(logit_stats)(x)
        want = jax.jit(_logit_stats_top_k)(x)
        assert got.shape == (rows, 3) and got.dtype == jnp.float32
        np.testing.assert_array_equal(_bits(got), _bits(want))
        if kind in ("tie_at_top", "all_equal", "nonfinite_row"):
            assert np.all(np.asarray(got)[:1, 2] == 0.0)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    def test_one_dimensional_row_is_one_row(self, dtype):
        x = jnp.asarray(_stats_rows("nonfinite", 1)[0]).astype(dtype)
        got = logit_stats(x)
        assert got.shape == (1, 3)
        np.testing.assert_array_equal(_bits(got),
                                      _bits(_logit_stats_top_k(x)))
        np.testing.assert_array_equal(_bits(got),
                                      _bits(logit_stats(x[None, :])))


# --- sample_tokens against the unconditional pipeline -------------------------

def _sample_tokens_unconditional(logits, temps, top_ks, top_ps, keys):
    """``sample_tokens`` as it stood before ISSUE 28: every launch sorts,
    masks and draws, and greedy rows drop the result at the last
    ``where``."""
    x32 = logits.astype(jnp.float32)
    n = x32.shape[-1]
    greedy = jnp.argmax(x32, axis=-1).astype(jnp.int32)
    x = x32 / jnp.maximum(temps[:, None], 1e-6)
    sorted_desc = -jnp.sort(-x, axis=-1)
    k_eff = jnp.where(top_ks <= 0, n, jnp.minimum(top_ks, n))
    kth = jnp.take_along_axis(
        sorted_desc, (k_eff - 1).astype(jnp.int32)[:, None], axis=-1)
    x = jnp.where(x < kth, _NEG, x)
    sorted_masked = jnp.where(sorted_desc < kth, _NEG, sorted_desc)
    e = jnp.exp(sorted_masked - sorted_masked[:, 0:1])
    csum = jnp.cumsum(e, axis=-1)
    cut = jnp.argmax(csum >= top_ps[:, None] * csum[:, -1:], axis=-1)
    pth = jnp.take_along_axis(sorted_masked, cut[:, None], axis=-1)
    x = jnp.where(x < pth, _NEG, x)
    g = _gumbel_from_keys(keys, n)
    sampled = jnp.argmax(x + g, axis=-1).astype(jnp.int32)
    return jnp.where(temps <= 0.0, greedy, sampled)


ROWS = 8
# which rows sample (temperature > 0); the last two rows of "padded" are
# padding as SamplingPack leaves it: temperature 0, top_k 0, top_p 1, key 0
LAUNCHES = {
    "all_greedy": [False] * ROWS,
    "all_sampling": [True] * ROWS,
    "mixed": [True, False, False, True, True, False, True, False],
    "padded": [False, True, True, False, True, True, False, False],
}


def _quartet(launch, top_k, top_p, seed=3):
    rng = np.random.default_rng(seed)
    samples = np.array(LAUNCHES[launch])
    temps = np.where(samples, rng.uniform(0.5, 1.3, ROWS), 0.0)
    top_ks = np.full((ROWS,), top_k, np.int32)
    top_ps = np.full((ROWS,), top_p, np.float32)
    keys = rng.integers(0, 2**32, (ROWS, 2), dtype=np.uint64)
    if launch == "padded":
        top_ks[-2:], top_ps[-2:], keys[-2:] = 0, 1.0, 0
    return (jnp.asarray(temps, jnp.float32), jnp.asarray(top_ks),
            jnp.asarray(top_ps), jnp.asarray(keys.astype(np.uint32)))


def _logits(seed=11, rows=ROWS):
    rng = np.random.default_rng(seed)
    return jnp.asarray(3.0 * rng.standard_normal((rows, V)), jnp.float32)


def _burst_tokens(sample, logits, quartet, steps=4):
    """``steps`` chained draws as ``decode_burst.run_burst`` makes them:
    inside a ``fori_loop``, inactive rows at temperature 0, the draw
    index advanced by the iteration."""
    temps, top_ks, top_ps, keys = quartet
    act = jnp.arange(ROWS) != 1       # one row finished before the burst

    def body(j, buf):
        step_logits = jnp.roll(logits, j, axis=-1) * (1.0 + 0.1 * j)
        toks = sample(step_logits, jnp.where(act, temps, 0.0), top_ks,
                      top_ps, _step_keys(keys, j))
        return buf.at[:, j].set(toks)

    return jax.lax.fori_loop(jnp.int32(0), jnp.int32(steps), body,
                             jnp.full((ROWS, steps), -1, jnp.int32))


class TestSampleTokensIdentity:
    @pytest.mark.parametrize("where", ["jit", "fori_loop"])
    @pytest.mark.parametrize("top_p", [1.0, 0.95])
    @pytest.mark.parametrize("top_k", [0, 20])
    @pytest.mark.parametrize("launch", sorted(LAUNCHES))
    def test_equals_unconditional_pipeline(self, launch, top_k, top_p,
                                           where):
        quartet = _quartet(launch, top_k, top_p)
        logits = _logits()
        if where == "jit":
            got = jax.jit(sample_tokens)(logits, *quartet)
            want = jax.jit(_sample_tokens_unconditional)(logits, *quartet)
        else:
            got = jax.jit(_burst_tokens, static_argnums=0)(
                sample_tokens, logits, quartet)
            want = jax.jit(_burst_tokens, static_argnums=0)(
                _sample_tokens_unconditional, logits, quartet)
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        if launch == "all_greedy" and where == "jit":
            np.testing.assert_array_equal(
                np.asarray(got), np.argmax(np.asarray(logits), axis=-1))

    def test_sampling_rows_do_sample(self):
        """The reference comparison is not vacuous: at temperature 1 the
        draw leaves the argmax in some row."""
        quartet = _quartet("all_sampling", 0, 1.0)
        logits = _logits() * 0.1
        got = np.asarray(sample_tokens(logits, *quartet))
        assert np.any(got != np.argmax(np.asarray(logits), axis=-1))

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16],
                             ids=["bf16", "f16"])
    def test_narrow_logits_upcast_the_same(self, dtype):
        quartet = _quartet("mixed", 20, 0.95)
        logits = _logits().astype(dtype)
        np.testing.assert_array_equal(
            np.asarray(sample_tokens(logits, *quartet)),
            np.asarray(_sample_tokens_unconditional(logits, *quartet)))


# --- structure: where a sort may stand ----------------------------------------

SORTS = ("sort", "top_k", "approx_top_k")


def _sorting_eqns(jaxpr, in_cond=False, out=None):
    """``[(primitive name, stands inside a cond)]`` of every sorting
    equation in ``jaxpr`` and in every jaxpr nested in it."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in SORTS:
            out.append((name, in_cond))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _sorting_eqns(sub, in_cond or name == "cond", out)
    return out


# family -> (engine settings, scheduler settings), as
# test_zzzzzzzzzzzz_step_phases.py has them
FAMILIES = {
    "prefill": ({}, {}),
    "chunk": ({}, {"max_prefill_tokens_per_step": 8}),
    "decode": ({}, {}),
    "ragged": ({"unified_step": True}, {"max_tokens_per_step": 16}),
    "burst": ({"burst_steps": 4}, {}),
}
PROMPTS = [[5, 6, 7, 8] * 3, [40, 2, 11, 40, 2, 11, 40, 2], [9, 1, 4]]


def _engine(family):
    paddle.seed(0)
    eng_kw, sched_kw = FAMILIES[family]
    model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=1))
    return EngineCore(model, config=EngineConfig(
        num_blocks=64, block_size=4,
        scheduler=SchedulerConfig(max_num_seqs=4, **sched_kw), **eng_kw))


def _serve(eng, sampling, max_new=6):
    reqs = [eng.add_request(p, SamplingParams(max_new_tokens=max_new,
                                              **sampling))
            for p in PROMPTS]
    eng.run(max_steps=2000)
    assert all(r.finished for r in reqs)
    return [list(r.output_tokens) for r in reqs]


def _first_launches(eng):
    """Serve a few requests and return ``{program: (jitted function,
    arguments)}`` of the first launch of every family the run reached."""
    seen = {}
    step_call = eng._step_call

    def spy(program, bucket, jit_fn, *args):
        seen.setdefault(program, (jit_fn, args))
        return step_call(program, bucket, jit_fn, *args)

    eng._step_call = spy
    _serve(eng, {})
    return seen


class TestNoSortOutsideCond:
    @pytest.mark.parametrize("family", sorted(FAMILIES))
    def test_step_program_sorts_only_inside_a_cond(self, family):
        seen = _first_launches(_engine(family))
        assert family in seen, sorted(seen)
        jit_fn, args = seen[family]
        found = _sorting_eqns(jax.make_jaxpr(jit_fn)(*args).jaxpr)
        # the sampler's sort is there, so the walk reaches the epilogue
        assert ("sort", True) in found, found
        assert [f for f in found if not f[1]] == [], found

    def test_walk_sees_an_unconditional_sort(self):
        """The walk is not blind: the pipeline ISSUE 28 replaced fails it,
        nested in a jit and in a loop as a step program nests it."""
        found = _sorting_eqns(jax.make_jaxpr(
            jax.jit(_burst_tokens, static_argnums=0), static_argnums=0)(
                _sample_tokens_unconditional, _logits(),
                _quartet("mixed", 20, 0.95)).jaxpr)
        assert ("sort", False) in found, found
        found = _sorting_eqns(jax.make_jaxpr(_logit_stats_top_k)(
            _logits()).jaxpr)
        assert ("top_k", False) in found, found

    @pytest.mark.parametrize("shape", [(8, V), (V,)], ids=["rows", "row"])
    def test_logit_stats_lowers_to_no_sort(self, shape):
        x = jnp.zeros(shape, jnp.float32)
        assert _sorting_eqns(jax.make_jaxpr(logit_stats)(x).jaxpr) == []
        text = jax.jit(logit_stats).lower(x).as_text().lower()
        assert "sort" not in text and "top_k" not in text \
            and "topk" not in text


# --- the counters that say how often the sort is skipped ----------------------

def _launch_counts(eng):
    c = eng._sampling_counters
    return (int(c["sampling_launches"].value),
            int(c["greedy_launches"].value),
            int(eng._burst_counters["roundtrips"].value))


class TestLaunchCounters:
    @pytest.mark.parametrize("family", sorted(FAMILIES))
    def test_greedy_run_counts_no_sampling_launch(self, family):
        eng = _engine(family)
        assert _launch_counts(eng) == (0, 0, 0)
        _serve(eng, {})
        sampling, greedy, launches = _launch_counts(eng)
        assert launches > 0
        assert (sampling, greedy) == (0, launches)

    @pytest.mark.parametrize("family", sorted(FAMILIES))
    def test_sampling_run_counts_every_launch(self, family):
        eng = _engine(family)
        _serve(eng, dict(temperature=0.8, top_k=20, top_p=0.9, seed=1234))
        sampling, greedy, launches = _launch_counts(eng)
        assert launches > 0
        assert (sampling, greedy) == (launches, 0)

    def test_mixed_run_splits_all_launches(self):
        """One sampling request among greedy ones: its launches count as
        sampling, the ones after it has finished as greedy, and the two
        sum to every launch."""
        eng = _engine("decode")
        eng.add_request(PROMPTS[0], SamplingParams(
            max_new_tokens=2, temperature=0.8, seed=5))
        eng.add_request(PROMPTS[1], SamplingParams(max_new_tokens=8))
        eng.run(max_steps=2000)
        sampling, greedy, launches = _launch_counts(eng)
        assert sampling > 0 and greedy > 0
        assert sampling + greedy == launches

    def test_series_are_exported_from_the_first_scrape(self):
        text = _engine("decode").metrics.registry.prometheus_text()
        assert "serving_sampling_launches_total 0" in text
        assert "serving_greedy_launches_total 0" in text
