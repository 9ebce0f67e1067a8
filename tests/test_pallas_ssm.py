"""The selective-scan decode step as ONE Pallas kernel that reads a row's
state from its slot, steps it and writes it back in place
(``paddle_tpu/ops/pallas_ssm.py``), in interpret mode on the CPU: against
the XLA path it replaces on the chip (gather by slot, ``selective_step``,
scatter), over the engine's row buckets, with scattered slots and padding
rows on the null slot; and the dispatch that chooses between the two
(``ops.selective_scan.state_step_path`` / ``last_path``)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import pallas_ssm
from paddle_tpu.ops import selective_scan as ss

N, D = 8, 256       # whole float32 tiles: the kernel's widths


def inputs(rows, n=N, d=D, slots=None, pool_slots=None, seed=0):
    """A launch of ``rows`` rows over a pool of ``pool_slots`` slots, every
    row on a slot of its own, scattered (no two neighbours) unless
    ``slots`` says otherwise."""
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    pool_slots = pool_slots or 2 * rows + 1
    if slots is None:
        slots = rng.permutation(np.arange(1, pool_slots))[:rows]
    return dict(
        x=f(rows, d), dt=jnp.asarray(rng.uniform(1e-3, 0.1, (rows, d)),
                                     jnp.float32),
        A=-jnp.exp(f(n, d)), Bm=f(rows, n), Cm=f(rows, n),
        pool=f(pool_slots, n, d), slots=jnp.asarray(slots, jnp.int32))


def kernel(a):
    return pallas_ssm.state_step(a["x"], a["dt"], a["A"], a["Bm"], a["Cm"],
                                 a["pool"], a["slots"])


def oracle(a):
    """What the XLA path does: gather, step, scatter."""
    y, h = ss.selective_step(a["x"], a["dt"], a["A"], a["Bm"], a["Cm"],
                             a["pool"][a["slots"]])
    return y, a["pool"].at[a["slots"]].set(h)


@pytest.mark.parametrize("rows", [8, 32, 256])
def test_kernel_is_gather_step_scatter_over_the_row_buckets(rows):
    a = inputs(rows)
    y, pool = kernel(a)
    want_y, want_pool = oracle(a)
    np.testing.assert_allclose(y, want_y, rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(pool, want_pool, rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("rows,n,d", [(8, 16, 128), (3, 8, 128),
                                      (16, 8, 640)])
def test_other_widths_and_a_launch_of_no_whole_step(rows, n, d):
    """16 state indices, a channel count that is no multiple of the
    kernel's tile of 512, and 3 rows: null-slot rows are appended to a
    whole step of 8 and cut off again."""
    a = inputs(rows, n, d)
    y, pool = kernel(a)
    want_y, want_pool = oracle(a)
    assert y.shape == (rows, d)
    np.testing.assert_allclose(y, want_y, rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(pool[1:], want_pool[1:], rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("rows", [8, 32])
def test_slots_the_launch_does_not_name_are_left_bit_for_bit(rows):
    a = inputs(rows, pool_slots=3 * rows)
    _, pool = kernel(a)
    others = np.setdiff1d(np.arange(3 * rows), np.asarray(a["slots"]))
    assert len(others) == 2 * rows
    assert (np.asarray(pool)[others] == np.asarray(a["pool"])[others]).all()
    named = np.asarray(a["slots"])
    assert (np.asarray(pool)[named] != np.asarray(a["pool"])[named]).any()


@pytest.mark.parametrize("padding", [0, 3, 7])
def test_padding_rows_on_the_null_slot_change_no_real_row(padding):
    """A bucket's padding rows all name slot 0 and race on it; what a real
    row yields and leaves in its slot is, bit for bit, what it does with
    no padding row beside it."""
    a = inputs(8, seed=3)
    want_y, want_pool = kernel(a)
    real = 8 - padding
    b = dict(a, slots=a["slots"].at[real:].set(0))
    y, pool = kernel(b)
    assert (np.asarray(y)[:real] == np.asarray(want_y)[:real]).all()
    kept = np.asarray(a["slots"])[:real]
    assert (np.asarray(pool)[kept] == np.asarray(want_pool)[kept]).all()
    # the slots of the rows that became padding were not stepped
    dropped = np.asarray(a["slots"])[real:]
    assert (np.asarray(pool)[dropped] == np.asarray(a["pool"])[dropped]).all()
    assert np.isfinite(np.asarray(pool)).all()


def test_inside_an_outer_jit_with_the_pool_donated():
    """As a step program calls it: traced inside a jit under the package's
    64-bit default, the pool donated, twice in a row at one shape."""
    a = inputs(8, seed=5)
    want_y, want_pool = oracle(a)

    @jax.jit
    def twice(pool, slots):
        y1, pool = pallas_ssm.state_step(a["x"], a["dt"], a["A"], a["Bm"],
                                         a["Cm"], pool, slots)
        y2, pool = pallas_ssm.state_step(a["x"], a["dt"], a["A"], a["Bm"],
                                         a["Cm"], pool, slots)
        return y1, y2, pool

    y1, y2, pool = twice(a["pool"], a["slots"])
    np.testing.assert_allclose(y1, want_y, rtol=2e-6, atol=2e-6)
    again = oracle(dict(a, pool=want_pool))
    np.testing.assert_allclose(y2, again[0], rtol=4e-6, atol=4e-6)
    np.testing.assert_allclose(pool, again[1], rtol=4e-6, atol=4e-6)


# --- which path a launch takes -----------------------------------------------------

def routed(n_valid=None, use_pallas=None):
    c = ss.StateCache(None, None)
    c.route(np.arange(4)[:, None], start=None if n_valid is None else 0,
            n_valid=n_valid)
    c.use_pallas = use_pallas
    return c


def test_off_the_chip_every_launch_takes_the_xla_path_unless_forced():
    shape = (9, N, D)
    assert ss.route_state_step(routed(), shape) == "xla"     # a CPU run
    assert ss.last_path == "xla"
    assert ss.route_state_step(routed(use_pallas=True), shape) == "pallas"
    assert ss.last_path == "pallas"
    assert ss.route_state_step(routed(use_pallas=False), shape) == "xla"
    # a prefill or chunk launch scans: never the kernel, forced or not
    assert ss.route_state_step(routed(n_valid=5, use_pallas=True),
                               shape) == "xla"
    assert ss.last_path == "xla"


@pytest.mark.parametrize("shape,use_pallas,env,want", [
    ((257, 16, 5120), None, None, "pallas"),    # the published widths
    ((9, 8, 128), None, None, "pallas"),
    ((9, 4, 128), None, None, "xla"),           # no whole sublane tile
    ((9, 8, 64), None, None, "xla"),            # no whole lane tile
    ((257, 16, 5120), False, None, "xla"),      # pinned
    ((9, 8, 64), True, None, "pallas"),         # forced past the widths
    ((257, 16, 5120), True, "1", "xla"),        # the kill switch wins
])
def test_on_the_chip_shape_and_hint_choose(monkeypatch, shape, use_pallas,
                                           env, want):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if env:
        monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", env)
    assert ss.state_step_path(shape, use_pallas) == want
    assert ss.state_step_path(shape, use_pallas, decode=False) == "xla"


def mixer_step(use_pallas):
    """One decode step of ``HybridMambaConfig.tiny``'s mixer over slot
    pools, rows on slots 5, 2 and the null slot."""
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.mamba_hybrid import HybridMambaConfig, MambaMixer

    cfg = HybridMambaConfig.tiny()
    paddle.seed(11)
    mixer = MambaMixer(cfg)
    rng = np.random.default_rng(2)
    d, n, k = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
    cache = ss.StateCache(
        Tensor(jnp.asarray(rng.standard_normal((7, n, d)), jnp.float32)),
        Tensor(jnp.asarray(rng.standard_normal((7, (k - 1) * d)),
                           jnp.float32)))
    cache.route(np.asarray([[5], [2], [0]]))    # a row's slot: its first block
    cache.use_pallas = use_pallas
    x = Tensor(jnp.asarray(rng.standard_normal((3, 1, cfg.hidden_size)),
                           jnp.float32))
    with paddle.no_grad():
        y = mixer(x, cache=cache)
    return (np.asarray(y._value), np.asarray(cache.state_pool._value),
            np.asarray(cache.conv_pool._value))


def test_the_tiny_mixer_steps_through_xla_and_the_kernel_agrees():
    y, state, conv = mixer_step(None)
    assert ss.last_path == "xla"
    ky, kstate, kconv = mixer_step(True)
    assert ss.last_path == "pallas"
    np.testing.assert_allclose(ky[:2], y[:2], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(kstate[1:], state[1:], rtol=1e-5, atol=1e-6)
    assert (kconv == conv).all()
    assert (kstate[[1, 3, 4, 6]] == state[[1, 3, 4, 6]]).all()
