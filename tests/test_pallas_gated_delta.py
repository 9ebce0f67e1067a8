"""The gated delta rule's decode step IN PLACE on the slot pool (ISSUE 50:
``ops/pallas_gated_delta.py``), in interpret mode on the CPU against
``gated_delta_step`` on gathered states; the routing of
``selective_scan.state_step_path`` at both ranks of state pool; and a tiny
configuration of whole float32 tiles served through ``EngineCore`` with the
step forced through the kernel.  The sums over ``d_k`` run in another order
than XLA's, so results agree to float32 rounding and not bit for bit; what
the launch does not name is bit for bit what it was."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gdn_common import (TINY, build, builder, chunks_of_eight, make_engine,
                        prompt_of, serve)     # noqa: F401  (fixtures)

D = 128     # d_k = d_v: whole float32 tiles


def launch(rng, rows, hk, hv, slots, n_slots=None):
    """One decode launch's operands as the mixer hands them over (``q``
    and ``k`` L2-normalised a key head), over a pool of random states."""
    def f32(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q, k = unit(f32(rows, hk, D)) / np.sqrt(D), unit(f32(rows, hk, D))
    v = f32(rows, hv, D)
    log_alpha = -jnp.abs(f32(rows, hv)) * 0.2
    beta = jax.nn.sigmoid(f32(rows, hv))
    n_slots = n_slots or max(slots) + 2
    return (q, k, v, log_alpha, beta), f32(n_slots, hv, D, D), \
        jnp.asarray(slots, jnp.int32)


def oracle(step, pool, slots):
    """Gather by slot, ``gated_delta_step``, the key heads repeated."""
    from paddle_tpu.ops.gated_delta import gated_delta_step

    q, k, v, log_alpha, beta = step
    rep = v.shape[1] // q.shape[1]
    return gated_delta_step(jnp.repeat(q, rep, 1), jnp.repeat(k, rep, 1), v,
                            log_alpha, beta, pool[slots])


def close(got, want):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("rows,hk,hv", [
    (8, 2, 4),      # one step of rows, key heads shared in pairs
    (5, 1, 2),      # no multiple of the kernel's step: null-slot rows appended
    (11, 2, 2),     # a key head a value head, two steps
    (3, 1, 4),      # four value heads on one key head
])
def test_the_kernel_agrees_with_the_step_on_gathered_states(rows, hk, hv):
    from paddle_tpu.ops import pallas_gated_delta

    rng = np.random.default_rng(rows)
    slots = (rng.permutation(rows + 3)[:rows] + 1).tolist()   # none null
    step, pool, slot_ids = launch(rng, rows, hk, hv, slots)
    o, new = pallas_gated_delta.state_step(*step, pool, slot_ids)
    want_o, want_s = oracle(step, pool, slot_ids)
    assert o.shape == (rows, hv, D) and new.shape == pool.shape
    close(o, want_o)
    close(new[slot_ids], want_s)
    # every slot the launch does not name, bit for bit (the null slot is
    # named by the rows the kernel appends to fill its step)
    others = jnp.asarray([s for s in range(1, pool.shape[0])
                          if s not in slots], jnp.int32)
    assert len(others) and bool(jnp.all(new[others] == pool[others]))


def test_padding_rows_on_the_null_slot_change_no_real_row():
    """A bucket's padding rows all name slot 0 and race on it: the real
    rows' outputs and states are bit for bit those of the launch without
    them, wherever the padding stands among them."""
    from paddle_tpu.ops import pallas_gated_delta

    rng = np.random.default_rng(50)
    slots = [4, 0, 2, 0, 0, 7, 1, 0]
    real = [i for i, s in enumerate(slots) if s]
    step, pool, slot_ids = launch(rng, 8, 2, 4, slots)
    o, new = pallas_gated_delta.state_step(*step, pool, slot_ids)
    alone = tuple(a[jnp.asarray(real)] for a in step)
    o_alone, new_alone = pallas_gated_delta.state_step(
        *alone, pool, slot_ids[jnp.asarray(real)])
    assert bool(jnp.all(o[jnp.asarray(real)] == o_alone))
    assert bool(jnp.all(new[1:] == new_alone[1:]))
    close(new[slot_ids[jnp.asarray(real)]],
          oracle(alone, pool, slot_ids[jnp.asarray(real)])[1])


def test_two_launches_on_one_pool_are_two_steps_of_the_oracle():
    from paddle_tpu.ops import pallas_gated_delta

    rng = np.random.default_rng(51)
    slots = [3, 1, 6, 5, 2]
    first, pool, slot_ids = launch(rng, 5, 2, 4, slots, n_slots=8)
    second = launch(rng, 5, 2, 4, slots, n_slots=8)[0]
    _, mid = pallas_gated_delta.state_step(*first, pool, slot_ids)
    o, new = pallas_gated_delta.state_step(*second, mid, slot_ids)
    _, s1 = oracle(first, pool, slot_ids)
    want_o, s2 = oracle(second, pool.at[slot_ids].set(s1), slot_ids)
    close(o, want_o)
    close(new[slot_ids], s2)
    assert bool(jnp.all(new[7] == pool[7]))


@pytest.mark.parametrize("heads,rep,dk,dv,want", [
    (64, 2, 128, 128, 16),      # GigaChat3.5's: 1 MB a chunk
    (4, 2, 128, 128, 4),        # the tiny pool: a row a chunk
    (6, 2, 128, 128, 6),
    (24, 2, 128, 128, 12),      # the largest divisor in whole key heads
    (64, 2, 256, 512, 4),       # 512 KB a head: four fit the buffers
    (8, 8, 512, 1024, 8),       # never less than one key head's
])
def test_a_chunk_is_whole_key_heads_that_fit_the_buffers(heads, rep, dk, dv,
                                                         want):
    from paddle_tpu.ops.pallas_gated_delta import head_group

    assert head_group(heads, rep, dk, dv) == want


@pytest.mark.parametrize("shape,use_pallas,decode,want", [
    # off the chip nothing takes a kernel unless it is forced
    ((129, 64, 128, 128), None, True, "xla"),
    ((257, 16, 5120), None, True, "xla"),
    ((129, 64, 128, 128), True, True, "pallas"),
    ((257, 16, 5120), True, True, "pallas"),
    ((129, 64, 128, 128), False, True, "xla"),
    ((257, 16, 5120), False, True, "xla"),
    # a prefill or chunk launch never does, forced or not
    ((129, 64, 128, 128), True, False, "xla"),
    ((257, 16, 5120), True, False, "xla"),
    # an untileable width only when forced (interpret mode takes any)
    ((5, 4, 16, 16), None, True, "xla"),
    ((5, 4, 16, 16), True, True, "pallas"),
    ((5, 4, 128, 96), None, True, "xla"),
    ((5, 12, 100), True, True, "pallas"),
    # a pool of no known rank has no kernel to be forced through
    ((5, 128), True, True, "xla"),
    ((5, 2, 4, 128, 128), True, True, "xla"),
])
def test_the_routing_of_a_state_pool_by_its_shape(shape, use_pallas, decode,
                                                  want):
    from paddle_tpu.ops.selective_scan import state_step_path

    assert state_step_path(shape, use_pallas, decode=decode) == want


@pytest.mark.parametrize("shape,tileable", [
    ((129, 64, 128, 128), True),
    ((257, 16, 5120), True),
    ((5, 4, 16, 16), False),        # d_v no multiple of 128 lanes
    ((5, 4, 4, 128), False),        # d_k no multiple of 8 sublanes
    ((5, 12, 128), False),
])
def test_on_a_tpu_backend_the_shape_alone_chooses(monkeypatch, shape,
                                                  tileable):
    """What the chip's process sees: the kernel wherever the pool's last
    two widths are whole float32 tiles, at either rank; the pin and the
    kill switch still win."""
    from paddle_tpu.ops.selective_scan import state_step_path

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert state_step_path(shape, None) == ("pallas" if tileable else "xla")
    assert state_step_path(shape, None, decode=False) == "xla"
    assert state_step_path(shape, False) == "xla"
    monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "1")
    assert state_step_path(shape, None) == "xla"
    assert state_step_path(shape, True) == "xla"


def test_the_kill_switch_wins_over_the_force(monkeypatch):
    from paddle_tpu.ops.selective_scan import state_step_path

    monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "1")
    assert state_step_path((129, 64, 128, 128), True) == "xla"
    assert state_step_path((257, 16, 5120), True) == "xla"


# --- through the engine, the step forced through the kernel ---------------------------
# ``gdn_common.TINY`` has a latent layer, and a latent engine refuses
# ``use_pallas_paged=True`` (the paged decode kernel reads keys and values); so
# the variant served here is every layer a delta-rule mixer, at heads of 128 x
# 128: whole float32 tiles, 64 KB a head.

WIDE = dict(TINY, full_attention_layers=[], linear_key_head_dim=D,
            linear_value_head_dim=D)
FORCED = dict(use_pallas_paged=True)


@pytest.fixture(scope="module")
def wide(builder):
    return build(builder, WIDE)


def decode_traces(engine):
    """The attributes of every ``decode_jit_trace`` instant the engine
    emits from here on."""
    seen, real = [], engine.tracer.instant

    def instant(name, **kw):
        if name == "decode_jit_trace":
            seen.append(kw)
        return real(name, **kw)

    engine.tracer.instant = instant
    return seen


def test_forced_kernel_serves_the_xla_tokens_and_leaves_the_xla_states(wide):
    """Prefill (three chunks) and eight decode steps: the same tokens, and
    every layer's slot holds the XLA path's state to rounding; the trace
    instant says which path the program was traced through."""
    from paddle_tpu.ops import selective_scan

    prompt = prompt_of(21, 3)
    xla = make_engine(wide)
    seen_xla = decode_traces(xla)
    want = serve(xla, prompt, 8).output_tokens
    assert selective_scan.last_path == "xla"
    assert seen_xla and all(kw["state_step"] == "xla" for kw in seen_xla)

    eng = make_engine(wide, **FORCED)
    seen = decode_traces(eng)
    got = serve(eng, prompt, 8).output_tokens
    assert selective_scan.last_path == "pallas"
    assert seen and all(kw["state_step"] == "pallas" for kw in seen)
    assert got == want
    assert [p.shape for p in eng._k_pools] == [(5, 4, D, D)] * 5
    for a, b in zip(eng._k_pools, xla._k_pools):
        # slot 0 is the null slot: padding rows race on it
        np.testing.assert_allclose(np.asarray(a[1:]), np.asarray(b[1:]),
                                   rtol=1e-4, atol=1e-7)
        assert float(jnp.max(jnp.abs(a[1:]))) > 1e-4


def test_forced_kernel_under_preemption_gives_the_calm_xla_tokens(wide):
    """Four rows that do not fit their pages: rows leave and come back
    (their slots given up and taken again, the bucket's padding rows on the
    null slot meanwhile), and every request's tokens are those of the XLA
    path served alone."""
    from paddle_tpu.serving.request import SamplingParams

    calm = make_engine(wide)
    prompts = [prompt_of(14, seed=s) for s in range(4)]
    want = [serve(calm, p, 24).output_tokens for p in prompts]
    # 5 common blocks beside the 4 slots' own: four rows of 39 tokens
    # (3 blocks each) do not fit
    tight = make_engine(wide, num_blocks=10, **FORCED)
    reqs = [tight.add_request(p, SamplingParams(max_new_tokens=25,
                                                temperature=0.0))
            for p in prompts]
    for _ in range(400):
        if all(r.finished for r in reqs):
            break
        tight.step()
    reg, labels = tight.metrics.registry, tight.metrics.labels
    assert reg.counter("serving_preemptions_total", **labels).value > 0
    assert [r.output_tokens for r in reqs] == want
    assert tight.kv.state_slots_held == 0
