"""The two decode kernels of chunk-summarised (EVA) attention
(``ops/pallas_eva.py``) in interpret mode on the CPU: each half's partial
attention ``(weighted sum, max, sum)`` against a dense form of the same
sums, the merged result against ``decode_attention``'s XLA form (the
kernels' oracle), and the merge against one softmax over both score sets.
What a tile may hold beside what its rows see -- a freed block's stale
rows, the open window's rows, a tile nobody sees -- must not be read."""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import eva_attention as eva
from paddle_tpu.ops import pallas_eva as pk

H, D, W, C = 4, 32, 32, 16
PER_W = W // C                  # summary rows a closed window
LOUD = 6.0                      # a row nobody may see: it would take all the mass


def launch(poss, R=1, dtype=jnp.bfloat16, padding=0, num_blocks=None,
           seed=0, scattered=True):
    """A decode launch of rows at positions ``poss`` (then ``padding`` rows
    on slot 0 / block 0 at position 0): rings and pools of random values,
    every row of the pool that NO row of the launch sees made LOUD (a freed
    block's stale rows and the open windows' rows among them)."""
    rng = np.random.default_rng(seed)
    B = len(poss) + padding
    per_block = R * C
    need = [-(-(p + 1) // per_block) for p in poss]
    num_blocks = num_blocks or sum(need) + 9

    def f(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    ids = np.arange(1, num_blocks)
    if scattered:
        ids = rng.permutation(ids)
    tables = np.zeros((B, max(need) + 1), np.int32)
    seen = np.zeros((num_blocks, R), bool)
    at = 0
    for b, (p, n) in enumerate(zip(poss, need)):
        tables[b, :n] = ids[at:at + n]
        at += n
        for c in range(PER_W * (p // W)):
            seen[tables[b, c // R], c % R] = True
    k_rows, v_rows = f(num_blocks, R, H, D), f(num_blocks, R, H, D)
    k_rows[~seen] = LOUD
    v_rows[~seen] = 100.0
    slots = np.r_[1:len(poss) + 1, np.zeros(padding, int)].astype(np.int32)
    pos = np.r_[np.asarray(poss, int), np.zeros(padding, int)].astype(np.int32)
    cast = lambda a: jnp.asarray(a, dtype)             # noqa: E731
    return dict(q=cast(f(B, H, D)), k_ring=cast(f(len(poss) + 1, W, H, D)),
                v_ring=cast(f(len(poss) + 1, W, H, D)), k_rows=cast(k_rows),
                v_rows=cast(v_rows), slots=jnp.asarray(slots),
                tables=jnp.asarray(tables), pos=jnp.asarray(pos))


def forms(a):
    """The XLA form's and the kernels' result of launch ``a``, float32."""
    args = (a["q"], a["k_ring"], a["v_ring"], a["k_rows"], a["v_rows"],
            a["slots"], a["tables"], a["pos"], W, C)
    want = eva.decode_attention(*args, use_pallas=False)
    assert eva._paged.last_path == "xla"
    got = eva.decode_attention(*args, use_pallas=True)
    assert eva._paged.last_path == "pallas"
    return np.asarray(want, np.float32), np.asarray(got, np.float32)


def dense(q, k, v, visible):
    """``(o, m, l)`` of ``q`` ``[B, H, D]`` over ``k`` / ``v`` ``[B | 1, n,
    H, D]`` where ``visible`` ``[B, n]``: float32 scores of the operands as
    they lie, weights cast to the values' type, as both forms do."""
    s = jnp.einsum("bhd,bnhd->bhn", q, jnp.broadcast_to(
        k, (q.shape[0],) + k.shape[1:]), preferred_element_type=jnp.float32)
    s = jnp.where(visible[:, None], s / math.sqrt(q.shape[-1]), -1e30)
    m = s.max(-1)
    p = jnp.where(visible[:, None], jnp.exp(s - m[..., None]), 0.0)
    o = jnp.einsum("bhn,bnhd->bhd", p.astype(v.dtype), jnp.broadcast_to(
        v, (q.shape[0],) + v.shape[1:]), preferred_element_type=jnp.float32)
    return tuple(np.asarray(x) for x in (o, m, p.sum(-1)))


def seen_rows(a):
    return eva._seen_rows(a["tables"], a["pos"], a["k_rows"].shape, W, C)


def assert_partials(got, want, tol):
    go, gm, gl = (np.asarray(x) for x in got)
    wo, wm, wl = want
    live = wl > 0
    assert (gl[~live] == 0).all() and (go[~live] == 0).all()
    np.testing.assert_allclose(gm[live], wm[live], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(gl[live], wl[live], rtol=tol, atol=tol)
    np.testing.assert_allclose(go[live], wo[live], rtol=tol,
                               atol=tol * max(1.0, np.abs(wo[live]).max(
                                   initial=0.0)))


def tol_of(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 1e-5


CASES = {
    "a_ring_of_one_entry_beside_one_and_two_closed_windows":
        dict(poss=[W, 2 * W]),
    "a_ring_of_two_entries": dict(poss=[W + 1, 1]),
    "a_full_ring": dict(poss=[W - 1, 3 * W - 1]),
    "no_closed_window_all_mass_on_the_ring": dict(poss=[5, 20]),
    "one_closed_window": dict(poss=[W + 7]),
    "fifteen_closed_windows": dict(poss=[15 * W + 9, 15 * W]),
    "none_one_and_fifteen_closed_in_one_launch":
        dict(poss=[3, W + 3, 15 * W + 3]),
    "two_rows_a_block": dict(poss=[2 * W + 5, 5 * W + 31, 9], R=2),
    "two_rows_a_block_float32": dict(poss=[W, 4 * W + 17], R=2,
                                     dtype=jnp.float32),
    "one_head_a_word_float32": dict(poss=[7, 3 * W + 2, 6 * W + 31],
                                    dtype=jnp.float32),
    "padding_rows_on_slot_0_and_block_0":
        dict(poss=[2 * W + 4, 11, 5 * W], padding=5),
    "a_pool_that_is_no_multiple_of_the_tile":
        dict(poss=[4 * W + 1, 7 * W + 30, W], num_blocks=150),
    "a_pool_of_less_than_a_tile": dict(poss=[3 * W + 1, 2 * W], num_blocks=40),
    "row_bucket_1": dict(poss=[5 * W + 12]),
    "row_bucket_2": dict(poss=[5 * W + 12, 40]),
    "row_bucket_8": dict(poss=[17 * (i + 1) for i in range(8)]),
    "row_bucket_16": dict(poss=[11 * (i + 1) + 3 for i in range(16)]),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_agree_with_the_xla_form(case):
    """The merged result, and each half's partial attention, at ``pos mod
    W`` of 0, 1 and ``W - 1``; no, one and fifteen closed windows; one and
    two rows a block; one and two heads a word; row buckets 1 to 16;
    padding rows; a pool that ends inside a tile (the rows past its last
    whole tile are XLA's) and one of less than a tile.  Every row of the pool
    that nobody sees is LOUD: read, it would take the softmax's mass."""
    kw = dict(CASES[case])
    a = launch(**kw)
    dtype = a["q"].dtype
    want, got = forms(a)
    real = len(kw["poss"])
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[:real], want[:real], atol=tol_of(dtype),
                               rtol=tol_of(dtype))
    assert (got[real:] == 0).all()      # a padding row reads nothing
    # the ring's half: a row's slot up to its position, nothing of slot 0
    lens = jnp.where(a["slots"] > 0, a["pos"] % W + 1, 0)
    loc = pk.ring_partials(a["q"], a["k_ring"], a["v_ring"], a["slots"],
                           lens)
    assert_partials(loc, dense(
        a["q"], a["k_ring"][a["slots"]], a["v_ring"][a["slots"]],
        jnp.arange(W)[None] < lens[:, None]), tol_of(dtype))
    # the pool's half: the rows of closed windows, nobody else's
    seen = seen_rows(a)
    if "no_multiple" in case:   # the rows past the last whole tile are seen
        assert a["k_rows"].shape[0] % 64 == 22 \
            and np.asarray(seen)[:, -22:].any()
    rem = pk.pool_partials(a["q"], a["k_rows"], a["v_rows"], seen, tile=64)
    flat = lambda x: x.reshape((1, -1) + x.shape[2:])   # noqa: E731
    assert_partials(rem, dense(a["q"], flat(a["k_rows"]), flat(a["v_rows"]),
                               seen), tol_of(dtype))
    none = np.asarray(a["pos"]) < W
    assert (np.asarray(rem[2])[none] == 0).all()        # l_rem = 0


@pytest.mark.parametrize("tile,dtype", [(64, jnp.bfloat16),
                                        (128, jnp.float32)])
def test_a_tile_nobody_sees_is_neither_copied_nor_computed(tile, dtype):
    """Blocks handed out in order fill a prefix of the pool; the tiles past
    it (and the null block's, where nobody holds a neighbour) hold NaN: the
    kernel skips them, so its result is finite and is the XLA form's over a
    pool without them."""
    a = launch([3 * W + 4, 5 * W + 20], dtype=dtype, num_blocks=4 * tile,
               scattered=False)
    seen = np.asarray(seen_rows(a))
    some = seen.reshape(2, -1, tile).any((0, 2))
    assert some.any() and not some.all()
    bad = np.repeat(~some, tile)
    clean = dict(a)
    for side in ("k_rows", "v_rows"):
        rows = np.array(a[side], np.float32)
        rows[bad] = np.nan
        a[side] = jnp.asarray(rows, dtype)
        rows[bad] = 0.0
        clean[side] = jnp.asarray(rows, dtype)
    want, _ = forms(clean)
    args = (a["q"], a["k_rows"], a["v_rows"], jnp.asarray(seen))
    rem = pk.pool_partials(*args, tile=tile)
    assert all(np.isfinite(np.asarray(x)).all() for x in rem)
    _, tile_of, n = pk.pool_sight(jnp.asarray(seen), tile, pk.ROWS_MIN)
    assert int(n[0]) == some.sum()
    assert set(np.asarray(tile_of).tolist()) == set(np.flatnonzero(some))
    loc = pk.ring_partials(a["q"], a["k_ring"], a["v_ring"], a["slots"],
                           a["pos"] % W + 1)
    got = np.asarray(pk.merge(loc, rem)).reshape(want.shape)
    np.testing.assert_allclose(got, want, atol=tol_of(dtype),
                               rtol=tol_of(dtype))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_merge_is_one_softmax_over_both_score_sets(seed):
    """``merge`` of two partial attentions is the softmax over the union
    of their scores; a part that saw nothing weighs nothing; a row that
    saw nothing at all yields zeros."""
    rng = np.random.default_rng(seed)
    B, n1, n2 = 5, 7, 11
    s1, s2 = (rng.normal(0, 3, (B, H, n)).astype(np.float32)
              for n in (n1, n2))
    v1, v2 = (rng.normal(0, 1, (B, H, n, D)).astype(np.float32)
              for n in (n1, n2))
    vis1 = np.ones((B, n1), bool)
    vis2 = np.ones((B, n2), bool)
    vis2[0] = False             # no closed window: all mass on the ring
    vis1[1] = False
    vis1[2] = vis2[2] = False   # a bucket's padding row

    def part(s, v, vis):
        s = np.where(vis[:, None], s, -1e30)
        m = s.max(-1)
        p = np.where(vis[:, None], np.exp(s - m[..., None]), 0.0)
        return (np.einsum("bhn,bhnd->bhd", p, v).astype(np.float32),
                m.astype(np.float32), p.sum(-1).astype(np.float32))

    got = np.asarray(pk.merge(part(s1, v1, vis1), part(s2, v2, vis2)))
    s = np.concatenate([np.where(vis1[:, None], s1, -np.inf),
                        np.where(vis2[:, None], s2, -np.inf)], -1)
    live = np.isfinite(s).any(-1)[:, 0]
    p = np.exp(s[live] - s[live].max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("bhn,bhnd->bhd", p,
                     np.concatenate([v1, v2], 2)[live])
    np.testing.assert_allclose(got[live], want, rtol=1e-5, atol=1e-5)
    assert (got[~live] == 0).all() and live.sum() == B - 1


def test_the_tiles_follow_the_pools_shape_and_the_platform_decides():
    """A tile is 2 MB a side of the pool and 1 MB of a ring at the served
    shapes, 64 rows at least, no more than the pool's rows (down to a power
    of two) or the window; off the
    TPU the XLA form is traced unless a test forces the kernels; shapes
    that do not tile are not taken compiled."""
    assert pk.pool_tile_rows(34816, 32, 128) == 256
    assert pk.ring_tile_rows(2048, 32, 128) == 128
    assert pk.pool_tile_rows(300, 2, 32) == 256     # never over the rows
    assert pk.pool_tile_rows(100, 2, 32) == 64
    assert pk.pool_tile_rows(24, 2, 32) == 64       # no whole tile: XLA's
    assert pk.ring_tile_rows(32, 2, 32) == 32

    def shapes(dtype, heads, dim):
        z = lambda *s: jax.ShapeDtypeStruct(s, dtype)   # noqa: E731
        return z(2, heads, dim), z(3, 2048, heads, dim), z(64, 1, heads, dim)

    assert pk.takes(*shapes(jnp.bfloat16, 32, 128))
    assert not pk.takes(*shapes(jnp.float32, 32, 128))
    assert not pk.takes(*shapes(jnp.bfloat16, 8, 128))
    assert not pk.takes(*shapes(jnp.bfloat16, 32, 64))
    a = launch([W + 3])
    eva.decode_attention(a["q"], a["k_ring"], a["v_ring"], a["k_rows"],
                         a["v_rows"], a["slots"], a["tables"], a["pos"], W, C)
    assert eva._paged.last_path == ("pallas" if jax.default_backend() == "tpu"
                                    and pk.takes(a["q"], a["k_ring"],
                                                 a["k_rows"]) else "xla")
