"""The walk of the flash grids: a list, laid out as the call is traced, of
the (resident tile, streamed tile) pairs whose score tile holds an unmasked
pair (``ops/flash_attention.py`` ``_live_tiles`` / ``_live_pairs``).  The
list against the dense mask for the three masks — counts, order, every live
tile and no dead one, unequal lengths and tile widths, offsets; the grids a
cell's call is traced with and what the counter holds of them; and forward
and gradients bit for bit those of a walk over every tile of the rectangle.
(``tests/test_flash_attention_window.py`` and
``tests/test_block_diffusion_attention.py`` have the band's and the
block-diffusion mask's own cases.)"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _flash_kernels import force_tile, pallas_grids, tiles_built
from horovod_tpu.ops import flash_attention as fa


def dense(sq, sk, window=None, bd=None, offsets=(0, 0)) -> np.ndarray:
    """The mask pair by pair, (sq, sk) bool, query by key."""
    if bd is not None:
        at = np.arange(sq)
        noised, beta = at < bd[1], (at % bd[1]) // bd[0]
        q_n, k_n = noised[:, None], noised[None, :]
        q_b, k_b = beta[:, None], beta[None, :]
        return np.where(k_n, q_n & (q_b == k_b),
                        np.where(q_n, k_b < q_b, k_b <= q_b))
    q_pos = offsets[0] + np.arange(sq)[:, None]
    k_pos = offsets[1] + np.arange(sk)[None, :]
    keep = q_pos >= k_pos
    return keep if window is None else keep & (q_pos - k_pos < window)


def dense_tiles(mask, bq, bk) -> set:
    return {(i, j) for i in range(mask.shape[0] // bq)
            for j in range(mask.shape[1] // bk)
            if mask[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk].any()}


# (sq, sk, bq, bk, window, bd, offsets): the triangle; unequal tile widths
# either way; unequal lengths (the queries the last 64 positions of the
# keys' 96, and the first); chunks of a longer sequence, later, overlapping
# and earlier than their keys; bands wider, narrower and no multiple of
# either tile; the block-diffusion mask at three blocks.
LAYOUTS = [
    (64, 64, 8, 8, None, None, (0, 0)),
    (64, 64, 16, 8, None, None, (0, 0)),
    (64, 64, 8, 16, None, None, (0, 0)),
    (64, 96, 16, 8, None, None, (32, 0)),
    (64, 96, 8, 32, None, None, (0, 0)),
    (64, 64, 8, 8, None, None, (64, 0)),
    (64, 64, 8, 8, None, None, (24, 0)),
    (64, 64, 16, 8, None, None, (0, 24)),
    (64, 64, 8, 8, None, None, (0, 40)),
    (64, 64, 8, 8, 8, None, (0, 0)),
    (64, 64, 8, 8, 9, None, (0, 0)),
    (64, 64, 8, 8, 10, None, (0, 0)),
    (64, 64, 16, 8, 8, None, (0, 0)),
    (64, 64, 8, 16, 20, None, (0, 0)),
    (64, 64, 8, 8, 1, None, (0, 0)),
    (64, 64, 8, 8, 30, None, (16, 8)),
    (64, 64, 8, 8, None, (2, 32), (0, 0)),
    (64, 64, 8, 8, None, (8, 32), (0, 0)),
    (64, 64, 16, 16, None, (4, 32), (0, 0)),
]


@pytest.mark.parametrize("sq, sk, bq, bk, window, bd, offsets", LAYOUTS, ids=[
    f"q{c[0]}k{c[1]}-{c[2]}x{c[3]}-w{c[4]}-bd{c[5] and c[5][0]}"
    f"-off{c[6][0]}.{c[6][1]}" for c in LAYOUTS])
def test_the_list_is_the_dense_masks_live_tiles_in_order(
        sq, sk, bq, bk, window, bd, offsets):
    want = dense_tiles(dense(sq, sk, window, bd, offsets), bq, bk)
    live = fa._live_tiles(sq, sk, bq, bk, window, bd, offsets)
    assert live.shape == (sq // bq, sk // bk) and live.dtype == bool
    assert {(i, j) for i, j in zip(*np.nonzero(live))} == want
    for keys_streamed in (True, False):
        pairs = fa._live_pairs(sq, sk, bq, bk, window, bd, offsets,
                               keys_streamed)
        assert pairs.dtype == np.int32
        steps = [tuple(p) for p in pairs.tolist()]
        assert len(steps) == len(set(steps))            # no tile twice
        as_qk = {(r, s) if keys_streamed else (s, r) for r, s in steps}
        # All live, none dead, but the one step a resident tile keeps where
        # it has no live tile at all (a chunk's tiles before its keys').
        grid = live if keys_streamed else live.T
        fill = {(r, 0) if keys_streamed else (0, r)
                for r in np.flatnonzero(~grid.any(axis=1))}
        assert as_qk == want | fill and not want & fill
        # Resident tile ascending, every resident tile there, its steps in
        # a row, streamed tile ascending inside it (a block-diffusion
        # forward: the clean key tiles first, then the noised one).
        assert (np.diff(pairs[:, 0]) >= 0).all()
        n_res = (sq // bq) if keys_streamed else (sk // bk)
        assert sorted(set(pairs[:, 0].tolist())) == list(range(n_res))
        half = (sk // bk) // 2
        for r in range(n_res):
            run = pairs[pairs[:, 0] == r, 1].tolist()
            key = ((lambda t: (t < half, t)) if bd and keys_streamed
                   else (lambda t: t))
            assert run == sorted(run, key=key)


@pytest.mark.parametrize("n", [1, 4, 8, 32])
def test_the_causal_list_is_the_triangle(n):
    """n (n + 1) / 2 of n^2: 10 of 16 at 4,096, 36 of 64 at 8,192, 528 of
    1,024 at 32,768 under tiles of 1024; query tile i reads key tiles 0 ..
    i, key tile c is read by query tiles c .. n - 1."""
    s = 1024 * n
    fwd = fa._live_pairs(s, s, 1024, 1024, None, None, (0, 0), True)
    bwd = fa._live_pairs(s, s, 1024, 1024, None, None, (0, 0), False)
    assert len(fwd) == len(bwd) == n * (n + 1) // 2
    assert fwd.tolist() == [[i, j] for i in range(n) for j in range(i + 1)]
    assert bwd.tolist() == [[c, i] for c in range(n) for i in range(c, n)]


def test_the_bands_and_the_block_diffusion_masks_counts():
    """The band of Laguna's call (8192 positions, window and tiles of 512):
    two tiles a resident tile but the first's one, 31 of 256; SDAR's (n = 4
    tiles of 1024 a half): n^2 + 2n = 24 of 64, from either side."""
    for keys_streamed in (True, False):
        assert len(fa._live_pairs(8192, 8192, 512, 512, 512, None, (0, 0),
                                  keys_streamed)) == 31
        for n in (1, 2, 4):
            assert len(fa._live_pairs(2048 * n, 2048 * n, 1024, 1024, None,
                                      (4, 1024 * n), (0, 0),
                                      keys_streamed)) == n * n + 2 * n


def test_a_resident_tile_with_no_live_tile_keeps_one_step():
    """An earlier chunk's queries against a later chunk's keys: every tile
    is dead, and each resident tile still has the one step that writes its
    zeros (the mask empties it)."""
    assert not fa._live_tiles(64, 64, 16, 16, None, None, (0, 64)).any()
    for keys_streamed in (True, False):
        pairs = fa._live_pairs(64, 64, 16, 16, None, None, (0, 64),
                               keys_streamed)
        assert pairs.tolist() == [[r, 0] for r in range(4)]
    # Half dead: query tiles 0, 1 see nothing of keys at 32 .. 95.
    pairs = fa._live_pairs(64, 64, 16, 16, None, None, (0, 32), True)
    assert pairs.tolist() == [[0, 0], [1, 0], [2, 0], [3, 0], [3, 1]]


# -- the grids a call is traced with ---------------------------------------------------

def grad_of(**kw):
    return jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
        q, k, v, **kw).astype(jnp.float32)), (0, 1, 2))


@pytest.mark.parametrize("shape, steps, dq_grid", [
    ((2, 8192, 16, 64), 36, (2, 16)),          # the flagship's call
    ((1, 32768, 32, 64), 528, (1, 32, 4)),     # LFM2's
    ((4, 4096, 16, 128), 10, (4, 16))])        # OLMoE's
def test_a_cells_causal_call_takes_a_step_a_live_tile(shape, steps, dq_grid):
    """Both grids that walk score tiles are (batch, heads, live tiles) with
    the list as their one scalar-prefetch operand; the counter holds, a
    (batch, head), the steps taken, none skipped, and the dead tiles of the
    rectangle as never visited: 36 / 0 / 28 for the flagship."""
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    assert fa._supported(x, x) == (1024, 1024)
    kernels = ("hvd_flash_fwd", "hvd_flash_bwd_dkv")
    before = tiles_built(*kernels)
    calls = pallas_grids(grad_of(causal=True), x, x, x)
    b, _, h, _ = shape
    assert calls == {"hvd_flash_fwd": ((b, h, steps), 1),
                     "hvd_flash_bwd_dkv": ((b, h, steps), 1),
                     "hvd_flash_bwd_dq": (dq_grid, 0)}
    after = tiles_built(*kernels)
    n = shape[1] // 1024
    for kernel in kernels:
        assert [(after[kernel, state] - before[kernel, state]) / (b * h)
                for state in ("live", "skipped", "unvisited")] == [
                    steps, 0, n * n - steps]


def test_a_call_with_nothing_to_lay_out_keeps_the_rectangle():
    """No mask (BERT's call), or a causal call whose offsets are traced
    (ring attention's): (batch, heads, resident tiles, streamed tiles), no
    list, nothing counted."""
    kernels = ("hvd_flash_fwd", "hvd_flash_bwd_dkv")
    before = tiles_built(*kernels)
    x = jax.ShapeDtypeStruct((256, 512, 12, 64), jnp.bfloat16)
    assert pallas_grids(grad_of(causal=False), x, x, x) == {
        "hvd_flash_fwd": ((256, 12, 1, 1), 0),
        "hvd_flash_bwd_dkv": ((256, 12, 1, 1), 0),
        "hvd_flash_bwd_dq": ((256, 1), 0)}
    x = jax.ShapeDtypeStruct((1, 4096, 2, 64), jnp.bfloat16)
    off = jax.ShapeDtypeStruct((), jnp.int32)
    traced = pallas_grids(
        lambda q, k, v, o: grad_of(causal=True, kv_offset=o)(q, k, v),
        x, x, x, off)
    assert traced["hvd_flash_fwd"] == ((1, 2, 4, 4), 0)
    assert traced["hvd_flash_bwd_dkv"] == ((1, 2, 4, 4), 0)
    assert tiles_built(*kernels) == before


# -- the same sums as a walk over every tile ---------------------------------------------

def out_lse_grads(s, seed=0, **kw):
    """(out, dq, dk, dv, lse) of the kernels in the interpreter, fp32 (the
    ``lse`` entry point takes no window and no tile: the output twice)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v, g = (jax.random.normal(key, (1, s, 2, 16), jnp.float32)
                  for key in keys)
    out, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
        q, k, v, interpret=True, **kw), q, k, v)
    lse = out if {"window", "block_q"} & set(kw) else (
        fa.flash_attention_with_lse(q, k, v, interpret=True, **kw)[1])
    return [np.asarray(x) for x in (out,) + vjp(g) + (lse,)]


# mask -> (the call's arguments, its (bq, bk))
MASKS = {
    "causal": (dict(causal=True), (128, 128)),
    "causal-256x128": (dict(causal=True), (256, 128)),
    "causal-offsets": (dict(causal=True, q_offset=128, kv_offset=256),
                       (128, 128)),
    "window": (dict(causal=True, window=128), (128, 128)),
    "window-130": (dict(causal=True, window=130), (128, 128)),
    # The tile is the half's (the override is not a block-diffusion call's).
    "block-diffusion": (dict(diffusion_block=4, block_q=128, block_k=128),
                        (128, 128)),
    "block-diffusion-lse": (dict(diffusion_block=4), (256, 256)),
    "block-diffusion-tile": (dict(diffusion_block=128, block_q=128,
                                  block_k=128), (128, 128)),
}


@pytest.mark.parametrize("mask", sorted(MASKS))
def test_the_list_sums_what_the_rectangle_walk_sums_bit_for_bit(
        mask, monkeypatch):
    """With every tile of the rectangle in the list (a dead one is then
    computed, and its mask leaves nothing of it) the forward output, ``lse``
    and the three gradients are the live tiles' list's to the bit: no live
    tile is left out, and the order of every sum is the rectangle's."""
    kw, tile = MASKS[mask]
    force_tile(monkeypatch, *tile)
    got = out_lse_grads(512, **kw)
    steps = []
    real = fa._live_tiles
    monkeypatch.setattr(fa, "_live_tiles", lambda *layout: (
        steps.append(real(*layout)), np.ones_like(real(*layout)))[1])
    want = out_lse_grads(512, **kw)
    assert steps and not any(live.all() for live in steps)
    assert all(live.shape == (512 // tile[0], 512 // tile[1])
               for live in steps)
    for name, a, b in zip(("out", "dq", "dk", "dv", "lse"), got, want):
        assert np.abs(b).max() > 0, name
        assert np.array_equal(a, b), name


@pytest.mark.parametrize("q_offset, kv_offset", [(0, 0), (256, 0), (0, 256),
                                                 (128, 384), (0, 512)])
def test_traced_offsets_give_what_the_list_gives_bit_for_bit(
        q_offset, kv_offset, monkeypatch):
    """Ring attention's calls hand the offsets over as traced values: the
    rectangle, the bodies skipping the dead tiles.  Python ints lay the list
    out.  Same results, to the bit, the wholly dead chunk's zeros and
    ``lse`` of -1e30 among them."""
    force_tile(monkeypatch, 128, 128)
    def run(q_offset, kv_offset):
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        q, k, v, g = (jax.random.normal(key, (1, 512, 2, 16), jnp.float32)
                      for key in keys)
        kw = dict(causal=True, q_offset=q_offset, kv_offset=kv_offset,
                  interpret=True)
        out, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
            q, k, v, **kw), q, k, v)
        return (out,) + vjp(g) + (
            fa.flash_attention_with_lse(q, k, v, **kw)[1],)

    # Both compiled, so that XLA's own work around the kernels is the same.
    laid_out = jax.jit(lambda: run(q_offset, kv_offset))()
    traced = jax.jit(run)(jnp.int32(q_offset), jnp.int32(kv_offset))
    for name, a, b in zip(("out", "dq", "dk", "dv", "lse"), laid_out, traced):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
    if kv_offset >= q_offset + 512:
        assert not np.asarray(laid_out[0]).any()
        assert (np.asarray(laid_out[4]) == fa._NEG_INF).all()
