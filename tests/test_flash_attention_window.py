"""The flash kernels with a sliding window: each query sees the ``window``
keys up to and including its own.  Forward and the three gradients against
``reference_attention`` with the same window, in the Pallas interpreter; the
grid that walks the list of the band's tiles only; the tile rule; the
callers' ``window`` arguments.  (``tests/test_flash_attention.py`` has the
calls without one.)"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _flash_kernels import bwd_operands, pallas_grids, two_kernel_bwd_call
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.parallel import ring_attention as ra
from horovod_tpu.parallel import ulysses


def qkvg(s, b=1, h=2, d=16, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(s), 4)
    return tuple(jax.random.normal(k, (b, s, h, d), dtype) for k in keys)


def both(s, window, **blocks):
    """((out, dq, dk, dv) of the kernels, the same of the reference)."""
    q, k, v, g = qkvg(s)

    def kernels(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=window,
                                  interpret=True, **blocks)

    def reference(q, k, v):
        return ra.reference_attention(q, k, v, causal=True, window=window)

    out = []
    for fn in (kernels, reference):
        o, vjp = jax.vjp(fn, q, k, v)
        out.append((o,) + vjp(g))
    return out


# sequence / window of 1 (less one key), 2, 4 and 16; windows that are not a
# multiple of the tile; tiles of unequal widths; a window of one key.
CASES = [(256, 255, {}), (256, 128, {}), (512, 128, {}), (2048, 128, {}),
         (512, 100, {}), (512, 300, {}), (512, 129, dict(block_k=256)),
         (512, 257, dict(block_q=256, block_k=128)),
         (512, 128, dict(block_q=128, block_k=256)), (256, 1, {})]


@pytest.mark.parametrize("s, window, blocks", CASES, ids=[
    f"s{s}-w{w}" + "".join(f"-{k[-1]}{v}" for k, v in b.items())
    for s, w, b in CASES])
def test_windowed_kernels_match_the_reference(s, window, blocks):
    got, want = both(s, window, **blocks)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5, err_msg=name)


@pytest.mark.parametrize("s, window, blocks", CASES, ids=[
    f"s{s}-w{w}" + "".join(f"-{k[-1]}{v}" for k, v in b.items())
    for s, w, b in CASES])
def test_the_windowed_pass_equals_the_two_kernels_bit_for_bit(
        s, window, blocks):
    """The one backward pass over the band against the dQ and dK/dV kernels
    it replaced (``_flash_kernels.two_kernel_bwd_call``): the band's tiles
    are the same set from either side, and a query tile's dQ is summed over
    them in ascending key tile in both."""
    args, kw = bwd_operands(s, s, 16, jnp.float32, True, window=window)
    x = jax.ShapeDtypeStruct((1, s, 2, 16), jnp.float32)
    bq, bk = fa._supported(x, x, window)
    kw.update(interpret=True, window=window,
              block_q=blocks.get("block_q", bq),
              block_k=blocks.get("block_k", bk))
    for name, a, b in zip(("dq", "dk", "dv"), fa._bwd_call(*args, **kw),
                          two_kernel_bwd_call(*args, **kw)):
        assert np.abs(np.asarray(b)).max() > 0, name
        assert np.array_equal(np.asarray(a), np.asarray(b)), name


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("d", [64, 128])
def test_a_window_of_512_at_the_cells_heads(d, dtype):
    """Laguna's band at its head width and at the flagship's: 2048
    positions under the chooser's own 512 x 512 tiles, so four key tiles,
    and two query tiles in the band of each."""
    q, k, v, g = qkvg(2048, h=1, d=d, dtype=dtype)
    assert fa._supported(q, k, 512) == (512, 512)
    grads = jax.vjp(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, window=512, interpret=True), q, k, v)[1](g)
    refs = jax.vjp(lambda q, k, v: ra.reference_attention(
        q, k, v, causal=True, window=512),
        *(x.astype(jnp.float32) for x in (q, k, v)))[1](
            g.astype(jnp.float32))
    for name, got, ref in zip("qkv", grads, refs):
        assert got.dtype == dtype
        got, ref = np.asarray(got, np.float32), np.asarray(ref)
        err = np.linalg.norm(got - ref) / np.linalg.norm(ref)
        # fp32: round-off; bf16: three roundings of 2**-8 (the tests of
        # test_flash_attention.py set out which).
        assert err <= (3 * 2.0 ** -8 if dtype == jnp.bfloat16 else 1e-5), (
            name, err)


@pytest.mark.parametrize("window", [256, 257, 10_000])
def test_a_window_that_reaches_the_first_key_is_the_causal_call(window):
    """Bit for bit, forward and gradients: it is the same call."""
    q, k, v, g = qkvg(256)

    def run(**kw):
        o, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, interpret=True, **kw), q, k, v)
        return (o,) + vjp(g)

    for a, b in zip(run(window=window), run()):
        assert (np.asarray(a) == np.asarray(b)).all()


def grids(s, window, d=16):
    """{kernel name: grid} of the differentiated call's pallas_calls."""
    q, k, v, _ = qkvg(s, d=d)
    return {name: grid for name, (grid, _) in pallas_grids(
        jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
            q, k, v, causal=True, window=window, interpret=True)),
            (0, 1, 2)), q, k, v).items()}


def band(n_res, block_res, block_str, window, keys_streamed):
    """Steps a resident tile of the band's list, the most of any: (n_res
    resident tiles of block_res, streamed in tiles of block_str)."""
    bq, bk = ((block_res, block_str) if keys_streamed
              else (block_str, block_res))
    s = n_res * block_res
    pairs = fa._live_pairs(s, s, bq, bk, window, None, (0, 0), keys_streamed)
    return int(np.bincount(pairs[:, 0]).max())


def test_the_windowed_grids_walk_the_band_only():
    """The list holds the band's tiles alone: for a window and tiles of 128
    two steps a resident tile whatever the sequence (one for the first,
    whose band is cut by the sequence's start and is no step at all), where
    the causal call's list is the triangle."""
    names = {"hvd_flash_fwd_win", "hvd_flash_bwd_dq_win",
             "hvd_flash_bwd_dkv_win"}
    def walked(g):
        """The two grids that walk score tiles: the forward's and the
        backward pass's, (batch, heads, steps).  The dQ kernel's is a step
        a (batch, head)."""
        assert all(len(grid) == 2 for name, grid in g.items()
                   if "bwd_dq" in name)
        return [grid for name, grid in g.items() if "bwd_dq" not in name]

    for s in (512, 1024, 4096):
        g = grids(s, 128)
        assert set(g) == names
        assert {grid[2] for grid in walked(g)} == {2 * (s // 128) - 1}, (s, g)
        assert all(len(grid) == 3 for grid in walked(g))
    # 100 keys under tiles of 128 still touch two tiles; 129 touch two too
    # (one key into the tile before), 130 three steps of one resident tile.
    n = 1024 // 128
    assert {grid[2] for grid in walked(grids(1024, 100))} == {2 * n - 1}
    assert {grid[2] for grid in walked(grids(1024, 129))} == {2 * n - 1}
    assert {grid[2] for grid in walked(grids(1024, 130))} == {3 * n - 3}
    causal = grids(1024, None)
    assert set(causal) == {"hvd_flash_fwd", "hvd_flash_bwd_dq",
                           "hvd_flash_bwd_dkv"}
    assert {grid[2] for grid in walked(causal)} == {1}    # 1024-wide tiles
    causal = grids(4096, None)
    assert {grid[2] for grid in walked(causal)} == {10}   # 4 x 4: the triangle
    assert band(64, 512, 512, 512, True) == 2
    assert band(64, 512, 512, 512, False) == 2
    assert band(8, 1024, 512, 512, True) == 3
    assert band(8, 1024, 1024, 512, True) == 2


def test_tiles_of_a_windowed_call_are_no_wider_than_the_window():
    """From the shapes and the window, by the rule that tiles every call:
    the widest candidate that divides the length and does not pass the
    window (bare sweep on a v5e, ``_window_block``)."""
    def blocks(s, window, d=128, dtype=jnp.bfloat16):
        t = jax.ShapeDtypeStruct((2, s, 4, d), dtype)
        return fa._supported(t, t, window)

    assert blocks(8192, None) == (1024, 1024)
    assert blocks(8192, 512) == (512, 512)
    assert blocks(8192, 1024) == (1024, 1024)
    assert blocks(8192, 4096) == (1024, 1024)
    assert blocks(8192, 300) == (256, 256)
    assert blocks(8192, 64) == (128, 128)
    assert blocks(1536, 512) == (512, 512)
    assert blocks(8192, 512, d=256) == (512, 512)     # the VMEM rule stays


def test_a_window_is_a_causal_whole_sequence_calls():
    q, k, v, _ = qkvg(256)
    with pytest.raises(ValueError, match="causal"):
        fa.flash_attention(q, k, v, causal=False, window=64, interpret=True)
    with pytest.raises(ValueError, match="at least 1"):
        fa.flash_attention(q, k, v, window=0, interpret=True)
    with pytest.raises(NotImplementedError, match="whole sequence"):
        fa.flash_attention(q, k, v, window=64, kv_offset=256, interpret=True)
    with pytest.raises(NotImplementedError, match="whole sequence"):
        fa.flash_attention(q[:, :128], k, v, window=64, interpret=True)
    with pytest.raises(ValueError, match="causal"):
        ra.reference_attention(q, k, v, causal=False, window=64)
    with pytest.raises(NotImplementedError, match="sliding window"):
        ra.ring_attention(q, k, v, axis_name="mp", window=64)
    with pytest.raises(NotImplementedError, match="sliding window"):
        ulysses.ulysses_attention(q, k, v, axis_name="mp", window=64)


def test_the_xla_paths_take_the_same_window():
    """``full_attention`` off the chip, the kernels' XLA fallback and its
    ``lse``: the band by hand for one query."""
    q, k, v, _ = qkvg(96, d=12)             # a head the kernels cannot tile
    want = ra.reference_attention(q, k, v, causal=True, window=7)
    np.testing.assert_allclose(
        ra.full_attention(q, k, v, causal=True, window=7), want, atol=1e-6)
    np.testing.assert_allclose(
        fa.flash_attention(q, k, v, window=7, interpret=True), want,
        atol=1e-6)
    out, lse = fa._xla_attention_with_lse(q, k, v, True, 12 ** -0.5, 0, 0, 7)
    np.testing.assert_allclose(out, want, atol=1e-6)
    t = 50
    scores = np.einsum("hd,khd->hk", np.asarray(q[0, t]),
                       np.asarray(k[0, t - 6:t + 1])) * 12 ** -0.5
    np.testing.assert_allclose(
        lse[0, :, t], np.log(np.exp(scores).sum(-1)), rtol=1e-5)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    np.testing.assert_allclose(
        want[0, t], np.einsum("hk,khd->hd", p, np.asarray(v[0, t - 6:t + 1])),
        atol=1e-5)


def test_the_saved_names_are_the_full_calls():
    """Output and ``lse`` of a windowed forward are saved under the names
    the layer checkpoint keeps, so its recompute runs no forward kernel."""
    q, k, v, _ = qkvg(512)

    def layer(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, window=128,
                                          interpret=True) ** 2)

    jaxpr = str(jax.make_jaxpr(jax.grad(
        ra.checkpoint_keeping_attention(layer)))(q, k, v))
    assert jaxpr.count("name=hvd_flash_fwd_win") == 1
    assert jaxpr.count("name=hvd_flash_bwd_dq_win") == 1
    assert jaxpr.count("name=hvd_flash_bwd_dkv_win") == 1
