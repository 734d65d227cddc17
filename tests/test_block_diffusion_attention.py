"""The flash kernels under the block-diffusion mask: the sequence is a noised
copy and then the clean copy of the same tokens, in blocks; a noised query
sees its own noised block and the clean blocks strictly before it, a clean
query the clean blocks up to its own, nobody else a noised key.  Forward and
the three gradients against the dense mask in the Pallas interpreter; the
lists of the live tiles that the grids walk; the tile rule; the callers'
arguments; and the calls without the mask, whose traces are pinned.
(``tests/test_flash_attention.py`` and
``tests/test_flash_attention_window.py`` have the other calls.)"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _flash_kernels import pallas_grids, tiles_built
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.parallel import ring_attention as ra
from horovod_tpu.parallel import ulysses


def dense_mask(half: int, block: int) -> np.ndarray:
    """The rule, written out pair by pair in numpy: (2 half, 2 half) bool,
    query by key."""
    at = np.arange(2 * half)
    noised, beta = at < half, (at % half) // block
    q_n, k_n = noised[:, None], noised[None, :]
    q_b, k_b = beta[:, None], beta[None, :]
    return np.where(k_n, q_n & (q_b == k_b),
                    np.where(q_n, k_b < q_b, k_b <= q_b))


def dense_attention(q, k, v, block):
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    scores = jnp.where(dense_mask(q.shape[1] // 2, block)[None, None],
                       scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def qkvg(s, b=1, h=2, d=16, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(s), 4)
    return tuple(jax.random.normal(k, (b, s, h, d), dtype) for k in keys)


def test_the_mask_is_the_rule():
    """Block 2 over a half of 4: written out by hand."""
    want = np.array([
        # noised keys   clean keys
        [1, 1, 0, 0,    0, 0, 0, 0],      # noised block 0: itself
        [1, 1, 0, 0,    0, 0, 0, 0],
        [0, 0, 1, 1,    1, 1, 0, 0],      # noised block 1: itself, clean 0
        [0, 0, 1, 1,    1, 1, 0, 0],
        [0, 0, 0, 0,    1, 1, 0, 0],      # clean block 0: clean 0
        [0, 0, 0, 0,    1, 1, 0, 0],
        [0, 0, 0, 0,    1, 1, 1, 1],      # clean block 1: clean 0, 1
        [0, 0, 0, 0,    1, 1, 1, 1]], bool)
    assert (dense_mask(4, 2) == want).all()
    assert (np.asarray(fa.diffusion_mask(8, 2)) == want).all()
    for half, block in [(128, 4), (256, 32), (128, 128)]:
        mask = dense_mask(half, block)
        assert (np.asarray(fa.diffusion_mask(2 * half, block)) == mask).all()
        assert mask.sum() == half * half + half * block
        assert mask.any(axis=1).all()          # every query sees a key


# (half, tile, block): two tile widths and blocks of 4 and 32 at one and two
# tiles a half; four tiles a half; a block as wide as the tile.
CASES = [(256, 128, 4), (256, 128, 32), (256, 256, 4), (256, 256, 32),
         (512, 128, 32), (128, 128, 128)]


@pytest.mark.parametrize("half, tile, block", CASES,
                         ids=[f"h{h}-t{t}-b{b}" for h, t, b in CASES])
def test_block_diffusion_kernels_match_the_dense_mask(half, tile, block):
    q, k, v, g = qkvg(2 * half)

    def kernels(q, k, v):
        return fa.flash_attention(q, k, v, diffusion_block=block,
                                  block_q=tile, block_k=tile, interpret=True)

    got, want = [], []
    for fn, into in ((kernels, got), (lambda q, k, v: dense_attention(
            q, k, v, block), want)):
        o, vjp = jax.vjp(fn, q, k, v)
        into.extend((o,) + vjp(g))
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5, err_msg=name)


@pytest.mark.parametrize("block", [4, 32])
def test_bf16_kernels_match_the_dense_mask_at_a_head_of_128(block):
    q, k, v, g = qkvg(256, h=1, d=128, dtype=jnp.bfloat16)
    o, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
        q, k, v, diffusion_block=block, interpret=True), q, k, v)
    f32 = [t.astype(jnp.float32) for t in (q, k, v)]
    o_ref, vjp_ref = jax.vjp(lambda q, k, v: dense_attention(q, k, v, block),
                             *f32)
    for name, a, b in zip(("out", "dq", "dk", "dv"), (o,) + vjp(g),
                          (o_ref,) + vjp_ref(g.astype(jnp.float32))):
        np.testing.assert_allclose(a.astype(jnp.float32), b, atol=6e-2,
                                   rtol=6e-2, err_msg=name)


def test_the_lse_entry_point_takes_the_mask():
    q, k, v, _ = qkvg(256)
    out, lse = fa.flash_attention_with_lse(q, k, v, diffusion_block=4,
                                           interpret=True)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / 4.0
    scores = jnp.where(dense_mask(128, 4)[None, None], scores, -jnp.inf)
    np.testing.assert_allclose(lse, jax.nn.logsumexp(scores, -1), atol=2e-5)
    np.testing.assert_allclose(out, dense_attention(q, k, v, 4), atol=2e-5)


# -- the walk ----------------------------------------------------------------------

def live_tiles(n: int, tile: int, block: int) -> set:
    """{(query tile, key tile)} that hold an unmasked pair, enumerated
    densely."""
    mask = dense_mask(n * tile, block)
    return {(i, j) for i in range(2 * n) for j in range(2 * n)
            if mask[i * tile:(i + 1) * tile, j * tile:(j + 1) * tile].any()}


@pytest.mark.parametrize("n", [1, 2, 4])
@pytest.mark.parametrize("keys_streamed", [True, False],
                         ids=["forward", "backward"])
def test_the_walk_visits_exactly_the_live_tiles(n, keys_streamed):
    tile, block = 8, 2
    want = live_tiles(n, tile, block)
    assert len(want) == n * n + 2 * n
    pairs = fa._live_pairs(2 * n * tile, 2 * n * tile, tile, tile, None,
                           (block, n * tile), (0, 0), keys_streamed)
    assert pairs.dtype == np.int32 and pairs.shape == (n * n + 2 * n, 2)
    visited = [(i, t) if keys_streamed else (t, i) for i, t in pairs.tolist()]
    assert len(visited) == len(set(visited))           # no tile twice
    assert set(visited) == want                        # and no dead one
    # Resident tiles ascending, every one of them there; a resident tile's
    # steps in a row.
    assert sorted(set(pairs[:, 0])) == list(range(2 * n))
    assert (np.diff(pairs[:, 0]) >= 0).all()
    for i in range(2 * n):
        run = pairs[pairs[:, 0] == i, 1].tolist()
        r = i % n
        if keys_streamed:
            # A noised query tile reads the clean key tiles n .. n + r and
            # then its own noised one; a clean one n .. n + r.
            assert run == list(range(n, n + r + 1)) + ([r] if i < n else [])
        else:
            # A noised key tile is read by its own query tile alone; a clean
            # one n + c by noised query tiles c .. n - 1, then clean ones.
            assert run == ([r] if i < n else
                           list(range(r, n)) + list(range(n + r, 2 * n)))


@pytest.mark.parametrize("tile, block", [(8, 2), (8, 8), (8, 4)])
def test_a_tile_is_live_where_the_dense_mask_has_a_pair(tile, block):
    """``_live_tiles`` at every tile of the square: with a block as wide as
    the tile the clean copy of a noised tile's own positions is dead, and no
    step of either list."""
    n = 2
    want = live_tiles(n, tile, block)
    live = fa._live_tiles(2 * n * tile, 2 * n * tile, tile, tile, None,
                          (block, n * tile), (0, 0))
    assert live.shape == (2 * n, 2 * n)
    assert {(i, j) for i, j in zip(*np.nonzero(live))} == want
    for keys_streamed in (True, False):
        pairs = fa._live_pairs(2 * n * tile, 2 * n * tile, tile, tile, None,
                               (block, n * tile), (0, 0), keys_streamed)
        assert {(i, t) if keys_streamed else (t, i)
                for i, t in pairs.tolist()} == want


BD_KERNELS = ("hvd_flash_fwd_bd", "hvd_flash_bwd_dkv_bd")


def test_the_grids_the_names_and_the_counter():
    """The cell's call, traced: 8192 positions of 32 heads of 128 in bf16
    take tiles of 1024, n = 4; both grids walk the list of the n^2 + 2n = 24
    live tiles of the 64; the kernels carry the ``_bd`` names; and the
    trace-time counter holds 24 live steps a (batch, head) for each grid,
    none skipped, 40 tiles unvisited."""
    x = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.bfloat16)
    assert fa._supported(x, x, diffusion_block=4) == (1024, 1024)
    before = tiles_built(*BD_KERNELS)
    grids = pallas_grids(jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
        q, k, v, diffusion_block=4).astype(jnp.float32)), (0, 1, 2)), x, x, x)
    assert grids == {"hvd_flash_fwd_bd": ((2, 32, 24), 1),
                     "hvd_flash_bwd_dkv_bd": ((2, 32, 24), 1),
                     "hvd_flash_bwd_dq_bd": ((2, 32), 0)}
    after = tiles_built(*BD_KERNELS)
    built = {key: after[key] - before[key] for key in after}
    heads = 2 * 32
    assert built == {("hvd_flash_fwd_bd", "live"): 24 * heads,
                     ("hvd_flash_fwd_bd", "skipped"): 0,
                     ("hvd_flash_fwd_bd", "unvisited"): 40 * heads,
                     ("hvd_flash_bwd_dkv_bd", "live"): 24 * heads,
                     ("hvd_flash_bwd_dkv_bd", "skipped"): 0,
                     ("hvd_flash_bwd_dkv_bd", "unvisited"): 40 * heads}
    # A causal call counts under its own kernels' names.
    pallas_grids(lambda q, k, v: fa.flash_attention(q, k, v), x, x, x)
    assert tiles_built(*BD_KERNELS) == after


# -- the tile rule, the callers' arguments ---------------------------------------------

def test_the_tile_is_picked_from_the_half():
    def pick(s, d=128, dtype=jnp.bfloat16, block=4):
        x = jax.ShapeDtypeStruct((1, s, 2, d), dtype)
        return fa._supported(x, x, diffusion_block=block)

    assert pick(8192) == (1024, 1024)         # the cell's 4096 a half
    assert pick(256) == (128, 128)            # the rehearsal's 128 a half
    assert pick(3072) == (512, 512)           # 1536 a half
    assert pick(8192, d=128, dtype=jnp.float32) == (512, 512)   # wide rows
    assert pick(256, block=256) is None       # no tile the block divides
    assert pick(192) is None                  # 96 a half: no tile divides it
    assert pick(2 * 49152) is None            # dQ would not fit VMEM
    x, y = (jax.ShapeDtypeStruct((1, s, 2, 64), jnp.float32)
            for s in (256, 512))
    assert fa._supported(x, y, diffusion_block=4) is None


def test_a_shape_the_kernels_cannot_tile_takes_the_xla_path():
    q, k, v, g = qkvg(192)                    # 96 a half
    o, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
        q, k, v, diffusion_block=4), q, k, v)
    o_ref, vjp_ref = jax.vjp(lambda q, k, v: dense_attention(q, k, v, 4),
                             q, k, v)
    for a, b in zip((o,) + vjp(g), (o_ref,) + vjp_ref(g)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda q, k, v: fa.flash_attention(q, k, v, diffusion_block=4))(
            q, k, v))


def test_what_a_block_diffusion_call_refuses():
    q, k, v, _ = qkvg(256)
    with pytest.raises(ValueError, match="exclusive with a window"):
        fa.flash_attention(q, k, v, window=64, diffusion_block=4)
    with pytest.raises(ValueError, match="causal call's"):
        fa.flash_attention(q, k, v, causal=False, diffusion_block=4)
    with pytest.raises(ValueError, match="power of two"):
        fa.flash_attention(q, k, v, diffusion_block=6)
    with pytest.raises(NotImplementedError, match="doubled sequence"):
        fa.flash_attention(q, k[:, :128], v[:, :128], diffusion_block=4)
    with pytest.raises(NotImplementedError, match="no offsets"):
        fa.flash_attention(q, k, v, q_offset=128, diffusion_block=4)
    with pytest.raises(ValueError, match="one tile width"):
        fa.flash_attention(q, k, v, diffusion_block=4, block_q=128,
                           block_k=64, interpret=True)
    with pytest.raises(NotImplementedError, match="block-diffusion mask"):
        ra.ring_attention(q, k, v, axis_name="mp", diffusion_block=4)
    with pytest.raises(NotImplementedError, match="block-diffusion mask"):
        ulysses.ulysses_attention(q, k, v, axis_name="mp", diffusion_block=4)


def test_full_attention_dispatches_the_mask(monkeypatch):
    q, k, v, _ = qkvg(256)
    want = dense_attention(q, k, v, 32)
    np.testing.assert_allclose(
        ra.reference_attention(q, k, v, diffusion_block=32), want, atol=2e-5)
    np.testing.assert_allclose(                     # off the chip: XLA
        ra.full_attention(q, k, v, diffusion_block=32), want, atol=2e-5)
    seen = []
    real = fa.flash_attention
    monkeypatch.setenv("HVD_TPU_FLASH", "1")
    monkeypatch.setattr(fa, "flash_attention", lambda *a, **kw: (
        seen.append(kw), real(*a, **kw, interpret=True))[1])
    np.testing.assert_allclose(
        ra.full_attention(q, k, v, diffusion_block=32), want, atol=2e-5)
    assert seen[0]["diffusion_block"] == 32 and seen[0]["window"] is None


# -- a call without the mask ---------------------------------------------------------

# Every equation's primitive and result types, every kernel's name, grid,
# compiler parameters, block shapes and index maps, of the forward and the
# three gradients, by this very walk.  Hashed on PR 43's tree, where the
# causal and the windowed calls took the list of their live tiles for a grid
# (until then: on the commit before the block-diffusion mask existed, PR
# 37's).  BERT's call keeps the rectangle, and its text differs from that
# commit's in where six scalar comparisons of the grid indices stand.
PINNED = {
    "flagship": ((1, 8192, 16, 64), dict(causal=True),
                 283, "fdac602a6bcf12d0"),
    "bert": ((2, 512, 12, 64), dict(causal=False), 243, "1a62923671b7d7f4"),
    "laguna_full": ((1, 8192, 24, 128), dict(causal=True),
                    283, "88dd3cd486d3a78e"),
    "laguna_window": ((1, 8192, 36, 128), dict(causal=True, window=512),
                      313, "5919f4702e2ce7cc"),
}


def trace_of(shape, **kw) -> list:
    seen = []

    def walk(jp):
        for eqn in jp.eqns:
            seen.append(f"{eqn.primitive.name}:"
                        f"{[str(v.aval) for v in eqn.outvars]}")
            if eqn.primitive.name == "pallas_call":
                gm = eqn.params["grid_mapping"]
                seen.append(f"name={eqn.params['name']} grid={gm.grid} "
                            f"params={eqn.params['compiler_params']}")
                for bm in gm.block_mappings:
                    seen.append(f"block={bm.block_shape}")
                    walk(bm.index_map_jaxpr.jaxpr)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    walk(jax.make_jaxpr(jax.value_and_grad(
        lambda q, k, v: jnp.sum(fa.flash_attention(q, k, v, **kw).astype(
            jnp.float32)), argnums=(0, 1, 2)))(x, x, x).jaxpr)
    return seen


@pytest.mark.parametrize("call", sorted(PINNED))
def test_a_call_without_the_mask_traces_to_its_pinned_text(call):
    shape, kw, n, digest = PINNED[call]
    seen = trace_of(shape, **kw)
    assert len(seen) == n
    assert hashlib.sha256("\n".join(seen).encode()).hexdigest()[:16] == digest
    # The walk does see a mask: the same shape with one reads otherwise.
    if kw == dict(causal=True):
        masked = trace_of((1, 8192) + shape[2:], diffusion_block=4)
        assert hashlib.sha256("\n".join(masked).encode()).hexdigest()[:16] \
            != digest
