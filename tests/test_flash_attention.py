"""Pallas flash-attention kernels (ops/flash_attention.py) validated in
interpret mode against the XLA reference — fwd, custom-VJP bwd, LSE
composition, and the flash ring-attention path on a virtual mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from horovod_tpu.compat import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from _flash_kernels import ONCE, force_tile, kernel_calls
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.parallel import ring_attention as ra


def _qkv(b=2, s=256, h=2, d=32, dtype=jnp.float32, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return [jax.random.normal(k, (b, s, h, d), dtype=dtype) for k in keys]


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal):
    q, k, v = _qkv()
    ref = ra.reference_attention(q, k, v, causal=causal)
    out = fa.flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2, rtol=1e-3)


def test_gradients_match_reference():
    q, k, v = _qkv(s=128)

    def loss_flash(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True,
                                          interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(ra.reference_attention(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-2, rtol=1e-2)


def test_lse_combine_splits_keys_exactly():
    q, k, v = _qkv(s=256)
    o1, l1 = fa.flash_attention_with_lse(
        q, k[:, :128], v[:, :128], causal=True, kv_offset=0, interpret=True)
    o2, l2 = fa.flash_attention_with_lse(
        q, k[:, 128:], v[:, 128:], causal=True, kv_offset=128,
        interpret=True)
    oc, _ = fa.combine_blocks(o1, l1, o2, l2)
    ref = ra.reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(oc), np.asarray(ref),
                               atol=2e-2, rtol=1e-3)


def test_causal_offsets_shift_mask():
    """With q_offset=S the whole key block is visible (past context)."""
    q, k, v = _qkv(s=128)
    out = fa.flash_attention(q, k, v, causal=True, q_offset=128,
                             interpret=True)
    ref = ra.reference_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2, rtol=1e-3)


def test_unsupported_shapes_fall_back():
    q, k, v = _qkv(s=48, d=20)  # d not multiple of 8 → XLA fallback
    out = fa.flash_attention(q, k, v, causal=True)
    ref = ra.reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.fixture
def sp_mesh():
    devs = jax.devices()[:4]
    if len(devs) < 4:
        pytest.skip("needs 4 virtual devices")
    return Mesh(np.array(devs), ("sp",))


def test_ring_flash_matches_oracle(sp_mesh):
    q, k, v = _qkv(b=1, s=256, h=2, d=32)
    ref = ra.reference_attention(q, k, v, causal=True)

    f = shard_map(
        lambda q, k, v: ra.ring_attention(q, k, v, "sp", causal=True,
                                          use_flash=True, interpret=True),
        mesh=sp_mesh, in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp"), check_vma=False)
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2, rtol=1e-3)


def test_ring_flash_gradients_ride_the_ring(sp_mesh):
    """dK/dV must land back on their owner shard after a full revolution."""
    q, k, v = _qkv(b=1, s=256, h=2, d=32)

    f = shard_map(
        lambda q, k, v: ra.ring_attention(q, k, v, "sp", causal=True,
                                          use_flash=True, interpret=True),
        mesh=sp_mesh, in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp"), check_vma=False)

    def loss_f(q, k, v):
        return jnp.sum(f(q, k, v) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(ra.reference_attention(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-2, rtol=1e-2)


def test_block_size_env_override():
    """The chooser's tile (no environment name forces one any more), and
    ``block_q=`` / ``block_k=``, which force one for a sweep through the
    public entry point and must divide their side."""
    q, k, v = _qkv(s=256)
    # The widest candidate that divides the side, not the narrowest.
    assert fa._supported(q, k) == (256, 256)
    ref = ra.reference_attention(q, k, v, causal=True)
    out = fa.flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2, rtol=1e-3)
    # A forced tile that does not divide its side is refused by name.
    for side, bad in (("block_q", 96), ("block_k", 96), ("block_q", 1024)):
        with pytest.raises(ValueError, match=f"{side}={bad} must divide"):
            fa.flash_attention(q, k, v, causal=True, interpret=True,
                               **{side: bad})
    # A head of 512 bytes a row (fp32, 128) keeps to 512 where 1024 would
    # divide; 128 keys are one tile.
    q2, k2, _ = _qkv(s=1024, d=128)
    assert fa._supported(q2, k2) == (512, 512)
    assert fa._supported(q2, k2[:, :128]) == (512, 128)
    # 2048 is tiled by the widest candidate, 1024, not taken whole.
    q3, k3, _ = _qkv(s=2048)
    assert fa._supported(q3, k3)[0] == 1024


def test_kernel_is_never_chosen_or_interpreted_behind_the_callers_back(
        monkeypatch):
    """Off-TPU, auto mode takes the XLA path (no pallas_call traced), and
    the kernel, once asked for, is compiled unless ``interpret=True`` is
    passed: nothing in the dispatch looks at the backend to pick
    interpret mode."""
    monkeypatch.delenv("HVD_TPU_FLASH", raising=False)
    q, k, v = _qkv(s=512)
    auto = jax.make_jaxpr(
        lambda q, k, v: ra.full_attention(q, k, v, causal=True))(q, k, v)
    assert "pallas_call" not in str(auto)

    seen = []
    real = fa.pl.pallas_call

    def spy(*args, **kwargs):
        seen.append(kwargs["interpret"])
        return real(*args, **kwargs)

    monkeypatch.setattr(fa.pl, "pallas_call", spy)
    jax.make_jaxpr(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, interpret=True))(q, k, v)
    assert seen == [True]
    del seen[:]
    # The default is the compiled kernel, on any backend (tracing only:
    # Mosaic lowering needs the chip).
    jax.make_jaxpr(lambda q, k, v: ra.full_attention(
        q, k, v, causal=True, use_flash=True))(q, k, v)
    jax.make_jaxpr(lambda q, k, v: fa.flash_attention_with_lse(
        q, k, v, causal=True))(q, k, v)
    assert seen == [False, False]


# ---------------------------------------------------------------------------
# bf16 callers: the operands of the seven MXU dots are the caller's type, the
# softmax and every accumulator stay fp32 (ROADMAP Sk).
# ---------------------------------------------------------------------------

# bf16 keeps 8 significant bits: rounding to it moves a value by at most
# 2**-8 of itself.  Every bound below is a small count of such roundings.
U = 2.0 ** -8


def _f32_attention(q, k, v, causal, cotangent=None, window=None):
    """The XLA oracle in fp32 on the inputs as they are (bf16 values widened
    exactly); with a cotangent, (dq, dk, dv) in fp32 too."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    out, vjp = jax.vjp(
        lambda q, k, v: ra.reference_attention(q, k, v, causal=causal,
                                               window=window),
        q, k, v)
    return out if cotangent is None else vjp(cotangent.astype(jnp.float32))


def _rel_l2(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _assert_bf16_forward(out, q, k, v, causal):
    assert out.dtype == jnp.bfloat16
    ref = _f32_attention(q, k, v, causal)
    # out = sum(p v) / l with l exact: the cast of p at the PV dot moves
    # each term by at most U |p v|, so the row by at most U max|v|; the
    # result's own rounding to bf16 adds U |out| <= U max|v|.
    bound = 2 * U * float(jnp.max(jnp.abs(v.astype(jnp.float32))))
    err = np.abs(np.asarray(out, np.float32) - np.asarray(ref))
    assert err.max() <= bound, (err.max(), bound)


def _assert_bf16_grads(grads, q, k, v, g, causal, window=None):
    refs = _f32_attention(q, k, v, causal, cotangent=g, window=window)
    # Three roundings of at most U each separate a gradient from the fp32
    # one: the cast of P or dS at its dot, the bf16 ``out`` inside delta
    # (the caller's, as before this change) and the result's own rounding.
    # They are independent, so the norm moves by well under their sum.
    for name, got, ref in zip("qkv", grads, refs):
        assert got.dtype == jnp.bfloat16
        assert _rel_l2(got, ref) <= 3 * U, (name, _rel_l2(got, ref))


@pytest.mark.parametrize("what", ["fwd", "grad"])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("causal", [True, False])
def test_bf16_inputs_match_fp32_attention_to_bf16_rounding(causal, d, what):
    q, k, v = _qkv(b=1, s=256, h=2, d=d, dtype=jnp.bfloat16, seed=d)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal, block_q=128,
                                  block_k=128, interpret=True)

    if what == "fwd":
        _assert_bf16_forward(flash(q, k, v), q, k, v, causal)
    else:
        g = jax.random.normal(jax.random.PRNGKey(7), q.shape, jnp.bfloat16)
        _assert_bf16_grads(jax.vjp(flash, q, k, v)[1](g), q, k, v, g, causal)


@pytest.mark.parametrize("causal", [True, False])
def test_bf16_long_sequence_sums_l_across_four_key_tiles(causal):
    """2048 keys in 4 tiles of 512 under 1024 queries: ``l``, the output
    accumulator and dQ are carried across four tiles, the first two rescaled
    by ``alpha`` each time.  (The chooser's own tile at 2048, the cells', is
    1024 x 1024 — two key tiles; ``block_k`` keeps the four.)"""
    q, k, v = _qkv(b=1, s=2048, h=1, d=64, dtype=jnp.bfloat16, seed=3)
    assert fa._supported(q, k) == (1024, 1024)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal, block_k=512,
                                  interpret=True)

    out, vjp = jax.vjp(flash, q, k, v)
    _assert_bf16_forward(out, q, k, v, causal)
    g = jax.random.normal(jax.random.PRNGKey(8), q.shape, jnp.bfloat16)
    _assert_bf16_grads(vjp(g), q, k, v, g, causal)


@pytest.mark.parametrize("causal", [True, False])
def test_bf16_lse_is_the_fp32_kernels(causal, monkeypatch):
    """``l`` is summed from the fp32 probabilities, before the cast at the
    PV dot: on the same values the bf16 call's ``lse`` is the fp32 call's
    to fp32 round-off (bf16 x bf16 products are exact in fp32), where a sum
    of rounded probabilities would sit 2**-9 or so away.  The outputs do
    differ by the rounding: that the cast is there at all."""
    q, k, v = _qkv(b=1, s=512, h=2, d=64, dtype=jnp.bfloat16, seed=5)
    # Four 128-wide key tiles through the public entry point's own tiling.
    force_tile(monkeypatch, 128, 128)
    out16, lse16 = fa.flash_attention_with_lse(
        q, k, v, causal=causal, interpret=True)
    out32, lse32 = fa.flash_attention_with_lse(
        *(x.astype(jnp.float32) for x in (q, k, v)), causal=causal,
        interpret=True)
    assert lse16.dtype == lse32.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(lse16), np.asarray(lse32),
                               atol=2e-6, rtol=2e-6)
    assert _rel_l2(out16, out32) > U / 64


def _kernel_bodies(dtype, causal=True):
    """name -> jaxpr of the three kernel bodies, as the custom VJP traces
    them for inputs of ``dtype`` (nothing runs)."""
    x = jax.ShapeDtypeStruct((1, 256, 2, 64), dtype)

    def f(q, k, v):
        out, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=causal, block_q=128, block_k=128,
            interpret=True), q, k, v)
        return vjp(out)

    return {eqn.params["name"]: (eqn.params["jaxpr"],
                                 eqn.params["grid_mapping"])
            for eqn in _pallas_eqns(jax.make_jaxpr(f)(x, x, x).jaxpr)}


def _pallas_eqns(jaxpr):
    """Every pallas_call equation under ``jaxpr``, not looking inside one."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_eqns(sub)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


# kernel -> (dots, casts of an operand the kernel computed (P', dS') to the
# caller's type, results written in the caller's type, fp32 scratch
# accumulators).  The backward is one pass over the score tiles: dS' is cast
# once for its two dots, and ``hvd_flash_bwd_dq`` only turns the pass's
# finished dQ' tiles into dQ's.
KERNELS = {"hvd_flash_fwd": (2, 1, 1, 3),        # KQ', V'P'; out; m, l, acc
           "hvd_flash_bwd_dkv": (5, 2, 3, 3),    # KQ', V dO', P'dO, dS'Q,
           "hvd_flash_bwd_dq": (0, 0, 0, 0)}     # K'dS'; dk, dv, dq'; sums


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_the_seven_dots_take_the_callers_type_and_softmax_stays_fp32(
        dtype, causal):
    """Does the mechanism engage: 7 of 7 dots on bf16 operands for a bf16
    caller, 0 of 7 (and no rounding to bf16 anywhere) for an fp32 one; fp32
    results, softmax and scratch either way.  Five of the seven are the
    backward's, all in one kernel (nine until the pass: dQ's kernel remade
    K Q' and V dO')."""
    bodies = _kernel_bodies(dtype, causal)
    assert set(bodies) == set(KERNELS)
    n_in_callers_type = 0
    for name, (body, grid_mapping) in bodies.items():
        n_dots, n_computed, n_results, n_scratch = KERNELS[name]
        eqns = list(_eqns(body))
        dots = [e for e in eqns if e.primitive.name == "dot_general"]
        assert len(dots) == n_dots, name
        for e in dots:
            assert [v.aval.dtype for v in e.invars] == [dtype, dtype], name
            assert e.outvars[0].aval.dtype == jnp.float32, name
            assert e.params["preferred_element_type"] == jnp.float32
            # The bodies work on (bk, bq) score tiles, so that a computed
            # tile (P', dS') enters its dot as it lies — (M, K) on the left,
            # (K, N) on the right — and only the caller's narrow (block, D)
            # tiles are ever transposed for the MXU.
            (lhs_contract, rhs_contract), _ = e.params["dimension_numbers"]
            lhs, rhs = e.invars
            if lhs.aval.shape == (128, 128):
                assert lhs_contract == (1,), name
            if rhs.aval.shape == (128, 128):
                assert rhs_contract == (0,), name
            n_in_callers_type += 1
        names = {e.primitive.name for e in eqns}
        assert {"hvd_flash_fwd": {"exp", "reduce_max", "reduce_sum"},
                "hvd_flash_bwd_dkv": {"exp"},
                "hvd_flash_bwd_dq": set()}[name] <= names, name
        if name == "hvd_flash_bwd_dq":
            assert not {"exp", "dot_general", "add", "mul"} & names
        # Every floating-point value a body computes is fp32 (scores, mask,
        # m, exp, alpha, l, lse, delta, dp - delta, the rescaled sums);
        # the caller's type appears only where a ref is read or written
        # and at the casts counted below (and in the dQ kernel's transposes
        # of values already rounded).
        for e in eqns:
            for out in e.outvars:
                if (jnp.issubdtype(out.aval.dtype, jnp.floating)
                        and out.aval.dtype != jnp.float32):
                    assert e.primitive.name in (
                        "get", "swap", "convert_element_type") or (
                        name == "hvd_flash_bwd_dq"
                        and e.primitive.name == "transpose"), (name, e)
        # m, l and the accumulators: the scratch operands are the body's
        # last arguments, and a write to a ref has the ref's type.
        assert grid_mapping.num_scratch_operands == n_scratch, name
        for ref in body.invars[len(body.invars) - n_scratch:]:
            assert ref.aval.dtype == jnp.float32, name
        converts = [e for e in eqns
                    if e.primitive.name == "convert_element_type"]
        to_bf16 = [e for e in converts
                   if e.params["new_dtype"] == jnp.bfloat16]
        if dtype == jnp.float32:
            assert not to_bf16, name
        else:
            # One cast of each computed operand, one for each result; and
            # nothing widens what the caller passed.
            assert len(to_bf16) == n_computed + n_results, name
            assert not [e for e in converts
                        if e.invars[0].aval.dtype == jnp.bfloat16], name
    assert n_in_callers_type == 7


@pytest.mark.parametrize("causal", [True, False])
def test_the_backward_visits_a_score_tile_once(causal):
    """The gauge of the one pass: the backward's two calls hold five
    ``dot_general``s and one ``exp`` over a (bk, bq) score tile, all in
    ``hvd_flash_bwd_dkv``, and none in ``hvd_flash_bwd_dq`` (seven, two and
    two kernels with dots before)."""
    bodies = _kernel_bodies(jnp.bfloat16, causal)
    found = {}
    for name in ("hvd_flash_bwd_dkv", "hvd_flash_bwd_dq"):
        eqns = list(_eqns(bodies[name][0]))
        found[name] = (
            sum(e.primitive.name == "dot_general" for e in eqns),
            [tuple(e.outvars[0].aval.shape) for e in eqns
             if e.primitive.name == "exp"])
    assert found == {"hvd_flash_bwd_dkv": (5, [(128, 128)]),
                     "hvd_flash_bwd_dq": (0, [])}


# ---------------------------------------------------------------------------
# Tiles up to 1024 x 1024 (ROADMAP Sk): chosen from the two lengths, and from
# the head's width and type for what fits VMEM.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("s,block", [(256, 256), (384, 128), (512, 512),
                                     (1536, 512), (2048, 1024),
                                     (4096, 1024), (8192, 1024)])
def test_tile_is_chosen_from_the_lengths(s, block, d):
    """The cells' shapes among them: the flagship (8192, head 64) and OLMoE
    (4096, head 128) take 1024 x 1024, BERT (512) the 512 x 512 it had."""
    x = jax.ShapeDtypeStruct((1, s, 2, d), jnp.bfloat16)
    assert fa._supported(x, x) == (block, block)


@pytest.mark.parametrize("dtype,d,block", [
    (jnp.bfloat16, 128, 1024), (jnp.bfloat16, 256, 512),
    (jnp.bfloat16, 512, 512), (jnp.float32, 64, 1024),
    (jnp.float32, 128, 512), (jnp.float32, 256, 512)])
def test_a_wide_head_keeps_the_512_tile(dtype, d, block):
    """1024 x 1024 only where a row of an operand block is at most 256
    bytes: beyond, dK/dV's blocks and score temporaries pass Mosaic's
    default scoped VMEM (compiled for a described v5e, PERF.md PR 29)."""
    x = jax.ShapeDtypeStruct((1, 2048, 2, d), dtype)
    assert fa._supported(x, x) == (block, block)
    y = jax.ShapeDtypeStruct((1, 1536, 2, d), dtype)   # each side its own
    assert fa._supported(x, y) == (block, 512)


def _pallas_calls(fn, *args):
    """name -> (grid, block shapes of inputs + outputs, scratch shapes) of
    every pallas_call ``fn`` traces (nothing runs)."""
    calls = {}
    for eqn in _pallas_eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        gm = eqn.params["grid_mapping"]
        invars = eqn.params["jaxpr"].invars
        scratch = invars[len(invars) - gm.num_scratch_operands:]
        calls[eqn.params["name"]] = (
            tuple(gm.grid),
            [tuple(getattr(b, "block_size", b) for b in bm.block_shape)
             for bm in gm.block_mappings],
            [tuple(v.aval.shape) for v in scratch])
    return calls


def _fwd_and_bwd(**kwargs):
    def f(q, k, v):
        out, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
            q, k, v, **kwargs), q, k, v)
        return out, vjp(out)
    return f


def _expected_calls(b, h, sq, sk, d, bq, bk, hb=1, steps=None):
    """The three calls' grids, blocks and scratch on one (bq, bk) pair: the
    forward's, the backward pass's with a (batch, head)'s dQ' resident as
    (q tiles, D, bq), and the dQ kernel's, ``hb`` heads a step (as many as
    make a block of at most 1 MiB).  The two grids that walk score tiles are
    the rectangle, or with ``steps`` a list that long (a causal call's live
    tiles; the list itself is scalar prefetch and has no block)."""
    grid = (b, h, steps) if steps else (b, h, sq // bq, sk // bk)
    off, row = (1, 2), (1, 1, 8, bq)
    qt, kt = (1, 1, bq, d), (1, 1, bk, d)
    dqt = (1, 1, sq // bq, d, bq)
    ins = [off, qt, kt, kt]
    return {
        "hvd_flash_fwd": (grid, ins + [qt, row],
                          [(8, bq), (8, bq), (d, bq)]),
        "hvd_flash_bwd_dkv": (grid if steps else
                              (b, h, sk // bk, sq // bq),
                              ins + [qt, row, row, kt, kt, dqt],
                              [(bk, d), (bk, d), dqt[2:]]),
        "hvd_flash_bwd_dq": ((b, h // hb), [(1, hb) + dqt[2:],
                                            (1, hb, sq, d)], [])}


@pytest.mark.parametrize("causal", [True, False])
def test_at_512_the_program_is_the_one_it_was(causal):
    """BERT's length admits no 1024 tile: forward and backward trace to the
    text an explicit (512, 512) gives, on the grids and blocks 512 x 512
    gives the three calls: one score tile a (batch, head), so the backward
    pass is one grid step where the two kernels took one each."""
    x = jax.ShapeDtypeStruct((2, 512, 12, 64), jnp.bfloat16)
    auto = _fwd_and_bwd(causal=causal)
    explicit = _fwd_and_bwd(causal=causal, block_q=512, block_k=512)
    assert (str(jax.make_jaxpr(auto)(x, x, x))
            == str(jax.make_jaxpr(explicit)(x, x, x)))
    assert _pallas_calls(auto, x, x, x) == _expected_calls(
        2, 12, 512, 512, 64, 512, 512, hb=12, steps=1 if causal else None)


@pytest.mark.parametrize("s,h,d", [(8192, 16, 64), (4096, 16, 128)])
def test_at_the_long_cells_shapes_every_call_is_on_1024_tiles(s, h, d):
    x = jax.ShapeDtypeStruct((1, s, h, d), jnp.bfloat16)
    n = s // 1024
    assert _pallas_calls(_fwd_and_bwd(causal=True), x, x, x) == (
        _expected_calls(1, h, s, s, d, 1024, 1024, steps=n * (n + 1) // 2))


def _xla_out_lse_grads(q, k, v, g, causal, q_offset=0, kv_offset=0):
    """(out, lse, (dq, dk, dv)) of the XLA path in fp32, offsets and all."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    scale = 1.0 / q.shape[-1] ** 0.5
    (out, lse), vjp = jax.vjp(
        lambda q, k, v: fa._xla_attention_with_lse(
            q, k, v, causal, scale, q_offset, kv_offset), q, k, v)
    return out, lse, vjp((g.astype(jnp.float32), jnp.zeros_like(lse)))


# The chooser's own pair at these lengths, and the two unequal ones an
# override (or unequal lengths) puts under the same three bodies.
TILES = [(1024, 1024), (1024, 512), (512, 1024)]


def _assert_fp32_matches_xla(q, k, v, causal, q_offset=0, kv_offset=0):
    """Forward, ``lse``, dQ, dK, dV of the kernels against the XLA path, at
    the fp32 tests' tolerances."""
    out, lse = fa.flash_attention_with_lse(
        q, k, v, causal=causal, q_offset=q_offset, kv_offset=kv_offset,
        interpret=True)
    g = jax.random.normal(jax.random.PRNGKey(11), q.shape, q.dtype)
    grads = jax.vjp(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=causal, q_offset=q_offset, kv_offset=kv_offset,
        interpret=True), q, k, v)[1](g)
    ref_out, ref_lse, ref_grads = _xla_out_lse_grads(
        q, k, v, g, causal, q_offset, kv_offset)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               atol=2e-2, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               atol=2e-2, rtol=1e-3)
    for got, ref in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=5e-2, rtol=1e-2)


@pytest.mark.parametrize("tile", TILES)
@pytest.mark.parametrize("causal", [True, False])
def test_fp32_on_wide_tiles_matches_xla(causal, tile, monkeypatch):
    q, k, v = _qkv(b=1, s=2048, h=1, d=64, seed=4)
    assert fa._supported(q, k) == TILES[0]
    force_tile(monkeypatch, *tile)
    assert fa._supported(q, k) == tile
    _assert_fp32_matches_xla(q, k, v, causal)


@pytest.mark.parametrize("tile", [TILES[0], TILES[2]])   # (1024, 512): above
@pytest.mark.parametrize("causal", [True, False])
def test_bf16_on_wide_tiles_keeps_its_bounds(causal, tile):
    q, k, v = _qkv(b=1, s=2048, h=1, d=64, dtype=jnp.bfloat16, seed=5)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal, block_q=tile[0],
                                  block_k=tile[1], interpret=True)

    out, vjp = jax.vjp(flash, q, k, v)
    _assert_bf16_forward(out, q, k, v, causal)
    g = jax.random.normal(jax.random.PRNGKey(8), q.shape, jnp.bfloat16)
    _assert_bf16_grads(vjp(g), q, k, v, g, causal)


@pytest.mark.parametrize("tile", TILES)
@pytest.mark.parametrize("q_offset,kv_offset", [
    (2048, 0),     # q a later chunk than k: every tile live, nothing masked
    (0, 2048),     # q an earlier chunk: every tile dead, out 0, lse -inf
    (512, 0),      # chunks overlap: the diagonal cuts tiles off their
    (0, 512),      # corners, some wholly live, some wholly dead
])
def test_ring_shard_offsets_on_wide_tiles(q_offset, kv_offset, tile,
                                          monkeypatch):
    """The ``live`` test and the mask at global positions."""
    q, k, v = _qkv(b=1, s=2048, h=1, d=64, seed=6)
    force_tile(monkeypatch, *tile)
    assert fa._supported(q, k) == tile
    _assert_fp32_matches_xla(q, k, v, True, q_offset, kv_offset)


@pytest.mark.parametrize("causal", [True, False])
def test_1024_queries_on_1536_keys(causal):
    """Sq != Sk: each side's tile follows its own length, here to an
    unequal pair with no override (the queries are the last 1024 positions
    of the keys' 1536 when causal)."""
    q, _, _ = _qkv(b=1, s=1024, h=1, d=64, seed=9)
    _, k, v = _qkv(b=1, s=1536, h=1, d=64, seed=10)
    assert fa._supported(q, k) == (1024, 512)
    _assert_fp32_matches_xla(q, k, v, causal, q_offset=512 if causal else 0)


@pytest.mark.parametrize("dtype,tile", [(jnp.float32, t) for t in TILES]
                         + [(jnp.bfloat16, TILES[0])])
def test_ring_flash_on_wide_tiles(dtype, tile, monkeypatch):
    """Two shards of 2048 through ``_ring_flash``: rank 0's second step is
    the fully masked earlier-chunk case, rank 1's the later-chunk one; the
    backward walk hands ``_bwd_call`` the chooser's pair at every step."""
    mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
    q, k, v = _qkv(b=1, s=4096, h=1, d=64, dtype=dtype, seed=12)
    g = jax.random.normal(jax.random.PRNGKey(13), q.shape, dtype)
    force_tile(monkeypatch, *tile)
    assert fa._supported(q[:, :2048], k[:, :2048]) == tile
    f = shard_map(
        lambda q, k, v: ra.ring_attention(q, k, v, "sp", causal=True,
                                          use_flash=True, interpret=True),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp"), check_vma=False)
    out, vjp = jax.vjp(f, q, k, v)
    if dtype == jnp.bfloat16:
        _assert_bf16_forward(out, q, k, v, True)
        _assert_bf16_grads(vjp(g), q, k, v, g, True)
        return
    ref_out, _, ref_grads = _xla_out_lse_grads(q, k, v, g, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               atol=2e-2, rtol=1e-3)
    for got, ref in zip(vjp(g), ref_grads):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=5e-2, rtol=1e-2)


# ---------------------------------------------------------------------------
# The layer checkpoint keeps what the forward kernel made (ROADMAP Sr): its
# output and lse are saved by name, so the recompute never runs the forward
# kernel a second time.
# ---------------------------------------------------------------------------

N_LAYERS = 3


def _scanned_layers(h, d, dtype, attention):
    """``grads(wrap)`` of a three-layer scan of qkv -> attention -> wo, each
    layer wrapped by ``wrap`` (a checkpoint, or nothing), with its inputs."""
    b, s, dm = 1, 128, h * d
    keys = jax.random.split(jax.random.PRNGKey(21), 3)
    params = {
        "wqkv": (jax.random.normal(keys[0], (N_LAYERS, dm, 3 * dm))
                 * dm ** -0.5).astype(dtype),
        "wo": (jax.random.normal(keys[1], (N_LAYERS, dm, dm))
               * dm ** -0.5).astype(dtype)}
    x = jax.random.normal(keys[2], (b, s, dm)).astype(dtype)

    def layer(act, lp):
        qkv = jnp.einsum("bsd,de->bse", act, lp["wqkv"]).reshape(
            b, s, h, 3, d)
        o = attention(qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :])
        return act + jnp.einsum("bse,ed->bsd", o.reshape(b, s, dm),
                                lp["wo"]), None

    def grads(wrap):
        def loss(params, x):
            out, _ = lax.scan(wrap(layer), x, params)
            return jnp.sum(out.astype(jnp.float32) ** 2)
        return jax.grad(loss, argnums=(0, 1))

    return grads, (params, x)


def _leaves(tree):
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("d", [64, 128])
def test_keeping_the_forward_calls_it_once_and_moves_no_bit(d, causal, dtype):
    """Under the keeping checkpoint the gradient's program holds one call
    of each kernel where the bare checkpoint's holds the forward twice, and
    the gradients are, bit for bit, the bare checkpoint's and those of no
    checkpoint at all."""
    grads, args = _scanned_layers(
        2, d, dtype, lambda q, k, v: fa.flash_attention(
            q, k, v, causal=causal, interpret=True))
    kept = grads(ra.checkpoint_keeping_attention)
    bare = grads(jax.checkpoint)
    plain = grads(lambda layer: layer)
    assert kernel_calls(jax.make_jaxpr(kept)(*args)) == ONCE
    assert kernel_calls(jax.make_jaxpr(plain)(*args)) == ONCE
    assert kernel_calls(jax.make_jaxpr(bare)(*args)) == {
        **ONCE, "hvd_flash_fwd": 2}
    got = _leaves(jax.jit(kept)(*args))
    for other in (bare, plain):
        for a, b in zip(got, _leaves(jax.jit(other)(*args))):
            assert a.dtype == b.dtype and np.array_equal(a, b)


def test_what_the_backward_reads_is_the_named_value():
    """The primal output and the residual ``out`` are one value, the named
    (B, S, H·D) activation reshaped back, and the residual ``lse`` is the
    named one: nothing downstream reads the kernel's own output."""
    offsets = jnp.zeros((1, 2), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v: fa._flash_fwd(q, k, v, offsets, True, 0.125, 128,
                                      128, True))(
        *_qkv(b=1, s=128, h=2, d=64)).jaxpr
    made_by = {out: eqn for eqn in jaxpr.eqns for out in eqn.outvars}
    # out, then the residuals (q, k, v, offsets, out, lse).
    primal, *_, res_out, res_lse = jaxpr.outvars
    assert primal is res_out
    reshape = made_by[primal]
    assert reshape.primitive.name == "reshape"
    named = made_by[reshape.invars[0]]
    assert named.primitive.name == "name"
    assert named.params["name"] == fa.SAVED_OUT
    assert named.outvars[0].aval.shape == (1, 128, 2 * 64)
    assert made_by[res_lse].primitive.name == "name"
    assert made_by[res_lse].params["name"] == fa.SAVED_LSE
    assert res_lse.aval.shape == (1, 2, 128)
    assert res_lse.aval.dtype == jnp.float32
    assert not any(n.startswith("hvd_") for n in (fa.SAVED_OUT, fa.SAVED_LSE))


def test_a_named_copy_beside_the_kernels_own_output_keeps_both_forwards(
        monkeypatch):
    """The mutation the call count is there to catch: name a copy, return
    and keep the kernel's own output, and the recompute needs the kernel
    again."""
    from jax.ad_checkpoint import checkpoint_name

    def fwd(q, k, v, offsets, *static):
        out, lse = fa._flash_impl(q, k, v, offsets, *static)
        checkpoint_name(out.reshape(*out.shape[:2], -1), fa.SAVED_OUT)
        return out, (q, k, v, offsets, out,
                     checkpoint_name(lse, fa.SAVED_LSE))

    mutant = jax.custom_vjp(fa._flash.fun,
                            nondiff_argnums=fa._flash.nondiff_argnums)
    mutant.defvjp(fwd, fa._flash_bwd)
    monkeypatch.setattr(fa, "_flash", mutant)
    grads, args = _scanned_layers(
        2, 64, jnp.float32, lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, interpret=True))
    calls = kernel_calls(jax.make_jaxpr(
        grads(ra.checkpoint_keeping_attention))(*args))
    assert calls["hvd_flash_fwd"] == 2


def _without_policy(jaxpr) -> str:
    """A jaxpr's text less the checkpoint equations' ``policy=`` parameter,
    which prints the policy function's address."""
    return "\n".join(ln for ln in str(jaxpr).splitlines()
                     if "policy=" not in ln)


@pytest.mark.parametrize("path", ["xla", "ring"])
def test_a_path_that_sets_no_names_is_recomputed_whole_as_before(
        path, sp_mesh):
    """The XLA attention and ``_ring_flash`` (a custom VJP of its own over
    ``flash_attention_with_lse``) name nothing: under the keeping checkpoint
    their gradient traces to the bare checkpoint's program."""
    if path == "xla":
        def attention(q, k, v):
            return ra.full_attention(q, k, v, causal=True, use_flash=False)
    else:
        attention = shard_map(
            lambda q, k, v: ra.ring_attention(q, k, v, "sp", causal=True,
                                              use_flash=True, interpret=True),
            mesh=sp_mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False)

    grads, args = _scanned_layers(2, 32, jnp.float32, attention)
    kept = jax.make_jaxpr(grads(ra.checkpoint_keeping_attention))(*args)
    bare = jax.make_jaxpr(grads(jax.checkpoint))(*args)
    assert fa.SAVED_OUT not in str(kept) and fa.SAVED_LSE not in str(kept)
    assert _without_policy(kept) == _without_policy(bare)
    if path == "ring":
        # Forward and recompute, four ring steps each, unrolled.
        assert kernel_calls(kept) == kernel_calls(bare)
        assert kernel_calls(kept)["hvd_flash_fwd"] > 1
