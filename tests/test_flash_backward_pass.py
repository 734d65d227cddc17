"""The flash backward as one pass over the score tiles (ISSUE 34, ROADMAP Sk).

``hvd_flash_bwd_dkv`` keeps a key tile resident, streams the query tiles past
it and makes dK, dV and dQ from one S', P', dP' and dS' a tile; a (batch,
head)'s dQ' lives in fp32 VMEM scratch across the key tiles, and
``hvd_flash_bwd_dq`` turns the finished (D, bq) tiles into dQ.  Here: the
three gradients against the two kernels the pass replaced (bit for bit for
fp32 callers, ``_flash_kernels.two_kernel_bwd_call``) and against
``reference_attention``; the scratch's zeroing; the VMEM the call states; the
length it refuses; and the backward compiled by Mosaic for a described
``v5e:2x2`` at the cells' shapes (no chip; a compile that passes is not a chip
run; the topology is described inside the benchmark tests' module-scoped
fixture, used as it is).  The structure of the kernels (dots, casts, grids,
blocks, scratch) is in ``tests/test_flash_attention.py``, a file of its own
so that the interpreter's minutes spread over two workers.
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _flash_kernels import bwd_operands, two_kernel_bwd_call
from horovod_tpu.ops import flash_attention as fa
from test_flash_attention import (
    U, _assert_bf16_grads, _f32_attention, _fwd_and_bwd, _pallas_eqns, _qkv,
    _rel_l2)

sys.path.insert(0, str(Path(__file__).resolve().parent / "benchmark_tests"))
from test_benchmark_compile_v5e import topo  # noqa: E402,F401  (a fixture)


# (sq, sk, block_q, block_k, causal, q_offset, kv_offset, window): several
# tiles on both sides, so that dQ really sums across the outer axis and dK /
# dV across the inner; unequal lengths and tiles; ring-style offsets that
# leave tiles wholly live, wholly dead and cut by the diagonal.
PASS_CASES = [
    (512, 512, 128, 128, True, 0, 0, None),
    (512, 512, 128, 128, False, 0, 0, None),
    (512, 512, 128, 256, True, 0, 0, None),
    (512, 512, 256, 128, False, 0, 0, None),
    (256, 512, 128, 128, True, 256, 0, None),
    (512, 384, 128, 128, False, 0, 0, None),
    (512, 512, 128, 128, True, 512, 0, None),
    (512, 512, 128, 128, True, 0, 512, None),
    (512, 512, 128, 128, True, 256, 0, None),
    (512, 512, 128, 128, True, 0, 256, None),
    (1024, 1024, 256, 256, True, 0, 0, 512),
    (1024, 1024, 256, 128, True, 0, 0, 512),
]


@pytest.mark.parametrize("dtype, d", [(jnp.float32, 64), (jnp.float32, 128),
                                      (jnp.bfloat16, 128)])
@pytest.mark.parametrize(
    "sq, sk, bq, bk, causal, q_off, kv_off, window", PASS_CASES,
    ids=[f"q{c[0]}k{c[1]}-{c[2]}x{c[3]}-{'causal' if c[4] else 'full'}"
         f"-off{c[5]}.{c[6]}-w{c[7]}" for c in PASS_CASES])
def test_the_pass_equals_the_two_kernels_bit_for_bit(
        sq, sk, bq, bk, causal, q_off, kv_off, window, dtype, d):
    """Same operands, same roundings, a query tile's dQ summed over ascending
    key tiles as the dQ kernel summed it: in the interpreter an fp32 caller's
    three gradients are the old two kernels' to the bit.  A bf16 caller's dK
    and dV too; its dQ may sit one bf16 rounding away in a few elements of
    ten thousand, where the CPU compiled the old dQ body's ``exp(s * scale -
    lse)`` into one fused expression and the old dK/dV body's (the pass's)
    into two."""
    args, kw = bwd_operands(sq, sk, d, dtype, causal, q_off, kv_off, window)
    kw.update(block_q=bq, block_k=bk, interpret=True, window=window)
    got = fa._bwd_call(*args, **kw)
    want = two_kernel_bwd_call(*args, **kw)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype == dtype and a.shape == b.shape, name
        assert np.abs(b.astype(np.float32)).max() > 0 or (
            causal and kv_off >= q_off + sq), name
        if dtype == jnp.float32 or name != "dq" or not b.any():
            assert np.array_equal(a, b), name
        else:
            assert np.mean(a != b) < 1e-3 and _rel_l2(a, b) < U / 8, name


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("causal, window", [(True, None), (False, None),
                                            (True, 512)])
def test_the_pass_matches_the_references_gradients(causal, window, d, dtype):
    """1024 positions under 256 x 256 tiles: four key tiles and four query
    tiles a (batch, head) (the band of 512 three wide), two heads."""
    q, k, v = _qkv(b=1, s=1024, h=2, d=d, dtype=dtype, seed=d + 1)
    g = jax.random.normal(jax.random.PRNGKey(17), q.shape, dtype)
    grads = jax.vjp(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=causal, window=window, block_q=256, block_k=256,
        interpret=True), q, k, v)[1](g)
    if dtype == jnp.bfloat16:
        _assert_bf16_grads(grads, q, k, v, g, causal, window)
        return
    refs = _f32_attention(q, k, v, causal, cotangent=g, window=window)
    for name, got, ref in zip("qkv", grads, refs):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4, err_msg=name)


@pytest.mark.parametrize("causal", [True, False])
def test_a_batch_and_head_does_not_inherit_anothers_dq(causal):
    """dQ's scratch is zeroed at a (batch, head)'s first grid step: every
    (batch, head) of a call of 2 x 3 is, to the bit, what it is alone."""
    args, kw = bwd_operands(512, 512, 64, jnp.float32, causal, b=2, h=3)
    kw.update(block_q=128, block_k=128, interpret=True)
    together = fa._bwd_call(*args, **kw)
    for b in range(2):
        for h in range(3):
            alone = fa._bwd_call(
                *(x[b:b + 1, h:h + 1] for x in args[:-1]), args[-1], **kw)
            for name, a, t in zip(("dq", "dk", "dv"), alone, together):
                assert np.array_equal(np.asarray(a[0, 0]),
                                      np.asarray(t[b, h])), (name, b, h)


def _bwd_vmem_limits(fn, *args):
    """``vmem_limit_bytes`` of the backward pass's call, as traced."""
    return [eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
            for eqn in _pallas_eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if eqn.params["name"] == "hvd_flash_bwd_dkv"]


def test_the_pass_states_its_vmem_from_the_shapes():
    """What dK and dV always fitted in, plus a (batch, head)'s dQ' once in
    fp32 and twice in the caller's type: it grows with sq x D, equal shapes
    get equal limits, and batch, heads and keys do not count."""
    mib = 1 << 20
    limit = fa._bwd_vmem_limit
    # The cells: the flagship, Laguna / OLMoE heads of 128, BERT.
    assert limit(8192, 64, jnp.bfloat16) == 20 * mib
    assert limit(8192, 128, jnp.bfloat16) == 24 * mib
    assert limit(4096, 128, jnp.bfloat16) == 20 * mib
    assert limit(512, 64, jnp.bfloat16) == 16 * mib + 256 * 1024
    assert limit(8192, 64, jnp.float32) == 22 * mib
    sizes = [limit(s, d, jnp.bfloat16)
             for s in (1024, 2048, 4096) for d in (64, 128)]
    assert sizes == sorted(sizes) and len(set(sizes)) == 4
    assert sizes[1] == sizes[2] and sizes[3] == sizes[4]   # equal sq x D
    # The traced call carries it, whatever the batch, heads and keys.
    for b, h, sk in [(1, 2, 1024), (2, 4, 2048)]:
        q = jax.ShapeDtypeStruct((b, 1024, h, 64), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((b, sk, h, 64), jnp.bfloat16)
        assert _bwd_vmem_limits(_fwd_and_bwd(causal=False), q, k, k) == [
            limit(1024, 64, jnp.bfloat16)]


def test_a_length_whose_dq_does_not_fit_is_refused_aloud():
    """Past half of a v5e's VMEM the kernels are not offered
    (``_supported`` is None and the callers take the XLA path, as for any
    shape they cannot tile), and ``_bwd_call`` itself raises."""
    x = lambda s, d, dt=jnp.bfloat16: jax.ShapeDtypeStruct((1, s, 2, d), dt)
    assert fa._supported(x(32768, 128), x(32768, 128)) == (1024, 1024)
    assert fa._supported(x(65536, 64), x(65536, 64)) == (1024, 1024)
    assert fa._supported(x(65536, 128), x(65536, 128)) is None
    assert fa._supported(x(49152, 128, jnp.float32), x(1024, 128)) is None
    assert fa._supported(x(1024, 128), x(65536, 128)) == (1024, 1024)
    big = jax.ShapeDtypeStruct((1, 2, 65536, 128), jnp.bfloat16)
    row = jax.ShapeDtypeStruct((1, 2, 65536), jnp.float32)
    with pytest.raises(ValueError, match="dQ resident"):
        jax.eval_shape(
            lambda *a: fa._bwd_call(*a, causal=True, scale=1.0,
                                    block_q=1024, block_k=1024,
                                    interpret=True),
            big, big, big, big, row, row,
            jax.ShapeDtypeStruct((1, 2), jnp.int32))


def _compile_bwd_for_v5e(topo, b, h, s, d, causal, window):
    """``_bwd_call`` at (b, h, s, d) bf16 on the chooser's tile, compiled by
    Mosaic for a described v5e (no chip; a compile that passes is not a
    chip run)."""
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)
    bq, bk = fa._supported(x, x, window)
    t = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16, sharding=one)
    row = jax.ShapeDtypeStruct((b, h, s), jnp.float32, sharding=one)
    off = jax.ShapeDtypeStruct((1, 2), jnp.int32, sharding=one)
    return jax.jit(lambda *a: fa._bwd_call(
        *a, causal=causal, scale=d ** -0.5, block_q=bq, block_k=bk,
        interpret=False, window=window, static_offsets=(0, 0))).lower(
            t, t, t, t, row, row, off).compile()


# The five shapes the benchmark's cells hand the backward: the flagship,
# BERT, OLMoE, Laguna's full and windowed layers.
CELL_SHAPES = [(5, 16, 8192, 64, True, None), (256, 12, 512, 64, False, None),
               (4, 16, 4096, 128, True, None), (3, 24, 8192, 128, True, None),
               (3, 36, 8192, 128, True, 512)]


@pytest.mark.parametrize("b, h, s, d, causal, window", CELL_SHAPES)
def test_the_backward_compiles_for_a_v5e_at_the_cells_shapes(
        topo, b, h, s, d, causal, window):
    hlo = _compile_bwd_for_v5e(topo, b, h, s, d, causal, window).as_text()
    win = "" if window is None else "_win"
    assert f"%hvd_flash_bwd_dkv{win}" in hlo and f"%hvd_flash_bwd_dq{win}" in hlo


def test_the_stated_vmem_is_what_the_pass_needs(topo, monkeypatch):
    """At the flagship's shape the pass no longer fits Mosaic's default
    scoped VMEM: held to 16 MiB the compiler refuses it, so the limit from
    the shapes is what lets 8192 queries' dQ stay resident."""
    monkeypatch.setattr(fa, "_bwd_vmem_limit",
                        lambda *_: fa._SCOPED_VMEM_BYTES)
    with pytest.raises(Exception, match="(?i)vmem"):
        _compile_bwd_for_v5e(topo, 5, 16, 8192, 64, True, None)
