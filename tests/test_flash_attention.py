"""Pallas flash-attention kernels (ops/flash_attention.py) validated in
interpret mode against the XLA reference — fwd, custom-VJP bwd, LSE
composition, and the flash ring-attention path on a virtual mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from horovod_tpu.compat import shard_map
from jax.sharding import Mesh, PartitionSpec as P

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


def test_block_size_env_override(monkeypatch):
    """HVD_TPU_FLASH_BLOCK_Q/K force the kernel block sizes (silicon
    tuning knob) through the auto-selection path — no explicit kwargs,
    so the env plumbing itself is what is exercised; illegal overrides
    (non-divisor, non-128-aligned, oversized whole-dim) are ignored."""
    monkeypatch.setenv("HVD_TPU_FLASH_BLOCK_Q", "128")
    monkeypatch.setenv("HVD_TPU_FLASH_BLOCK_K", "128")
    q, k, v = _qkv(s=256)
    assert fa._supported(q, k) == (128, 128)
    ref = ra.reference_attention(q, k, v, causal=True)
    out = fa.flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2, rtol=1e-3)
    # Illegal overrides fall back to auto-selection: non-divisor,
    # non-128-aligned divisor, and non-divisor larger than the dim.
    for bad in ("96", "64", "1024"):
        monkeypatch.setenv("HVD_TPU_FLASH_BLOCK_Q", bad)
        assert fa._supported(q, k)[0] == 256, bad
    # A 128-aligned divisor above the 512 VMEM cap is rejected too:
    # s=1024 forced to 1024 falls back to the auto-selected 512.
    q2, k2, _ = _qkv(s=1024)
    monkeypatch.setenv("HVD_TPU_FLASH_BLOCK_Q", "1024")
    assert fa._supported(q2, k2)[0] == 512


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
