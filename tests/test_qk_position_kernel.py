"""The kernel ``hvd_qk_position`` (``ops/qk_position.py``) in the Pallas
interpreter against the statement of its mathematics,
``models/transformer._position``: results and the gradients of q, k and the
norm's scales, for every form a configuration gives it."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _flash_kernels
from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import qk_position

YARN = (128.0, 64, 32.0, 1.0, 1.4852030263919618)
EPS = 1e-6


def _streams(b, s):
    """(b, 3, s) positions: three streams that differ, a sequence each."""
    at = jnp.arange(s)
    return jnp.stack([jnp.stack([at + i, at // 2 + i, at % 7])
                      for i in range(b)])


def _one(positions, fraction=1.0, theta=1e6, yarn=None):
    return lambda s, d: (int(d * fraction) // 2, partial(
        tfm._rope_angles, positions(s), int(d * fraction), theta, yarn))


def _three(s, d):
    return d // 2, partial(tfm._stream_angles, _streams(2, s), d, 1e7,
                           (16, 24, 24))


# name: (head width, query heads, kv heads, batch, positions,
#        (S, D) -> (half, angles(lanes=None)))
CASES = {
    "head128_whole": (128, 2, 1, 2, 16, _one(jnp.arange, theta=1e4)),
    "head128_half_yarn": (128, 2, 1, 1, 16, _one(jnp.arange, 0.5, 5e5, YARN)),
    "two_heads_of_64": (64, 4, 2, 1, 16, _one(jnp.arange)),
    "two_heads_of_64_norm": (64, 2, 2, 1, 16, _one(jnp.arange)),
    "three_streams": (128, 2, 1, 2, 16, _three),
    "head_norm": (128, 2, 1, 1, 64, _one(jnp.arange)),
    "head_norm_streams": (128, 1, 1, 2, 16, _three),
    "doubled_sequence": (128, 1, 1, 1, 32,
                         _one(lambda s: jnp.arange(s) % (s // 2))),
}
NORMED = ("two_heads_of_64_norm", "head_norm", "head_norm_streams",
          "doubled_sequence")


def _ulps(got, want):
    """The largest distance in units of ``want``'s bf16 spacing."""
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    spacing = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1e-30))) - 7)
    return float(np.max(np.abs(got - want) / spacing))


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_is_position_to_a_bf16_ulp(case):
    hd, hq, hkv, b, s, angles = CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 6)
    q, k, gq, gk = (
        jax.random.normal(key, (b, s, h, hd), jnp.float32).astype(
            jnp.bfloat16)
        for key, h in zip(keys, (hq, hkv, hq, hkv)))
    scales = tuple(1.0 + 0.3 * jax.random.normal(key, (hd,))
                   for key in keys[4:]) if case in NORMED else ()
    half, angles = angles(s, hd)
    block = qk_position.block(s, hq * hd, hkv * hd, hd, 2)
    assert block in (16, 32, 64)
    kw = dict(head_dim=hd, half=half, eps=EPS, block=block, interpret=True)

    def both(q, k, gq, gk, scales):
        cos, sin = angles()
        tab = qk_position.tables(*angles(qk_position.lanes(hd, half)), hd,
                                 half)
        rows = tfm._rows
        out = qk_position.forward(rows(q), rows(k), tab, scales, **kw)
        dq, dk, sums = qk_position.backward(
            rows(q) if scales else None, rows(k) if scales else None, tab,
            scales, rows(gq), rows(gk), **kw)
        # The reference in fp32 rows, so that its own roundings between the
        # norm and the rotation do not count.
        f32 = [t.astype(jnp.float32) for t in (q, k)]
        want, pull = jax.vjp(
            lambda q, k, scales: tfm._position(q, k, cos, sin, scales, EPS),
            *f32, scales)
        return (out, (dq, dk),
                tuple(t.reshape(-1, hd).sum(0) for t in sums),
                want, pull(tuple(g.astype(jnp.float32) for g in (gq, gk))))

    out, (dq, dk), dscales, want, (wq, wk, wscales) = _flash_kernels.quick(
        both, q, k, gq, gk, scales)
    for got, ref in zip(out + (dq, dk), want + (wq, wk)):
        assert got.dtype == jnp.bfloat16
        assert _ulps(got.reshape(ref.shape), ref) <= 1.0
    assert len(dscales) == len(scales)
    for got, ref in zip(dscales, wscales):
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-4)


def test_block_says_what_fits():
    assert qk_position.block(8192, 3072, 512, 128, 2) == 256
    assert qk_position.block(8192, 2048, 512, 64, 2) == 256
    assert qk_position.block(48, 256, 128, 128, 2) == 16
    # a head that does not tile the lanes, rows that are no whole register,
    # positions that are no multiple of a block
    assert qk_position.block(8192, 96 * 4, 96, 96, 2) is None
    assert qk_position.block(8192, 1024, 64, 64, 2) is None
    assert qk_position.block(100, 256, 128, 128, 2) is None
    # rows so wide that 16 positions pass the blocks' VMEM
    assert qk_position.block(8192, 1 << 18, 128, 128, 2) is None
