"""ResNet-50 v1.5 — the reference's headline benchmark model (BASELINE.md:
examples/pytorch/pytorch_synthetic_benchmark.py, docs/benchmarks.rst).

Pure-functional JAX implementation, NHWC (TPU-native conv layout), bfloat16
compute with fp32 parameters and batch-norm statistics.  Batch norm supports
cross-replica synchronization over a mesh axis — capability parity with the
reference's SyncBatchNormalization (tensorflow/sync_batch_norm.py,
torch/sync_batch_norm.py) where mean/var are allreduced across ranks.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

STAGE_BLOCKS = {18: (2, 2, 2, 2), 34: (3, 4, 6, 3), 50: (3, 4, 6, 3),
                101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}
BOTTLENECK = {50, 101, 152}


class ResNetConfig(NamedTuple):
    depth: int = 50
    num_classes: int = 1000
    width: int = 64
    dtype: Any = jnp.bfloat16
    sync_bn_axis: Optional[str] = None   # mesh axis for cross-replica BN
    bn_momentum: float = 0.9
    # Compute the 7x7/s2 stem as a 4x4/s1 conv over a 2x2 space-to-depth
    # transform of the input (3 -> 12 channels): mathematically
    # equivalent (exact-arithmetic equal; float rounding differs, the
    # test compares at rtol 1e-4), and the MXU sees a dense 12-channel
    # contraction at half the spatial size instead of a 3-channel one
    # padded 42x to the lane width — the standard TPU ResNet stem
    # formulation (MLPerf conv0 space-to-depth).
    stem_s2d: bool = False


def _conv_init(key, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    std = math.sqrt(2.0 / fan_in)
    return (jax.random.normal(key, (kh, kw, cin, cout)) * std).astype(
        jnp.float32)


def _bn_init(c):
    return {"scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32)}


def _bn_stats(c):
    return {"mean": jnp.zeros((c,), jnp.float32),
            "var": jnp.ones((c,), jnp.float32)}


def init_params(key, cfg: ResNetConfig) -> Tuple[Dict, Dict]:
    """Returns (params, batch_stats)."""
    blocks = STAGE_BLOCKS[cfg.depth]
    bottleneck = cfg.depth in BOTTLENECK
    expansion = 4 if bottleneck else 1
    keys = iter(jax.random.split(key, 1024))
    params: Dict[str, Any] = {"stem": {
        "conv": _conv_init(next(keys), 7, 7, 3, cfg.width),
        "bn": _bn_init(cfg.width)}}
    stats: Dict[str, Any] = {"stem": _bn_stats(cfg.width)}
    cin = cfg.width
    for si, nblocks in enumerate(blocks):
        cmid = cfg.width * (2 ** si)
        cout = cmid * expansion
        stage_p, stage_s = [], []
        for bi in range(nblocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            bp: Dict[str, Any] = {}
            bs: Dict[str, Any] = {}
            if bottleneck:
                shapes = [(1, 1, cin, cmid), (3, 3, cmid, cmid),
                          (1, 1, cmid, cout)]
            else:
                shapes = [(3, 3, cin, cmid), (3, 3, cmid, cout)]
            for ci, (kh, kw, ci_, co_) in enumerate(shapes):
                bp[f"conv{ci}"] = _conv_init(next(keys), kh, kw, ci_, co_)
                bp[f"bn{ci}"] = _bn_init(co_)
                bs[f"bn{ci}"] = _bn_stats(co_)
            if bi == 0 and (stride != 1 or cin != cout):
                bp["proj"] = _conv_init(next(keys), 1, 1, cin, cout)
                bp["proj_bn"] = _bn_init(cout)
                bs["proj_bn"] = _bn_stats(cout)
            stage_p.append(bp)
            stage_s.append(bs)
            cin = cout
        params[f"stage{si}"] = stage_p
        stats[f"stage{si}"] = stage_s
    head_std = 1.0 / math.sqrt(cin)
    params["head"] = {
        "w": (jax.random.normal(next(keys), (cin, cfg.num_classes))
              * head_std).astype(jnp.float32),
        "b": jnp.zeros((cfg.num_classes,), jnp.float32)}
    return params, stats


def _conv(x, w, stride=1, padding="SAME"):
    return lax.conv_general_dilated(
        x, w.astype(x.dtype), window_strides=(stride, stride),
        padding=padding, dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _space_to_depth2(x):
    """(B, H, W, C) -> (B, H/2, W/2, 4C), channel order (di, dj, c)."""
    b, h, w, c = x.shape
    if h % 2 or w % 2:
        raise ValueError(
            f"stem_s2d requires even input H/W, got {(h, w)}; use the "
            "default stem for odd sizes")
    x = x.reshape(b, h // 2, 2, w // 2, 2, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, h // 2, w // 2, 4 * c)


def _s2d_stem_kernel(w):
    """Transform the (7,7,C,K) stride-2 stem kernel into the equivalent
    (4,4,4C,K) stride-1 kernel over the space-to-depth input.

    With SAME padding (k=7, s=2, even input) the conv reads
    X[2i+p-2, 2j+q-2]; writing p = 2a+di maps taps onto s2d channel
    (di, dj, c) at spatial offset (a-1, b-1) — i.e. a 4x4 window with
    asymmetric padding (1,2).  Tap p=7 never occurs: zero-pad 7->8."""
    kh, kw, c, k = w.shape
    assert (kh, kw) == (7, 7), (kh, kw)
    wp = jnp.pad(w, ((0, 1), (0, 1), (0, 0), (0, 0)))
    wp = wp.reshape(4, 2, 4, 2, c, k)          # (a, di, b, dj, c, k)
    wp = wp.transpose(0, 2, 1, 3, 4, 5)        # (a, b, di, dj, c, k)
    return wp.reshape(4, 4, 4 * c, k)


def _stem_s2d_conv(x, w):
    y = _space_to_depth2(x)
    w4 = _s2d_stem_kernel(w)
    return lax.conv_general_dilated(
        y, w4.astype(x.dtype), window_strides=(1, 1),
        padding=((1, 2), (1, 2)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _batch_norm(x, bn, stats, cfg: ResNetConfig, training: bool):
    """BN in fp32; with ``sync_bn_axis`` the batch moments are allreduced
    over the mesh axis (reference SyncBatchNormalization semantics).
    Returns (normalized, new_stats)."""
    xf = x.astype(jnp.float32)
    if training:
        mean = jnp.mean(xf, axis=(0, 1, 2))
        mean_sq = jnp.mean(xf * xf, axis=(0, 1, 2))
        if cfg.sync_bn_axis is not None:
            mean = lax.pmean(mean, cfg.sync_bn_axis)
            mean_sq = lax.pmean(mean_sq, cfg.sync_bn_axis)
        var = mean_sq - mean * mean
        m = cfg.bn_momentum
        new_stats = {"mean": m * stats["mean"] + (1 - m) * mean,
                     "var": m * stats["var"] + (1 - m) * var}
    else:
        mean, var = stats["mean"], stats["var"]
        new_stats = stats
    # Moments in fp32 (above); the normalization itself runs in the compute
    # dtype with per-channel (scale·rsqrt, shift) folded in fp32 first —
    # halves the bandwidth of the elementwise chain vs materializing fp32
    # activations.
    inv = lax.rsqrt(var + 1e-5)
    w = (inv * bn["scale"]).astype(x.dtype)
    b = (bn["bias"] - mean * inv * bn["scale"]).astype(x.dtype)
    return x * w + b, new_stats


def apply(params, stats, images, cfg: ResNetConfig,
          training: bool = True) -> Tuple[jax.Array, Dict]:
    """Forward pass: images (N, H, W, 3) → logits (N, classes).

    Returns (logits, new_batch_stats).
    """
    bottleneck = cfg.depth in BOTTLENECK
    x = images.astype(cfg.dtype)
    new_stats: Dict[str, Any] = {}
    if cfg.stem_s2d:
        x = _stem_s2d_conv(x, params["stem"]["conv"])
    else:
        x = _conv(x, params["stem"]["conv"], stride=2)
    x, new_stats["stem"] = _batch_norm(x, params["stem"]["bn"],
                                       stats["stem"], cfg, training)
    x = jax.nn.relu(x)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    n_convs = 3 if bottleneck else 2
    for si in range(4):
        stage_p = params[f"stage{si}"]
        stage_s = stats[f"stage{si}"]
        out_stage = []
        for bi, (bp, bs) in enumerate(zip(stage_p, stage_s)):
            stride = 2 if (si > 0 and bi == 0) else 1
            shortcut = x
            h = x
            nbs: Dict[str, Any] = {}
            for ci in range(n_convs):
                # v1.5: stride lives on the 3x3 conv (index 1 in bottleneck).
                s = stride if ci == (1 if bottleneck else 0) else 1
                h = _conv(h, bp[f"conv{ci}"], stride=s)
                h, nbs[f"bn{ci}"] = _batch_norm(h, bp[f"bn{ci}"],
                                                bs[f"bn{ci}"], cfg, training)
                if ci < n_convs - 1:
                    h = jax.nn.relu(h)
            if "proj" in bp:
                shortcut = _conv(shortcut, bp["proj"], stride=stride)
                shortcut, nbs["proj_bn"] = _batch_norm(
                    shortcut, bp["proj_bn"], bs["proj_bn"], cfg, training)
            x = jax.nn.relu(h + shortcut)
            out_stage.append(nbs)
        new_stats[f"stage{si}"] = out_stage
    x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))
    logits = x @ params["head"]["w"] + params["head"]["b"]
    return logits, new_stats


def cross_entropy_loss(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.mean(
        jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0])


def synthetic_batch(key, batch: int, image_size: int = 224,
                    num_classes: int = 1000):
    ki, kl = jax.random.split(key)
    images = jax.random.normal(ki, (batch, image_size, image_size, 3),
                               dtype=jnp.float32)
    labels = jax.random.randint(kl, (batch,), 0, num_classes,
                                dtype=jnp.int32)
    return images, labels


# fwd GFLOP/img @224x224, width 64 (standard torchvision counts),
# importable so training loops can feed hvd.metrics.set_step_flops().
_FWD_GFLOP_PER_IMG = {18: 1.82, 34: 3.68, 50: 4.09, 101: 7.83, 152: 11.53}


def train_flops_per_image(cfg: ResNetConfig, image_size: int = 224) -> float:
    """Model FLOPs ONE training image executes (fwd + bwd ~= 3x fwd),
    scaled quadratically with image size and width from the standard
    @224/width-64 counts.  The live-MFU input::

        hvd.metrics.set_step_flops(
            per_chip_batch * resnet.train_flops_per_image(cfg))
    """
    fwd = _FWD_GFLOP_PER_IMG.get(cfg.depth, 4.09) * 1e9
    fwd *= (image_size / 224.0) ** 2 * (cfg.width / 64.0) ** 2
    return 3.0 * fwd
