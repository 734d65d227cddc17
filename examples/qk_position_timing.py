"""Bare timings, on a TPU, of q and k's position prologue at the benchmark
cells' site shapes: the jnp form (``models/transformer.py`` ``_position``:
slices and a concatenate, the norm a pass of its own) against the Pallas
kernel ``hvd_qk_position`` (``ops/qk_position.py``), forward and backward,
with the kernel's results checked against the jnp form's.

    python examples/qk_position_timing.py [site,site] [--blocks 128,256,512]
        [--chunks 16,32,64] [--out FILE]

q and k are the (B, S, H·D) rows the projections write, as the step holds
them (a (B, S, H, D) array of 28 or 36 heads is laid out padded on the chip,
and a reshape of it is a copy the step never makes); ``--blocks`` and
``--chunks`` try other positions a program and rows a trip than the shipped
ones.  Twenty calls are queued and waited for once.  A line a timing, with
the GB/s of one read and one write of q and k (of q, k and the cotangents
backward with a norm); ``--out`` also writes them as JSON.  PERF.md section
6, PR 53, holds the readings.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp

from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import qk_position as qp

YARN = (128.0, 8192, 32.0, 1.0, 1.4852030263919618)
SITES = {  # batch, positions, query heads, kv heads, head, fraction, yarn,
           # streams, head norm
    "laguna_full": (3, 8192, 24, 4, 128, 0.5, YARN, False, False),
    "laguna_window": (3, 8192, 36, 4, 128, 1.0, None, False, False),
    "sdar": (1, 8192, 32, 4, 128, 1.0, None, False, True),
    "keye_vl": (1, 16384, 32, 4, 128, 1.0, None, True, True),
    "smallthinker": (1, 16384, 28, 4, 128, 1.0, None, False, False),
    "lfm2": (1, 32768, 32, 8, 64, 1.0, None, False, True),
    "olmoe": (4, 4096, 16, 16, 64, 1.0, None, False, False)}


def timed(fn, *args, queue=20, repeats=5):
    """Seconds a call: the least of ``repeats`` waits for ``queue`` calls."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = [fn(*args) for _ in range(queue)]
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / queue)
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("sites", nargs="?", default=",".join(SITES))
    ap.add_argument("--blocks", default="")
    ap.add_argument("--chunks", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("qk_position_timing: no TPU here", file=sys.stderr)
        return 1
    lines = []

    def say(**line):
        lines.append(line)
        print(json.dumps(line), flush=True)

    for site in args.sites.split(","):
        b, s, hq, hkv, hd, fraction, yarn, streams, normed = SITES[site]
        keys = jax.random.split(jax.random.PRNGKey(0), 6)
        q, k, gq, gk = (
            jax.random.normal(key, (b, s, h * hd), jnp.bfloat16)
            for key, h in zip(keys, (hq, hkv, hq, hkv)))
        scales = tuple(1.0 + 0.1 * jax.random.normal(key, (hd,))
                       for key in keys[4:]) if normed else ()
        if streams:
            at = jnp.arange(s)
            half, angles = hd // 2, functools.partial(
                tfm._stream_angles,
                jnp.stack([at, at // 3, at % 97])[None].repeat(b, 0), hd,
                1e7, (16, 24, 24))
        else:
            rot = int(hd * fraction)
            half, angles = rot // 2, functools.partial(
                tfm._rope_angles, jnp.arange(s), rot, 1e6, yarn)
        moved = 2 * (q.size + k.size) * 2

        def heads(t):
            return t.reshape(b, s, -1, hd)

        def position(q, k, scales):
            return tuple(tfm._rows(t) for t in tfm._position(
                heads(q), heads(k), *angles(), scales, 1e-6))

        xla_fwd = jax.jit(position)

        @jax.jit
        def xla_bwd(q, k, scales, gq, gk):
            return jax.vjp(position, q, k, scales)[1]((gq, gk))

        want = xla_fwd(q, k, scales)
        want_d = xla_bwd(q, k, scales, gq, gk)
        say(site=site, form="xla", fwd_ms=timed(xla_fwd, q, k, scales) * 1e3,
            bwd_ms=timed(xla_bwd, q, k, scales, gq, gk) * 1e3)
        shipped = qp.block(s, hq * hd, hkv * hd, hd, 2), qp._CHUNK
        for block, chunk in [shipped] + [
                (int(n), shipped[1]) for n in args.blocks.split(",")
                if n and int(n) != shipped[0]] + [
                (shipped[0], int(n)) for n in args.chunks.split(",")
                if n and int(n) != shipped[1]]:
            kw = dict(head_dim=hd, half=half, eps=1e-6, block=block)
            qp._CHUNK = chunk           # read as a call is traced
            jax.clear_caches()

            @jax.jit
            def tab():
                return qp.tables(*angles(qp.lanes(hd, half)), hd, half)

            @jax.jit
            def fwd(q, k, tab, scales):
                return qp.forward(q, k, tab, scales, **kw)

            @jax.jit
            def bwd(q, k, tab, scales, gq, gk):
                dq, dk, sums = qp.backward(
                    q if scales else None, k if scales else None, tab,
                    scales, gq, gk, **kw)
                return dq, dk, tuple(t.reshape(-1, hd).sum(0) for t in sums)

            t = tab()
            got = fwd(q, k, t, scales)
            got_d = bwd(q, k, t, scales, gq, gk)

            def worst(got, want):
                return max(float(jnp.max(jnp.abs(
                    g.reshape(w.shape).astype(jnp.float32)
                    - w.astype(jnp.float32)))) for g, w in zip(got, want))

            fwd_s = timed(fwd, q, k, t, scales)
            bwd_s = timed(bwd, q, k, t, scales, gq, gk)
            say(site=site, form="kernel", block=block, chunk=chunk,
                tables_ms=timed(tab) * 1e3, fwd_ms=fwd_s * 1e3,
                bwd_ms=bwd_s * 1e3, fwd_gb_s=moved / fwd_s / 1e9,
                bwd_gb_s=moved * (1.5 if normed else 1.0) / bwd_s / 1e9,
                fwd_max_abs=worst(got, want),
                bwd_max_abs=worst(got_d[:2], want_d[:2]),
                scales_max_rel=max(
                    [float(jnp.max(jnp.abs(g - w) / (jnp.abs(w) + 1e-3)))
                     for g, w in zip(got_d[2], want_d[2])] or [0.0]))
        qp._CHUNK = shipped[1]
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(lines, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
