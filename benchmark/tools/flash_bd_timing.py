#!/usr/bin/env python3
"""Bare timing of the block-diffusion flash kernels on the chip.

    python benchmark/tools/flash_bd_timing.py [--batch 1] [--half 4096]
        [--heads 32] [--head-dim 128] [--block 4] [--reps 10]

Times, at (batch, 2 x half, heads, head_dim) bf16, the forward kernel alone
and the forward + backward of ``flash_attention(..., diffusion_block=)``,
beside the causal call of the same length (which walks every tile of its
square grid and computes the 2 n^2 + n tiles of its triangle where the
block-diffusion call has n^2 + 2n), and prints one JSON line: milliseconds a
call, the live and skipped tile steps the block-diffusion grids were built
with (the program's own counter ``hvd_flash_tiles_built_total``), and
picoseconds a live score element.  Through ``flash_attention`` alone, as a
model calls it.  No model, no optimizer: the kernels only.  TPUs only.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def timed(fn, args, reps: int) -> float:
    """Median milliseconds a call of the jitted ``fn`` over ``reps``."""
    import jax
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(1e3 * (time.perf_counter() - t))
    return sorted(out)[len(out) // 2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--half", type=int, default=4096)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--block", type=int, default=4)
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from horovod_tpu.metrics import registry
    from horovod_tpu.ops import flash_attention as fa
    if jax.devices()[0].platform != "tpu":
        print("flash_bd_timing: TPUs only", file=sys.stderr)
        return 1
    shape = (args.batch, 2 * args.half, args.heads, args.head_dim)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, g = (jax.random.normal(kk, shape, jnp.bfloat16) for kk in keys)

    def calls(**mask):
        def fwd(q, k, v):
            return fa.flash_attention(q, k, v, **mask)

        def both(q, k, v, g):
            out, vjp = jax.vjp(fwd, q, k, v)
            return out, vjp(g)
        return jax.jit(fwd), jax.jit(both)

    line = {"shape": list(shape), "device": jax.devices()[0].device_kind}
    pairs = {"block_diffusion": args.half ** 2 + args.half * args.block,
             "causal": (2 * args.half) ** 2 / 2 + args.half}
    for name, mask in (("block_diffusion", {"diffusion_block": args.block}),
                       ("causal", {})):
        fwd, both = calls(**mask)
        fwd_ms = timed(fwd, (q, k, v), args.reps)
        both_ms = timed(both, (q, k, v, g), args.reps)
        elements = args.batch * args.heads * pairs[name]
        line[name] = {
            "forward_ms": fwd_ms, "forward_backward_ms": both_ms,
            "backward_ms": both_ms - fwd_ms,
            "forward_ps_a_live_pair": 1e9 * fwd_ms / elements,
            "backward_ps_a_live_pair": 1e9 * (both_ms - fwd_ms) / elements}
    # Counted as each call is traced: the forward kernel under both jitted
    # functions, the backward pass under one.
    line["block_diffusion"]["tile_steps_a_head"] = {
        f"{kernel}:{state}": registry().counter(
            "hvd_flash_tiles_built_total", kernel=kernel,
            state=state).value / (traced * args.batch * args.heads)
        for kernel, traced in (("hvd_flash_fwd_bd", 2),
                               ("hvd_flash_bwd_dkv_bd", 1))
        for state in ("live", "skipped")}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
