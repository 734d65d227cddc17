"""Bare timings, on a TPU, of the held experts' token sums at the benchmark's
five share-holding cells' site shapes: the scatter-added loop that PR 40
shipped (512 live rows a trip into a (tokens, d) fp32 carry in HBM) against
the sum that never scatters (``parallel/moe.py`` ``_token_sums``: the live
rows gathered in token order, added in VMEM by the Pallas kernel
``hvd_moe_token_sum``), over a few tilings of the kernel.

    python examples/token_sum_timing.py [site,site] [--out FILE]

Twenty calls are queued and waited for once (one call and one wait carry
~0.2-0.5 ms of host floor on a one-chip machine).  A line a timing; ``--out``
also writes them as JSON.  PERF.md section 6, PR 47, holds the readings that
chose the shipped tiling.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import json
import time

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.ops import token_sum as ts
from horovod_tpu.parallel import moe

SITES = {  # tokens a step, row width, experts held, experts routed, top_k
    "lfm2": (32768, 2048, 8, 64, 4),
    "smallthinker": (16384, 2560, 16, 64, 6),
    "laguna": (24576, 3072, 8, 256, 10),
    "sdar": (8192, 2048, 16, 128, 8),
    "nemotron": (16384, 1024, 8, 512, 22)}
TILINGS = ((128, 128), (256, 128), (128, 256), (256, 256), (512, 512))


def scattered_sums(z, scale, token_of_row, n_live, tokens, chunk=512):
    """``_token_sums`` as PRs 40-46 had it."""
    rows, d = z.shape

    def trip(c, out):
        at = jnp.minimum(c * chunk, rows - chunk)
        zs = lax.dynamic_slice(z, (at, 0), (chunk, d)).astype(jnp.float32)
        if scale is not None:
            zs = zs * lax.dynamic_slice(scale, (at,), (chunk,))[:, None]
        row = at + jnp.arange(chunk)
        token = jnp.where(
            (row >= c * chunk) & (row < n_live),
            lax.dynamic_slice(token_of_row, (at,), (chunk,)), tokens)
        return out.at[token].add(zs, mode="drop")

    return lax.fori_loop(0, (n_live + chunk - 1) // chunk, trip,
                         jnp.zeros((tokens, d), jnp.float32))


def timed(fn, *args, queue=20, repeats=5):
    """Milliseconds a call: the least and the median of ``repeats`` queues."""
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeats):
        start = time.perf_counter()
        for _ in range(queue):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - start) / queue * 1e3)
    return min(times), sorted(times)[len(times) // 2]


def time_site(name):
    t, d, held, experts, top_k = SITES[name]
    rows = moe.held_row_buffer(t, top_k, held, experts, 4.0)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(len(name)), 3)
    chosen = jax.random.uniform(k1, (t, held)) < top_k / experts

    @jax.jit
    def routing(chosen):
        key = jnp.where(chosen, jnp.arange(held, dtype=jnp.int32), held)
        pair_of_row = jnp.argsort(key.reshape(t * held), stable=True)[:rows]
        return pair_of_row, jnp.minimum(jnp.sum(chosen), rows)

    pair_of_row, n_live = routing(chosen)
    z = jax.random.normal(k2, (rows, d), jnp.bfloat16)
    weights = jax.random.uniform(k3, (t, held), jnp.float32, 0.1, 1.0)
    site = {"tokens": t, "d": d, "rows": rows, "n_live": int(n_live)}
    def order_fn():    # traced under the tiling of the moment
        return jax.jit(lambda p, n: moe._token_order(p, n, held, t))

    site["order_and_walk_ms"] = timed(order_fn(), pair_of_row, n_live)
    want = {}
    for which in ("combine", "dispatch_bwd"):
        fn = jax.jit(lambda z, w, p, n, which=which: scattered_sums(
            z, w.reshape(-1)[p] if which == "combine" else None, p // held,
            n, t))
        site[f"scatter_{which}_ms"] = timed(fn, z, weights, pair_of_row,
                                            n_live)
        want[which] = fn(z, weights, pair_of_row, n_live)
    for tile, chunk in TILINGS:
        ts._TILE, ts._CHUNK = tile, chunk
        order = order_fn()(pair_of_row, n_live)
        for which in ("combine", "dispatch_bwd"):
            fn = jax.jit(lambda z, w, o, n, which=which: moe._token_sums(
                z, w.reshape(-1) if which == "combine" else None, o, n, t,
                which))
            site[f"kernel_{which}_{tile}x{chunk}_ms"] = timed(
                fn, z, weights, order, n_live)
            site[f"kernel_{which}_{tile}x{chunk}_max_abs_diff"] = float(
                jnp.max(jnp.abs(fn(z, weights, order, n_live)
                                - want[which])))
    return site


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("sites", nargs="?", default=",".join(SITES))
    parser.add_argument("--out")
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit("a timing comes from a TPU; this is "
                         f"{device.platform}")
    results = {"device": device.device_kind, "sites": {}}
    for name in args.sites.split(","):
        results["sites"][name] = site = time_site(name)
        for key, value in site.items():
            print(name, key, value, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
