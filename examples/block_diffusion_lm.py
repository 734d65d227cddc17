"""Block-diffusion language-model training (BD3-LMs / SDAR) on a tiny model.

A sequence of L tokens goes through the stack as 2L positions: a noised copy
(some ids replaced by the mask token, block by block) and then the clean
copy, under a mask in which a noised block sees itself and the clean blocks
before it.  The loss is the cross-entropy of the noised positions, weighted
by masked / t.  ``TransformerConfig(diffusion_block=...)`` is the whole
switch; the noise is data, made outside the step by ``synthetic_batch``
(``noised_batch``).

Virtual 8-chip:   XLA_FLAGS=--xla_force_host_platform_device_count=8 \
                  JAX_PLATFORMS=cpu python examples/block_diffusion_lm.py
On TPU the attention runs the ``hvd_flash_*_bd`` kernels, whose grids walk
the live tiles of the mask only.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse

import jax

if os.environ.get("JAX_PLATFORMS"):
    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
import jax.numpy as jnp
import optax

from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel.mesh import create_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--block", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--dp", type=int, default=2)
    args = ap.parse_args()

    cfg = tfm.TransformerConfig(
        vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2, attn_head_dim=16,
        d_ff=32, n_layers=4, seq_len=args.seq, layer_pattern="*E",
        n_experts=8, top_k=2, dropless=True, gated_experts=True,
        router_renormalise=True, tied_head=False, learned_positions=False,
        rope_theta=1e6, head_qk_norm=True, dtype=jnp.float32,
        diffusion_block=args.block)
    par = tfm.ParallelConfig(dp=args.dp)
    mesh = create_mesh({"dp": args.dp, "pp": 1, "mp": 1},
                       devices=jax.devices()[:args.dp])
    optimizer = optax.adamw(3e-3)
    step, shard = tfm.make_train_step(cfg, par, mesh, optimizer)
    params = shard(tfm.init_params(jax.random.PRNGKey(0), cfg, par))
    opt_state = optimizer.init(params)

    # One fixed set of clean sequences, noised afresh every step.
    ids = tfm.synthetic_batch(jax.random.PRNGKey(1), cfg, args.batch)[1]
    print(f"{args.batch} sequences of {args.seq} tokens = {2 * args.seq} "
          f"positions, blocks of {args.block}, dp {args.dp}")
    for i in range(args.steps):
        tokens, labels, weights = tfm.noised_batch(
            jax.random.PRNGKey(100 + i), ids, args.block, cfg.vocab_size - 1)
        params, opt_state, loss = step(params, opt_state, tokens, labels,
                                       weights)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:3d} loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
