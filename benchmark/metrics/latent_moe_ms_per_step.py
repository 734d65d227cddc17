"""Device self time of the latent-space expert blocks in a step, forward,
recompute and backward, in milliseconds: every operation whose ``op_name``
holds the program's scope ``hvd_mlp`` (the block's norm, router, latent
projections, shared expert, gathers) and the grouped-matmul kernels XLA
makes of ``lax.ragged_dot``, whose ``op_name`` it overwrites: the same sum
as ``moe_ms_per_step``, through ``trace/moe.py``."""

from benchmark.trace import moe


def read(layers, metric):
    return moe.ms_per_step(layers, metric, lambda d: d["block_ns"])
