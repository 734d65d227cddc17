"""Device self time of the MoE block in a step, forward, recompute and
backward, in milliseconds: every operation whose ``op_name`` holds the
program's scope ``hvd_mlp`` (final-norm of the block, router, gathers,
activation, casts) and the grouped-matmul kernels XLA makes of
``lax.ragged_dot``, whose ``op_name`` it overwrites (``trace/moe.py``)."""

from benchmark.trace import moe


def read(layers, metric):
    return moe.ms_per_step(layers, metric, lambda d: d["block_ns"])
