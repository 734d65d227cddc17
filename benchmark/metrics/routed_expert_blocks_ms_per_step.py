"""Device self time of the expert blocks in a step, forward, the blocks'
recompute and backward, in milliseconds, whether or not the model has a
dense block beside them: every operation whose ``op_name`` holds the
program's scope ``hvd_mlp`` and not ``hvd_mlp_dense`` (the blocks' norms,
routers, the rows gathered and summed back, gates) plus the grouped-matmul
kernels XLA makes of ``lax.ragged_dot``, taken by name as ``trace/moe.py``
takes them.  ``expert_block_ms_per_step`` is the same time for a program
that also carries a dense block's name, and gives nothing without it."""

from benchmark.trace import laguna


def read(layers, metric):
    return laguna.names_ms_per_step(layers, metric, (laguna.EXPERT_BLOCKS,))
