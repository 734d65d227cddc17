"""Device self time of the expert blocks of a model that also has a dense
MLP block, in milliseconds a step: every operation whose ``op_name`` holds
``hvd_mlp`` and not ``hvd_mlp_dense`` (the blocks' norms, routers, shared
experts, gathers) plus the grouped-matmul kernels XLA makes of
``lax.ragged_dot``, taken by name as ``trace/moe.py`` takes them.  A program
without a dense block's name has no such split, and no value here."""

from benchmark.trace import laguna


def read(layers, metric):
    return laguna.names_ms_per_step(layers, metric, (laguna.EXPERT_BLOCKS,),
                                    needs=(laguna.DENSE,))
