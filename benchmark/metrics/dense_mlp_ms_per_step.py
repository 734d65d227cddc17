"""Device self time of the gated dense MLP block in a step, forward, the
block's recompute and backward, in milliseconds: every operation whose
``op_name`` holds the program's scope ``hvd_mlp_dense`` (three matmuls at
the dense width, silu and the gated product; the block's norm and residual
are ``hvd_mlp``'s)."""

from benchmark.trace import laguna


def read(layers, metric):
    return laguna.names_ms_per_step(layers, metric, (laguna.DENSE,))
