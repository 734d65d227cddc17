"""Device self time of the gated short-convolution blocks in a step, forward,
the blocks' recompute and backward, in milliseconds: every operation whose
``op_name`` holds the program's scope ``hvd_conv`` (the block's norm, the
in-projection to [B | C | u], the gate path, the out-projection, the
residual add)."""

from benchmark.trace import lfm2


def read(layers, metric):
    return lfm2.ms_per_step(layers, metric, lfm2.BLOCK)
