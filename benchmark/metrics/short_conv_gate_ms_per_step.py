"""Device self time of the convolution blocks' gate path in a step, forward,
the blocks' recompute and backward, in milliseconds: every operation whose
``op_name`` holds the program's scope ``hvd_conv_gate`` (``B * u``, the
causal depthwise convolution over it, ``C *`` its result: everything between
the block's two matmuls)."""

from benchmark.trace import lfm2


def read(layers, metric):
    return lfm2.ms_per_step(layers, metric, lfm2.GATE)
