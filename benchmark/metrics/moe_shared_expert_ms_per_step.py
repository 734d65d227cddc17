"""Device self time of the shared expert in a step, forward, recompute and
backward, in milliseconds: every operation whose ``op_name`` holds the
program's scope ``hvd_moe_shared`` (the two matmuls at the shared width on
the hidden state and the squared ReLU between them)."""

from benchmark.trace import ssm


def read(layers, metric):
    return ssm.ms_per_step(layers, metric, "hvd_moe_shared")
