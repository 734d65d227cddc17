"""Device self time of the state-space (Mamba-2) blocks in a step, forward,
the blocks' recompute and backward, in milliseconds: every operation whose
``op_name`` holds the program's scope ``hvd_ssm`` (the block's norm, both
projections, the conv, the scan, the gated group norm)."""

from benchmark.trace import ssm


def read(layers, metric):
    return ssm.ms_per_step(layers, metric, "hvd_ssm")
