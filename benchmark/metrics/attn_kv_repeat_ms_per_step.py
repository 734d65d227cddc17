"""Device self time of repeating each K / V head across its query heads in
front of the flash kernels in a step, forward, recompute and the backward's
sum over a group, in milliseconds: every operation whose ``op_name`` holds
the program's scope ``hvd_attn_kv_repeat``.  Nothing for a program without
that scope (as many K / V heads as query heads; this repository before
it)."""

from benchmark.trace import parts


def read(layers, metric):
    return parts.ms_per_step(layers, metric, names=("hvd_attn_kv_repeat",))
