"""Device self time of making the flash backward's ``delta`` operand in a
step, in milliseconds: sum(dO * O) over a head in fp32, its transpose to
(B, H, S) and its broadcast to eight sublanes, which XLA runs in front of
the backward kernel: every operation whose ``op_name`` holds the program's
scope ``hvd_attn_delta``.  Nothing for a program without that scope."""

from benchmark.trace import parts


def read(layers, metric):
    return parts.ms_per_step(layers, metric, names=("hvd_attn_delta",))
