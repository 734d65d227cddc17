"""Device self time of the MoE block's routing and data movement in a step,
in milliseconds: the operations under the program's scopes ``hvd_moe_route``
(router matmul, softmax, top-k, the two sorts, the group sizes) and
``hvd_moe_dispatch`` (rows gathered into expert order and put back, the
weighted sum over a token's experts), forward, recompute and backward."""

from benchmark.trace import moe


def read(layers, metric):
    return moe.ms_per_step(
        layers, metric,
        lambda d: (d["part_ns"].get("hvd_moe_route", 0)
                   + d["part_ns"].get("hvd_moe_dispatch", 0)))
