"""Device self time of the experts' part of the MoE block in a step, in
milliseconds: the grouped-matmul kernels (``lax.ragged_dot``; three
forward, three in the recompute, six backward a layer) and the operations
under the program's scope ``hvd_moe_experts`` (the weights' casts to the
compute type, the gate's activation and product)."""

from benchmark.trace import moe


def read(layers, metric):
    return moe.ms_per_step(
        layers, metric, lambda d: d["part_ns"].get("hvd_moe_experts"))
