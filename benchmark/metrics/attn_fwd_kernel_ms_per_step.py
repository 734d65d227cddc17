"""Device self time of the forward flash-attention kernel (the Pallas call
the program names ``hvd_flash_fwd``) in a step, in milliseconds: the first
forward and every recompute of it under remat."""

from benchmark.trace import scopes


def read(layers, metric):
    return scopes.ms_per_step(
        layers, metric, lambda d: d["kernel_ns"].get("hvd_flash_fwd"))
