"""Device self time of the Pallas kernels (``tpu_custom_call``; in these
programs the three flash-attention kernels and nothing else) in a step, in
milliseconds: a time, not a share, so that it moves only when the kernels
do."""

from benchmark.trace.reduce import over_devices, per_step_ms


def read(layers, metric):
    return over_devices(layers["trace"], metric["better"],
                        lambda d: per_step_ms(d, d["self_ns"]["kernel"]))
