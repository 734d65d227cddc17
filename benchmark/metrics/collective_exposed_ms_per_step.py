"""The part of ``collective_ms_per_step`` during which no compute
operation or kernel runs on the same device, in milliseconds."""

from benchmark.trace.reduce import over_devices, per_step_ms


def read(layers, metric):
    return over_devices(layers["trace"], metric["better"],
                        lambda d: per_step_ms(d, d["collective_exposed_ns"]))
