"""Device self time in operations that are neither a Pallas kernel nor a
collective (XLA's fusions, copies and the loops around them) in a step, in
milliseconds."""

from benchmark.trace.reduce import over_devices, per_step_ms


def read(layers, metric):
    return over_devices(
        layers["trace"], metric["better"],
        lambda d: per_step_ms(
            d, d["self_ns"]["compute"] + d["self_ns"]["control"]))
