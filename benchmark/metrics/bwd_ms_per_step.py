"""Device self time of the backward pass in a step, in milliseconds: every
operation, of every kind, whose ``op_name`` holds ``transpose``, which
includes the forward recomputed under remat."""

from benchmark.trace import scopes


def read(layers, metric):
    return scopes.ms_per_step(
        layers, metric, lambda d: d["phase_ns"].get("bwd"))
