"""Device self time of the forward pass in a step, in milliseconds: every
operation, of every kind, whose ``op_name`` holds ``jvp`` and no
``transpose``."""

from benchmark.trace import scopes


def read(layers, metric):
    return scopes.ms_per_step(
        layers, metric, lambda d: d["phase_ns"].get("fwd"))
