"""Device self time of the optimizer in a step, in milliseconds: every
operation outside ``jvp`` and ``transpose`` whose ``op_name`` holds the
program's scope ``hvd_optimizer`` (the optax update and the parameter
add)."""

from benchmark.trace import scopes


def read(layers, metric):
    return scopes.ms_per_step(
        layers, metric, lambda d: d["phase_ns"].get("optimizer"))
