"""Device self time of the forward recomputed under remat in a step, in
milliseconds: every operation, of every kind (as ``bwd_ms_per_step`` counts
every kind, and keeps counting these), whose ``op_name`` holds JAX's
``rematted_computation``: what a ``jax.checkpoint`` runs again in the
backward pass.  Nothing for a program that recomputes nothing."""

from benchmark.trace import parts


def read(layers, metric):
    return parts.ms_per_step(layers, metric, passes=("recompute",))
