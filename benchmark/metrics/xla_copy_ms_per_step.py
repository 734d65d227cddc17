"""Device self time of XLA's layout copies in a step, in milliseconds: every
operation that is neither a Pallas kernel nor a collective and whose
``hlo_category`` in the trace is ``data formatting`` (copies, transposes
and the fusions that only move data).  Nothing for a trace that carries no
category."""

from benchmark.trace import parts


def read(layers, metric):
    return parts.ms_per_step(layers, metric, kinds=("compute",),
                             categories=parts.COPY)
