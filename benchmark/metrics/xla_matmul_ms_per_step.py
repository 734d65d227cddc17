"""Device self time of XLA's own matmuls in a step, in milliseconds: every
operation that is neither a Pallas kernel nor a collective and whose
``hlo_category`` in the trace is ``convolution fusion`` or ``convolution``
(a dot is a convolution to the TPU compiler).  The experts' grouped matmuls
are Mosaic kernels and stay with ``moe_expert_matmul_ms_per_step``.
Nothing for a trace that carries no category."""

from benchmark.trace import parts


def read(layers, metric):
    return parts.ms_per_step(layers, metric, kinds=("compute",),
                             categories=parts.MATMUL)
