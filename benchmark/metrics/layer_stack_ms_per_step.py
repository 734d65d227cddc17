"""Device self time of what the scan over the layer stack does itself in a
step, in milliseconds: the saved stacks written and read a layer at a time,
the per-layer weight slices, the gradient stacks: every operation that is
neither a Pallas kernel nor a collective, whose ``op_name`` holds the
program's scope ``hvd_layers`` and no other ``hvd_*`` name (a block's
operations carry the block's too), and whose ``hlo_category`` is not a
matmul's: a stack written behind a matmul in one fusion is that matmul.
Nothing for a program without that scope or a trace without categories."""

from benchmark.trace import parts


def read(layers, metric):
    return parts.ms_per_step(
        layers, metric, names=("hvd_layers",), without_names=True,
        kinds=("compute",), without_categories=parts.MATMUL)
