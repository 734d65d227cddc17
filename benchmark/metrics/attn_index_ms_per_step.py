"""Device self time of the learned indexer of the attention blocks in a step (its
three projections, its key head's LayerNorm and rotation, its scores a tile of
queries at a time and the exact choice of the keys), forward only: it is held
out of the gradient and its result is saved for the backward, in milliseconds:
every operation whose ``op_name`` holds the program's scope
``hvd_attn_index``. Nothing for a program without that scope."""

from benchmark.trace import keye_vl


def read(layers, metric):
    return keye_vl.name_ms_per_step(layers, metric, "hvd_attn_index")
