"""Device self time of the indexer's loss in a step, forward and backward: the
pass over the main attention's score tiles that takes the heads' mean
probability from the saved lse, the indexer's scores again, the divergence,
and the scores' pullback to the indexer's operands, in milliseconds: every
operation whose ``op_name`` holds the program's scope ``hvd_attn_index_loss``.
Nothing for a program without that scope."""

from benchmark.trace import keye_vl


def read(layers, metric):
    return keye_vl.name_ms_per_step(layers, metric, "hvd_attn_index_loss")
