"""Device self time of the per-head QK-norm and the rotary positions of the
attention blocks in a step, forward, the blocks' recompute and backward, in
milliseconds: every operation whose ``op_name`` holds the program's scope
``hvd_attn_qknorm`` (RMSNorm over each head of q and of k) or
``hvd_attn_rope`` (q and k rotated at ``p mod L``, before K / V are repeated
to the query heads).  Both run over the doubled sequence."""

from benchmark.trace import sdar


def read(layers, metric):
    return sdar.names_ms_per_step(layers, metric, sdar.NAMES)
