"""Device self time of the rotary positions and the per-head output gate of
the attention blocks in a step, forward, the blocks' recompute and backward,
in milliseconds: every operation whose ``op_name`` holds the program's scope
``hvd_attn_rope`` (q and k rotated, before K / V are repeated to the query
heads) or ``hvd_attn_gate`` (the gate's projection, sigmoid and product)."""

from benchmark.trace import laguna


def read(layers, metric):
    return laguna.names_ms_per_step(layers, metric,
                                    ("hvd_attn_rope", "hvd_attn_gate"))
