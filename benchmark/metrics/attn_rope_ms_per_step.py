"""Device self time of the rotary positions of the attention blocks in a
step, forward, the blocks' recompute and backward, in milliseconds: every
operation whose ``op_name`` holds the program's scope ``hvd_attn_rope`` (q
and k rotated, before K / V are repeated to the query heads), alone.
``attn_rope_gate_ms_per_step`` and ``attn_qknorm_rope_ms_per_step`` read it
together with an output gate's or a head norm's name and give nothing to a
program without that one."""

from benchmark.trace import laguna


def read(layers, metric):
    return laguna.names_ms_per_step(layers, metric, ("hvd_attn_rope",))
