"""Device self time of the ranking alone inside the indexer (``hvd_attn_index``
holds it too): the order-preserving keys, the 32 counts that build the 2,048th
largest of a row, the tie's cutoff and the visibility tile, in milliseconds:
every operation whose ``op_name`` holds the program's scope
``hvd_attn_select``. Nothing for a program without that scope."""

from benchmark.trace import keye_vl


def read(layers, metric):
    return keye_vl.name_ms_per_step(layers, metric, "hvd_attn_select")
