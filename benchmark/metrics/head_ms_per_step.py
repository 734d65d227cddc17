"""Device self time of the vocabulary head, forward and backward (every
operation whose ``op_name`` holds the program's scope ``hvd_head``: final
norm, logits, softmax, loss and their transposes) in a step, in
milliseconds."""

from benchmark.trace import scopes


def read(layers, metric):
    return scopes.ms_per_step(
        layers, metric, lambda d: d["block_ns"].get("hvd_head"))
