"""The least time the chip could take for the step's gate paths of the
convolution blocks, forward, recompute and backward (the larger of FLOPs
over peak FLOP/s and bytes over peak bytes/s: each array of the path read or
written once in the compute type, ``trace/lfm2.gate_path_cost`` through the
dict ``attention_cost()`` returns, and ``peaks.json``), over
``short_conv_gate_ms_per_step``, in percent.  Bytes-bound.

The count includes the recompute, because a checkpoint a block cannot avoid
it; it does not include what XLA adds: operands converted or padded in
passes of their own, a cotangent written in fp32, the filter's gradient
reduced over the sequence apart from the pass that made its terms."""

from benchmark import loader
from benchmark.trace import lfm2


def least_seconds(layers) -> tuple[float, str] | None:
    cost = (layers["attention"] or {}).get(lfm2.GATE_COST)
    peaks = layers["peaks"]
    if not cost or not peaks:
        return None
    return loader.least_seconds(cost, peaks)


def read(layers, metric):
    least = least_seconds(layers)
    if least is None:
        return None
    ms = lfm2.ms_per_step(layers, {"better": "lower"}, lfm2.GATE)
    return None if not ms else 100.0 * least[0] / (ms / 1e3)
