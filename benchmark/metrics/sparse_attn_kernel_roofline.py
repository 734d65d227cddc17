"""The least time the chip could take for the step's attention over the
chosen keys, forward + backward (the larger of FLOPs over peak FLOP/s and
bytes over peak bytes/s: the CHOSEN (query, key) pairs only, whatever the
program visits, and q, k, v, o, dO, dQ, dK, dV once, from the family's
``sparse_attention_cost`` under ``sparse_attention`` in the dict
``attention_cost()`` returns, and ``peaks.json``), over
``sparse_attn_kernel_ms_per_step``, in percent.

The time holds what the count does not: a kernel that walks every causal
tile computes the triangle's scores for the chosen pairs' result, reads the
visibility tile a step, and the backward computes the scores a second time.
So it cannot reach 100, and a low reading is the program's to mend."""

from benchmark import loader
from benchmark.trace import keye_vl


def least_seconds(layers) -> tuple[float, str] | None:
    cost = (layers["attention"] or {}).get(keye_vl.COST)
    peaks = layers["peaks"]
    if not cost or not peaks:
        return None
    return loader.least_seconds(cost, peaks)


def read(layers, metric):
    least = least_seconds(layers)
    if least is None:
        return None
    ms = keye_vl.kernels_ms_per_step(layers, {"better": "lower"})
    return None if not ms else 100.0 * least[0] / (ms / 1e3)
