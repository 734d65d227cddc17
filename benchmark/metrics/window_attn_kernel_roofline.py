"""The least time the chip could take for the step's windowed attention,
forward + backward (the larger of FLOPs over peak FLOP/s and bytes over peak
bytes/s: the band's (query, key) pairs only, the forward counted once, from
the family's shape arithmetic under ``window_attention`` in the dict
``attention_cost()`` returns, and ``peaks.json``), over
``window_attn_kernel_ms_per_step``, in percent.

The time holds what the count does not: under tiles as wide as the window
half the score elements a kernel computes lie outside the band, and the
backward computes the scores twice (dQ and dK/dV).  So it cannot reach
100."""

from benchmark import loader
from benchmark.trace import laguna


def least_seconds(layers) -> tuple[float, str] | None:
    cost = (layers["attention"] or {}).get("window_attention")
    peaks = layers["peaks"]
    if not cost or not peaks:
        return None
    return loader.least_seconds(cost, peaks)


def read(layers, metric):
    least = least_seconds(layers)
    if least is None:
        return None
    ms = laguna.ms_per_step(layers, {"better": "lower"},
                            lambda d: sum(d["kernel_ns"].values()))
    return None if not ms else 100.0 * least[0] / (ms / 1e3)
