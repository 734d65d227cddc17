"""The least time the chip could take for the step's state-space scans,
forward + backward (the larger of FLOPs over peak FLOP/s and bytes over peak
bytes/s: the chunked algorithm's four products and its least traffic, from
the family's shape arithmetic under ``ssm_scan`` in the dict
``attention_cost()`` returns, and ``peaks.json``), over
``ssm_scan_ms_per_step``, in percent.

The time holds what the count does not: every "M" block runs under remat, so
its forward scan runs twice (a third of the counted FLOPs again), and the
program writes the (chunk x chunk) decay and score tiles and the chunks'
states to HBM where the count assumes a fused kernel keeps them on chip.  So
it cannot reach 100."""

from benchmark import loader
from benchmark.trace import ssm


def least_seconds(layers) -> tuple[float, str] | None:
    cost = (layers["attention"] or {}).get("ssm_scan")
    peaks = layers["peaks"]
    if not cost or not peaks:
        return None
    return loader.least_seconds(cost, peaks)


def read(layers, metric):
    least = least_seconds(layers)
    if least is None:
        return None
    ms = ssm.ms_per_step(layers, {"better": "lower"}, "hvd_ssm_scan")
    return None if not ms else 100.0 * least[0] / (ms / 1e3)
