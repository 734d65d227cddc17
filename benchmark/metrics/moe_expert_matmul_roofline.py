"""The least time the chip could take for the step's grouped expert matmuls,
forward + backward (the larger of FLOPs over peak FLOP/s and bytes over peak
bytes/s, from the family's shape arithmetic under ``moe_expert_matmul`` in
the dict ``attention_cost()`` returns, and ``peaks.json``), over
``moe_expert_matmul_ms_per_step``, in percent.  The recompute's three matmuls
are in the time and not in the count, so it cannot reach 100."""

from benchmark.trace import moe


def least_seconds(layers) -> tuple[float, str] | None:
    cost = (layers["attention"] or {}).get("moe_expert_matmul")
    peaks = layers["peaks"]
    if not cost or not peaks:
        return None
    by_flops = cost["flops"] / peaks["flops_per_s_bf16"]
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (max(by_flops, by_bytes),
            "flops" if by_flops >= by_bytes else "bytes")


def read(layers, metric):
    least = least_seconds(layers)
    if least is None:
        return None
    ms = moe.ms_per_step(
        layers, {"better": "lower"},
        lambda d: d["part_ns"].get("hvd_moe_experts"))
    return None if not ms else 100.0 * least[0] / (ms / 1e3)
