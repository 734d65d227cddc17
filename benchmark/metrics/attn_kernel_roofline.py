"""The least time the chip could take for the step's forward + backward
attention (the larger of FLOPs over peak FLOP/s and bytes over peak
bytes/s, from the family's shape arithmetic and ``peaks.json``) over the
flash kernels' time per step, in percent."""

from benchmark.trace.reduce import over_devices


def least_seconds(layers) -> tuple[float, str]:
    cost, peaks = layers["attention"], layers["peaks"]
    by_flops = cost["flops"] / peaks["flops_per_s_bf16"]
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (max(by_flops, by_bytes),
            "flops" if by_flops >= by_bytes else "bytes")


def read(layers, metric):
    if not layers["peaks"]:
        return None
    least, _bound = least_seconds(layers)

    def share(d):
        if not d["n_programs"] or not d["self_ns"]["kernel"]:
            return None
        return 100.0 * least / (d["self_ns"]["kernel"] / 1e9
                                / d["n_programs"])

    return over_devices(layers["trace"], metric["better"], share)
