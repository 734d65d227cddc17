"""Device self time of the two backward flash-attention kernels (the Pallas
calls the program names ``hvd_flash_bwd_dq`` and ``hvd_flash_bwd_dkv``) in a
step, in milliseconds."""

from benchmark.trace import scopes

BACKWARD = ("hvd_flash_bwd_dq", "hvd_flash_bwd_dkv")


def read(layers, metric):
    def ns(d):
        found = [d["kernel_ns"][k] for k in BACKWARD if k in d["kernel_ns"]]
        return sum(found) if found else None
    return scopes.ms_per_step(layers, metric, ns)
