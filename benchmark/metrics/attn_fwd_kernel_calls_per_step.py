"""How often the forward flash-attention kernel (``hvd_flash_fwd``) runs
in a whole step: layers times (1 + the number of times remat runs the
forward again).  The count that shows recompute."""

from benchmark.trace import reduce, scopes


def read(layers, metric):
    def calls(d):
        n = d["kernel_events"].get("hvd_flash_fwd")
        return n / d["n_programs"] if n and d["n_programs"] else None
    return reduce.over_devices(scopes.classified(layers), metric["better"],
                               calls)
