"""``compiled.memory_analysis().peak_memory_in_bytes`` of the step, per
device, in GB (1e9 bytes).  Not ``memory_stats``: PERF.md section 7."""


def read(layers, metric):
    return layers["step_peak_bytes"] / 1e9
