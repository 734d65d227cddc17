"""Device self time of the three windowed flash kernels in a step, in
milliseconds: the Pallas kernels named ``hvd_flash_fwd_win``,
``hvd_flash_bwd_dq_win`` and ``hvd_flash_bwd_dkv_win``, the sliding-window
layers' calls.  ``attn_kernel_ms_per_step`` holds them too (it matches the
kernels by prefix); that less this is the full layers' kernels."""

from benchmark.trace import laguna


def read(layers, metric):
    return laguna.ms_per_step(layers, metric,
                              lambda d: sum(d["kernel_ns"].values()))
