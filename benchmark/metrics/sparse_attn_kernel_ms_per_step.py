"""Device self time of the three flash kernels of a selected call in a
step, in milliseconds: the Pallas kernels named ``hvd_flash_fwd_sel``,
``hvd_flash_bwd_dq_sel`` and ``hvd_flash_bwd_dkv_sel``, by their whole name
(attention over the keys a learned indexer chose, the visibility array an
operand).  ``attn_kernel_ms_per_step`` holds them too (it matches the
kernels by prefix)."""

from benchmark.trace import keye_vl


def read(layers, metric):
    return keye_vl.kernels_ms_per_step(layers, metric)
