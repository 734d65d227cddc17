"""Device self time of the state-space scan in a step, forward, recompute
and backward, in milliseconds: every operation whose ``op_name`` holds the
program's scope ``hvd_ssm_scan`` (softplus of dt, the chunked algorithm's
four products, its decays and the carried state; ``ops/ssd.ssd_scan``)."""

from benchmark.trace import ssm


def read(layers, metric):
    return ssm.ms_per_step(layers, metric, "hvd_ssm_scan")
