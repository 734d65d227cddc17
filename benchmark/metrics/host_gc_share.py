"""Time inside the program's ``hvd.gc.gen<n>`` spans (one around every
garbage collection: ``horovod_tpu/debug/pause.py``) over the traced interval
the ``bench.*`` spans cover, in percent.  Nothing where the trace holds no
mark of the pause sentinel's."""

from benchmark.trace import host


def read(layers, metric):
    return host.gc_share(layers)
