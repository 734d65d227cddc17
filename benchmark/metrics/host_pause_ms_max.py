"""The widest spacing of consecutive ``hvd.tick`` marks in the traced
interval the ``bench.*`` spans cover, less the heartbeat's period, in
milliseconds; 0 where none is late.  The marks come from a thread that needs
the interpreter lock to run (``horovod_tpu/debug/pause.py``), so this is the
longest stretch in which no Python thread of the process ran.  Nothing where
the trace holds no mark of the pause sentinel's."""

from benchmark.trace import host


def read(layers, metric):
    return host.pause_ms_max(layers)
