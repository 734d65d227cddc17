"""The longest part of an idle gap of any device, in the traced window of
whole steps, that neither an ``hvd.gc.*`` span nor a late ``hvd.tick`` mark
covers, in milliseconds: what the device waited for while the interpreter
was running, so the cause is beneath the main thread's call and not a stop
of the process.  Nothing where there is no device plane or no mark of the
pause sentinel's."""

from benchmark.trace import host


def read(layers, metric):
    return host.idle_unexplained_ms_max(layers)
