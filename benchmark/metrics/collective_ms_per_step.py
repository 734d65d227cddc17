"""Device time in collective operations on the device's own line of
operations (synchronous ones whole, asynchronous ones for their ``-start``
and ``-done``) in a step, in milliseconds.  What asynchronous collectives
spend in flight is not in it: the profiler writes that for one device only,
and the traced run prints it."""

from benchmark.trace.reduce import over_devices, per_step_ms


def read(layers, metric):
    return over_devices(layers["trace"], metric["better"],
                        lambda d: per_step_ms(d, d["collective_ns"]))
