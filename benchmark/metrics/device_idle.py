"""1 - union of the intervals in which an operation that does work ran
(not a loop that only contains others) over the traced window of whole
steps, in percent."""

from benchmark.trace.reduce import over_devices


def idle(d):
    return 100.0 * (1.0 - d["busy_ns"] / d["window_ns"])


def read(layers, metric):
    return over_devices(layers["trace"], metric["better"], idle)
