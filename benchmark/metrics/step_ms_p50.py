"""Median completion-to-completion time of the steps in the window."""

import statistics


def read(layers, metric):
    return statistics.median(layers["step_ms"]) if layers["step_ms"] else None
