"""Lowering and compiling the step, or loading it from the compile cache
(benchmark span, host clock)."""


def read(layers, metric):
    return layers["spans"].get("setup_compile")
