"""Backend compilations (cache loads included) between window open and
close, from ``jax.monitoring``.  Must read 0."""


def read(layers, metric):
    return float(layers["compiles_in_window"])
