"""``hvd.init()`` and the first ``jax.devices()``: whichever touches JAX's
backend first starts the TPU runtime, which is nearly all of this span and
nothing a program can change (benchmark span, host clock)."""


def read(layers, metric):
    return layers["spans"].get("setup_backend")
