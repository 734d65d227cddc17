"""The check against the plain reference that every run makes, its compiles
included: loss and gradients, or the loss alone where the cell's traffic
file keeps the gradients for the traced run (benchmark span, host clock)."""


def read(layers, metric):
    return layers["spans"].get("setup_check")
