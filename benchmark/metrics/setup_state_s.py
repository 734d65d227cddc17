"""Parameters and optimizer state made on the device, two jitted calls
(benchmark span, host clock)."""


def read(layers, metric):
    return layers["spans"].get("setup_state")
