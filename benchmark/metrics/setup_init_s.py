"""Process start to the mesh less ``setup_backend_s``: imports and
``create_mesh`` (benchmark span, host clock)."""


def read(layers, metric):
    return layers["spans"].get("setup_init")
