"""How often a program calls each flash kernel, read from its jaxpr.

Shared via ``import _flash_kernels`` (as ``_loadprobe`` is) by the tests of
what the layer checkpoint keeps: tests/test_flash_attention.py,
test_transformer.py, test_bert.py.
"""

KERNELS = ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv")
ONCE = dict.fromkeys(KERNELS, 1)


def kernel_calls(jaxpr) -> dict:
    """``pallas_call`` equations by kernel ``name=`` in a jaxpr's text.  A
    scan's body is printed once, so a layer's calls count once whatever the
    depth; no kernel's name starts another's."""
    text = str(jaxpr)
    return {k: text.count(f"name={k}") for k in KERNELS}
