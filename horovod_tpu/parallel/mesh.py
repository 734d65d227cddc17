"""Device-mesh construction and topology helpers.

The reference's communicator axes are GLOBAL / LOCAL (per-node) / CROSS
(one-per-node) built via MPI_COMM_TYPE_SHARED splits (mpi_context.cc:140-156).
The TPU-native equivalent is a ``jax.sharding.Mesh`` whose axes map onto the
physical interconnect: intra-slice axes ride ICI, the inter-slice axis rides
DCN.  ``mesh_utils.create_device_mesh`` gives ICI-topology-aware device
ordering; ``create_hybrid_device_mesh`` keeps the DCN axis outermost.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from ..utils import logging as log

# Canonical axis names used across the framework.
DATA = "data"       # data parallel (allreduce axis)
FSDP = "fsdp"       # sharded data parallel (zero-style weight sharding)
TENSOR = "model"    # tensor/model parallel (megatron-style)
SEQUENCE = "seq"    # sequence/context parallel (ring attention / ulysses)
PIPELINE = "pipe"   # pipeline parallel
EXPERT = "expert"   # expert parallel (MoE alltoall)


def ici_device_array(dims: Sequence[int], devices,
                     allow_split_physical_axes: bool = True) -> np.ndarray:
    """``devices`` arranged to ``dims`` in ICI-topology order.  When
    ``mesh_utils`` cannot place them the order is plain ``devices`` order
    and neighbours on a mesh axis need not be ICI neighbours — logged with
    the reason, never silent."""
    from jax.experimental import mesh_utils
    try:
        return mesh_utils.create_device_mesh(
            tuple(dims), devices=devices,
            allow_split_physical_axes=allow_split_physical_axes)
    except (ValueError, NotImplementedError, AssertionError) as e:
        log.warning("mesh %s: create_device_mesh failed (%r); falling back "
                    "to device-list order, ICI adjacency is not guaranteed",
                    tuple(dims), e)
        return np.array(devices).reshape(tuple(dims))


def create_mesh(shape: Dict[str, int], devices=None, allow_split_physical_axes: bool = True):
    """Create a Mesh from {axis_name: size}. Product must equal device count.

    Axis order in ``shape`` is the logical-to-physical assignment order:
    earlier axes change slowest, so put DCN-spanning axes (usually ``data``)
    first and the most communication-intense axes (``model``/``seq``) last —
    they land on adjacent ICI neighbors.
    """
    import jax

    names = tuple(shape.keys())
    dims = tuple(int(v) for v in shape.values())
    pool = list(devices) if devices is not None else jax.devices()
    total = int(np.prod(dims))
    if total > len(pool):
        raise ValueError(f"mesh shape {shape} has {total} slots but there are "
                         f"only {len(pool)} devices")
    pool = pool[:total]
    dev_array = ici_device_array(dims, pool, allow_split_physical_axes)
    return jax.sharding.Mesh(dev_array, names)


def data_parallel_mesh():
    """1-D mesh over all devices, axis "data" — the Horovod-equivalent
    communicator."""
    import jax
    return create_mesh({DATA: jax.device_count()})


def parse_mesh_spec(spec: str) -> Dict[str, int]:
    """Parse "data:8,model:4" → {"data": 8, "model": 4}."""
    out: Dict[str, int] = {}
    for part in spec.split(","):
        if not part.strip():
            continue
        name, _, dim = part.partition(":")
        out[name.strip()] = int(dim)
    return out


def local_mesh_axes(mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def axis_size(mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape))[name]
