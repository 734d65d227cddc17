"""``hvd.parallel`` — the mesh-axis toolbox behind the flagship models.

One package per parallelism axis, composable inside one ``shard_map``:

* :mod:`.mesh` — named-axis mesh construction and the canonical axis
  vocabulary (``DATA``/``FSDP``/``TENSOR``/``SEQUENCE``/``PIPELINE``/
  ``EXPERT``).
* :mod:`.tensor_parallel` — Megatron column/row-parallel matmuls and
  the sequence-parallel gather/scatter pair, over more than one member
  as rings of ``ppermute`` beside the matmuls' pieces.
* :mod:`.ring_attention` — exact blockwise ring attention (sequence
  stays sharded through attention); :mod:`.ulysses` — the all_to_all
  head-scatter alternative.
* :mod:`.pipeline` — GPipe and 1F1B microbatch schedules over a
  ``ppermute`` stage ring, plus the bubble-fraction arithmetic the
  attribution engine charges (docs/parallel.md).
* :mod:`.moe` — top-k token routing with capacity-bounded all_to_all
  dispatch/combine, load-balancing aux loss, dropped-token accounting,
  and the optional int8/int4 block-scaled dispatch wire.

Import the submodules for the full surface; the names re-exported here
are the stable API (docs/api.md).
"""

from . import mesh
from . import moe
from . import pipeline
from . import ring_attention
from . import tensor_parallel
from . import ulysses

from .mesh import (
    DATA, EXPERT, FSDP, PIPELINE, SEQUENCE, TENSOR,
    create_mesh, data_parallel_mesh, parse_mesh_spec,
)
from .moe import (
    MoEParams, MoEStats, RoutingInfo, dispatch_wire_bytes,
    expert_capacity, init_moe_params, moe_layer, moe_load_balancing_loss,
    top_k_routing,
)
from .pipeline import (
    Schedule1F1B, bubble_fraction, build_1f1b_schedule, note_bubble,
    pipeline_apply, pipeline_apply_1f1b, stack_microbatches,
    unstack_microbatches,
)
# NB: the ring_attention FUNCTION is deliberately NOT re-exported here —
# binding it onto the package would shadow the `parallel.ring_attention`
# SUBMODULE (`from horovod_tpu.parallel import ring_attention as ra`
# would silently hand back the function).  Reach it via the submodule.
from .ring_attention import full_attention, reference_attention
from .tensor_parallel import (
    column_parallel, gather_column_parallel, gather_column_parallel_ring,
    gather_sequence, row_parallel,
    vocab_parallel_cross_entropy, vocab_parallel_logits,
)
from .ulysses import ulysses_attention

__all__ = [
    "mesh", "moe", "pipeline", "ring_attention", "tensor_parallel",
    "ulysses",
    "DATA", "EXPERT", "FSDP", "PIPELINE", "SEQUENCE", "TENSOR",
    "create_mesh", "data_parallel_mesh", "parse_mesh_spec",
    "MoEParams", "MoEStats", "RoutingInfo", "dispatch_wire_bytes",
    "expert_capacity", "init_moe_params", "moe_layer",
    "moe_load_balancing_loss", "top_k_routing",
    "Schedule1F1B", "bubble_fraction", "build_1f1b_schedule",
    "note_bubble", "pipeline_apply", "pipeline_apply_1f1b",
    "stack_microbatches", "unstack_microbatches",
    "full_attention", "reference_attention",
    "column_parallel", "gather_column_parallel",
    "gather_column_parallel_ring", "gather_sequence", "row_parallel",
    "vocab_parallel_cross_entropy", "vocab_parallel_logits",
    "ulysses_attention",
]
