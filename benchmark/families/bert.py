"""The BERT encoder (``horovod_tpu.models.bert``) in masked-LM pretraining
with the gathered head, built through the path a user takes: ``BertConfig``
-> ``init_params`` -> ``make_train_step(gathered=True)`` / ``make_loss_fn``
on a ``(dp, mp)`` mesh.

The arithmetic below is the benchmark's yardstick and is deliberately a
copy, not an import (see ``flagship.py``).
"""

from __future__ import annotations

import numpy as np

MESH_AXES = ("dp", "mp")
DTYPE_BYTES = {"bfloat16": 2, "float32": 4}
MASK_ID = 0           # the system's [MASK]-like id (models/bert.py)


def model_flops_per_token(c: dict) -> float:
    """Matmul FLOPs one training token requires, forward + backward (3 x
    forward), recompute not counted.  Encoder per token and layer 8 d^2 +
    4 d d_ff; bidirectional attention 4 S^2 d per layer and sequence; the
    masked-LM head (transform 2 d^2 + tied projection 2 d V) once per
    predicted position, of which a sequence has ``max_predictions_per_seq``."""
    d, ff, n, s, v = (c["d_model"], c["d_ff"], c["n_layers"], c["seq_len"],
                      c["vocab_size"])
    enc = s * n * (8.0 * d * d + 4.0 * d * ff)
    attn = n * 4.0 * s * s * d
    head = c["max_predictions_per_seq"] * (2.0 * d * d + 2.0 * d * v)
    return 3.0 * (enc + attn + head) / s


def attention_cost(c: dict, seqs_per_device: float,
                   heads_per_device: float) -> dict:
    """As ``flagship.attention_cost`` without the causal half."""
    s, hd, n = c["seq_len"], c["d_model"] // c["n_heads"], c["n_layers"]
    item = DTYPE_BYTES[c["dtype"]]
    per_head = n * seqs_per_device * heads_per_device
    return {"flops": per_head * 12.0 * s * s * hd,
            "bytes": per_head * (12.0 * s * hd * item + 2.0 * s * 4)}


class Family:
    def __init__(self, config: dict, mesh_shape: dict):
        import jax.numpy as jnp
        from horovod_tpu.models import bert
        self.c = config
        self.bert = bert
        self.mesh_shape = {a: int(mesh_shape[a]) for a in MESH_AXES}
        self.cfg = bert.BertConfig(
            vocab_size=config["vocab_size"], d_model=config["d_model"],
            n_heads=config["n_heads"], d_ff=config["d_ff"],
            n_layers=config["n_layers"], seq_len=config["seq_len"],
            dtype=jnp.dtype(config["dtype"]), remat=config["remat"])
        self.dp = self.mesh_shape["dp"]
        self.tokens_per_seq = config["seq_len"]
        # The reference check's sequences for each data-parallel rank: a
        # sequence's loss is a mean over 80 positions only, and one sequence
        # left |system - reference| up to 1e-3 on the chip.
        self.check_seqs_per_rank = 8

    # -- the normal path ---------------------------------------------------
    def param_specs(self):
        return self.bert.param_specs(self.cfg)

    def init_params(self, key):
        return self.bert.init_params(key, self.cfg)

    def train_step(self, mesh, optimizer):
        step, _shard = self.bert.make_train_step(self.cfg, mesh, optimizer,
                                                 gathered=True)
        return step

    def loss_fn(self, mesh):
        return self.bert.make_loss_fn(self.cfg, mesh, gathered=True)

    # -- inputs --------------------------------------------------------------
    def draw_batch(self, rng: np.random.Generator, n_seq: int):
        """Uniform token ids, ``max_predictions_per_seq`` distinct masked
        positions in each sequence, the masked inputs replaced by the mask
        id and the original ids as labels: (inputs, positions, labels)."""
        s, n_pred = self.c["seq_len"], self.c["max_predictions_per_seq"]
        tokens = rng.integers(1, self.c["vocab_size"], (n_seq, s),
                              dtype=np.int32)
        positions = np.argsort(rng.random((n_seq, s), dtype=np.float32),
                               axis=1)[:, :n_pred].astype(np.int32)
        labels = np.take_along_axis(tokens, positions, axis=1)
        inputs = tokens.copy()
        np.put_along_axis(inputs, positions, MASK_ID, axis=1)
        return inputs, positions, labels

    # -- the yardstick ---------------------------------------------------------
    def flops_per_token(self) -> float:
        return model_flops_per_token(self.c)

    def attention_cost(self, global_batch: int) -> dict:
        return attention_cost(
            self.c, global_batch / self.dp,
            self.c["n_heads"] / self.mesh_shape["mp"])

    # -- the reference ---------------------------------------------------------
    def to_reference(self, tree):
        return tree

    def reference_args(self) -> dict:
        return {"n_heads": self.c["n_heads"]}
