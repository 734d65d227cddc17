"""The flagship decoder-only transformer (``horovod_tpu.models.transformer``)
built through the path a user takes: ``TransformerConfig`` /
``ParallelConfig`` -> ``init_params`` -> ``make_train_step`` /
``make_loss_fn`` on a ``(dp, pp, mp)`` mesh.

The arithmetic below (model FLOPs, attention FLOPs and bytes) is the
benchmark's yardstick and is deliberately a copy, not an import: a later PR
may change ``models/transformer.py`` and may not change this file.
"""

from __future__ import annotations

import numpy as np

MESH_AXES = ("dp", "pp", "mp")
DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def model_flops_per_token(c: dict) -> float:
    """Matmul FLOPs one training token requires, forward + backward (3 x
    forward), recompute not counted, causal attention halved.  Per token and
    layer 8 d^2 (qkv + output projection) + 4 d d_ff (MLP), the tied
    vocabulary head 2 d V once, and causal attention 2 S^2 d per layer and
    sequence (half of the bidirectional 4 S^2 d)."""
    d, ff, n, s, v = (c["d_model"], c["d_ff"], c["n_layers"], c["seq_len"],
                      c["vocab_size"])
    dense = s * (n * (8.0 * d * d + 4.0 * d * ff) + 2.0 * d * v)
    attn = n * 2.0 * s * s * d
    return 3.0 * (dense + attn) / s


def attention_cost(c: dict, seqs_per_device: float,
                   heads_per_device: float) -> dict:
    """What forward + backward attention needs per step on one device:
    FLOPs of the six matmuls (QK^T, PV; dP, dV, dQ, dK — the score
    recomputation of a flash backward is recompute and not counted), halved
    by the causal mask, and the least HBM traffic: read Q, K, V and write O
    forward; read Q, K, V, O, dO and write dQ, dK, dV backward, in the
    compute type, plus the fp32 row statistics written once and read once."""
    s, hd, n = c["seq_len"], c["d_model"] // c["n_heads"], c["n_layers"]
    item = DTYPE_BYTES[c["dtype"]]
    per_head = n * seqs_per_device * heads_per_device
    return {"flops": per_head * 12.0 * s * s * hd * 0.5,
            "bytes": per_head * (12.0 * s * hd * item + 2.0 * s * 4)}


class Family:
    def __init__(self, config: dict, mesh_shape: dict):
        import jax.numpy as jnp
        from horovod_tpu.models import transformer as tfm
        self.c = config
        self.tfm = tfm
        self.mesh_shape = {a: int(mesh_shape[a]) for a in MESH_AXES}
        self.cfg = tfm.TransformerConfig(
            vocab_size=config["vocab_size"], d_model=config["d_model"],
            n_heads=config["n_heads"], d_ff=config["d_ff"],
            n_layers=config["n_layers"], seq_len=config["seq_len"],
            attn_mode=config["attn_mode"], dtype=jnp.dtype(config["dtype"]),
            remat=config["remat"])
        self.par = tfm.ParallelConfig(**self.mesh_shape)
        self.dp = self.mesh_shape["dp"]
        self.tokens_per_seq = config["seq_len"]
        # The reference check's sequences for each data-parallel rank: the
        # loss is a mean over 8192 positions of one.
        self.check_seqs_per_rank = 1

    # -- the normal path ---------------------------------------------------
    def param_specs(self):
        return self.tfm.param_specs(self.cfg, self.par)

    def init_params(self, key):
        return self.tfm.init_params(key, self.cfg, self.par)

    def train_step(self, mesh, optimizer):
        step, _shard = self.tfm.make_train_step(self.cfg, self.par, mesh,
                                                optimizer)
        return step

    def loss_fn(self, mesh):
        return self.tfm.make_loss_fn(self.cfg, self.par, mesh)

    # -- inputs --------------------------------------------------------------
    def draw_batch(self, rng: np.random.Generator, n_seq: int):
        """Uniform token ids; the label of a position is the next token."""
        tokens = rng.integers(0, self.c["vocab_size"],
                              (n_seq, self.c["seq_len"]), dtype=np.int32)
        return tokens, np.roll(tokens, -1, axis=1)

    # -- the yardstick ---------------------------------------------------------
    def flops_per_token(self) -> float:
        return model_flops_per_token(self.c)

    def attention_cost(self, global_batch: int) -> dict:
        return attention_cost(
            self.c, global_batch / self.dp,
            self.c["n_heads"] / self.mesh_shape["mp"])

    # -- the reference ---------------------------------------------------------
    def to_reference(self, tree):
        """The system stacks layers as (pp stages, layers per stage, ...);
        the reference takes (layers, ...).  A reshape, so it maps gradients
        the same way."""
        out = dict(tree)
        out["layers"] = {k: v.reshape((-1,) + v.shape[2:])
                         for k, v in tree["layers"].items()}
        return out

    def reference_args(self) -> dict:
        return {"n_heads": self.c["n_heads"]}
