"""OLMoE-1B-7B for training (``horovod_tpu.models.transformer`` with its
architecture fields set: rotary positions, QK-norm, gated experts, dropless
top-k routing, an untied head, the router's two auxiliary losses) built
through the path a user takes: ``TransformerConfig`` / ``ParallelConfig`` ->
``init_params`` -> ``make_train_step`` / ``make_loss_fn`` on a ``(dp, pp,
mp)`` mesh.

The arithmetic below (model FLOPs, attention and expert-matmul FLOPs and
bytes) is the benchmark's yardstick and is deliberately a copy, not an
import: a later PR may change ``models/transformer.py`` and may not change
this file.
"""

from __future__ import annotations

import numpy as np

MESH_AXES = ("dp", "pp", "mp")
DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def model_flops_per_token(c: dict) -> float:
    """Matmul FLOPs one training token requires, forward + backward (3 x
    forward), recompute not counted, causal attention halved.  Per token and
    layer 8 d^2 (qkv + output projection) + 2 S d (causal attention: 2 S^2 d
    a sequence) + 2 d E (router) + top_k 6 d f (the gate, up and down
    projections of the chosen experts), and the untied head 2 d V once."""
    d, f, n, s, v = (c["d_model"], c["d_ff"], c["n_layers"], c["seq_len"],
                     c["vocab_size"])
    e, k = c["n_experts"], c["top_k"]
    layer = 8.0 * d * d + 2.0 * s * d + 2.0 * d * e + k * 6.0 * d * f
    return 3.0 * (n * layer + 2.0 * d * v)


def attention_cost(c: dict, seqs_per_device: float,
                   heads_per_device: float) -> dict:
    """The flagship's (``families/flagship.attention_cost``): six matmuls
    forward + backward, halved by the causal mask; q, k, v, o, do, dq, dk,
    dv once each in the compute type plus the fp32 row statistics."""
    s, hd, n = c["seq_len"], c["d_model"] // c["n_heads"], c["n_layers"]
    item = DTYPE_BYTES[c["dtype"]]
    per_head = n * seqs_per_device * heads_per_device
    return {"flops": per_head * 12.0 * s * s * hd * 0.5,
            "bytes": per_head * (12.0 * s * hd * item + 2.0 * s * 4)}


def expert_matmul_cost(c: dict, tokens_per_device: float) -> dict:
    """What the three grouped matmuls of every layer need per step on one
    device, forward + backward, recompute not counted: 3 x tokens x top_k x
    6 d f FLOPs; and the least HBM traffic in the compute type: each of
    the nine matmuls (three forward, a data and a weight gradient each
    backward) reads its two operands and writes its result once, which
    comes to 9 x (rows d + rows f + E d f) elements."""
    d, f, n = c["d_model"], c["d_ff"], c["n_layers"]
    rows = tokens_per_device * c["top_k"]
    item = DTYPE_BYTES[c["dtype"]]
    return {"flops": n * 3.0 * rows * 6.0 * d * f,
            "bytes": n * 9.0 * (rows * d + rows * f
                                + c["n_experts"] * d * f) * item}


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
            n_experts=config["n_experts"], top_k=config["top_k"],
            attn_mode=config["attn_mode"], dtype=jnp.dtype(config["dtype"]),
            remat=config["remat"], rope_theta=float(config["rope_theta"]),
            qk_norm=True, norm_eps=config["rms_norm_eps"],
            gated_experts=True, dropless=True, tied_head=False,
            aux_loss_coef=config["router_aux_loss_coef"],
            z_loss_coef=config["router_z_loss_coef"])
        self.par = tfm.ParallelConfig(**self.mesh_shape)
        self.dp = self.mesh_shape["dp"]
        self.tokens_per_seq = config["seq_len"]
        # The reference check's sequences for each data-parallel rank: two
        # of 4096, as many positions as the flagship's one of 8192.
        self.check_seqs_per_rank = 2

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
        """The attention kernels' cost, and under a key of its own the
        grouped expert matmuls' (``metrics/moe_expert_matmul_roofline``
        reads it from here: the runner hands readers this dict only)."""
        seqs = global_batch / self.dp
        cost = attention_cost(self.c, seqs,
                              self.c["n_heads"] / self.mesh_shape["mp"])
        cost["moe_expert_matmul"] = expert_matmul_cost(
            self.c, seqs * self.c["seq_len"] / self.mesh_shape["mp"])
        return cost

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
        return {"n_heads": self.c["n_heads"], "top_k": self.c["top_k"],
                "rope_theta": float(self.c["rope_theta"]),
                "norm_eps": self.c["rms_norm_eps"],
                "aux_loss_coef": self.c["router_aux_loss_coef"],
                "z_loss_coef": self.c["router_z_loss_coef"]}
