"""Nemotron 3 Super 120B-A12B (``nemotron_h``) for training, as one rank of
its deployment: ``horovod_tpu.models.transformer`` with a layer pattern of
Mamba-2 mixers ("M"), latent-space experts ("E": sigmoid router over 512,
top-22, 8 experts held here, one shared expert) and grouped-query attention
("*"), built through the path a user takes: ``TransformerConfig`` /
``ParallelConfig`` -> ``init_params`` -> ``make_train_step`` /
``make_loss_fn`` on a ``(dp, pp, mp)`` mesh.

The arithmetic below counts what THIS chip computes (the heads, experts and
vocabulary slice it holds; the shared expert, latent projections and router
whole) and is the benchmark's yardstick: deliberately a copy, not an import.
A later PR may change ``models/transformer.py`` and may not change this file.
"""

from __future__ import annotations

import numpy as np

MESH_AXES = ("dp", "pp", "mp")
DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def block_flops_per_token(c: dict) -> dict:
    """Forward matmul FLOPs one token needs in one block of each kind.

    M: the in-projection to [z | x | B | C | dt] and the out-projection, the
    conv's 4 taps, and the chunked scan's four products at chunk Q: C B^T
    (2 Q N a group; the whole Q x Q tile, as the algorithm computes it),
    (L o C B^T) X (2 Q P a head), the chunk's state and the read of the
    carried state (2 P N a head each).
    *: q, k, v, o projections of the heads held; scores and values over the
    causal half, 2 S hd a query head.
    E: router over all outputs, both latent projections, the shared expert's
    two matmuls, and top_k x held / routed experts of two matmuls each."""
    d, s = c["d_model"], c["seq_len"]
    h, p, g, n, q = (c["ssm_heads"], c["ssm_head_dim"], c["ssm_groups"],
                     c["ssm_state"], c["ssm_chunk"])
    hq, hkv, hd = c["n_heads"], c["n_kv_heads"], c["attn_head_dim"]
    lat, f = c["moe_latent"], c["d_ff"]
    scan = 2.0 * q * n * g + 2.0 * q * p * h + 4.0 * p * n * h
    return {
        "M": (2.0 * d * (2 * h * p + 2 * g * n + h) + 2.0 * h * p * d
              + 2.0 * c["ssm_conv"] * (h * p + 2 * g * n) + scan),
        "ssm_scan": scan,
        "*": 2.0 * d * hd * (2 * hq + 2 * hkv) + 2.0 * s * hq * hd,
        "E": (2.0 * d * c["n_experts"] + 4.0 * d * lat
              + 4.0 * d * c["shared_expert_ff"]
              + routed_experts_per_token(c) * 4.0 * lat * f),
    }


def routed_experts_per_token(c: dict) -> float:
    """Experts held here that a token visits, in the mean: 22 x 8 / 512."""
    return c["top_k"] * c["n_experts_held"] / c["n_experts"]


def model_flops_per_token(c: dict) -> float:
    """Matmul FLOPs one training token requires on this chip, forward +
    backward (3 x forward), recompute not counted, the sliced head once."""
    per = block_flops_per_token(c)
    periods = c["n_layers"] // len(c["layer_pattern"])
    blocks = sum(per[letter] for letter in c["layer_pattern"])
    return 3.0 * (periods * blocks + 2.0 * c["d_model"] * c["vocab_size"])


def attention_cost(c: dict, seqs_per_device: float) -> dict:
    """The flash kernels' cost for the "*" blocks: the flagship's count
    (six matmuls forward + backward halved by the causal mask; q, k, v, o,
    do, dq, dk, dv once each in the compute type plus the fp32 row
    statistics) for the query heads held.  K and V are repeated to the
    query heads before the kernels, so the kernels move a K / V a query
    head, as counted here."""
    s, hd = c["seq_len"], c["attn_head_dim"]
    item = DTYPE_BYTES[c["dtype"]]
    n_attn = (c["n_layers"] // len(c["layer_pattern"])
              * c["layer_pattern"].count("*"))
    per_head = n_attn * seqs_per_device * c["n_heads"]
    return {"flops": per_head * 12.0 * s * s * hd * 0.5,
            "bytes": per_head * (12.0 * s * hd * item + 2.0 * s * 4)}


def ssm_scan_cost(c: dict, tokens_per_device: float) -> dict:
    """What the chunked scan of every "M" block needs per step on one
    device, forward + backward (3 x the forward's four products; recompute
    not counted), and the least HBM traffic: forward reads x, B, C in the
    compute type and dt in fp32 and writes y; backward reads those and dy
    and writes dx, dB, dC and ddt.  The (chunk x chunk) decay and score
    tiles and the carried states are the algorithm's own temporaries, which
    a fused kernel keeps on chip; they are not counted."""
    h, p, g, n = (c["ssm_heads"], c["ssm_head_dim"], c["ssm_groups"],
                  c["ssm_state"])
    item = DTYPE_BYTES[c["dtype"]]
    n_ssm = (c["n_layers"] // len(c["layer_pattern"])
             * c["layer_pattern"].count("M"))
    x_bytes, bc_bytes, dt_bytes = h * p * item, 2 * g * n * item, h * 4
    forward = 2 * x_bytes + bc_bytes + dt_bytes          # x, y | B, C | dt
    backward = 4 * x_bytes + 2 * bc_bytes + 2 * dt_bytes  # + dy, dx | d..
    return {"flops": n_ssm * tokens_per_device * 3.0
            * block_flops_per_token(c)["ssm_scan"],
            "bytes": n_ssm * tokens_per_device * (forward + backward)}


def expert_matmul_cost(c: dict, tokens_per_device: float) -> dict:
    """The held experts' two grouped matmuls of every "E" block, forward +
    backward: 3 x rows x 4 latent f FLOPs with rows = tokens x 22 x 8 / 512
    in the mean; the least traffic reads each operand and writes each result
    of the six matmuls once (OLMoE's count with two matmuls for three)."""
    lat, f = c["moe_latent"], c["d_ff"]
    item = DTYPE_BYTES[c["dtype"]]
    n_moe = (c["n_layers"] // len(c["layer_pattern"])
             * c["layer_pattern"].count("E"))
    rows = tokens_per_device * routed_experts_per_token(c)
    return {"flops": n_moe * 3.0 * rows * 4.0 * lat * f,
            "bytes": n_moe * 6.0 * (rows * lat + rows * f
                                    + c["n_experts_held"] * lat * f) * item}


def period_at_depth(pattern: str, n_layers: int) -> str:
    """The period the model is built with: the configuration's where the
    depth is whole periods (every cell); where a test's rehearsal lays a
    smaller depth over the configuration, the period's first blocks and its
    last one, so that two blocks are still an expert block and attention."""
    if n_layers % len(pattern) == 0:
        return pattern
    return pattern[:n_layers - 1] + pattern[-1]


class Family:
    def __init__(self, config: dict, mesh_shape: dict):
        import jax.numpy as jnp
        from horovod_tpu.models import transformer as tfm
        self.c = c = {**config, "layer_pattern": period_at_depth(
            config["layer_pattern"], config["n_layers"])}
        self.tfm = tfm
        self.mesh_shape = {a: int(mesh_shape[a]) for a in MESH_AXES}
        self.cfg = tfm.TransformerConfig(
            vocab_size=c["vocab_size"], d_model=c["d_model"],
            n_heads=c["n_heads"], d_ff=c["d_ff"], n_layers=c["n_layers"],
            seq_len=c["seq_len"], n_experts=c["n_experts"],
            top_k=c["top_k"], attn_mode=c["attn_mode"],
            dtype=jnp.dtype(c["dtype"]), remat=c["remat"],
            norm_eps=c["rms_norm_eps"], dropless=True, tied_head=False,
            layer_pattern=c["layer_pattern"], learned_positions=False,
            n_kv_heads=c["n_kv_heads"], attn_head_dim=c["attn_head_dim"],
            ssm_heads=c["ssm_heads"], ssm_head_dim=c["ssm_head_dim"],
            ssm_groups=c["ssm_groups"], ssm_state=c["ssm_state"],
            ssm_conv=c["ssm_conv"], ssm_chunk=c["ssm_chunk"],
            ssm_dt_range=(c["time_step_min"], c["time_step_max"],
                          c["time_step_floor"]),
            router_scoring="sigmoid", router_renormalise=c["norm_topk_prob"],
            router_scale=float(c["router_scale"]),
            n_experts_held=c["n_experts_held"],
            expert_buffer_factor=c["expert_buffer_factor"],
            moe_latent=c["moe_latent"],
            shared_expert_ff=c["shared_expert_ff"],
            expert_activation=c["mlp_hidden_act"])
        self.par = tfm.ParallelConfig(**self.mesh_shape)
        self.dp = self.mesh_shape["dp"]
        self.tokens_per_seq = c["seq_len"]
        # The reference check's sequences for each data-parallel rank: one
        # of 8192, the flagship's count of positions.
        self.check_seqs_per_rank = 1

    # -- the normal path ---------------------------------------------------
    def param_specs(self):
        return self.tfm.param_specs(self.cfg, self.par)

    def init_params(self, key):
        """Seeded weights, then the router's correction bias balanced on one
        seeded sequence a data-parallel rank (``make_router_balancer``): a
        deployment's routing is balanced, which is what the bias is for, and
        a seeded router's is not — its held experts' load, and with it the
        step time, followed the seed by 1.2 % (PERF.md, PR 31)."""
        import jax
        import jax.numpy as jnp
        from horovod_tpu.parallel.mesh import create_mesh
        params = self.tfm.init_params(key, self.cfg, self.par)
        n = int(np.prod(list(self.mesh_shape.values())))
        mesh = create_mesh(self.mesh_shape, devices=jax.devices()[:n])
        tokens = jax.random.randint(
            jax.random.fold_in(key, 1), (self.dp, self.c["seq_len"]), 0,
            self.c["vocab_size"], dtype=jnp.int32)
        return self.tfm.make_router_balancer(self.cfg, self.par, mesh)(
            params, tokens, jnp.roll(tokens, -1, axis=1))

    def train_step(self, mesh, optimizer):
        step, _shard = self.tfm.make_train_step(self.cfg, self.par, mesh,
                                                optimizer)
        return step

    def loss_fn(self, mesh):
        return self.tfm.make_loss_fn(self.cfg, self.par, mesh)

    # -- inputs --------------------------------------------------------------
    def draw_batch(self, rng: np.random.Generator, n_seq: int):
        """Uniform token ids from the vocabulary slice; the label of a
        position is the next token."""
        tokens = rng.integers(0, self.c["vocab_size"],
                              (n_seq, self.c["seq_len"]), dtype=np.int32)
        return tokens, np.roll(tokens, -1, axis=1)

    # -- the yardstick ---------------------------------------------------------
    def flops_per_token(self) -> float:
        return model_flops_per_token(self.c)

    def attention_cost(self, global_batch: int) -> dict:
        """The attention kernels' cost for the 4 query heads held, and under
        keys of their own the chunked scan's (``metrics/ssm_scan_roofline``)
        and the held experts' grouped matmuls' (the runner hands readers
        this dict only)."""
        seqs = global_batch / self.dp
        tokens = seqs * self.c["seq_len"]
        cost = attention_cost(self.c, seqs)
        cost["ssm_scan"] = ssm_scan_cost(self.c, tokens)
        cost["moe_expert_matmul"] = expert_matmul_cost(self.c, tokens)
        return cost

    # -- the reference ---------------------------------------------------------
    def to_reference(self, tree):
        """The system stacks a kind's blocks as (1 stage, periods, blocks of
        the kind in a period, ...); the reference takes (periods, blocks,
        ...).  The router's correction bias travels as one more row of
        ``gate`` (the reference's layout): a buffer outside the gradient has
        a zero gradient on both sides, and 0 / 0 is no comparison, while a
        row of zeros under the router's own rows is one."""
        import jax.numpy as jnp
        layers = {kind: {k: v[0] for k, v in leaves.items()}
                  for kind, leaves in tree["layers"].items()}
        moe = layers["moe"]
        moe["gate"] = jnp.concatenate(
            [moe["gate"], moe.pop("router_bias")[..., None, :]], axis=-2)
        return {**tree, "layers": layers}

    def reference_args(self) -> dict:
        c = self.c
        return {"layer_pattern": c["layer_pattern"],
                "norm_eps": c["rms_norm_eps"],
                "n_heads": c["n_heads"], "n_kv_heads": c["n_kv_heads"],
                "ssm_heads": c["ssm_heads"], "ssm_groups": c["ssm_groups"],
                "ssm_state": c["ssm_state"], "top_k": c["top_k"],
                "router_scale": float(c["router_scale"]),
                "renormalise": c["norm_topk_prob"]}
