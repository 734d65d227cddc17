"""SDAR-30B-A3B-Chat (``sdar_moe``) for block-diffusion training, as one rank
of its deployment: ``horovod_tpu.models.transformer`` with periods of one
grouped-query attention block and one expert MLP ("*E": per-head QK-norm,
rotary positions, softmax router over 128, top-8 renormalised, 16 experts
held here, no shared expert) and ``diffusion_block``: a sequence of L data
tokens goes through the stack as 2L positions (a noised copy, then the clean
one) under the block-diffusion mask, and the loss is a weighted
cross-entropy over the noised half.  Built through the path a user takes:
``TransformerConfig`` / ``ParallelConfig`` -> ``init_params`` ->
``make_train_step`` / ``make_loss_fn`` on a ``(dp, pp, mp)`` mesh.

The noise is data: ``draw_batch`` makes the three arrays on the host from
the runner's seeded generator, so the program and the reference see the same
noise and neither knows the schedule.  The weights are data too:
``Family.init_params`` changes what the program's ``init_params`` draws so
that a seeded router sends this rank the rows a deployment's would, whatever
the seed (its docstring says how and why).

The arithmetic below counts what THIS chip computes (the experts and the
vocabulary slice it holds; attention and the router whole) per *data* token
— a sequence is L tokens, what a training user counts, though 2L positions
are computed — and is the benchmark's yardstick: deliberately a copy, not an
import.  A later PR may change ``models/transformer.py`` and may not change
this file.
"""

from __future__ import annotations

import math

import numpy as np

MESH_AXES = ("dp", "pp", "mp")
DTYPE_BYTES = {"bfloat16": 2, "float32": 4}
# Added to the mask token's logits for its eight experts at set-up
# (``Family.init_params``); a seeded logit's spread is 0.9.
MASK_MARGIN = 1.0


def live_pairs(c: dict) -> float:
    """(query, key) pairs a sequence has under the block-diffusion mask: L
    noised queries their own block (L x block), and the clean blocks
    strictly before it (L (L - block) / 2); L clean queries the clean blocks
    up to their own (L (L + block) / 2): L^2 + L block of the (2L)^2."""
    s, bk = c["seq_len"], c["diffusion_block"]
    return float(s * s + s * bk)


def n_layers(c: dict) -> int:
    """Published layers: an attention block and an expert block each."""
    return c["n_layers"] // len(c["layer_pattern"])


def routed_experts_per_token(c: dict) -> float:
    """Experts held here that a position visits, in the mean: 8 x 16 / 128."""
    return c["top_k"] * c["n_experts_held"] / c["n_experts"]


def position_flops(c: dict) -> float:
    """Forward matmul FLOPs one position needs in one layer, the scores
    left out: q, k, v, o projections, the router over all its outputs, and
    top_k x held / routed experts of three matmuls each."""
    d, hd = c["d_model"], c["attn_head_dim"]
    return (2.0 * d * hd * (2 * c["n_heads"] + 2 * c["n_kv_heads"])
            + 2.0 * d * c["n_experts"]
            + routed_experts_per_token(c) * 6.0 * d * c["d_ff"])


def score_flops_per_position(c: dict) -> float:
    """Scores and values: 4 hd a live (query, key) pair and query head, the
    live pairs over the 2L queries.  Never the 2L x 2L square and never the
    causal triangle of 2L: work no program need do is not counted."""
    return (live_pairs(c) / (2.0 * c["seq_len"])
            * 4.0 * c["attn_head_dim"] * c["n_heads"])


def model_flops_per_token(c: dict) -> float:
    """Matmul FLOPs one *data* token requires on this chip, forward +
    backward (3 x forward), recompute not counted: two positions through
    every layer, the sliced head on the noised one."""
    layers = n_layers(c) * 2.0 * (position_flops(c)
                                  + score_flops_per_position(c))
    return 3.0 * (layers + 2.0 * c["d_model"] * c["vocab_size"])


def attention_cost(c: dict, seqs_per_device: float) -> dict:
    """The three ``_bd`` flash kernels' cost a step by the accepted
    ``attn_kernel_roofline``'s count (the flagship's and Laguna's: six
    matmuls forward + backward of 2 hd FLOPs a pair, the score tiles'
    recompute not counted; q, k, v, o, do, dq, dk, dv once each plus the
    fp32 row statistics), over the live pairs of a (sequence, query head)
    call.  K and V are repeated to the query heads before the kernels, so
    the kernels move a K / V a query head, as counted here."""
    s2, hd = 2 * c["seq_len"], c["attn_head_dim"]
    calls = seqs_per_device * n_layers(c) * c["n_heads"]
    item = DTYPE_BYTES[c["dtype"]]
    return {"flops": calls * 12.0 * live_pairs(c) * hd,
            "bytes": calls * (8.0 * s2 * hd * item + 2.0 * s2 * 4)}


def expert_matmul_cost(c: dict, positions_per_device: float) -> dict:
    """The held experts' three grouped matmuls of every "E" block, forward +
    backward: 3 x rows x 6 d f FLOPs with rows = positions x 8 x 16 / 128
    in the mean; the least traffic reads each operand and writes each
    result of the nine matmuls once (OLMoE's count)."""
    d, f = c["d_model"], c["d_ff"]
    item = DTYPE_BYTES[c["dtype"]]
    rows = positions_per_device * routed_experts_per_token(c)
    return {"flops": n_layers(c) * 3.0 * rows * 6.0 * d * f,
            "bytes": n_layers(c) * 9.0 * (rows * d + rows * f
                                          + c["n_experts_held"] * d * f)
            * item}


def noise(rng: np.random.Generator, ids: np.ndarray, block: int,
          mask_id: int, floor: float):
    """The three arrays of a step from clean ``ids`` (B, L): each block of
    ``block`` positions draws ``t = floor + (1 - floor) u``, ``u ~ U(0,
    1)``, and each of its positions becomes ``mask_id`` independently with
    probability t.  Returns ``tokens`` (B, 2L) int32 = [noised ; clean],
    ``labels`` = ids, ``weights`` (B, L) fp32 = masked / t."""
    b, length = ids.shape
    t = floor + (1.0 - floor) * rng.random((b, length // block))
    t = np.repeat(t, block, axis=1)
    masked = rng.random((b, length)) < t
    noised = np.where(masked, mask_id, ids)
    return (np.concatenate([noised, ids], axis=1).astype(np.int32),
            ids.astype(np.int32), (masked / t).astype(np.float32))


class Family:
    def __init__(self, config: dict, mesh_shape: dict):
        import jax.numpy as jnp
        from horovod_tpu.models import transformer as tfm
        self.c = c = config
        if c["layer_pattern"] != "*E" or c["n_layers"] % 2:
            raise ValueError(f"{c['n_layers']} blocks of pattern "
                             f"{c['layer_pattern']!r} are not layers of one "
                             "attention and one expert MLP each")
        missing = {"diffusion_block", "head_qk_norm"} - set(
            tfm.TransformerConfig._fields)
        if missing:
            from benchmark import loader
            raise loader.BenchmarkError(
                f"this program's TransformerConfig has no {sorted(missing)}:"
                " it cannot train block diffusion")
        self.tfm = tfm
        self.mesh_shape = {a: int(mesh_shape[a]) for a in MESH_AXES}
        self.cfg = tfm.TransformerConfig(
            vocab_size=c["vocab_size"], d_model=c["d_model"],
            n_heads=c["n_heads"], d_ff=c["d_ff"], n_layers=c["n_layers"],
            seq_len=c["seq_len"], n_experts=c["n_experts"],
            top_k=c["top_k"], attn_mode=c["attn_mode"],
            dtype=jnp.dtype(c["dtype"]), remat=c["remat"],
            norm_eps=c["norm_eps"], dropless=c["dropless"],
            tied_head=c["tied_head"], gated_experts=c["gated_experts"],
            layer_pattern=c["layer_pattern"], learned_positions=False,
            n_kv_heads=c["n_kv_heads"], attn_head_dim=c["attn_head_dim"],
            rope_theta=float(c["rope_theta"]),
            head_qk_norm=c["head_qk_norm"], router_scoring="softmax",
            router_renormalise=c["router_renormalise"],
            n_experts_held=c["n_experts_held"],
            expert_buffer_factor=c["expert_buffer_factor"],
            diffusion_block=c["diffusion_block"])
        self.par = tfm.ParallelConfig(**self.mesh_shape)
        self.dp = self.mesh_shape["dp"]
        # Data tokens: what a training user counts.  2 x as many positions
        # go through the stack.
        self.tokens_per_seq = c["seq_len"]
        self.mask_id = c["vocab_size"] - 1
        # The reference check's sequences for each data-parallel rank.
        self.check_seqs_per_rank = 1

    # -- the normal path ---------------------------------------------------
    def param_specs(self):
        return self.tfm.param_specs(self.cfg, self.par)

    def init_params(self, key):
        """Seeded weights that route as a deployment's do, by three changes
        to what ``init_params`` draws (all facts about the weights: the
        program and the reference see the same tree).

        The embedding table times sqrt(d_model), to unit RMS.  Drawn at
        0.02 it is a tenth of what one attention block writes into the
        residual; past the first layer every position is then the running
        mean of the values before it, all 8192 choose the same 8 experts of
        128 (busiest / mean 14-16 of a possible 16), which 8 changes with
        the batch, and the 16 held here get 0 to 2.6 x their share, step by
        step (PERF.md section 6, PR 39).  At unit RMS a position is its own
        token first, as a trained model's is, and its experts follow its id.

        The mask token's ~2048 copies still go one way, to the same 8
        experts a layer, and they carry the whole loss (a position weighs
        m / t).  Two things about them would otherwise follow the seed.  A
        rank holds 8 x 16 / 128 = 1 of their experts in the mean, and
        which seed holds 0 and which 3 would set the held rows (6144 + 2048
        a mask expert held, of a mean of 8192) and with them the step time:
        so each layer's router columns are relabelled until this rank's
        first expert is one of the mask token's eight (the fourth by logit)
        and its other fifteen lie spread over the experts the mask token
        does not choose.  And where the mask token's 8th and 9th logits lie
        closer than bf16's noise, 2048 positions cross the tie together and
        a router's gradient moves whole (113 % on one layer's, CPU, PR 39):
        so its eight are decided by a margin, ``MASK_MARGIN`` of a logit
        added to their columns along the mask token's normalised
        embedding, which moves any other token's logits by a fiftieth of
        that."""
        import jax.numpy as jnp
        c = self.c
        params = self.tfm.init_params(key, self.cfg, self.par)
        embed = params["embed"] * math.sqrt(c["d_model"])
        moe = params["layers"]["moe"]
        gate = moe["gate"]                  # (stage, period, block, d, E)
        e = embed[self.mask_id]
        h = e * jnp.reciprocal(jnp.sqrt(jnp.mean(e * e) + c["norm_eps"]))
        h = h * moe["ln"]                   # (stage, period, block, d)
        by_logit = jnp.argsort(-jnp.einsum("...d,...de->...e", h, gate), -1)
        n, held, k = c["n_experts"], c["n_experts_held"], c["top_k"]
        # Ranks by the mask token's logit: one from the middle of its eight,
        # the rest evenly from the ranks past three times eight.
        others = 3 * k + (np.arange(held - 1) * (n - 3 * k)) // (held - 1)
        first = np.concatenate([[k // 2 - 1], others])
        rank = np.concatenate([first, np.setdiff1d(np.arange(n), first)])
        gate = jnp.take_along_axis(
            gate, jnp.take(by_logit, rank, axis=-1)[..., None, :], axis=-1)
        chosen = jnp.asarray(rank < k, gate.dtype) * MASK_MARGIN
        gate = gate + (h / jnp.sum(h * h, -1, keepdims=True))[
            ..., None] * chosen
        return {**params, "embed": embed,
                "layers": {**params["layers"], "moe": {**moe, "gate": gate}}}

    def train_step(self, mesh, optimizer):
        step, _shard = self.tfm.make_train_step(self.cfg, self.par, mesh,
                                                optimizer)
        return step

    def loss_fn(self, mesh):
        return self.tfm.make_loss_fn(self.cfg, self.par, mesh)

    # -- inputs --------------------------------------------------------------
    def draw_batch(self, rng: np.random.Generator, n_seq: int):
        """Uniform data ids of the vocabulary slice less the mask token,
        noised block by block: (tokens, labels, weights)."""
        ids = rng.integers(0, self.mask_id, (n_seq, self.c["seq_len"]),
                           dtype=np.int32)
        return noise(rng, ids, self.c["diffusion_block"], self.mask_id,
                     self.c["noise_floor"])

    # -- the yardstick ---------------------------------------------------------
    def flops_per_token(self) -> float:
        return model_flops_per_token(self.c)

    def attention_cost(self, global_batch: int) -> dict:
        """The attention kernels' cost by the accepted count, and under a
        key of their own the held experts' grouped matmuls'
        (``metrics/moe_expert_matmul_roofline``: the runner hands readers
        this dict only)."""
        seqs = global_batch / self.dp
        cost = attention_cost(self.c, seqs)
        cost["moe_expert_matmul"] = expert_matmul_cost(
            self.c, seqs * 2 * self.c["seq_len"])
        return cost

    # -- the reference ---------------------------------------------------------
    def to_reference(self, tree):
        """The system stacks a kind's blocks as (1 stage, periods, blocks of
        the kind in a period, ...); the reference takes a list of layers, an
        attention and an expert MLP each, under its own names."""
        names = {"attn": {"ln": "ln", "wq": "wq", "wk": "wk", "wv": "wv",
                          "q_norm": "q_norm", "k_norm": "k_norm",
                          "wo": "wo"},
                 "moe": {"ln": "ln", "gate": "router", "w_gate": "w1",
                         "w_up": "w3", "w_down": "w2"}}
        layers = tree["layers"]
        return {**{k: v for k, v in tree.items() if k != "layers"},
                "layers": [
                    {half: {names[kind][k]: v[0, p, 0]
                            for k, v in layers[kind].items()}
                     for half, kind in (("attn", "attn"), ("mlp", "moe"))}
                    for p in range(n_layers(self.c))]}

    def reference_args(self) -> dict:
        c = self.c
        return {"norm_eps": c["norm_eps"], "n_kv_heads": c["n_kv_heads"],
                "head_dim": c["attn_head_dim"],
                "rope_theta": float(c["rope_theta"]), "top_k": c["top_k"],
                "block": c["diffusion_block"]}
