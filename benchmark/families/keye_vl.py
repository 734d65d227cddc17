"""Keye-VL-2.0-30B-A3B's language model (``KeyeVL2``) for next-token
training, as one rank of its deployment: ``horovod_tpu.models.transformer``
with periods of one attention block with a learned indexer and one expert
MLP ("SE": per-head QK-norm, rotary positions read from three position
streams, an indexer of 16 heads of 64 that picks 2,048 keys a query and is
trained by its own loss; softmax router over 128, top-8 renormalised, 16
experts held here, no shared expert), built through the path a user takes:
``TransformerConfig`` / ``ParallelConfig`` -> ``init_params`` ->
``make_train_step`` / ``make_loss_fn`` on a ``(dp, pp, mp)`` mesh.  No
vision tower: image positions carry token ids and their grid's positions.

The positions are data: ``draw_batch`` lays four image spans into every
sequence on the host from the runner's seeded generator and numbers all
positions by Qwen2-VL's rule (``position_streams``), so the program and the
reference see the same three streams and neither knows the rule.

The arithmetic below counts what THIS chip computes (the experts and the
vocabulary slice it holds; attention, indexer and router whole) and is the
benchmark's yardstick: deliberately a copy, not an import.  A later PR may
change ``models/transformer.py`` and may not change this file.  The
attention's (query, key) pairs are the CHOSEN ones, never the causal
triangle: a program that visits every causal tile reads a low share here,
and that is the finding.
"""

from __future__ import annotations

import numpy as np

MESH_AXES = ("dp", "pp", "mp")
DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def n_layers(c: dict) -> int:
    """Published layers: an attention block and an expert block each."""
    return c["n_layers"] // len(c["layer_pattern"])


def chosen_pairs(c: dict) -> float:
    """(query, key) pairs a sequence's attention has: query t its t + 1
    causal keys while they are no more than ``index_topk``, then
    ``index_topk``."""
    s, k = c["seq_len"], min(c["index_topk"], c["seq_len"])
    return k * (k + 1) / 2.0 + (s - k) * float(k)


def causal_pairs(c: dict) -> float:
    """The pairs the indexer scores: every s <= t."""
    return c["seq_len"] * (c["seq_len"] + 1) / 2.0


def routed_experts_per_token(c: dict) -> float:
    """Experts held here that a token visits, in the mean: 8 x 16 / 128."""
    return c["top_k"] * c["n_experts_held"] / c["n_experts"]


def layer_flops_per_token(c: dict) -> dict:
    """Forward matmul FLOPs one token needs in one layer, by part: q, k, v,
    o projections; the indexer's three projections; its scores (2 J Di a
    causal pair); the main scores and values (4 hd a chosen pair and query
    head); the router over all its outputs; top_k x held / routed experts
    of three matmuls each."""
    d, s, hd = c["d_model"], c["seq_len"], c["attn_head_dim"]
    j, di = c["index_heads"], c["index_head_dim"]
    return {
        "projections": 2.0 * d * hd * (2 * c["n_heads"] + 2 * c["n_kv_heads"]),
        "index_projections": 2.0 * d * (j * di + di + j),
        "index_scores": causal_pairs(c) / s * 2.0 * j * di,
        "chosen_scores": chosen_pairs(c) / s * 4.0 * hd * c["n_heads"],
        "router": 2.0 * d * c["n_experts"],
        "held_experts": routed_experts_per_token(c) * 6.0 * d * c["d_ff"],
    }


def model_flops_per_token(c: dict) -> float:
    """Matmul FLOPs one token requires on this chip, forward + backward
    (3 x forward), recompute not counted, the pass the indexer's loss makes
    over the main scores not counted: the model's count takes the scores
    once."""
    return 3.0 * (n_layers(c) * sum(layer_flops_per_token(c).values())
                  + 2.0 * c["d_model"] * c["vocab_size"])


def sparse_attention_cost(c: dict, seqs_per_device: float) -> dict:
    """The three ``_sel`` flash kernels' cost a step by the accepted
    ``attn_kernel_roofline``'s count (six matmuls forward + backward of 2 hd
    FLOPs a pair, the score tiles' recompute not counted; q, k, v, o, dO,
    dQ, dK, dV once each plus the fp32 row statistics), over the CHOSEN
    pairs of a (sequence, query head) call, whatever the program visits.
    K and V are repeated to the query heads before the kernels, so the
    kernels move a K / V a query head, as counted here; the visibility
    array is the program's own and not counted."""
    s, hd = c["seq_len"], c["attn_head_dim"]
    calls = seqs_per_device * n_layers(c) * c["n_heads"]
    item = DTYPE_BYTES[c["dtype"]]
    return {"flops": calls * 12.0 * chosen_pairs(c) * hd,
            "bytes": calls * (8.0 * s * hd * item + 2.0 * s * 4)}


def position_streams(length: int, spans) -> np.ndarray:
    """(3, length) int32: the temporal, height and width position of every
    token of a sequence that holds the image ``spans`` [(start index, grid
    height, grid width), ...] with text around them, by Qwen2-VL's
    ``get_rope_index``: a text token's three are one running value; an image
    span of h x w, row-major, after position value m has temporal m + 1 for
    all its h w tokens, height m + 1 + row, width m + 1 + column, and the
    text after it goes on at m + 1 + max(h, w)."""
    out = np.empty((3, length), np.int64)
    at = value = 0
    for start, h, w in sorted(spans):
        if start < at or start + h * w > length:
            raise ValueError(f"image spans {spans} overlap or pass {length}")
        out[:, at:start] = value + np.arange(start - at)
        value += start - at
        cell = np.arange(h * w)
        out[:, start:start + h * w] = value + np.stack(
            [np.zeros_like(cell), cell // w, cell % w])
        value += max(h, w)
        at = start + h * w
    out[:, at:] = value + np.arange(length - at)
    return out.astype(np.int32)


def draw_spans(rng: np.random.Generator, length: int, n: int, grid):
    """``n`` image spans of h x w, h and w uniform in ``grid`` = (least,
    most), at seeded starts, at least one text token before, between and
    after them: [(start index, h, w), ...].  A sequence too short for them
    (a rehearsal's) takes grids whose largest fill half of it."""
    most = min(grid[1], max(int((length / (2 * n)) ** 0.5), 1))
    grid = (min(grid[0], most), most)
    grids = rng.integers(grid[0], grid[1] + 1, (n, 2))
    text = length - int(np.sum(grids[:, 0] * grids[:, 1]))
    if text <= n:
        raise ValueError(f"{length} positions do not hold {n} grids of up "
                         f"to {grid[1]} x {grid[1]} and text")
    cuts = np.sort(rng.choice(np.arange(1, text), n, replace=False))
    spans, images = [], 0
    for cut, (h, w) in zip(cuts, grids):
        spans.append((int(cut) + images, int(h), int(w)))
        images += int(h * w)
    return spans


class Family:
    def __init__(self, config: dict, mesh_shape: dict):
        import jax.numpy as jnp
        from horovod_tpu.models import transformer as tfm
        self.c = c = config
        if c["layer_pattern"] != "SE" or c["n_layers"] % 2:
            raise ValueError(f"{c['n_layers']} blocks of pattern "
                             f"{c['layer_pattern']!r} are not layers of one "
                             "selected attention and one expert MLP each")
        missing = {"index_topk", "rope_sections"} - set(
            tfm.TransformerConfig._fields)
        if missing:
            from benchmark import loader
            raise loader.BenchmarkError(
                f"this program's TransformerConfig has no {sorted(missing)}:"
                " it cannot train learned sparse attention")
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
            index_heads=c["index_heads"],
            index_head_dim=c["index_head_dim"], index_topk=c["index_topk"],
            index_loss_coef=c["index_loss_coef"],
            rope_sections=tuple(c["rope_sections"]))
        self.par = tfm.ParallelConfig(**self.mesh_shape)
        self.dp = self.mesh_shape["dp"]
        self.tokens_per_seq = c["seq_len"]
        # The reference check's sequences for each data-parallel rank.
        self.check_seqs_per_rank = 1

    # -- the normal path ---------------------------------------------------
    def param_specs(self):
        return self.tfm.param_specs(self.cfg, self.par)

    def init_params(self, key):
        """Seeded weights that route as a deployment's do, by two changes to
        what ``init_params`` draws (facts about the weights: the program and
        the reference see the same tree).  The embedding table over its own
        RMS, to unit RMS, for SDAR's and SmallThinker's reason: drawn at
        0.02 it is a fraction of what the first attention block writes into
        the residual, past it every position is a mean of the values before
        it, all positions choose the same 8 experts of 128 and the indexer
        ranks the same keys for every query.  At unit RMS a position is its
        own token first, as a trained model's is: its experts follow its id
        and an indexer's score its query and its key.  And every norm gain
        (the blocks', the heads' and the indexer's LayerNorm's) is drawn
        uniform in (0.5, 1.5) a feature and the LayerNorm's bias in (-0.5,
        0.5): at one and zero a norm with its parameters could not be told
        from one without."""
        import jax
        import jax.numpy as jnp
        params = self.tfm.init_params(key, self.cfg, self.par)
        keys = iter(jax.random.split(jax.random.fold_in(key, 1), 64))
        gains = ("['ln']", "['q_norm']", "['k_norm']", "['index_k_norm']")

        def off_their_defaults(path, leaf):
            name = jax.tree_util.keystr(path)
            if name.endswith(gains):
                return leaf * jax.random.uniform(
                    next(keys), leaf.shape, leaf.dtype, 0.5, 1.5)
            if name.endswith("['index_k_bias']"):
                return jax.random.uniform(
                    next(keys), leaf.shape, leaf.dtype, -0.5, 0.5)
            return leaf

        return {**params,
                "embed": params["embed"] * jax.lax.rsqrt(
                    jnp.mean(params["embed"] ** 2)),
                "layers": jax.tree_util.tree_map_with_path(
                    off_their_defaults, params["layers"])}

    def train_step(self, mesh, optimizer):
        step, _shard = self.tfm.make_train_step(self.cfg, self.par, mesh,
                                                optimizer)
        return step

    def loss_fn(self, mesh):
        return self.tfm.make_loss_fn(self.cfg, self.par, mesh)

    # -- inputs --------------------------------------------------------------
    def draw_batch(self, rng: np.random.Generator, n_seq: int):
        """Uniform token ids from the vocabulary slice at every position
        (image positions too: ids stand in for the absent tower's rows), the
        label of a position the next id, and the three position streams of
        ``image_spans`` image spans of ``image_grid`` at seeded places:
        (tokens, labels, positions (n_seq, 3, S))."""
        c, s = self.c, self.c["seq_len"]
        tokens = rng.integers(0, c["vocab_size"], (n_seq, s), dtype=np.int32)
        positions = np.stack([
            position_streams(s, draw_spans(rng, s, c["image_spans"],
                                           c["image_grid"]))
            for _ in range(n_seq)])
        return tokens, np.roll(tokens, -1, axis=1), positions

    # -- the yardstick ---------------------------------------------------------
    def flops_per_token(self) -> float:
        return model_flops_per_token(self.c)

    def attention_cost(self, global_batch: int) -> dict:
        """The attention kernels' cost by the accepted count over the chosen
        pairs (every flash kernel this step runs is a ``_sel`` one, so the
        accepted ``attn_kernel_roofline`` and ``sparse_attn_kernel_roofline``
        read the same cost), and under a key of its own the same again for
        the new reader (the runner hands readers this dict only).  No cost
        of the held experts' grouped matmuls: no metric of this cell reads
        one (``moe_expert_matmul_roofline`` lists the cells it had, and
        SDAR's accepted test holds that list to the two it names)."""
        cost = sparse_attention_cost(self.c, global_batch / self.dp)
        cost["sparse_attention"] = dict(cost)
        return cost

    # -- the reference ---------------------------------------------------------
    def to_reference(self, tree):
        """The system stacks a kind's blocks as (1 stage, periods, blocks of
        the kind in a period, ...); the reference takes a list of layers, an
        attention and an expert MLP each, under its own names (the
        attention's are the program's)."""
        moe = {"ln": "ln", "gate": "router", "w_gate": "w1", "w_up": "w3",
               "w_down": "w2"}
        layers = tree["layers"]
        return {**{k: v for k, v in tree.items() if k != "layers"},
                "layers": [
                    {"attn": {k: v[0, p, 0] for k, v in layers["sel"].items()},
                     "mlp": {moe[k]: v[0, p, 0]
                             for k, v in layers["moe"].items()}}
                    for p in range(n_layers(self.c))]}

    def reference_args(self) -> dict:
        c = self.c
        return {"norm_eps": c["norm_eps"], "n_kv_heads": c["n_kv_heads"],
                "head_dim": c["attn_head_dim"],
                "rope_theta": float(c["rope_theta"]),
                "sections": tuple(c["rope_sections"]),
                "index_heads": c["index_heads"], "topk": c["index_topk"],
                "top_k": c["top_k"],
                "index_loss_coef": c["index_loss_coef"]}
