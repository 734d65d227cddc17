"""SmallThinker-21BA3B-Instruct (``smallthinker``) for training, as one rank
of its deployment: ``horovod_tpu.models.transformer`` with periods of one
full-attention layer without any position encoding and three sliding-window
layers with rotary positions, each followed by an expert MLP ("*EWEWEWE":
softmax router over 64, top-6 renormalised, 16 experts held here, ReLU-gated,
no shared expert) whose router reads the layer's input, ahead of the
attention, built through the path a user takes: ``TransformerConfig`` /
``ParallelConfig`` -> ``init_params`` -> ``make_train_step`` /
``make_loss_fn`` on a ``(dp, pp, mp)`` mesh.

The arithmetic below counts what THIS chip computes (the experts and the
vocabulary slice it holds; attention and the routers whole) and is the
benchmark's yardstick: deliberately a copy, not an import.  A later PR may
change ``models/transformer.py`` and may not change this file.
"""

from __future__ import annotations

import math

import numpy as np

MESH_AXES = ("dp", "pp", "mp")
DTYPE_BYTES = {"bfloat16": 2, "float32": 4}
ATTENTION, MLPS = "*W", "E"


def band_pairs(c: dict) -> float:
    """(query, key) pairs a sequence has under the window: every query its
    ``window`` keys, less what the first ``window - 1`` queries lack."""
    w = min(c["attn_window"], c["seq_len"])
    return w * c["seq_len"] - w * (w - 1) / 2.0


def routed_experts_per_token(c: dict) -> float:
    """Experts held here that a token visits, in the mean: 6 x 16 / 64."""
    return c["top_k"] * c["n_experts_held"] / c["n_experts"]


def block_flops_per_token(c: dict) -> dict:
    """Forward matmul FLOPs one token needs in one block of each kind.

    *, W: q, k, v, o projections; scores and values 4 hd a (query, key) pair
    and query head, over the causal half of the sequence (S / 2 pairs a
    query) or the band.  E: the router over all its outputs and top_k x held
    / routed experts of three matmuls each."""
    d, s, hd = c["d_model"], c["seq_len"], c["attn_head_dim"]
    hq, hkv = c["n_heads"], c["n_kv_heads"]

    def attention(pairs_a_query):
        return (2.0 * d * hd * (2 * hq + 2 * hkv)
                + 4.0 * pairs_a_query * hq * hd)

    return {
        "*": attention(s / 2.0),
        "W": attention(band_pairs(c) / s),
        "E": (2.0 * d * c["n_experts"]
              + routed_experts_per_token(c) * 6.0 * d * c["d_ff"]),
    }


def blocks(c: dict) -> str:
    """Every block of the model as built, one letter each, in order."""
    return (c["n_layers"] // len(c["layer_pattern"])) * c["layer_pattern"]


def model_flops_per_token(c: dict) -> float:
    """Matmul FLOPs one training token requires on this chip, forward +
    backward (3 x forward), recompute not counted, the sliced head once."""
    per = block_flops_per_token(c)
    return 3.0 * (sum(per[letter] for letter in blocks(c))
                  + 2.0 * c["d_model"] * c["vocab_size"])


def _kernel_cost(c: dict, seq_heads: float, pairs: float) -> dict:
    """The three flash kernels' cost for ``seq_heads`` (sequence, query
    head) calls of ``pairs`` live (query, key) pairs each: the flagship's
    count — six matmuls forward + backward of 2 hd FLOPs a pair, the
    forward counted once; q, k, v, o, do, dq, dk, dv once each in the
    compute type plus the fp32 row statistics.  K and V are repeated to
    the query heads before the kernels, so the kernels move a K / V a query
    head, as counted here."""
    s, hd = c["seq_len"], c["attn_head_dim"]
    item = DTYPE_BYTES[c["dtype"]]
    return {"flops": seq_heads * 12.0 * pairs * hd,
            "bytes": seq_heads * (12.0 * s * hd * item + 2.0 * s * 4)}


def window_attention_cost(c: dict, seqs_per_device: float) -> dict:
    """The windowed kernels' cost: the band's pairs only."""
    return _kernel_cost(
        c, seqs_per_device * blocks(c).count("W") * c["n_heads"],
        band_pairs(c))


def full_attention_cost(c: dict, seqs_per_device: float) -> dict:
    """The full layers' kernels: the causal half of S x S."""
    return _kernel_cost(
        c, seqs_per_device * blocks(c).count("*") * c["n_heads"],
        c["seq_len"] ** 2 / 2.0)


def attention_cost(c: dict, seqs_per_device: float) -> dict:
    """Both kinds of call, which ``attn_kernel_ms_per_step`` times together
    (the windowed kernels' names start with the full ones')."""
    full, win = (full_attention_cost(c, seqs_per_device),
                 window_attention_cost(c, seqs_per_device))
    return {k: full[k] + win[k] for k in ("flops", "bytes")}


def pattern_at_depth(pattern: str, n_layers: int) -> str:
    """The period the model is built with: the configuration's where the
    depth is whole periods (the cell); where a test's rehearsal lays a
    smaller depth over the configuration, the period's first ``n_layers``
    blocks: two are the full layer with its experts, four add a windowed
    one."""
    return pattern if not n_layers % len(pattern) else pattern[:n_layers]


class Family:
    def __init__(self, config: dict, mesh_shape: dict):
        import jax.numpy as jnp
        from horovod_tpu.models import transformer as tfm
        from benchmark import loader
        if "router_before_attention" not in tfm.TransformerConfig._fields:
            raise loader.BenchmarkError(
                "this program's TransformerConfig has no "
                "router_before_attention: its expert blocks cannot route on "
                "the stream their layer received, so it cannot train "
                "SmallThinker")
        pattern = pattern_at_depth(config["layer_pattern"],
                                   config["n_layers"])
        # The key / value heads a rehearsal's fewer query heads can share;
        # the configuration's own 4 under its 28.
        self.c = c = {**config, "layer_pattern": pattern,
                      "n_kv_heads": math.gcd(config["n_heads"],
                                             config["n_kv_heads"])}
        if any(a not in ATTENTION or m not in MLPS for a, m in zip(
                blocks(c)[::2], blocks(c)[1::2])) or len(blocks(c)) % 2:
            raise ValueError(f"blocks {blocks(c)!r} are not layers of one "
                             "attention and one expert MLP each")
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
            expert_activation=c["expert_activation"],
            layer_pattern=pattern, learned_positions=False,
            rope_theta=None, n_kv_heads=c["n_kv_heads"],
            attn_head_dim=c["attn_head_dim"], attn_window=c["attn_window"],
            window_rope_theta=float(c["window_rope_theta"]),
            router_scoring=c["router_scoring"],
            router_renormalise=c["router_renormalise"],
            router_before_attention=c["router_before_attention"],
            n_experts_held=c["n_experts_held"],
            expert_buffer_factor=c["expert_buffer_factor"])
        self.par = tfm.ParallelConfig(**self.mesh_shape)
        self.dp = self.mesh_shape["dp"]
        self.tokens_per_seq = c["seq_len"]
        # The reference check's sequences for each data-parallel rank.
        self.check_seqs_per_rank = 1

    # -- the normal path ---------------------------------------------------
    def param_specs(self):
        return self.tfm.param_specs(self.cfg, self.par)

    def init_params(self, key):
        """Seeded weights that route as a deployment's do, by one change to
        what ``init_params`` draws (a fact about the weights: the program
        and the reference see the same tree): the embedding table over
        its own RMS (x 50), to unit RMS, as the SDAR cell's.  Drawn at 0.02 it
        is a fraction of what the first attention block writes into the
        residual, and that block is a full layer without positions: past
        it every position is the running mean of the values before it, so
        from the third layer on all 16,384 choose the same 6 experts of 64
        (busiest / mean 9.8 of a possible 10.7) and the 16 held here get
        nothing or everything, seed by seed (PERF.md section 6, PR 46).
        At unit RMS a position is its own token first, as a trained
        model's is, its experts follow its id in every layer, and the
        router's unnormed operand gives logits of unit size.  And every
        block's norm gain is drawn uniform in (0.5, 1.5), a feature each."""
        import jax
        import jax.numpy as jnp
        params = self.tfm.init_params(key, self.cfg, self.par)
        keys = iter(jax.random.split(jax.random.fold_in(key, 1), 8))

        def gains_off_one(path, leaf):
            # A trained model's norm gains are not 1; at 1 and unit RMS a
            # block's normed input is its input, and a router that read the
            # one could not be told from a router that reads the other.
            if jax.tree_util.keystr(path).endswith("['ln']"):
                return leaf * jax.random.uniform(
                    next(keys), leaf.shape, leaf.dtype, 0.5, 1.5)
            return leaf

        return {**params,
                "embed": params["embed"] * jax.lax.rsqrt(
                    jnp.mean(params["embed"] ** 2)),
                "layers": jax.tree_util.tree_map_with_path(
                    gains_off_one, params["layers"])}

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
        """The attention kernels' cost for both kinds of layer, and under
        a key of their own the windowed kernels' alone
        (``metrics/window_attn_kernel_roofline``; the runner hands readers
        this dict only).  No cost of the held experts' grouped matmuls: no
        metric of this cell reads one (``moe_expert_matmul_roofline`` lists
        the cells it had), and the PR that appends the cell there writes
        it."""
        seqs = global_batch / self.dp
        cost = attention_cost(self.c, seqs)
        cost["window_attention"] = window_attention_cost(self.c, seqs)
        return cost

    # -- the reference ---------------------------------------------------------
    def to_reference(self, tree):
        """The system stacks a kind's blocks as (1 stage, periods, blocks of
        the kind in a period, ...); the reference takes a list of layers, an
        attention and an expert MLP each, under its own names."""
        layers = tree["layers"]
        names = {"attn": {"ln": "ln", "wq": "wq", "wk": "wk", "wv": "wv",
                          "wo": "wo"},
                 "moe": {"ln": "ln", "gate": "router", "w_gate": "w1",
                         "w_up": "w3", "w_down": "w2"}}
        names["swa"] = names["attn"]
        kinds = {letter: kind for letter, (kind, _scope) in
                 self.tfm.BLOCK_KINDS.items()}
        pattern = self.c["layer_pattern"]
        halves = []
        for p in range(self.c["n_layers"] // len(pattern)):
            seen = {}
            for letter in pattern:
                kind = kinds[letter]
                j = seen.get(kind, 0)
                seen[kind] = j + 1
                halves.append({names[kind][k]: v[0, p, j]
                               for k, v in layers[kind].items()})
        return {**{k: v for k, v in tree.items() if k != "layers"},
                "layers": [{"attn": a, "mlp": m}
                           for a, m in zip(halves[::2], halves[1::2])]}

    def reference_args(self) -> dict:
        c = self.c
        return {"layer_types": tuple(
                    "sliding" if letter == "W" else "full"
                    for letter in blocks(c)[::2]),
                "norm_eps": c["norm_eps"], "n_kv_heads": c["n_kv_heads"],
                "head_dim": c["attn_head_dim"], "window": c["attn_window"],
                "rope_theta": float(c["window_rope_theta"]),
                "top_k": c["top_k"]}
