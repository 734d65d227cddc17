"""Laguna-S-2.1 (``laguna``) for training, as one rank of its deployment:
``horovod_tpu.models.transformer`` with a leading full-attention + dense-MLP
layer ("*D") in front of periods of three sliding-window layers and one full
one, each with an expert MLP ("WEWEWE*E": softmax router over 256, top-10
renormalised x 2.5, 8 experts held here, one shared expert), two rotary
schemes and a per-head output gate, built through the path a user takes:
``TransformerConfig`` / ``ParallelConfig`` -> ``init_params`` ->
``make_train_step`` / ``make_loss_fn`` on a ``(dp, pp, mp)`` mesh.

The arithmetic below counts what THIS chip computes (the heads, experts and
vocabulary slice it holds; the dense MLP, shared expert and router whole)
and is the benchmark's yardstick: deliberately a copy, not an import.  A
later PR may change ``models/transformer.py`` and may not change this file.
"""

from __future__ import annotations

import numpy as np

MESH_AXES = ("dp", "pp", "mp")
DTYPE_BYTES = {"bfloat16": 2, "float32": 4}
ATTENTION, MLPS = "*W", "DE"


def band_pairs(c: dict) -> float:
    """(query, key) pairs a sequence has under the window: every query its
    ``window`` keys, less what the first ``window - 1`` queries lack."""
    w = min(c["attn_window"], c["seq_len"])
    return w * c["seq_len"] - w * (w - 1) / 2.0


def block_flops_per_token(c: dict) -> dict:
    """Forward matmul FLOPs one token needs in one block of each kind.

    *, W: q, k, v, o projections of the heads held and the gate's (d x
    heads); scores and values 4 hd a (query, key) pair and query head, over
    the causal half of the sequence (S / 2 pairs a query) or the band.
    D: three matmuls at the dense width.  E: router over all outputs, the
    shared expert's three matmuls, and top_k x held / routed experts of
    three matmuls each."""
    d, s, hd = c["d_model"], c["seq_len"], c["attn_head_dim"]
    hkv = c["n_kv_heads"]

    def attention(hq, pairs_a_query):
        return (2.0 * d * hd * (2 * hq + 2 * hkv) + 2.0 * d * hq
                + 4.0 * pairs_a_query * hq * hd)

    return {
        "*": attention(c["n_heads"], s / 2.0),
        "W": attention(c["window_heads"], band_pairs(c) / s),
        "D": 6.0 * d * c["dense_ff"],
        "E": (2.0 * d * c["n_experts"] + 6.0 * d * c["shared_expert_ff"]
              + routed_experts_per_token(c) * 6.0 * d * c["d_ff"]),
    }


def routed_experts_per_token(c: dict) -> float:
    """Experts held here that a token visits, in the mean: 10 x 8 / 256."""
    return c["top_k"] * c["n_experts_held"] / c["n_experts"]


def n_periods(c: dict) -> int:
    return (c["n_layers"] - len(c["leading_pattern"])) // len(
        c["layer_pattern"])


def blocks(c: dict) -> str:
    """Every block of the model as built, one letter each, in order."""
    return c["leading_pattern"] + n_periods(c) * c["layer_pattern"]


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
        c, seqs_per_device * blocks(c).count("W") * c["window_heads"],
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


def expert_matmul_cost(c: dict, tokens_per_device: float) -> dict:
    """The held experts' three grouped matmuls of every "E" block, forward +
    backward: 3 x rows x 6 d f FLOPs with rows = tokens x 10 x 8 / 256 in
    the mean; the least traffic reads each operand and writes each result
    of the nine matmuls once (OLMoE's count)."""
    d, f = c["d_model"], c["d_ff"]
    item = DTYPE_BYTES[c["dtype"]]
    n_moe = blocks(c).count("E")
    rows = tokens_per_device * routed_experts_per_token(c)
    return {"flops": n_moe * 3.0 * rows * 6.0 * d * f,
            "bytes": n_moe * 9.0 * (rows * d + rows * f
                                    + c["n_experts_held"] * d * f) * item}


def patterns_at_depth(leading: str, pattern: str, n_layers: int):
    """(leading blocks, period) the model is built with: the
    configuration's where the depth is the leading blocks and whole periods
    (every cell); where a test's rehearsal lays a smaller depth over the
    configuration, no leading blocks and the period's first ``n_layers``
    blocks, so that two blocks are a windowed layer with its experts."""
    if n_layers >= len(leading) + len(pattern) and not (
            n_layers - len(leading)) % len(pattern):
        return leading, pattern
    return "", pattern[:n_layers]


class Family:
    def __init__(self, config: dict, mesh_shape: dict):
        import jax.numpy as jnp
        from horovod_tpu.models import transformer as tfm
        leading, pattern = patterns_at_depth(
            config["leading_pattern"], config["layer_pattern"],
            config["n_layers"])
        self.c = c = {**config, "leading_pattern": leading,
                      "layer_pattern": pattern}
        if any(a not in ATTENTION or m not in MLPS for a, m in zip(
                blocks(c)[::2], blocks(c)[1::2])) or len(blocks(c)) % 2:
            raise ValueError(f"blocks {blocks(c)!r} are not layers of one "
                             "attention and one MLP each")
        self.tfm = tfm
        self.mesh_shape = {a: int(mesh_shape[a]) for a in MESH_AXES}
        full, sliding = (c["rope_parameters"][k] for k in
                         ("full_attention", "sliding_attention"))
        self.full_rope = (float(full["rope_theta"]),
                          float(full["partial_rotary_factor"]),
                          float(full["factor"]),
                          int(full["original_max_position_embeddings"]),
                          float(full["beta_fast"]), float(full["beta_slow"]),
                          float(full["attention_factor"]))
        self.cfg = tfm.TransformerConfig(
            vocab_size=c["vocab_size"], d_model=c["d_model"],
            n_heads=c["n_heads"], d_ff=c["d_ff"], n_layers=c["n_layers"],
            seq_len=c["seq_len"], n_experts=c["n_experts"],
            top_k=c["top_k"], attn_mode=c["attn_mode"],
            dtype=jnp.dtype(c["dtype"]), remat=c["remat"],
            norm_eps=c["rms_norm_eps"], dropless=True, tied_head=False,
            gated_experts=True, layer_pattern=pattern,
            leading_pattern=leading, learned_positions=False,
            n_kv_heads=c["n_kv_heads"], attn_head_dim=c["attn_head_dim"],
            rope_theta=self.full_rope[0], rope_fraction=self.full_rope[1],
            rope_yarn=self.full_rope[2:],
            attn_window=c["attn_window"], window_heads=c["window_heads"],
            window_rope_theta=float(sliding["rope_theta"]), attn_gate=True,
            dense_ff=c["dense_ff"], router_scoring="softmax",
            router_renormalise=c["norm_topk_prob"],
            router_scale=float(c["router_scale"]),
            n_experts_held=c["n_experts_held"],
            expert_buffer_factor=c["expert_buffer_factor"],
            shared_expert_ff=c["shared_expert_ff"])
        self.par = tfm.ParallelConfig(**self.mesh_shape)
        self.dp = self.mesh_shape["dp"]
        self.tokens_per_seq = c["seq_len"]
        # The reference check's sequences for each data-parallel rank.
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
        keys of their own the windowed kernels' alone
        (``metrics/window_attn_kernel_roofline``) and the held experts'
        grouped matmuls' (the runner hands readers this dict only)."""
        seqs = global_batch / self.dp
        cost = attention_cost(self.c, seqs)
        cost["window_attention"] = window_attention_cost(self.c, seqs)
        cost["moe_expert_matmul"] = expert_matmul_cost(
            self.c, seqs * self.c["seq_len"])
        return cost

    # -- the reference ---------------------------------------------------------
    def to_reference(self, tree):
        """The system stacks a kind's blocks as (1 stage, periods, blocks of
        the kind in a period, ...) and the leading blocks as (1 stage,
        blocks of the kind, ...); the reference takes a list of layers, an
        attention and an MLP each, under its own names for the weights."""
        layers = tree["layers"]
        names = {
            "attn": {"ln": "ln", "wq": "wq", "wk": "wk", "wv": "wv",
                     "w_head_gate": "wg", "wo": "wo"},
            "dense": {"ln": "ln", "w_gate": "w1", "w_up": "w3",
                      "w_down": "w2"},
            "moe": {"ln": "ln", "gate": "router", "w_gate": "w1",
                    "w_up": "w3", "w_down": "w2", "shared_gate": "s1",
                    "shared_up": "s3", "shared_down": "s2"}}
        names["swa"] = names["attn"]
        kinds = {letter: kind for letter, (kind, _scope) in
                 self.tfm.BLOCK_KINDS.items()}

        def walk(pattern, leaves_of, seen):
            for letter in pattern:
                kind = kinds[letter]
                j = seen.get(kind, 0)
                seen[kind] = j + 1
                yield {names[kind][k]: v for k, v in
                       leaves_of(kind, j).items()}

        c = self.c
        halves = list(walk(
            c["leading_pattern"], lambda kind, j: {
                k: v[0, j] for k, v in layers["leading"][kind].items()}, {}))
        for p in range(n_periods(c)):
            halves += walk(c["layer_pattern"], lambda kind, j: {
                k: v[0, p, j] for k, v in layers[kind].items()}, {})
        return {**{k: v for k, v in tree.items() if k != "layers"},
                "layers": [{"attn": a, "mlp": m}
                           for a, m in zip(halves[::2], halves[1::2])]}

    def reference_args(self) -> dict:
        c = self.c
        return {"layer_types": tuple(
                    "sliding" if letter == "W" else "full"
                    for letter in blocks(c)[::2]),
                "norm_eps": c["rms_norm_eps"],
                "n_kv_heads": c["n_kv_heads"],
                "head_dim": c["attn_head_dim"], "window": c["attn_window"],
                "full_rope": self.full_rope,
                "sliding_theta": float(
                    c["rope_parameters"]["sliding_attention"]["rope_theta"]),
                "top_k": c["top_k"],
                "router_scale": float(c["router_scale"])}
