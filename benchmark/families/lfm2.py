"""LFM2-24B-A2B (``lfm2_moe``) for training, as one rank of its deployment:
``horovod_tpu.models.transformer`` with a leading gated short-convolution +
dense-MLP layer ("CD") in front of periods of one grouped-query attention
layer and three convolution layers, each with an expert MLP ("*ECECECE":
sigmoid router over 64, top-4 by score + bias, renormalised over sum + 1e-6,
8 experts held here, no shared expert), per-head QK-norm and rotary
positions, a tied head, built through the path a user takes:
``TransformerConfig`` / ``ParallelConfig`` -> ``init_params`` ->
``make_train_step`` / ``make_loss_fn`` on a ``(dp, pp, mp)`` mesh.

The arithmetic below counts what THIS chip computes (the experts and the
vocabulary slice it holds; the operators, the dense MLP and the routers
whole) and is the benchmark's yardstick: deliberately a copy, not an import.
A later PR may change ``models/transformer.py`` and may not change this
file.
"""

from __future__ import annotations

import math

import numpy as np

MESH_AXES = ("dp", "pp", "mp")
DTYPE_BYTES = {"bfloat16": 2, "float32": 4}
OPERATORS, MLPS = "C*", "DE"


def routed_experts_per_token(c: dict) -> float:
    """Experts held here that a token visits, in the mean: 4 x 8 / 64."""
    return c["top_k"] * c["n_experts_held"] / c["n_experts"]


def block_flops_per_token(c: dict) -> dict:
    """Forward matmul FLOPs one token needs in one block of each kind.

    C: the in-projection to [B | C | u] (d x 3 d) and the out-projection
    (d x d), and the convolution's taps (2 a tap and channel).
    *: q, k, v, o projections; scores and values 4 hd a (query, key) pair
    and query head over the causal half of the sequence (S / 2 pairs a
    query).
    D: three matmuls at the dense width.  E: the router over all its
    outputs and top_k x held / routed experts of three matmuls each."""
    d, s, hd = c["d_model"], c["seq_len"], c["attn_head_dim"]
    hq, hkv = c["n_heads"], c["n_kv_heads"]
    return {
        "C": 8.0 * d * d + 2.0 * c["conv_taps"] * d,
        "*": 2.0 * d * hd * (2 * hq + 2 * hkv) + 4.0 * (s / 2.0) * hq * hd,
        "D": 6.0 * d * c["dense_ff"],
        "E": (2.0 * d * c["n_experts"]
              + routed_experts_per_token(c) * 6.0 * d * c["d_ff"]),
    }


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


def attention_cost(c: dict, seqs_per_device: float) -> dict:
    """The three flash kernels' cost a step by the accepted
    ``attn_kernel_roofline``'s count (the flagship's: six matmuls forward +
    backward of 2 hd FLOPs a pair over the causal half, the score tiles'
    recompute not counted; q, k, v, o forward and q, k, v, o, do, dq, dk,
    dv backward once each in the compute type plus the fp32 row
    statistics).  K and V are repeated to the query heads before the
    kernels, so the kernels move a K / V a query head, as counted here."""
    s, hd = c["seq_len"], c["attn_head_dim"]
    item = DTYPE_BYTES[c["dtype"]]
    calls = seqs_per_device * blocks(c).count("*") * c["n_heads"]
    return {"flops": calls * 12.0 * s * s * hd * 0.5,
            "bytes": calls * (12.0 * s * hd * item + 2.0 * s * 4)}


def patterns_at_depth(leading: str, pattern: str, n_layers: int):
    """(leading blocks, period) the model is built with: the
    configuration's where the depth is the leading blocks and whole periods
    (the cell); where a test's rehearsal lays a smaller depth over the
    configuration, no leading blocks and the period's first ``n_layers``
    blocks: two are the attention layer with its experts, four add a
    convolution layer."""
    if n_layers >= len(leading) + len(pattern) and not (
            n_layers - len(leading)) % len(pattern):
        return leading, pattern
    return "", pattern[:n_layers]


def heads_at_their_own_scales(key, w, head_dim: int):
    """``w`` (..., d, heads x head_dim) with each head's columns times 2^u,
    u uniform in (-1, 1).  Under a norm over each head's features the
    factor cancels; under one over all of q's or k's features the larger
    heads take the scores over.  Seeded heads all have one size to within
    9 %, so that fault would move no gradient leaf by more than the router's
    own flips do (PERF.md section 6, PR 41); a trained model's heads differ
    in size, which is what the per-head norm is for."""
    import jax
    heads = w.shape[-1] // head_dim
    u = jax.random.uniform(key, (*w.shape[:-2], 1, heads, 1), w.dtype, -1.0,
                           1.0)
    return (w.reshape(*w.shape[:-1], heads, head_dim) * 2.0 ** u).reshape(
        w.shape)


class Family:
    def __init__(self, config: dict, mesh_shape: dict):
        import jax.numpy as jnp
        from horovod_tpu.models import transformer as tfm
        from benchmark import loader
        missing = {"conv_taps", "router_renorm_eps"} - set(
            tfm.TransformerConfig._fields)
        if missing or "C" not in tfm.BLOCK_KINDS:
            raise loader.BenchmarkError(
                "this program's TransformerConfig has no gated "
                f"short-convolution block (no {sorted(missing) or 'C'}): it "
                "cannot train LFM2")
        if config["conv_bias"]:
            raise loader.BenchmarkError(
                "conv_bias is true: the \"C\" block's convolution has no "
                "bias, as the published lfm2_moe config has none")
        leading, pattern = patterns_at_depth(
            config["leading_pattern"], config["layer_pattern"],
            config["n_layers"])
        # The key / value heads a rehearsal's fewer query heads can share;
        # the configuration's own 8 under its 32.
        self.c = c = {**config, "leading_pattern": leading,
                      "layer_pattern": pattern,
                      "n_kv_heads": math.gcd(config["n_heads"],
                                             config["n_kv_heads"])}
        if any(a not in OPERATORS or m not in MLPS for a, m in zip(
                blocks(c)[::2], blocks(c)[1::2])) or len(blocks(c)) % 2:
            raise ValueError(f"blocks {blocks(c)!r} are not layers of one "
                             "operator and one MLP each")
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
            layer_pattern=pattern, leading_pattern=leading,
            learned_positions=False, n_kv_heads=c["n_kv_heads"],
            attn_head_dim=c["attn_head_dim"],
            rope_theta=float(c["rope_theta"]),
            head_qk_norm=c["head_qk_norm"],
            router_scoring=c["router_scoring"],
            router_renormalise=c["router_renormalise"],
            router_renorm_eps=float(c["router_renorm_eps"]),
            router_scale=float(c["router_scale"]),
            n_experts_held=c["n_experts_held"],
            expert_buffer_factor=c["expert_buffer_factor"],
            dense_ff=c["dense_ff"], conv_taps=c["conv_taps"])
        self.par = tfm.ParallelConfig(**self.mesh_shape)
        self.dp = self.mesh_shape["dp"]
        self.tokens_per_seq = c["seq_len"]
        # The reference check's sequences for each data-parallel rank.
        self.check_seqs_per_rank = 1

    # -- the normal path ---------------------------------------------------
    def param_specs(self):
        return self.tfm.param_specs(self.cfg, self.par)

    def init_params(self, key):
        """Seeded weights, the query and key heads each at a size of its
        own (``heads_at_their_own_scales``), then the routers' correction bias
        (``use_expert_bias``) balanced on one seeded sequence a
        data-parallel rank (``make_router_balancer``): a deployment's
        routing is balanced, which is what the bias is for, and a seeded
        router's is not — its held experts' load, and with it the step
        time, would follow the seed (PERF.md section 6, PRs 31 and 41)."""
        import jax
        import jax.numpy as jnp
        from horovod_tpu.parallel.mesh import create_mesh
        params = self.tfm.init_params(key, self.cfg, self.par)
        attn = params["layers"]["attn"]
        for i, name in enumerate(("wq", "wk")):
            attn[name] = heads_at_their_own_scales(
                jax.random.fold_in(key, 2 + i), attn[name],
                self.c["attn_head_dim"])
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
        """The attention kernels' cost by the accepted count, and under a
        key of its own the least traffic of the convolution blocks' gate
        path (``metrics/short_conv_gate_roofline``: the runner hands
        readers this dict only; the count is ``trace/lfm2.py``'s)."""
        from benchmark.trace import lfm2 as trace
        seqs = global_batch / self.dp
        cost = attention_cost(self.c, seqs)
        cost[trace.GATE_COST] = trace.gate_path_cost(
            self.c, blocks(self.c).count("C"), seqs * self.c["seq_len"])
        return cost

    # -- the reference ---------------------------------------------------------
    def to_reference(self, tree):
        """The system stacks a kind's blocks as (1 stage, periods, blocks of
        the kind in a period, ...) and the leading blocks as (1 stage,
        blocks of the kind, ...); the reference takes a list of layers, an
        operator and an MLP each, under its own names for the weights.  The
        router's correction bias travels as one more row of ``router`` (the
        reference's layout): a buffer outside the gradient has a zero
        gradient on both sides, and 0 / 0 is no comparison, while a row of
        zeros under the router's own rows is one."""
        import jax.numpy as jnp
        layers = tree["layers"]
        names = {
            "conv": {"ln": "ln", "w_in": "w_in", "conv_w": "conv",
                     "w_out": "w_out"},
            "attn": {"ln": "ln", "wq": "wq", "wk": "wk", "wv": "wv",
                     "q_norm": "q_norm", "k_norm": "k_norm", "wo": "wo"},
            "dense": {"ln": "ln", "w_gate": "w1", "w_up": "w3",
                      "w_down": "w2"},
            "moe": {"ln": "ln", "gate": "router", "w_gate": "w1",
                    "w_up": "w3", "w_down": "w2"}}
        kinds = {letter: kind for letter, (kind, _scope) in
                 self.tfm.BLOCK_KINDS.items()}

        def renamed(kind, leaves):
            leaves = dict(leaves)
            if kind == "moe":
                leaves["gate"] = jnp.concatenate(
                    [leaves["gate"], leaves.pop("router_bias")[None, :]], 0)
            return {names[kind][k]: v for k, v in leaves.items()}

        def walk(pattern, leaves_of, seen):
            for letter in pattern:
                kind = kinds[letter]
                j = seen.get(kind, 0)
                seen[kind] = j + 1
                yield renamed(kind, leaves_of(kind, j))

        c = self.c
        halves = list(walk(
            c["leading_pattern"], lambda kind, j: {
                k: v[0, j] for k, v in layers["leading"][kind].items()}, {}))
        for p in range(n_periods(c)):
            halves += walk(c["layer_pattern"], lambda kind, j: {
                k: v[0, p, j] for k, v in layers[kind].items()}, {})
        return {**{k: v for k, v in tree.items() if k != "layers"},
                "layers": [{"op": a, "ffn": m}
                           for a, m in zip(halves[::2], halves[1::2])]}

    def reference_args(self) -> dict:
        c = self.c
        return {"norm_eps": c["norm_eps"], "n_kv_heads": c["n_kv_heads"],
                "head_dim": c["attn_head_dim"],
                "rope_theta": float(c["rope_theta"]), "top_k": c["top_k"],
                "renorm_eps": float(c["router_renorm_eps"]),
                "router_scale": float(c["router_scale"])}
