#!/usr/bin/env python3
"""What the Keye-VL cell's comparison sees: the system against references
with one thing wrong, through the runner's own ``check_against_reference``;
and what its selection looks like on seeded weights.

    python benchmark/tools/keye_vl_controls.py [--seeds n,n,...]
        [--controls name,name,...] [--loss-only] [--selection]

At the cell's timed sizes on the chip (one sequence of the configuration's
length; the cell's family, reference, tolerances and seeded draws, as
``runners/train.run`` hands them to ``check_against_reference``), every seed
is compared with ``reference/keye_vl.py`` as it is (``none``: must be
correct), and then the first seed with each of ``CONTROLS`` patched into the
reference (a control that the limits catch reads ``correct: false``).  One
JSON line a comparison: the runner's verdict, its numbers beside their
limits and what it said.

``--selection`` prints instead, a seed a line, what the first layer's
indexer chooses on the family's seeded weights (its input is the embedding,
the same in program and reference): the share of a query's choice that the
next query shares, the exact ties at the threshold, and the (query, key)
pairs on which the program's set at the configuration's compute type
differs from the set of the same scores computed in fp32 at the highest
precision.  TPUs only; the CPU tests apply the same patches at a small size
(``tests/benchmark_tests/test_benchmark_keye_vl.py``).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path
from unittest import mock

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import loader                      # noqa: E402

CELL = "keye-vl-2.0-30b-a3b-s16384-train-1chip"


def rounded_to(dtype):
    """x -> x rounded to ``dtype``, the gradient passed through."""
    def f(x):
        import jax
        import jax.numpy as jnp
        return x + jax.lax.stop_gradient(
            x.astype(dtype).astype(jnp.float32) - x)
    return f


def patches(ref, name: str, block: int = 512) -> dict:
    """{attribute of the reference module: its replacement} for one control;
    ``block``: the keys a block of ``choice_by_block_of_512`` (a test's
    short sequence takes fewer)."""
    import jax.numpy as jnp

    choose, exact, plain = ref.choose, ref.attention_matmul, ref.matmul

    def by_block(block: int):
        """The ``topk / block`` blocks of keys whose best score is highest,
        whole: a choice by block of ``block``, not by key."""
        def chosen(scores, seen, topk):
            t, s = scores.shape
            n, keep = s // block, max(topk // block, 1)
            best = jnp.max(jnp.where(seen, scores, -jnp.inf).reshape(
                t, n, block), axis=-1)
            blocks = choose(best, jnp.isfinite(best), keep)
            return jnp.repeat(blocks, block, axis=1) & seen
        return chosen

    def on_8_bit_scores(scores, seen, topk):
        """The choice made on scores quantised to 256 levels a row."""
        lo = jnp.min(jnp.where(seen, scores, jnp.inf), -1, keepdims=True)
        hi = jnp.max(jnp.where(seen, scores, -jnp.inf), -1, keepdims=True)
        levels = jnp.round((scores - lo) / jnp.maximum(hi - lo, 1e-30) * 255)
        return choose(levels, seen, topk)

    def every_causal_key(scores, seen, topk):
        return seen

    def one_key_short(scores, seen, topk):
        return choose(scores, seen, topk - 1)

    def ties_to_the_earlier_key(scores, seen, topk):
        return choose(scores[:, ::-1], seen[:, ::-1], topk)[:, ::-1]

    def rounding(product, dtype):
        to = rounded_to(dtype)
        return lambda a, b: product(to(a), to(b))

    table = {
        "none": {},
        "choice_by_block_of_512": {"choose": by_block(block)},
        "choice_on_8_bit_scores": {"choose": on_8_bit_scores},
        "no_selection": {"choose": every_causal_key},
        "index_loss_dropped": {"index_loss_of": lambda kl: 0.0 * kl},
        "indexer_input_not_detached": {"indexer_input": lambda n: n},
        "pbar_not_detached": {"heads_mean": lambda p: jnp.mean(p, axis=0)},
        "sections_rotated": {
            "stream_sections": lambda s: tuple(s[1:]) + tuple(s[:1])},
        "positions_are_the_index": {
            "position_streams": lambda p: jnp.broadcast_to(
                jnp.arange(p.shape[-1], dtype=p.dtype), p.shape)},
        "attention_matmuls_in_e4m3": {
            "attention_matmul": rounding(exact, jnp.float8_e4m3fn)},
        "matmuls_in_e4m3": {
            "attention_matmul": rounding(exact, jnp.float8_e4m3fn),
            "matmul": rounding(plain, jnp.float8_e4m3fn)},
        "topk_one_key_short": {"choose": one_key_short},
        "ties_to_the_earlier_key": {"choose": ties_to_the_earlier_key},
    }
    return table[name]


# The last two are the fine ones: refused by the CPU tests at fp32 compute,
# reported on the chip.
CONTROLS = ("none", "choice_by_block_of_512", "choice_on_8_bit_scores",
            "no_selection", "index_loss_dropped",
            "indexer_input_not_detached", "pbar_not_detached",
            "sections_rotated", "positions_are_the_index",
            "attention_matmuls_in_e4m3", "matmuls_in_e4m3",
            "topk_one_key_short", "ties_to_the_earlier_key")


@contextlib.contextmanager
def patched(ref, name: str, **sizes):
    """The control in place until the block ends (``sizes``: ``patches``'
    own).  JAX keeps what it traced of a function it has seen, whatever the
    globals that function reads have become: its caches are emptied on the
    way in and on the way out."""
    import jax
    jax.clear_caches()
    try:
        with contextlib.ExitStack() as stack:
            for attr, new in patches(ref, name, **sizes).items():
                stack.enter_context(mock.patch.object(ref, attr, new))
            yield
    finally:
        jax.clear_caches()


def selection(fam, params, batch) -> dict:
    """What the first layer's indexer chooses for the first sequence of
    ``batch`` (see the module docstring), through the program's own
    ``_index`` and ``ops/sparse_index``."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.ops import sparse_index as si
    tfm, cfg = fam.tfm, fam.cfg
    topk = cfg.index_topk
    tokens, _labels, positions = (jnp.asarray(x[:1]) for x in batch)
    lp = jax.tree_util.tree_map(lambda a: a[0, 0, 0], params["layers"]["sel"])
    a = tfm.BLOCKS["S"].attention(cfg)

    def operands(dtype):
        x = params["embed"][tokens].astype(dtype)
        return tfm._index(cfg, lp, tfm._rmsnorm(x, lp["ln"], cfg.norm_eps),
                          positions, a)

    def one(qi, ki, w):
        s = qi.shape[1]
        tile = min(si.Q_TILE, s)
        visible, ties = [], 0
        for i in range(s // tile):
            rows = slice(i * tile, (i + 1) * tile)
            scores = si.tile_scores(qi[0, rows], w[0, rows], ki[0])
            chosen = si.select_tile(scores, i * tile, topk)
            causal = (jnp.arange(s)[None, :]
                      <= i * tile + jnp.arange(tile)[:, None])
            keys = jnp.where(causal, si._ordered(scores), 0)
            kth = si._kth_largest(keys, topk)[:, None]
            ties += jnp.sum((jnp.sum(keys >= kth, -1) > topk)
                            & (kth[:, 0] > 0))
            visible.append(chosen)
        return jnp.concatenate(visible), ties

    system, ties = jax.jit(one)(*operands(cfg.dtype))
    with jax.default_matmul_precision("highest"):
        exact, _ = jax.jit(one)(*operands(jnp.float32))
    past = slice(topk, None)      # the queries that choose at all
    shared = jnp.sum(system[past][1:] & system[past][:-1], -1) / topk
    return {"queries_that_choose": int(system[past].shape[0]),
            "neighbour_share_mean": float(jnp.mean(shared)),
            "neighbour_share_min": float(jnp.min(shared)),
            "rows_with_a_tie_at_the_threshold": int(ties),
            "pairs_chosen": int(jnp.sum(system)),
            "pairs_that_differ_from_fp32_scores": int(
                jnp.sum(system != exact)) // 2,
            "queries_whose_set_differs": int(
                jnp.sum(jnp.any(system != exact, -1)))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="2147483700")
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ap.add_argument("--loss-only", action="store_true",
                    help="compare as an untraced run does: no gradients")
    ap.add_argument("--selection", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.parallel.mesh import create_mesh
    if jax.devices()[0].platform != "tpu":
        print("keye_vl_controls: TPUs only", file=sys.stderr)
        return 1
    hvd.init()
    try:
        train = loader.load_code("runners", "train")
        cell = loader.load_cell(CELL)
        config = cell["config"]
        fam = loader.load_code("families", config["family"]).Family(
            config, cell["traffic"]["mesh"])
        ref = loader.load_code("reference", config["family"])
        mesh = create_mesh(fam.mesh_shape, devices=jax.devices()[:1])
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), fam.param_specs(),
            is_leaf=lambda x: isinstance(x, P))
        init = jax.jit(fam.init_params, out_shardings=shardings)
        data = NamedSharding(mesh, P("dp"))
        seeds = [int(s) for s in args.seeds.split(",")]
        names = args.controls.split(",")
        if args.selection:
            for seed in seeds:
                params = init(jax.random.PRNGKey(seed))
                batch = fam.draw_batch(np.random.default_rng([seed, 1, 0]), 1)
                print(json.dumps({"seed": seed,
                                  **selection(fam, params, batch)}),
                      flush=True)
            return 0
        # Every seed against the reference as it is, then the first seed
        # against each control.
        for seed, name in ([(s, "none") for s in seeds if "none" in names]
                           + [(seeds[0], n) for n in names if n != "none"]):
            params = init(jax.random.PRNGKey(seed))
            batch = fam.draw_batch(np.random.default_rng([seed, 1, 0]),
                                   fam.dp * fam.check_seqs_per_rank)
            said = []
            with patched(ref, name):
                ok, compared = train.check_against_reference(
                    fam, ref, mesh, params, batch, data, said.append,
                    gradients=not args.loss_only)
            print(json.dumps({"control": name, "seed": seed,
                              "correct": bool(ok), **compared,
                              "said": said}), flush=True)
            # The next comparison's programs need the room this one's
            # parameters and loaded executables take.
            del params
            jax.clear_caches()
    finally:
        hvd.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
