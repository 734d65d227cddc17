#!/usr/bin/env python3
"""What the SDAR cell's comparison sees: the system against references with
one thing wrong, through the runner's own ``check_against_reference``.

    python benchmark/tools/sdar_controls.py [--seeds n,n,...]
        [--controls name,name,...]

At the cell's timed sizes on the chip (one sequence of the configuration's
length; the cell's family, reference, tolerances and seeded draws, as
``runners/train.run`` hands them to ``check_against_reference``), every seed
is compared with ``reference/sdar.py`` as it is (``none``: must be correct),
and then the first seed with each of ``CONTROLS`` patched into the reference
(a control that the limits catch reads ``correct: false``).  One JSON line a
comparison: the runner's verdict, its numbers beside their limits and what
it said.  ``LOSS_ONLY`` controls are compared without gradients, as an
untraced run of the cell compares.  TPUs only; the CPU tests apply the same
patches at a small size (``tests/benchmark_tests/test_benchmark_sdar.py``).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

CELL = "sdar-30b-a3b-s4096-train-1chip"


def _rounded_to(dtype):
    def f(x):             # the value rounded, the gradient passed through
        import jax
        import jax.numpy as jnp
        if jnp.dtype(dtype) == jnp.bfloat16:
            # Not a cast there and back: XLA's simplifier takes that pair
            # out again (excess precision is allowed by default), and the
            # control then reads what the sound reference reads, to the
            # digit (my chip run, PR 39, ``controls_loss.log``).
            rounded = jax.lax.reduce_precision(x, exponent_bits=8,
                                               mantissa_bits=7)
        else:
            rounded = x.astype(dtype).astype(jnp.float32)
        return x + jax.lax.stop_gradient(rounded - x)
    return f


def patches(ref, name: str) -> dict:
    """{attribute of the reference module: its replacement} for one control."""
    import jax
    import jax.numpy as jnp

    def visible_with(noised_sees_clean, noised_sees_noised):
        def visible(q_at, k_at, length, block):
            q_n, k_n = (q_at < length)[:, None], (k_at < length)[None, :]
            q_b = ((q_at % length) // block)[:, None]
            k_b = ((k_at % length) // block)[None, :]
            same = (q_at[:, None] % length) == (k_at[None, :] % length)
            return ((q_n & k_n & noised_sees_noised(q_b, k_b, same))
                    | (q_n & ~k_n & noised_sees_clean(q_b, k_b))
                    | (~q_n & ~k_n & (k_b <= q_b)))
        return visible

    def all_features(t, g, eps):
        """RMSNorm over every feature of q or k, all heads at once."""
        ms = jnp.mean(t * t, axis=(-2, -1), keepdims=True)
        return t * jax.lax.rsqrt(ms + eps) * g

    def bf16_head(x, params, norm_eps):
        """Logits and log-probabilities carried in bf16."""
        to_bf16 = _rounded_to(jnp.bfloat16)
        logits = ref.matmul(ref.rmsnorm(x, params["final_norm"], norm_eps),
                            params["lm_head"].T)
        return to_bf16(jax.nn.log_softmax(to_bf16(logits), axis=-1))

    exact, weighted = ref.matmul, ref.loss

    def unweighted(params, tokens, labels, weights, **architecture):
        return weighted(params, tokens, labels, jnp.ones_like(weights),
                        **architecture)

    def matmul_in(dtype):
        to = _rounded_to(dtype)
        return lambda a, b: exact(to(a), to(b))

    table = {
        "none": {},
        # The clean copy of block b visible to its own noised block: the
        # answer leaks.
        "the_answer_leaks": {"visible": visible_with(
            lambda q_b, k_b: k_b <= q_b, lambda q_b, k_b, same: q_b == k_b)},
        # A noised position sees itself and none of its block's other
        # noised keys.
        "own_noised_block_dropped": {"visible": visible_with(
            lambda q_b, k_b: k_b < q_b, lambda q_b, k_b, same: same)},
        "positions_not_wrapped": {"positions": lambda doubled: jnp.arange(
            doubled)},
        "qk_norm_over_all_features": {"head_norm": all_features},
        "weights_ignored": {"loss": unweighted},
        "a_bf16_head": {"head": bf16_head},
        "matmuls_in_e4m3": {"matmul": matmul_in(jnp.float8_e4m3fn)},
        "matmuls_in_e5m2": {"matmul": matmul_in(jnp.float8_e5m2)},
    }
    return table[name]


CONTROLS = ("none", "the_answer_leaks", "own_noised_block_dropped",
            "positions_not_wrapped", "weights_ignored",
            "qk_norm_over_all_features", "a_bf16_head", "matmuls_in_e4m3",
            "matmuls_in_e5m2")


@contextlib.contextmanager
def patched(ref, name: str):
    changes = patches(ref, name)
    saved = {attr: getattr(ref, attr) for attr in changes}
    for attr, new in changes.items():
        setattr(ref, attr, new)
    try:
        yield
    finally:
        for attr, old in saved.items():
            setattr(ref, attr, old)


# Compared on the loss alone: the gradients pass straight through the
# rounding of ``a_bf16_head``; ignored weights and e5m2 are the loss limit's.
LOSS_ONLY = ("weights_ignored", "a_bf16_head", "matmuls_in_e5m2")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="2147483700")
    ap.add_argument("--controls", default=",".join(CONTROLS))
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from benchmark import loader
    from horovod_tpu.parallel.mesh import create_mesh
    if jax.devices()[0].platform != "tpu":
        print("sdar_controls: TPUs only", file=sys.stderr)
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
                    gradients=name not in LOSS_ONLY)
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
