#!/usr/bin/env python3
"""What the SmallThinker cell's comparison sees: the system against
references with one thing wrong, through the runner's own
``check_against_reference``.

    python benchmark/tools/smallthinker_controls.py [--seeds n,n,...]
        [--controls name,name,...] [--loss-only]

At the cell's timed sizes on the chip (one sequence of the configuration's
length; the cell's family, reference, tolerances and seeded draws, as
``runners/train.run`` hands them to ``check_against_reference``), every seed
is compared with ``reference/smallthinker.py`` as it is (``none``: must be
correct), and then the first seed with each of ``CONTROLS`` patched into the
reference (a control that the limits catch reads ``correct: false``).  One
JSON line a comparison: the runner's verdict, its numbers beside their
limits and what it said.  TPUs only; the CPU tests apply the same patches at
a small size (``tests/test_smallthinker_layers.py``,
``tests/benchmark_tests/test_benchmark_smallthinker.py``).
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

CELL = "smallthinker-21b-a3b-s16384-train-1chip"


def rounded_to(dtype):
    """x -> x rounded to ``dtype``, the gradient passed through."""
    def f(x):
        import jax
        import jax.numpy as jnp
        return x + jax.lax.stop_gradient(
            x.astype(dtype).astype(jnp.float32) - x)
    return f


def patches(ref, name: str) -> dict:
    """{attribute of the reference module: its replacement} for one control."""
    import jax
    import jax.numpy as jnp

    attention, exact = ref.attention, ref.matmul

    def window_off_by(keys: int):
        def attend(q, k, v, window, **kw):
            return attention(q, k, v, window and window + keys, **kw)
        return attend

    def rotated_everywhere(q, k, sliding, theta):
        return ref.rope(q, theta), ref.rope(k, theta)

    def normed_after_attention(x, y, mp, norm_eps):
        return ref.rmsnorm(y, mp["ln"], norm_eps)

    def matmul_in(dtype):
        to = rounded_to(dtype)
        return lambda a, b: exact(to(a), to(b))

    table = {
        "none": {},
        "window_one_key_short": {"attention": window_off_by(-1)},
        "window_one_key_long": {"attention": window_off_by(1)},
        "full_layers_rotated": {"positioned": rotated_everywhere},
        "silu_for_relu": {"gate_activation": jax.nn.silu},
        "router_reads_the_normed_stream_after_attention": {
            "router_operand": normed_after_attention},
        "matmuls_in_bf16": {"matmul": matmul_in(jnp.bfloat16)},
        "matmuls_in_e4m3": {"matmul": matmul_in(jnp.float8_e4m3fn)},
        "matmuls_in_e5m2": {"matmul": matmul_in(jnp.float8_e5m2)},
    }
    return table[name]


CONTROLS = ("none", "window_one_key_short", "window_one_key_long",
            "full_layers_rotated", "silu_for_relu",
            "router_reads_the_normed_stream_after_attention",
            "matmuls_in_bf16", "matmuls_in_e4m3", "matmuls_in_e5m2")


@contextlib.contextmanager
def patched(ref, name: str):
    """The control in place until the block ends.  JAX keeps what it traced
    of a function it has seen (``jax.checkpoint(reglu)``), whatever the
    globals that function reads have become: its caches are emptied on the
    way in and on the way out."""
    import jax
    jax.clear_caches()
    try:
        with contextlib.ExitStack() as stack:
            for attr, new in patches(ref, name).items():
                stack.enter_context(mock.patch.object(ref, attr, new))
            yield
    finally:
        jax.clear_caches()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="2147483700")
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ap.add_argument("--loss-only", action="store_true",
                    help="compare as an untraced run does: no gradients")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.parallel.mesh import create_mesh
    if jax.devices()[0].platform != "tpu":
        print("smallthinker_controls: TPUs only", file=sys.stderr)
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
