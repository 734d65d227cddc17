#!/usr/bin/env python3
"""What the LFM2 cell's comparison sees: the system against references with
one thing wrong, through the runner's own ``check_against_reference``.

    python benchmark/tools/lfm2_controls.py [--seeds n,n,...]
        [--controls name,name,...] [--flips]

At the cell's timed sizes on the chip (one sequence of the configuration's
length; the cell's family, reference, tolerances and seeded draws, as
``runners/train.run`` hands them to ``check_against_reference``), every seed
is compared with ``reference/lfm2.py`` as it is (``none``: must be correct),
and then the first seed with each of ``CONTROLS`` patched into the reference
(a control that the limits catch reads ``correct: false``).  One JSON line a
comparison: the runner's verdict, its numbers beside their limits and what
it said.  TPUs only; the CPU tests apply the same patches at a small size
(``tests/benchmark_tests/test_benchmark_lfm2.py``).

``--flips`` asks instead what the widest limit rests on (``router_flips``):
for every seed, layer by layer, the share of tokens whose chosen experts
differ between the bf16 system and the fp32 reference, and each ``router``
leaf's error with those tokens' router path taken out of the gradient on
both sides, beside its error as the comparison reads it.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

CELL = "lfm2-24b-a2b-s32768-train-1chip"


def _rounded_to(dtype):
    def f(x):             # the value rounded, the gradient passed through
        import jax
        import jax.numpy as jnp
        rounded = x.astype(dtype).astype(jnp.float32)
        return x + jax.lax.stop_gradient(rounded - x)
    return f


def patches(ref, name: str) -> dict:
    """{attribute of the reference module: its replacement} for one control."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def taps_reversed(g, w):
        """c[t] = sum_j w[:, j] g[t + (K - 1) - j]: position t reads itself
        and the K - 1 positions after it."""
        taps, s = w.shape[1], g.shape[0]
        padded = jnp.concatenate(
            [g, jnp.zeros((taps - 1, g.shape[1]), g.dtype)])
        return sum(padded[taps - 1 - j:taps - 1 - j + s] * w[:, j]
                   for j in range(taps))

    def gates_swapped(n, lp):
        c, b, u = jnp.split(ref.matmul(n, lp["w_in"]), 3, axis=-1)
        return ref.matmul(c * ref.causal_conv(b * u, lp["conv"]), lp["w_out"])

    def route_with(bias: bool, renormalise: bool, weigh: bool = True):
        def route(n, router, top_k, renorm_eps, scale):
            s = jax.nn.sigmoid(ref.matmul(n, router[:-1]))
            choice = s + lax.stop_gradient(router[-1]) if bias else s
            kth = lax.top_k(choice, top_k)[0][:, -1:]
            w = jnp.where(choice >= kth, s if weigh else 1.0 / top_k, 0.0)
            if renormalise:
                w = w / (jnp.sum(w, axis=-1, keepdims=True) + renorm_eps)
            return w * scale
        return route

    def all_features(t, g, eps):
        """RMSNorm over every feature of q or k, all heads at once."""
        ms = jnp.mean(t * t, axis=(-2, -1), keepdims=True)
        return t * lax.rsqrt(ms + eps) * g

    exact = ref.matmul

    def matmul_in(dtype):
        to = _rounded_to(dtype)
        return lambda a, b: exact(to(a), to(b))

    table = {
        "none": {},
        "taps_reversed": {"causal_conv": taps_reversed},
        "gates_swapped": {"conv_block": gates_swapped},
        "bias_left_out_of_the_choice": {"route": route_with(False, True)},
        "renormalisation_left_out": {"route": route_with(True, False)},
        # The next-token loss has no weights of its own; the weights a layer
        # could ignore are the router's: every chosen expert counts 1 / k.
        "routing_weights_ignored": {"route": route_with(True, False, False)},
        "qk_norm_over_all_features": {"head_norm": all_features},
        "matmuls_in_e4m3": {"matmul": matmul_in(jnp.float8_e4m3fn)},
        "matmuls_in_e5m2": {"matmul": matmul_in(jnp.float8_e5m2)},
    }
    return table[name]


CONTROLS = ("none", "taps_reversed", "gates_swapped",
            "bias_left_out_of_the_choice", "renormalisation_left_out",
            "routing_weights_ignored", "qk_norm_over_all_features",
            "matmuls_in_e4m3", "matmuls_in_e5m2")


@contextlib.contextmanager
def _swapped(*changes):
    """Each (module, attribute, replacement) in place until the block ends."""
    saved = [(module, attr, getattr(module, attr))
             for module, attr, _new in changes]
    for module, attr, new in changes:
        setattr(module, attr, new)
    try:
        yield
    finally:
        for module, attr, old in saved:
            setattr(module, attr, old)


def patched(ref, name: str):
    return _swapped(*((ref, attr, new)
                      for attr, new in patches(ref, name).items()))


def router_flips(fam, ref, mesh, params, batch) -> dict:
    """Loss and gradients of the system and of the reference twice, as
    ``check_against_reference`` takes them: as they are, and with the
    sparse block of every token on whose experts the two disagree taken out
    of the gradients (that block's output for those tokens goes through
    ``stop_gradient`` on both sides: what their experts and the weights of
    their choice would add to any leaf is left out; values and choices are
    as they were).  {"layers": one dict a sparse layer — tokens, the share
    whose chosen experts differ, the share where an expert held here is
    among the difference, the ``router`` leaf's relative L2 error as it is
    and masked —, "leaves": every leaf's error as it is, "leaves_masked":
    the same with the mask, "rechosen": tokens whose system choice differed
    between the two system programs (0: the mask fits the program it is
    applied to)}.

    Nothing of the program is edited.  The system's choices leave its step
    where its routing statistics do: ``moe.dropless_moe`` is wrapped so that
    ``RouterStats.dropped`` carries each layer's (tokens, top_k) indices
    out of ``make_loss_fn(with_routing=True)`` (a host callback inside the
    scanned period does not compile for a v5e).  The blocks of a kind share
    one traced body, so a call knows its layer when it runs: by the router
    matrix it was handed."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.ad_checkpoint import checkpoint_name

    from horovod_tpu.ops.flash_attention import SAVED_LSE
    from horovod_tpu.parallel import moe
    cfg, kwargs = fam.cfg, fam.reference_args()
    n_seqs, held = batch[0].shape[0], cfg.n_experts_held
    batch = tuple(jnp.asarray(x) for x in batch)
    routers = jnp.stack([lp["ffn"]["router"][:-1] for lp in
                         fam.to_reference(params)["layers"]
                         if "router" in lp["ffn"]])
    marks = jnp.sum(routers, axis=(1, 2))
    scores_of, dropless_of, route_of, ffn_of = (
        moe._scores, moe.dropless_moe, ref.route, ref.ffn_block)

    def rows_kept(keep, gate):
        return keep[jnp.argmin(jnp.abs(
            jnp.sum(gate.astype(jnp.float32)) - marks))]

    def kept(out, rows):
        return jnp.where(rows[:, None], out, lax.stop_gradient(out))

    def system(keep):
        """(gradients in the reference's layout, (layers, tokens, top_k)
        chosen experts)."""
        chosen = []

        def scores(p, x, top_k, router):
            # The choice is kept for the backward pass, not made again in
            # the block's recompute: what leaves with the statistics is
            # what the gradients were taken with.
            s, top_i, lse = scores_of(p, x, top_k, router)
            top_i = checkpoint_name(top_i, SAVED_LSE)
            chosen.append(top_i)
            return s, top_i, lse

        def dropless(p, x, *args, **kw):
            out, stats = dropless_of(p, x, *args, **kw)
            if keep is not None:
                out = kept(out, rows_kept(keep, p.gate))
            return out, stats._replace(
                dropped=chosen.pop().astype(jnp.float32))

        with _swapped((moe, "_scores", scores),
                      (moe, "dropless_moe", dropless)):
            (_, routing), grads = jax.jit(jax.value_and_grad(
                fam.tfm.make_loss_fn(cfg, fam.par, mesh, with_routing=True),
                has_aux=True))(params, *batch)
        return fam.to_reference(grads), np.asarray(routing["dropped"]).astype(
            int).reshape(routers.shape[0], batch[0].size, cfg.top_k)

    def reference(keep, members=None):
        """Gradients; with ``members`` (a list) the forward pass alone,
        without its checkpoints, which appends (positions, experts) bool a
        sequence a layer."""
        seq = [0]

        def route(*args):
            w = route_of(*args)
            members.append(w > 0)
            return w

        def ffn(n, lp, **kw):
            out = ffn_of(n, lp, **kw)
            if keep is None or "router" not in lp:
                return out
            return kept(out, rows_kept(keep, lp["router"][:-1]).reshape(
                n_seqs, -1)[seq[0]])

        def loss(p, tokens, labels):
            # ``ref.loss`` with its ``lax.map`` over the sequences unrolled,
            # so that a block's call knows its sequence.
            p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
            total = 0.0
            for seq[0] in range(n_seqs):
                total += ref.sequence(p, tokens[seq[0]], labels[seq[0]],
                                      **kwargs)
            return total / labels.size

        ref_params = fam.to_reference(params)
        with jax.default_matmul_precision("highest"):
            if members is None:
                with _swapped((ref, "ffn_block", ffn)):
                    return jax.jit(jax.grad(loss))(ref_params, *batch)
            with _swapped((ref, "route", route),
                          (jax, "checkpoint", lambda f, **kw: f)):
                return jax.jit(lambda *a: (loss(*a), tuple(members)))(
                    ref_params, *batch)[1]

    def errors(keep):
        sys_grads, chosen = system(keep)
        errs = jax.jit(lambda a, b: jax.tree_util.tree_map(
            lambda x, y: jnp.sqrt(jnp.sum((x - y) ** 2) / jnp.sum(y ** 2)),
            a, b))(sys_grads, reference(keep))
        return {jax.tree_util.keystr(k): float(v) for k, v in
                jax.tree_util.tree_leaves_with_path(errs)}, chosen

    ref_member = np.stack(reference(None, [])).reshape(
        n_seqs, routers.shape[0], -1, cfg.n_experts).swapaxes(0, 1).reshape(
        routers.shape[0], -1, cfg.n_experts)
    errs, chosen = errors(None)
    sys_member = np.zeros(ref_member.shape, bool)
    np.put_along_axis(sys_member, chosen, True, axis=-1)
    differ = sys_member != ref_member
    masked, again = errors(jnp.asarray(~differ.any(-1)))
    return {
        "layers": [{
            "leaf": leaf, "tokens": int(d.shape[0]),
            "chosen_differ_share": float(d.any(-1).mean()),
            "held_expert_differs_share": float(d[:, :held].any(-1).mean()),
            "router_rel_l2": errs[leaf],
            "router_rel_l2_masked": masked[leaf]}
            for leaf, d in zip((k for k in errs if "router" in k), differ)],
        "leaves": errs, "leaves_masked": masked,
        "median_leaf": statistics.median(errs.values()),
        "median_leaf_masked": statistics.median(masked.values()),
        "rechosen": int((np.sort(chosen) != np.sort(again)).any(-1).sum())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="2147483700")
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ap.add_argument("--loss-only", action="store_true",
                    help="compare as an untraced run does: no gradients")
    ap.add_argument("--flips", action="store_true",
                    help="count the routers' disagreements and mask them")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from benchmark import loader
    from horovod_tpu.parallel.mesh import create_mesh
    if jax.devices()[0].platform != "tpu":
        print("lfm2_controls: TPUs only", file=sys.stderr)
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
        for seed in seeds if args.flips else ():
            params = init(jax.random.PRNGKey(seed))
            batch = fam.draw_batch(np.random.default_rng([seed, 1, 0]),
                                   fam.dp * fam.check_seqs_per_rank)
            print(json.dumps({"flips": True, "seed": seed, **router_flips(
                fam, ref, mesh, params, batch)}), flush=True)
            del params
            jax.clear_caches()
        if args.flips:
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
