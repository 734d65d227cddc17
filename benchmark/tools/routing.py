#!/usr/bin/env python3
"""What a share-holding cell's routers send this rank: rows to the held
experts, layer by layer, over seeds and batches.

    python benchmark/tools/routing.py [--cell name] [--seeds n,n,...]
        [--steps 3] [--seq-len 2048]

The cell's family and configuration at its published widths (``--seq-len``
shortens the sequence so that a CPU can run it), the family's seeded weights
as its ``init_params`` makes them, and seeded batches, through the program's
own ``make_routing_fn``.  One JSON line a (seed, step): the held experts'
rows a layer beside the mean share, the busiest and the least-loaded held
expert, the busiest of all the router's experts over the mean, and the rows
the static buffer dropped.  The SmallThinker cell's by default (PERF.md
section 6, PR 46, quotes it); any cell whose family has ``tfm``, ``cfg`` and
``par`` and whose configuration holds a share (``n_experts_held``) can be
named.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

CELL = "smallthinker-21b-a3b-s16384-train-1chip"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default=CELL)
    ap.add_argument("--seeds", default="2147483700,77,5")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--seq-len", type=int, default=None)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    import horovod_tpu as hvd
    from benchmark import loader
    from horovod_tpu.parallel.mesh import create_mesh
    hvd.init()
    try:
        cell = loader.load_cell(args.cell)
        config = dict(cell["config"])
        if args.seq_len:
            config["seq_len"] = args.seq_len
        fam = loader.load_code("families", config["family"]).Family(
            config, cell["traffic"]["mesh"])
        mesh = create_mesh(fam.mesh_shape, devices=jax.devices()[:1])
        routing = fam.tfm.make_routing_fn(fam.cfg, fam.par, mesh)
        init = jax.jit(fam.init_params)
        held = config["n_experts_held"]
        for seed in (int(s) for s in args.seeds.split(",")):
            params = init(jax.random.PRNGKey(seed))
            for step in range(args.steps):
                batch = fam.draw_batch(
                    np.random.default_rng([seed, 0, step]), fam.dp)
                r = routing(params, *batch)
                rows = np.asarray(r["assignments"]).reshape(
                    -1, config["n_experts"])[:, :held]
                print(json.dumps({
                    "seed": seed, "step": step,
                    "mean_share": batch[0].size * config["top_k"] * held
                    // config["n_experts"],
                    "held_rows": rows.sum(-1).astype(int).tolist(),
                    "busiest_held": rows.max(-1).astype(int).tolist(),
                    "least_held": rows.min(-1).astype(int).tolist(),
                    "busiest_over_mean": [round(float(x), 3) for x in
                                          np.asarray(r["load"]).reshape(-1)],
                    "dropped": int(r["dropped"])}), flush=True)
    finally:
        hvd.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
