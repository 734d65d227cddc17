#!/usr/bin/env python3
"""A digest of each training cell's step as JAX traces it, at the cell's
published sizes, with no chip: shapes only.

    JAX_PLATFORMS=cpu python benchmark/tools/step_jaxpr.py [--cells a,b]
        [--dump DIR] [--record FILE]

One JSON line a cell: the sha256 of ``str(jax.make_jaxpr(train_step))`` (the
Pallas calls' grids and bodies are part of it) with what differs from
process to process taken out (addresses, the order a set prints in), and its
length.  Run it from two checkouts (each run reads the checkout it lies in):
equal digests say the change leaves that cell's program as it was, which is
the reason not to pair the cell on the chip (PERF.md section 4, PR 41).  A
cell whose family the checkout's program cannot build says so and is
skipped.  ``--dump`` writes the normalised texts there, for ``diff``.
``--record`` writes the digests and the JAX version they were taken under
to a JSON file: ``tests/step_digests.json`` is such a record, which
``tests/test_lfm2_layers.py`` holds the checkout to; a change that means to
alter a recorded cell's program, or a new JAX, records it again
(``--cells`` as the file's keys, ``--record tests/step_digests.json``).
"""

from __future__ import annotations

import argparse
import hashlib
import json
import math
import os
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def normalised(text: str) -> str:
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    return re.sub(
        r"frozenset\(\{([^}]*)\}\)",
        lambda m: "frozenset({%s})" % ", ".join(sorted(m.group(1).split(", "))),
        text)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default=None)
    ap.add_argument("--dump", default=None)
    ap.add_argument("--record", default=None)
    args = ap.parse_args(argv)
    # The dispatch takes the kernels' branch off the chip too, and the
    # four-chip cell's mesh needs four (virtual) devices.
    os.environ["HVD_TPU_FLASH"] = "1"
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

    import jax
    import numpy as np
    from jax.sharding import Mesh

    from benchmark import loader
    train = loader.load_code("runners", "train")
    cells = [w["name"] for w in loader.load_benchmark()["workloads"]]
    digests = {}
    for name in (args.cells.split(",") if args.cells else cells):
        cell = loader.load_cell(name)
        config, traffic = cell["config"], cell["traffic"]
        if traffic["runner"] != "train":
            continue
        try:
            fam = loader.load_code("families", config["family"]).Family(
                config, traffic["mesh"])
        except loader.BenchmarkError as e:
            print(json.dumps({"cell": name, "skipped": str(e)}), flush=True)
            continue
        shape = tuple(fam.mesh_shape.values())
        mesh = Mesh(np.array(jax.devices()[:math.prod(shape)]).reshape(shape),
                    tuple(fam.mesh_shape))
        opt = train.make_optimizer(config["optimizer"])
        # The program's own initialiser where the family has one: a family's
        # ``init_params`` may go on to run set-up programs on real devices.
        init = ((lambda k, f=fam: f.tfm.init_params(k, f.cfg, f.par))
                if hasattr(fam, "tfm") else fam.init_params)
        params = jax.eval_shape(init, jax.random.PRNGKey(0))
        state = jax.eval_shape(opt.init, params)
        batch = tuple(jax.ShapeDtypeStruct(x.shape, x.dtype)
                      for x in fam.draw_batch(np.random.default_rng(0),
                                              traffic["global_batch"]))
        text = normalised(str(jax.make_jaxpr(fam.train_step(mesh, opt))(
            params, state, *batch)))
        if args.dump:
            Path(args.dump).mkdir(parents=True, exist_ok=True)
            (Path(args.dump) / f"{name}.jaxpr.txt").write_text(text)
        digests[name] = hashlib.sha256(text.encode()).hexdigest()
        print(json.dumps({"cell": name, "characters": len(text),
                          "sha256": digests[name]}), flush=True)
    if args.record:
        Path(args.record).write_text(json.dumps(
            {"jax": jax.__version__, "steps": digests}, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
