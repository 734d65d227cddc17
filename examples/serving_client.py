"""Serving-plane walkthrough: stand up one replica, fire an open-loop
load at it, and watch a weight hot-swap — the docs/serving.md example
as a runnable script (host-only; a tiny transformer on CPU works).

    python examples/serving_client.py

Against an already-running replica, use the load-client CLI instead::

    python -m horovod_tpu.serving.submit --server host:28643 \
        --requests 50 --rate 5
"""

import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

import horovod_tpu as hvd
from horovod_tpu.checkpoint import save_zero_state
from horovod_tpu.models import transformer as tfm
from horovod_tpu.serving import ServingService
from horovod_tpu.serving.loadgen import synthetic_workload
from horovod_tpu.serving.submit import generate, run_load


def main():
    hvd.init()
    cfg = tfm.TransformerConfig(
        vocab_size=128, d_model=64, n_heads=4, d_ff=256, n_layers=2,
        seq_len=128, dtype=jnp.float32, remat=False)
    par = tfm.ParallelConfig()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, par)

    # "Training" commits a step; the service cold-loads it.
    ckpt = tempfile.mkdtemp(prefix="hvd_serving_demo_")
    save_zero_state(ckpt, params, step=1)
    service = ServingService(cfg, checkpoint_dir=ckpt, port=0,
                             swap_poll_s=0.2, slots=4, page_tokens=16)
    port = service.serve()
    addr = f"127.0.0.1:{port}"
    print(f"replica at {addr}, weights step {service.engine.params_tag}")

    # One interactive request...
    out = generate({"tokens": [3, 1, 4, 1, 5], "max_new_tokens": 8},
                   server=addr)
    print("one request:", json.dumps(out))

    # ...then a seeded open-loop schedule.
    schedule = synthetic_workload(seed=0, n=12, rate_rps=20.0,
                                  prompt_lens=(4, 16),
                                  output_lens=(4, 16),
                                  vocab=cfg.vocab_size)
    results = run_load(schedule, server=addr, timeout=60.0)
    done = [r for r in results.values() if "tokens" in r]
    print(f"open-loop: {len(done)}/{len(results)} completed; "
          f"status {json.dumps(service.status())}")

    # The trainer commits a newer step: the watcher hot-swaps it
    # between decode iterations, bit-identical to a cold load.
    save_zero_state(
        ckpt, jax.tree_util.tree_map(lambda a: a * 1.01, params), step=2)
    import time
    deadline = time.monotonic() + 5
    while service.engine.params_tag != 2 and time.monotonic() < deadline:
        generate({"tokens": [3, 1, 4], "max_new_tokens": 2}, server=addr)
        time.sleep(0.2)
    print("after hot-swap, weights step:", service.engine.params_tag)
    service.close()
    hvd.shutdown()


if __name__ == "__main__":
    main()
