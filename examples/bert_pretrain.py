"""BERT-Base masked-LM pretraining on synthetic data, data-parallel over
all visible chips (dp) with optional tensor parallelism (mp).

Single host:      python examples/bert_pretrain.py
Virtual 8-chip:   XLA_FLAGS=--xla_force_host_platform_device_count=8 \
                  JAX_PLATFORMS=cpu python examples/bert_pretrain.py --mp 2
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import time

import jax
import jax.numpy as jnp
import optax

import horovod_tpu as hvd
from horovod_tpu.models import bert
from horovod_tpu.parallel.mesh import create_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch-per-chip", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--mp", type=int, default=1)
    ap.add_argument("--dense-head", action="store_true",
                    help="compute MLM logits at every position instead "
                         "of the default gathered masked-position head "
                         "(real-BERT max_predictions_per_seq)")
    args = ap.parse_args()

    hvd.init()
    n = jax.device_count()
    assert n % args.mp == 0
    mesh = create_mesh({"dp": n // args.mp, "mp": args.mp})

    cfg = bert.BertConfig(vocab_size=8192, d_model=256, n_heads=8,
                          d_ff=1024, n_layers=args.layers,
                          seq_len=args.seq_len, dtype=jnp.bfloat16)
    params = bert.init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(1e-4)
    gathered = not args.dense_head
    step, shard_params = bert.make_train_step(cfg, mesh, opt,
                                              gathered=gathered)
    params = shard_params(params)
    opt_state = opt.init(params)

    batch = args.batch_per_chip * (n // args.mp)
    key = jax.random.PRNGKey(1)
    for i in range(args.steps):
        key, sub = jax.random.split(key)
        if gathered:
            inputs, positions, labels = bert.synthetic_mlm_batch(
                sub, cfg, batch)
            batch_args = (inputs, positions, labels)
        else:
            inputs, labels = bert.synthetic_batch(sub, cfg, batch)
            batch_args = (inputs, labels)
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, *batch_args)
        loss = float(loss)
        if hvd.rank() == 0:
            print(f"step {i:3d}  mlm_loss {loss:.4f}  "
                  f"{(time.perf_counter()-t0)*1e3:.1f} ms")


if __name__ == "__main__":
    main()
