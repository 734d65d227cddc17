"""The benchmark's plain references against the system, at a tiny size on
the CPU, and the benchmark's copy of the FLOP arithmetic against the
program's.  On the chip ``benchmark/run.py`` makes the same comparison at the
published widths (``runners/train.check_against_reference``): the loss in
every run, the gradients in every run or in the traced one, as the cell's
traffic file says."""

from __future__ import annotations

import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import loader                      # noqa: E402
from horovod_tpu.models import bert as bert_model          # noqa: E402
from horovod_tpu.models import transformer as tfm          # noqa: E402
from horovod_tpu.parallel.mesh import create_mesh          # noqa: E402

TINY = {"vocab_size": 512, "d_model": 64, "n_heads": 4, "d_ff": 128,
        "n_layers": 2, "seq_len": 128, "max_predictions_per_seq": 16}
CELLS = {"flagship": "flagship-s8192-train-1chip",
         "bert": "bert-base-s512-train-1chip"}


def tiny_family(name: str):
    cell = loader.load_cell(CELLS[name])
    assert cell["config"]["family"] == name
    config = {**cell["config"], **TINY}
    fam = loader.load_code("families", name).Family(
        config, cell["traffic"]["mesh"])
    mesh = create_mesh(fam.mesh_shape, devices=jax.devices()[:1])
    params = fam.init_params(jax.random.PRNGKey(0))
    # At d_model 64 the 0.02 initialisation leaves attention scores near 0
    # and the softmax near uniform, where a missing mask or scale barely
    # shows.  Widen q/k/v so that attention is as peaked as at real widths.
    params["layers"]["wqkv"] = params["layers"]["wqkv"] * 8.0
    batch = fam.draw_batch(np.random.default_rng(5), 2)
    return fam, mesh, params, batch


def system_and_reference(fam, ref, mesh, params, batch):
    sys_loss, sys_grads = jax.jit(jax.value_and_grad(fam.loss_fn(mesh)))(
        params, *batch)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p, *b: ref.loss(p, *b, **fam.reference_args())))(
                fam.to_reference(params), *batch)
    errs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.sqrt(jnp.sum((a - b) ** 2) / jnp.sum(b ** 2))),
        fam.to_reference(sys_grads), ref_grads)
    return (abs(float(sys_loss) - float(ref_loss)),
            max(jax.tree_util.tree_leaves(errs)))


@pytest.mark.parametrize("name", sorted(CELLS))
def test_system_matches_reference_loss_and_gradients(name):
    fam, mesh, params, batch = tiny_family(name)
    ref = loader.load_code("reference", name)
    d_loss, worst = system_and_reference(fam, ref, mesh, params, batch)
    assert d_loss <= ref.TOLERANCES["loss_abs"], d_loss
    assert worst <= ref.TOLERANCES["grad_rel_l2"], worst


@pytest.mark.parametrize("name, fault", [
    ("flagship", "no_causal_mask"), ("flagship", "no_scale"),
    ("bert", "no_scale"), ("bert", "causal_where_bidirectional")])
def test_tolerance_catches_wrong_attention(monkeypatch, name, fault):
    """The tolerances are tight enough that a reference (standing in for a
    system) with the mask or the 1/sqrt(hd) scale wrong is refused."""
    fam, mesh, params, batch = tiny_family(name)
    ref = loader.load_code("reference", name)
    right = ref.attention
    hd = fam.c["d_model"] // fam.c["n_heads"]
    wrong = {
        "no_causal_mask": lambda q, k, v, causal: right(q, k, v, False),
        "causal_where_bidirectional":
            lambda q, k, v, causal: right(q, k, v, True),
        "no_scale":
            lambda q, k, v, causal: right(q * math.sqrt(hd), k, v, causal),
    }[fault]
    monkeypatch.setattr(ref, "attention", wrong)
    d_loss, worst = system_and_reference(fam, ref, mesh, params, batch)
    assert worst > 2 * ref.TOLERANCES["grad_rel_l2"], (d_loss, worst)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_tolerance_does_not_see_a_bf16_softmax(monkeypatch, name):
    """The gap written beside ``TOLERANCES``, pinned: probabilities rounded
    to bf16 move the worst gradient leaf by a few tenths of a percent at
    this size, inside the bound.  If a tighter bound ever refuses them, this
    test fails and the note beside ``TOLERANCES`` goes."""
    fam, mesh, params, batch = tiny_family(name)
    ref = loader.load_code("reference", name)

    def bf16_softmax(q, k, v, causal, q_block=None):
        s, hd = q.shape[1], q.shape[-1]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        if causal:
            pos = jnp.arange(s)
            scores = jnp.where(pos[:, None] >= pos[None, :], scores, -jnp.inf)
        p = jax.nn.softmax(scores.astype(jnp.bfloat16), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(jnp.float32), v)

    monkeypatch.setattr(ref, "attention", bf16_softmax)
    d_loss, worst = system_and_reference(fam, ref, mesh, params, batch)
    assert d_loss <= ref.TOLERANCES["loss_abs"]
    assert worst <= ref.TOLERANCES["grad_rel_l2"], worst


def test_reference_attention_blocks_agree_with_one_block():
    ref = loader.load_code("reference", "flagship")
    q, k, v = (jax.random.normal(kk, (2, 64, 2, 8))
               for kk in jax.random.split(jax.random.PRNGKey(1), 3))
    for causal in (True, False):
        np.testing.assert_allclose(
            ref.attention(q, k, v, causal, q_block=16),
            ref.attention(q, k, v, causal, q_block=64), atol=1e-6)


def test_flop_arithmetic_is_a_copy_of_the_programs_today():
    """The benchmark's copy is the yardstick.  It equals the program's own
    accounting today; if a later PR changes ``models/*`` and this fails, the
    copy stays as it is and this test is what gets the note."""
    cell = loader.load_cell(CELLS["flagship"])
    c = cell["config"]
    fam = loader.load_code("families", "flagship").Family(
        c, cell["traffic"]["mesh"])
    assert fam.flops_per_token() * c["seq_len"] == pytest.approx(
        tfm.train_flops_per_seq(fam.cfg), rel=1e-12)
    assert fam.flops_per_token() == 1711276032.0

    cell = loader.load_cell(CELLS["bert"])
    c = cell["config"]
    fam = loader.load_code("families", "bert").Family(
        c, cell["traffic"]["mesh"])
    assert c["max_predictions_per_seq"] == bert_model.max_predictions(fam.cfg)
    assert fam.flops_per_token() * c["seq_len"] == pytest.approx(
        bert_model.train_flops_per_seq(
            fam.cfg, n_pred=c["max_predictions_per_seq"]), rel=1e-12)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_attention_cost_is_the_models_attention_flops(name):
    """Forward + backward attention FLOPs per step from the family's shape
    arithmetic equal the attention term of its model FLOPs."""
    cell = loader.load_cell(CELLS[name])
    c = cell["config"]
    fam = loader.load_code("families", name).Family(c, cell["traffic"]["mesh"])
    batch = cell["traffic"]["global_batch"]
    causal = 0.5 if name == "flagship" else 1.0
    attn_term = 3.0 * c["n_layers"] * 4.0 * c["seq_len"] ** 2 * c["d_model"]
    assert fam.attention_cost(batch)["flops"] == pytest.approx(
        batch * causal * attn_term, rel=1e-12)
