"""OLMoE-1B-7B: the system against the benchmark's plain reference at a small
size on the CPU (d_model 64, 4 heads, expert width 32, 64 experts top-8, 2
layers, sequence 128), the faults the tolerances must catch, and the
family's arithmetic.  On the chip ``benchmark/run.py`` makes the same
comparison at the published widths."""

from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import loader                      # noqa: E402
from horovod_tpu.models import transformer as tfm          # noqa: E402
from horovod_tpu.parallel.mesh import create_mesh          # noqa: E402

CELL = "olmoe-1b-7b-s4096-train-1chip"
SMALL = {"vocab_size": 512, "d_model": 64, "n_heads": 4, "d_ff": 32,
         "n_layers": 2, "seq_len": 128}
ONE, DP2MP2 = (1, 1, 1), (2, 1, 2)
REF = loader.load_code("reference", "olmoe")


def small_family(mesh_shape=ONE, dtype="bfloat16"):
    cell = loader.load_cell(CELL)
    config = {**cell["config"], **SMALL, "dtype": dtype}
    assert (config["n_experts"], config["top_k"]) == (64, 8)
    fam = loader.load_code("families", "olmoe").Family(
        config, dict(zip(("dp", "pp", "mp"), mesh_shape)))
    n = int(np.prod(mesh_shape))
    mesh = create_mesh(fam.mesh_shape, devices=jax.devices()[:n])
    params = fam.init_params(jax.random.PRNGKey(0))
    # As test_benchmark_reference.py: at d_model 64 the 0.02 initialisation
    # leaves attention near uniform, where a wrong position or norm barely
    # shows.  Widen q/k/v.
    params["layers"]["wqkv"] = params["layers"]["wqkv"] * 8.0
    batch = fam.draw_batch(np.random.default_rng(5), 4)
    return fam, mesh, params, batch


def system(fam, mesh, params, batch):
    return jax.jit(jax.value_and_grad(fam.loss_fn(mesh)))(params, *batch)


def against_reference(fam, params, batch, sys_out, loss_fn=None, **args):
    """(|loss difference|, {leaf: relative L2 error of its gradient})."""
    loss_fn = loss_fn or REF.loss
    args = {**fam.reference_args(), **args}
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p, *b: loss_fn(p, *b, **args)))(
                fam.to_reference(params), *batch)
    sys_loss, sys_grads = sys_out
    errs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.sqrt(jnp.sum((a - b) ** 2) / jnp.sum(b ** 2))),
        fam.to_reference(sys_grads), ref_grads)
    return (abs(float(sys_loss) - float(ref_loss)),
            {jax.tree_util.keystr(k): v for k, v in
             jax.tree_util.tree_leaves_with_path(errs)})


@pytest.fixture(scope="module")
def bf16_system():
    """The system's loss and gradients on one device in bf16, once for the
    tests that only vary what it is compared with."""
    fam, mesh, params, batch = small_family()
    return fam, params, batch, system(fam, mesh, params, batch)


# -- the system is the reference ------------------------------------------------

@pytest.mark.parametrize("mesh_shape", [ONE, DP2MP2])
def test_in_fp32_the_system_is_the_reference_on_every_layout(mesh_shape):
    """With the compute type fp32 nothing rounds differently and no router
    decision can flip: loss and every gradient leaf agree to fp32 round-off.
    On (2, 1, 2) that holds the QK-norm's psum over mp, the experts
    replicated over dp and mp, and the router's statistics summed over the
    global batch before the two means are multiplied."""
    fam, mesh, params, batch = small_family(mesh_shape, "float32")
    d_loss, errs = against_reference(
        fam, params, batch, system(fam, mesh, params, batch))
    assert len(errs) == 13
    assert d_loss <= 2e-6, d_loss
    assert max(errs.values()) <= 1e-4, errs


@pytest.mark.parametrize("mesh_shape", [ONE, DP2MP2])
def test_in_bf16_the_system_is_inside_the_tolerances(mesh_shape):
    fam, mesh, params, batch = small_family(mesh_shape)
    d_loss, errs = against_reference(
        fam, params, batch, system(fam, mesh, params, batch))
    assert d_loss <= REF.TOLERANCES["loss_abs"], d_loss
    assert max(errs.values()) <= REF.TOLERANCES["grad_rel_l2"], errs
    # What the bound is made of (the note beside TOLERANCES): the leaves no
    # router decision reaches are as close as the flagship's.
    far = {k for k, v in errs.items() if v > 0.03}
    assert far <= {"['layers']['w_gate']", "['layers']['w_up']",
                   "['layers']['w_down']"}, errs


def skewed(params):
    """A router that sends every token to experts 0..7: feature 0 of the
    stream is made the same large number for every token and the router
    weighs it heavily for those eight (it has no bias of its own)."""
    params = dict(params, layers=dict(params["layers"]))
    params["embed"] = params["embed"].at[:, 0].set(1.0)
    params["layers"]["gate"] = (
        params["layers"]["gate"].at[..., 0, :8].add(0.5))
    return params


@pytest.mark.parametrize("mesh_shape", [ONE, DP2MP2])
def test_a_skewed_router_still_equals_the_reference_and_drops_nothing(
        mesh_shape):
    fam, mesh, params, batch = small_family(mesh_shape)
    params = skewed(params)
    routing = fam.tfm.make_routing_fn(fam.cfg, fam.par, mesh)(params, *batch)
    tokens = batch[0].size
    assert np.asarray(routing["assignments"]).tolist() == [
        [tokens] * 8 + [0] * 56] * 2
    assert float(routing["dropped"]) == 0
    assert np.asarray(routing["load"]).tolist() == [8.0, 8.0]
    d_loss, errs = against_reference(
        fam, params, batch, system(fam, mesh, params, batch))
    assert d_loss <= REF.TOLERANCES["loss_abs"], d_loss
    # 56 experts receive no token: their gradient is zero on both sides,
    # and the stacked leaves' error is that of the eight that work.  No
    # decision is close, so none flips.
    assert max(errs.values()) <= 0.03, errs


# -- what the tolerances refuse ---------------------------------------------------

def renormalised(route):
    def wrong(h, wg, top_k):
        weights, probs, lse = route(h, wg, top_k)
        return weights / jnp.sum(weights, -1, keepdims=True), probs, lse
    return wrong


def capacity_clamped(route, factor=1.25):
    """Switch semantics: an expert keeps its first ``capacity`` tokens."""
    def wrong(h, wg, top_k):
        weights, probs, lse = route(h, wg, top_k)
        capacity = int(np.ceil(h.shape[0] * top_k / weights.shape[1]
                               * factor))
        place = jnp.cumsum(weights > 0, axis=0)
        return jnp.where(place <= capacity, weights, 0.0), probs, lse
    return wrong


def halves_swapped(rope):
    def wrong(t, theta):
        half = t.shape[-1] // 2
        return jnp.roll(rope(jnp.roll(t, half, -1), theta), half, -1)
    return wrong


FAULTS = {
    "renormalised_top_k_weights":
        lambda mp: mp.setattr(REF, "route", renormalised(REF.route)),
    "a_capacity_clamp":
        lambda mp: mp.setattr(REF, "route", capacity_clamped(REF.route)),
    "no_qk_norm":
        lambda mp: mp.setattr(REF, "qk_norm", lambda t, g, eps: t),
    "rope_with_the_halves_swapped":
        lambda mp: mp.setattr(REF, "rope", halves_swapped(REF.rope)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_tolerance_catches(monkeypatch, bf16_system, fault):
    """A reference (standing in for a system) with one thing wrong is
    refused by the tolerances, with room: some gradient leaf is off by more
    than one and a half times the bound."""
    fam, params, batch, sys_out = bf16_system
    FAULTS[fault](monkeypatch)
    _d_loss, errs = against_reference(fam, params, batch, sys_out)
    assert max(errs.values()) > 1.5 * REF.TOLERANCES["grad_rel_l2"], errs


def test_tolerance_catches_top_7(bf16_system):
    fam, params, batch, sys_out = bf16_system
    d_loss, errs = against_reference(fam, params, batch, sys_out, top_k=7)
    assert max(errs.values()) > 1.5 * REF.TOLERANCES["grad_rel_l2"], errs
    assert d_loss > REF.TOLERANCES["loss_abs"]


def test_tolerance_catches_a_tied_head(bf16_system):
    fam, params, batch, sys_out = bf16_system

    def tied(p, *b, **kw):
        return REF.loss({**p, "lm_head": p["embed"]}, *b, **kw)

    d_loss, errs = against_reference(fam, params, batch, sys_out,
                                     loss_fn=tied)
    assert d_loss > REF.TOLERANCES["loss_abs"]
    assert errs["['layers']['wqkv']"] > 1.5 * REF.TOLERANCES["grad_rel_l2"]


@pytest.mark.parametrize("fp8", ["float8_e4m3fn", "float8_e5m2"])
def test_tolerance_refuses_the_precision_below_bf16(
        monkeypatch, bf16_system, fp8):
    """The configuration states bf16 compute; the reference with every
    matmul's operands rounded to an 8-bit float, the nearest precision below,
    must come out as not correct."""
    fam, params, batch, sys_out = bf16_system
    exact = REF.matmul

    def to_fp8(x):        # the value rounded, the gradient passed through
        return x + jax.lax.stop_gradient(
            x.astype(fp8).astype(jnp.float32) - x)

    def rounded(a, b):
        return exact(to_fp8(a), to_fp8(b))

    monkeypatch.setattr(REF, "matmul", rounded)
    _d_loss, errs = against_reference(fam, params, batch, sys_out)
    assert max(errs.values()) > 1.5 * REF.TOLERANCES["grad_rel_l2"], errs


# -- the family's data and arithmetic -----------------------------------------------

def test_both_spellings_of_a_size_agree_and_every_width_is_published():
    cell = loader.load_cell(CELL)
    c = cell["config"]
    for repo, published in [
            ("d_model", "hidden_size"), ("n_heads", "num_attention_heads"),
            ("d_ff", "intermediate_size"), ("n_layers", "num_hidden_layers"),
            ("seq_len", "max_position_embeddings"),
            ("n_experts", "num_experts"), ("top_k", "num_experts_per_tok")]:
        assert c[repo] == c[published], (repo, published)
    assert (c["hidden_size"], c["num_attention_heads"],
            c["intermediate_size"], c["num_experts"],
            c["num_experts_per_tok"], c["vocab_size"],
            c["max_position_embeddings"], c["rms_norm_eps"],
            c["rope_theta"]) == (2048, 16, 1024, 64, 8, 50304, 4096, 1e-5,
                                 10000)
    assert sorted(c["reduced"]) == ["n_layers", "num_hidden_layers"]
    assert cell["entry"]["chips"] == 1
    fam = loader.load_code("families", "olmoe").Family(
        c, cell["traffic"]["mesh"])
    shapes = jax.eval_shape(fam.init_params, jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(shapes)) == c["parameters"] \
        == 625_616_896


def test_flop_arithmetic_is_a_copy_of_the_programs_today():
    cell = loader.load_cell(CELL)
    c = cell["config"]
    fam = loader.load_code("families", "olmoe").Family(
        c, cell["traffic"]["mesh"])
    assert fam.flops_per_token() * c["seq_len"] == pytest.approx(
        tfm.train_flops_per_seq(fam.cfg), rel=1e-12)
    # 3 x (33.55 + 16.78 + 0.26 + 100.66 + 206.05) M
    assert fam.flops_per_token() == 1071906816.0
    batch = cell["traffic"]["global_batch"]
    cost = fam.attention_cost(batch)
    assert cost["flops"] == pytest.approx(
        batch * 0.5 * 3.0 * 4.0 * c["seq_len"] ** 2 * c["d_model"],
        rel=1e-12)
    tokens = batch * c["seq_len"]
    assert cost["moe_expert_matmul"]["flops"] == pytest.approx(
        3.0 * tokens * 8 * 6 * 2048 * 1024, rel=1e-12)
    # FLOPs bound at this size: 9 matmuls' operands and results once each.
    assert cost["moe_expert_matmul"]["bytes"] / 819e9 < \
        cost["moe_expert_matmul"]["flops"] / 197e12


def test_roofline_reader_takes_its_cost_from_the_attention_dict():
    read = loader.load_code("metrics", "moe_expert_matmul_roofline")
    layers = {"attention": {"flops": 1.0, "bytes": 1.0},
              "peaks": {"flops_per_s_bf16": 197e12,
                        "hbm_bytes_per_s": 819e9}, "trace": None}
    assert read.least_seconds(layers) is None       # another family's dict
    assert read.read(layers, {"better": "higher"}) is None
    layers["attention"]["moe_expert_matmul"] = {"flops": 197e12,
                                                "bytes": 819e9 / 2}
    assert read.least_seconds(layers) == (1.0, "flops")
    assert read.read(layers, {"better": "higher"}) is None      # no trace
