"""LFM2-24B-A2B (``lfm2``): the system against the benchmark's plain
reference at a small size on the CPU (the cell's ten blocks at hidden 64: 8
query heads on 2 kv heads of 16, 4 of 16 experts held at width 48, top-4, a
dense MLP of 160, 128 positions), the controls and the lower precisions the
comparison must see, the configuration's data and the family's arithmetic,
the balanced router, and the readers of the cell's own per-layer metrics.
On the chip ``benchmark/run.py`` makes the same comparison at the published
widths, and ``benchmark/tools/lfm2_controls.py`` the controls'."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import loader, run                 # noqa: E402
from benchmark.trace import laguna as L           # noqa: E402
from benchmark.trace import lfm2 as S             # noqa: E402
from benchmark.trace import reduce as R           # noqa: E402
from horovod_tpu.models import transformer as tfm          # noqa: E402
from horovod_tpu.parallel.mesh import create_mesh          # noqa: E402

CELL = "lfm2-24b-a2b-s32768-train-1chip"
CONFIG = "lfm2-24b-a2b-5l-s32768"
SMALL = {"vocab_size": 256, "d_model": 64, "attn_head_dim": 16, "n_heads": 8,
         "n_kv_heads": 2, "d_ff": 48, "dense_ff": 160, "n_experts": 16,
         "n_experts_held": 4, "top_k": 4, "seq_len": 128,
         "expert_buffer_factor": 8.0}
ONE, DP2 = (1, 1, 1), (2, 1, 1)
REF = loader.load_code("reference", "lfm2")
FAMILY = loader.load_code("families", "lfm2")
CONTROLS = loader.load_code("tools", "lfm2_controls")
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
SOURCE = "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
REDUCED = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8,
           "vocab_size": 8192,
           "layer_types": ["conv", "full_attention", "conv", "conv", "conv"]}
NEW_METRICS = ("short_conv_ms_per_step", "short_conv_gate_ms_per_step",
               "short_conv_gate_roofline")
# Accepted metrics whose ``workloads`` this cell joined: the dense block and
# the expert blocks beside it, read by ``trace/laguna.py`` (Laguna's).
JOINED_METRICS = ("expert_block_ms_per_step", "dense_mlp_ms_per_step")


def small_family(mesh_shape=ONE, dtype="bfloat16"):
    config = {**loader.load_cell(CELL)["config"], **SMALL, "dtype": dtype}
    fam = FAMILY.Family(config, dict(zip(("dp", "pp", "mp"), mesh_shape)))
    n = int(np.prod(mesh_shape))
    mesh = create_mesh(fam.mesh_shape, devices=jax.devices()[:n])
    params = jax.jit(fam.init_params)(jax.random.PRNGKey(0))
    # The per-head norm's unit scales are moved off 1 so that one that is
    # dropped or laid over the wrong axis shows.
    attn = params["layers"]["attn"]
    for name, key in (("q_norm", 1), ("k_norm", 2)):
        attn[name] = attn[name] * (1.5 + 0.5 * jax.random.normal(
            jax.random.PRNGKey(key), attn[name].shape))
    batch = fam.draw_batch(np.random.default_rng(5), 4)
    return fam, mesh, params, batch


def system(fam, mesh, params, batch):
    return jax.jit(jax.value_and_grad(fam.loss_fn(mesh)))(params, *batch)


def against_reference(fam, params, batch, sys_out):
    """(|loss difference|, {leaf: relative L2 error of its gradient})."""
    args = fam.reference_args()
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p, *b: REF.loss(p, *b, **args)))(
                fam.to_reference(params), *batch)
    sys_loss, sys_grads = sys_out
    errs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.sqrt(jnp.sum((a - b) ** 2) / jnp.sum(b ** 2))),
        fam.to_reference(jax.device_get(sys_grads)), ref_grads)
    return (abs(float(sys_loss) - float(ref_loss)),
            {jax.tree_util.keystr(k): v for k, v in
             jax.tree_util.tree_leaves_with_path(errs)})


def under_control(name, fam, params, batch, sys_out):
    with CONTROLS.patched(REF, name):
        return against_reference(fam, params, batch, sys_out)


@pytest.fixture(scope="module")
def bf16_system():
    fam, mesh, params, batch = small_family()
    return fam, params, batch, system(fam, mesh, params, batch)


@pytest.fixture(scope="module")
def fp32_system():
    fam, mesh, params, batch = small_family(ONE, "float32")
    return fam, params, batch, system(fam, mesh, params, batch)


# -- the system is the reference ------------------------------------------------

@pytest.mark.parametrize("mesh_shape", [ONE, DP2])
def test_in_fp32_the_system_is_the_reference_on_every_layout(
        fp32_system, mesh_shape):
    """With the compute type fp32 nothing rounds differently and no router
    decision can flip: the convolution's pad and shifted products against
    the reference's, the gates' order, the per-head norm, the rotation, the
    sorted rows against the mask of experts, the bias in the choice and not
    in the weights, the tied head — loss and every gradient leaf agree to
    fp32 round-off."""
    if mesh_shape == ONE:
        fam, params, batch, sys_out = fp32_system
    else:
        fam, mesh, params, batch = small_family(mesh_shape, "float32")
        sys_out = system(fam, mesh, params, batch)
    d_loss, errs = against_reference(fam, params, batch, sys_out)
    # 4 conv operators of 4 leaves, 1 attention of 7, the dense MLP's 4,
    # 4 expert MLPs of 5, the tied table, the final norm.
    assert len(errs) == 49
    assert d_loss <= 1e-5, d_loss
    assert max(errs.values()) <= 1e-5, errs


def test_in_bf16_the_system_is_inside_the_tolerances(bf16_system):
    fam, params, batch, sys_out = bf16_system
    d_loss, errs = against_reference(fam, params, batch, sys_out)
    assert d_loss <= REF.TOLERANCES["loss_abs"], d_loss
    assert max(errs.values()) <= REF.TOLERANCES["grad_rel_l2"], errs


# -- what the comparison sees -----------------------------------------------------

FAULTS = ("taps_reversed", "gates_swapped", "bias_left_out_of_the_choice",
          "renormalisation_left_out", "routing_weights_ignored",
          "qk_norm_over_all_features")
FP8 = ("matmuls_in_e4m3", "matmuls_in_e5m2")


@pytest.mark.parametrize("control", FAULTS)
def test_in_fp32_every_control_shows(fp32_system, control):
    """A reference (standing in for a system) with one thing wrong is far
    from the system where nothing rounds."""
    fam, params, batch, sys_out = fp32_system
    d_loss, errs = under_control(control, fam, params, batch, sys_out)
    assert max(errs.values()) > 1e-2 or d_loss > 1e-2, (
        control, d_loss, max(errs.values()))


@pytest.mark.parametrize("control", FAULTS + FP8)
def test_tolerance_catches(bf16_system, control):
    """Under bf16 compute, with the limits the chip's readings set
    (``TOLERANCES``), every control reads not correct here as there."""
    fam, params, batch, sys_out = bf16_system
    d_loss, errs = under_control(control, fam, params, batch, sys_out)
    assert (max(errs.values()) > REF.TOLERANCES["grad_rel_l2"]
            or d_loss > REF.TOLERANCES["loss_abs"]), (d_loss, errs)


@pytest.mark.parametrize("fp8", FP8)
def test_the_precision_below_bf16_is_far_from_the_system(bf16_system, fp8):
    """The configuration states bf16 compute; the reference with every
    matmul's operands rounded to an 8-bit float, the nearest precision
    below, must come out as not correct: its worst leaf is over the sound
    reading's worst (a router's, which a flipped choice moves at this small
    size) and its median leaf ten times the sound median."""
    fam, params, batch, sys_out = bf16_system
    _d, sound = against_reference(fam, params, batch, sys_out)
    d_loss, errs = under_control(fp8, fam, params, batch, sys_out)
    assert max(errs.values()) > 2 * max(sound.values()), (d_loss, errs)
    assert np.median(list(errs.values())) > 10 * np.median(
        list(sound.values())), (d_loss, errs)


def test_the_flips_tool_counts_the_disagreements_and_masks_them(bf16_system):
    """``router_flips``: where the bf16 system and the fp32 reference chose
    different experts, that token's sparse block leaves the gradients on
    both sides; what is left of every leaf's error is the rounding's, and
    the tool puts the modules back as they were."""
    from horovod_tpu.parallel import moe
    fam, params, batch, _ = bf16_system
    mesh = create_mesh(fam.mesh_shape, devices=jax.devices()[:1])
    before = (moe._scores, moe.dropless_moe, REF.route, REF.ffn_block)
    out = CONTROLS.router_flips(fam, REF, mesh, params,
                                tuple(b[:2] for b in batch))
    assert before == (moe._scores, moe.dropless_moe, REF.route,
                      REF.ffn_block)
    assert len(out["layers"]) == 4 and out["rechosen"] == 0
    assert [r["leaf"] for r in out["layers"]] == [
        f"['layers'][{i}]['ffn']['router']" for i in (1, 2, 3, 4)]
    for row in out["layers"]:
        assert row["tokens"] == 2 * SMALL["seq_len"]
        assert 0 <= row["held_expert_differs_share"] <= row[
            "chosen_differ_share"] < 0.1
        assert row["chosen_differ_share"] > 0
        assert row["router_rel_l2_masked"] < 0.25 * row["router_rel_l2"]
    assert max(out["leaves"].values()) > 0.1
    assert max(out["leaves_masked"].values()) < 0.03


def test_the_controls_tool_leaves_the_reference_as_it_was():
    before = {k: getattr(REF, k) for k in ("causal_conv", "conv_block",
                                           "route", "head_norm", "matmul")}
    for name in CONTROLS.CONTROLS:
        with CONTROLS.patched(REF, name):
            pass
    assert {k: getattr(REF, k) for k in before} == before
    assert set(FAULTS + FP8) | {"none"} == set(CONTROLS.CONTROLS)
    assert CONTROLS.CELL == CELL


# -- the configuration's data and the family's arithmetic ---------------------------

def test_every_published_key_is_there_and_only_the_stated_ones_differ():
    cell = loader.load_cell(CELL)
    c = cell["config"]
    assert cell["config_entry"]["source"].startswith(SOURCE)
    assert "lfm2_moe" in cell["config_entry"]["source"]
    if CATALOG.is_file():
        row = next(json.loads(ln) for ln in CATALOG.read_text().splitlines()
                   if '"LFM2-24B-A2B"' in ln)
        assert row["source_url"] == SOURCE
        published = row["config"]
    else:                       # the catalog is the builder's, not the repo's
        published = {**{k: v for k, v in c.items() if k not in REDUCED},
                     **c["published"]}
    for key, value in published.items():
        assert c[key] == REDUCED.get(key, value), key
    assert sorted(c["reduced"]) == sorted(REDUCED)
    assert c["reduced"] == cell["config_entry"]["reduced"]
    assert c["published"] == {k: published[k] for k in REDUCED}
    # Published layers 1-5 of the forty.
    assert c["layer_types"] == c["published"]["layer_types"][1:6]
    # No width is cut, no head, no tap, no router output, no expert a token.
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["intermediate_size"],
            c["moe_intermediate_size"], c["num_experts_per_tok"],
            c["conv_L_cache"], c["n_experts"]) == (
                2048, 32, 8, 11776, 1536, 4, 3, 64)
    assert cell["entry"]["chips"] == 1
    for key in ("assumed", "departures", "deployment", "distorts",
                "reduced_why"):
        assert c[key], key
    assert "8 chips share each layer" in c["deployment"]
    assert any("head_dim 64" in a for a in c["assumed"])
    assert any("tie_word_embeddings" in a for a in c["assumed"])
    assert cell["traffic"]["global_batch"] == 1
    assert cell["traffic"]["gradient_check"] == "traced_run"
    assert cell["traffic"]["sized_by"]
    assert c["optimizer"]["learning_rate"] == 1e-6


def test_both_spellings_of_a_size_agree():
    c = loader.load_cell(CELL)["config"]
    for repo, published in [
            ("d_model", "hidden_size"), ("n_heads", "num_attention_heads"),
            ("n_kv_heads", "num_key_value_heads"),
            ("n_experts_held", "num_experts"),
            ("top_k", "num_experts_per_tok"),
            ("d_ff", "moe_intermediate_size"),
            ("dense_ff", "intermediate_size"), ("conv_taps", "conv_L_cache"),
            ("router_renormalise", "norm_topk_prob"),
            ("router_scale", "routed_scaling_factor")]:
        assert c[repo] == c[published], (repo, published)
    assert c["attn_head_dim"] * c["n_heads"] == c["hidden_size"]
    assert c["rope_theta"] == c["rope_parameters"]["rope_theta"]
    assert c["router_scoring"] == "sigmoid" and c["use_expert_bias"] is True
    assert c["n_layers"] == 2 * c["num_hidden_layers"]
    assert c["n_experts"] == c["published"]["num_experts"] == 64
    assert c["published"]["vocab_size"] == 8 * c["vocab_size"]
    assert c["published"]["num_experts"] == 8 * c["num_experts"]
    assert c["seq_len"] <= c["max_position_embeddings"]
    # The letters are the published layer kinds, the MLP's after each.
    ops = {"conv": "C", "full_attention": "*"}
    letters = "".join(
        ops[kind] + ("D" if i < c["num_dense_layers"] else "E")
        for i, kind in enumerate(c["layer_types"]))
    assert c["leading_pattern"] + c["layer_pattern"] == letters


def test_flop_arithmetic_is_a_copy_of_the_programs_today():
    cell = loader.load_cell(CELL)
    c = cell["config"]
    fam = FAMILY.Family(c, cell["traffic"]["mesh"])
    assert fam.tokens_per_seq == 32768
    assert fam.flops_per_token() * c["seq_len"] == pytest.approx(
        tfm.train_flops_per_seq(fam.cfg), rel=1e-12)
    per = FAMILY.block_flops_per_token(c)
    assert per["C"] == 8 * 2048 * 2048 + 6 * 2048
    assert per["D"] == 6 * 2048 * 11776
    assert per["*"] == 2 * 2048 * 64 * 80 + 4 * 16384 * 32 * 64
    assert per["E"] == 2 * 2048 * 64 + 0.5 * 6 * 2048 * 1536
    head = 2 * 2048 * 8192
    forward = 4 * per["C"] + per["D"] + per["*"] + 4 * per["E"] + head
    assert fam.flops_per_token() == 3.0 * forward
    assert forward == pytest.approx(506.6e6, rel=1e-3)
    # The shares the cell's ``why`` states.
    assert 4 * 16384 * 32 * 64 / forward == pytest.approx(0.265, abs=0.005)
    assert per["*"] / forward == pytest.approx(0.30, abs=0.01)
    assert per["D"] / forward == pytest.approx(0.29, abs=0.005)
    assert 4 * per["C"] / forward == pytest.approx(0.26, abs=0.01)
    assert 4 * per["E"] / forward == pytest.approx(0.08, abs=0.005)
    batch = cell["traffic"]["global_batch"]
    cost = fam.attention_cost(batch)
    assert set(cost) == {"flops", "bytes", S.GATE_COST}
    calls = batch * 1 * 32
    assert cost["flops"] == calls * 12.0 * 32768 ** 2 * 64 * 0.5
    assert cost["bytes"] == calls * (12 * 32768 * 64 * 2 + 2 * 32768 * 4)
    # The gate path: 8 d bytes a token a block forward, the same in the
    # recompute, 14 d backward; 9.8 ms at the chip's 819 GB/s.
    gate = cost[S.GATE_COST]
    assert gate["bytes"] == 4 * 32768 * (8 + 8 + 14) * 2048
    peaks = loader.load_peaks("TPU v5 lite")
    least, bound = loader.least_seconds(gate, peaks)
    assert bound == "bytes" and least == pytest.approx(9.83e-3, rel=1e-2)


def test_the_family_refuses_another_pattern_and_adapts_a_rehearsals_depth():
    c = {**loader.load_cell(CELL)["config"], **SMALL}
    with pytest.raises(ValueError, match="one operator and one MLP"):
        FAMILY.Family({**c, "layer_pattern": "E*ECECEC"},
                      dict(dp=1, pp=1, mp=1))
    assert FAMILY.patterns_at_depth("CD", "*ECECECE", 10) == (
        "CD", "*ECECECE")
    assert FAMILY.patterns_at_depth("CD", "*ECECECE", 18) == (
        "CD", "*ECECECE")
    assert FAMILY.patterns_at_depth("CD", "*ECECECE", 2) == ("", "*E")
    assert FAMILY.patterns_at_depth("CD", "*ECECECE", 4) == ("", "*ECE")
    fam = FAMILY.Family({**c, "n_heads": 4, "n_kv_heads": 8, "n_layers": 4},
                        dict(dp=1, pp=1, mp=1))
    assert fam.cfg.n_kv_heads == 4 and fam.cfg.layer_pattern == "*ECE"
    assert FAMILY.Family(c, dict(dp=1, pp=1, mp=1)).cfg.n_kv_heads == 2


def test_a_program_without_the_block_is_refused_in_words(monkeypatch):
    """The parent commit under this benchmark: the family says what is
    missing, ``run.py`` prints it and exits 1, and nothing hangs."""
    Old = tfm.TransformerConfig
    fields = tuple(f for f in Old._fields
                   if f not in ("conv_taps", "router_renorm_eps"))
    monkeypatch.setattr(tfm, "TransformerConfig",
                        type("TransformerConfig", (), {"_fields": fields}))
    c = {**loader.load_cell(CELL)["config"], **SMALL}
    with pytest.raises(loader.BenchmarkError, match="conv_taps"):
        FAMILY.Family(c, dict(dp=1, pp=1, mp=1))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_balanced_bias_sends_this_rank_its_share_whatever_the_seed(seed):
    """``Family.init_params``: the correction bias balanced at set-up, so
    that on fresh batches every layer's held experts together get about the
    mean share, none gets nothing and nothing overflows the buffer; without
    it the busiest expert of a seeded sigmoid router takes more."""
    config = {**loader.load_cell(CELL)["config"], **SMALL, "seq_len": 512}
    fam = FAMILY.Family(config, dict(dp=1, pp=1, mp=1))
    mesh = create_mesh(fam.mesh_shape, devices=jax.devices()[:1])
    params = jax.jit(fam.init_params)(jax.random.PRNGKey(seed))
    bias = params["layers"]["moe"]["router_bias"]
    assert bias.shape == (1, 1, 4, 16) and float(jnp.abs(bias).max()) > 0
    routing = tfm.make_routing_fn(fam.cfg, fam.par, mesh)
    batch = fam.draw_batch(np.random.default_rng([seed, 0, 1]), 2)
    r = routing(params, *batch)
    mean = batch[0].size * 4 * 4 / 16
    held = np.asarray(r["assignments"]).reshape(4, 16)[:, :4]
    assert np.abs(np.asarray(r["held_rows"]) / mean - 1).max() < 0.15
    assert held.min() > 0 and int(r["dropped"]) == 0
    unbalanced = {**params, "layers": {**params["layers"], "moe": {
        **params["layers"]["moe"], "router_bias": 0.0 * bias}}}
    assert float(np.asarray(routing(unbalanced, *batch)["load"]).max()) > \
        float(np.asarray(r["load"]).max())
    # The table stays as drawn: the first block is a convolution.
    assert float(jnp.sqrt(jnp.mean(params["embed"] ** 2))) == pytest.approx(
        0.02, rel=0.05)


def test_the_seeded_query_and_key_heads_have_sizes_of_their_own():
    """``Family.init_params``: each head of wq and of wk times a factor in
    (1/2, 2), its own, which a norm over the head's features cancels and a
    norm over all features does not (the control the chip's comparison
    could otherwise not tell from the router's flips)."""
    w = jax.random.normal(jax.random.PRNGKey(3), (1, 1, 1, 64, 8 * 16))
    scaled = FAMILY.heads_at_their_own_scales(jax.random.PRNGKey(4), w, 16)
    factor = (scaled / w).reshape(64, 8, 16)
    per_head = factor[0, :, 0]
    np.testing.assert_allclose(factor, jnp.broadcast_to(
        per_head[None, :, None], factor.shape), rtol=1e-5)
    assert 0.5 < float(per_head.min()) < float(per_head.max()) < 2.0
    assert float(per_head.max() / per_head.min()) > 1.5

    def heads(t):
        return (jnp.ones((5, 64)) @ t[0, 0, 0]).reshape(5, 8, 16)
    np.testing.assert_allclose(
        REF.head_norm(heads(scaled), jnp.ones(16), 1e-12),
        REF.head_norm(heads(w), jnp.ones(16), 1e-12), rtol=1e-4)


def test_the_batch_is_next_token_training_from_the_seed():
    cell = loader.load_cell(CELL)
    fam = FAMILY.Family(cell["config"], cell["traffic"]["mesh"])
    tokens, labels = fam.draw_batch(
        np.random.default_rng([2147483659, 0, 7]), 1)
    again = fam.draw_batch(np.random.default_rng([2147483659, 0, 7]), 1)
    other = fam.draw_batch(np.random.default_rng([2147483659, 0, 8]), 1)
    assert (tokens == again[0]).all() and (tokens != other[0]).any()
    assert tokens.shape == labels.shape == (1, 32768)
    assert tokens.dtype == labels.dtype == np.int32
    assert 0 <= tokens.min() and tokens.max() < 8192
    assert (labels[:, :-1] == tokens[:, 1:]).all()


# -- the cell's own per-layer metrics --------------------------------------------

FUSION = ('%fusion.7 = bf16[32768,2048]{1,0:T(8,128)(2,1)} '
          'fusion(bf16[32768,2048]{1,0} %p.1), kind=kLoop')
WHILE = ('%while.1 = (s32[]{:T(128)}) while((s32[]{:T(128)}) %tuple.1), '
         'condition=%cond, body=%body')
FWD = "jit(train_step)/jvp()/while/body/closed_call/"
BWD = ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
       "rematted_computation/")


def grouped(name: str) -> str:
    return (f'%{name} = bf16[65536,1536]{{1,0:T(8,128)(2,1)}} custom-call('
            '%get-tuple-element.4, %x.1, %copy.1), '
            'custom_call_target="tpu_custom_call", '
            'frontend_attributes={ragged_dot_tiling="512,512,512"}')


def synthetic_device():
    """Two whole steps of 200 ns."""
    meta = {1: (WHILE, ""),
            2: (FUSION, FWD + "hvd_conv/dot_general"),
            3: (FUSION, FWD + "hvd_conv/hvd_conv_gate/mul"),
            4: (FUSION, BWD + "hvd_conv/hvd_conv_gate/mul"),
            5: (FUSION, "jit(train_step)/transpose(jvp())/checkpoint/"
                        "hvd_conv/hvd_conv_gate/reduce_sum"),
            6: (FUSION, "jit(train_step)/jvp()/hvd_mlp/hvd_mlp_dense/"
                        "dot_general"),
            7: (FUSION, FWD + "hvd_mlp/hvd_moe_route/dot_general"),
            8: (grouped("ragged-dot-none.11"), "ragged-dot-none"),
            9: (FUSION, BWD + "hvd_mlp/mul"),
            10: (FUSION, "jit(step)/not_hvd_conv_gate/hvd_convolution/mul")}
    ops = []
    for t0 in (1000, 1200):
        ops.append((1, t0, t0 + 195))
        t = t0 + 5
        for mid, ns in [(2, 40), (3, 6), (4, 8), (5, 4), (6, 20), (7, 30),
                        (8, 11), (9, 5), (10, 7)]:
            ops.append((mid, t, t + ns))
            t += ns
    ops.append((3, 900, 990))                       # before the first step
    return ({R.OPS_LINE: ops,
             R.STEPS_LINE: [("s", 1000, 1200), ("s", 1200, 1400)]}, meta)


def test_classify_device_sorts_self_time_by_the_new_names():
    """The block and its gate path, wherever the scope sits in the path
    (the scanned period's body, the leading block outside it, forward,
    recompute, backward); a name that merely contains one is not it."""
    d = S.classify_device(*synthetic_device())
    assert dict(d["name_ns"]) == {"hvd_conv": 2 * (40 + 6 + 8 + 4),
                                  "hvd_conv_gate": 2 * (6 + 8 + 4)}
    assert S.classify_device({}, {}) == {"name_ns": {}}


def synthetic_layers(monkeypatch):
    lines, meta = synthetic_device()
    device = {**S.classify_device(lines, meta), "n_programs": 2}
    blocks = {**L.classify_device(lines, meta), "n_programs": 2}
    monkeypatch.setattr(S, "classified",
                        lambda layers: {"devices": {0: device}})
    monkeypatch.setattr(L, "classified",
                        lambda layers: {"devices": {0: blocks}})
    cost = {"flops": 1.0, "bytes": 819e9 * 1.8e-9}      # least time 1.8 ns
    return device, {"attention": {"flops": 1.0, "bytes": 1.0,
                                  S.GATE_COST: cost},
                    "peaks": loader.load_peaks("TPU v5 lite"), "trace": {}}


def read_metric(layers, name, better="lower"):
    return loader.load_code("metrics", name).read(
        layers, {"name": name, "better": better})


@pytest.mark.parametrize("name, better, value", [
    ("short_conv_ms_per_step", "lower", 58e-6),
    ("short_conv_gate_ms_per_step", "lower", 18e-6),
    ("short_conv_gate_roofline", "higher", 10.0),
    # Laguna's readers: hvd_mlp_dense (20); hvd_mlp less it (30 + 5) and
    # the grouped matmul (11).
    ("dense_mlp_ms_per_step", "lower", 20e-6),
    ("expert_block_ms_per_step", "lower", 46e-6)])
def test_the_readers_over_a_synthetic_device(monkeypatch, name, better,
                                             value):
    _device, layers = synthetic_layers(monkeypatch)
    assert read_metric(layers, name, better) == pytest.approx(value)


def test_a_program_without_the_block_has_nothing_to_report(monkeypatch):
    device, layers = synthetic_layers(monkeypatch)
    device["name_ns"].clear()
    for name in NEW_METRICS:
        assert read_metric(layers, name) is None
    # ... and a family whose dict carries no gate cost gives no share.
    device, layers = synthetic_layers(monkeypatch)
    del layers["attention"][S.GATE_COST]
    assert read_metric(layers, "short_conv_gate_roofline", "higher") is None
    assert read_metric(layers, "short_conv_gate_ms_per_step") is not None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_names_gives_no_value(
        tmp_path, monkeypatch, name):
    """The parent's program on a traced run of any cell has none of the
    names: the readers find the trace and return nothing, and do not
    raise."""
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    from test_benchmark_trace_moe import NAMED, fake_layers
    layers = fake_layers(tmp_path, monkeypatch, NAMED)
    S._classified.cache_clear()
    out = S.classified(layers)
    assert out is not None and sorted(out["devices"]) == [0, 1, 2, 3]
    assert not any(d["name_ns"] for d in out["devices"].values())
    assert loader.load_code("metrics", name).read(
        layers, {"name": name, "better": "lower"}) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_an_untraced_run_gives_no_value(name):
    layers = {"trace": None, "attention": None, "peaks": None}
    assert loader.load_code("metrics", name).read(
        layers, {"name": name, "better": "lower"}) is None


# The names the benchmark had before this cell, in order (PR 40's tree).
WORKLOADS_BEFORE = [
    "flagship-s8192-train-1chip", "flagship-s8192-train-dp2mp2",
    "bert-base-s512-train-1chip", "olmoe-1b-7b-s4096-train-1chip",
    "nemotron-3-super-s8192-train-1chip", "laguna-s-2.1-s8192-train-1chip",
    "sdar-30b-a3b-s4096-train-1chip"]
CONFIGS_BEFORE = ["flagship-12l-s8192", "bert-base-s512",
                  "olmoe-1b-7b-1l-s4096", "nemotron-3-super-120b-11l-s8192",
                  "laguna-s-2.1-5l-s8192", "sdar-30b-a3b-6l-s4096"]
LAST_METRICS_BEFORE = ["host_gc_share", "host_pause_ms_max",
                       "idle_unexplained_ms_max",
                       "attn_qknorm_rope_ms_per_step"]


def test_the_benchmark_holds_this_cell_and_every_name_it_had():
    """No position or count is pinned: later PRs append too.  This cell's
    entries exist, and every name the parent had is still there, in the
    parent's order."""
    bench = loader.load_benchmark()

    def names(key):
        return [e["name"] for e in bench[key]]

    def in_order(had, now):
        kept = [n for n in now if n in set(had)]
        return kept == had

    assert in_order(WORKLOADS_BEFORE, names("workloads"))
    assert in_order(CONFIGS_BEFORE, names("configs"))
    assert in_order(LAST_METRICS_BEFORE, names("per_layer"))
    assert names("workloads").index(CELL) > names("workloads").index(
        WORKLOADS_BEFORE[-1])
    assert CONFIG in names("configs")
    entry = loader.find(bench["workloads"], CELL, "workload")
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "train-b1-1chip-lfm2", 1)
    for name in NEW_METRICS:
        m = loader.find(bench["per_layer"], name, "metric")
        assert CELL in m["workloads"]
        assert m["moves"] == "tokens_per_s_per_chip"
        assert m["source"] == "device_trace"
        assert m["layer"] == "short-convolution block"
    for name in JOINED_METRICS:
        m = loader.find(bench["per_layer"], name, "metric")
        assert "laguna-s-2.1-s8192-train-1chip" in m["workloads"]
        assert CELL in m["workloads"]
    for m in bench["end_to_end"]:
        if "workloads" in m:
            assert in_order(WORKLOADS_BEFORE, m["workloads"])
            assert CELL in m["workloads"]
    cell = loader.load_cell(CELL)
    assert {m["name"] for m in cell["per_layer"]} >= set(
        NEW_METRICS + JOINED_METRICS) | {
        "attn_kernel_ms_per_step", "attn_kernel_roofline",
        "attn_fwd_kernel_calls_per_step", "head_ms_per_step", "peak_hbm_gb"}
    assert len(json.dumps(bench)) < 64 * 1024


@pytest.mark.slow
def test_the_cell_rehearses_on_the_cpu_at_its_own_small_preset(capsys):
    """Through ``benchmark/run.py`` with the cell's ten blocks kept: the
    loss and, traced, every gradient leaf against the reference, inside the
    limits.  Slow (45 s beside the suite's other workers):
    ``test_benchmark_run_cpu.py`` rehearses the cell through the same
    command at the runner's own preset, and the fp32 tests above hold the
    ten blocks to the reference."""
    rc = run.main(
        ["--workload", CELL, "--seed", "2147483999", "--seconds", "1",
         "--trace", "1"],
        rehearsal=run.Rehearsal(sizes=SMALL, traffic={"global_batch": 2}))
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    checks = [ln for ln in out if "reference check" in ln]
    assert len(checks) == 2 and all(ln.endswith("-> ok") for ln in checks)
    assert "over 49 leaves" in checks[1]
    line = json.loads(out[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["compared"]) == {"loss_abs_diff", "grad_rel_l2_worst"}
