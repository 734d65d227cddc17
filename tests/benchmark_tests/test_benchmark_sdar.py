"""SDAR-30B-A3B-Chat (``sdar``): the system against the benchmark's plain
reference at a small size on the CPU (two layers at hidden 64: 4 query heads
on 2 kv heads of 16, 4 of 16 experts held at width 24, top-4, 64 data tokens
= 128 positions in blocks of 4), the controls and the lower precisions the
comparison must see, the configuration's data and the family's arithmetic,
the host-side noise, and the readers of the cell's own per-layer metrics.
On the chip ``benchmark/run.py`` makes the same comparison at the published
widths, and ``benchmark/tools/sdar_controls.py`` the controls'."""

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
from benchmark.trace import reduce as R           # noqa: E402
from benchmark.trace import sdar as S             # noqa: E402
from horovod_tpu.models import transformer as tfm          # noqa: E402
from horovod_tpu.parallel.mesh import create_mesh          # noqa: E402

CELL = "sdar-30b-a3b-s4096-train-1chip"
CONFIG = "sdar-30b-a3b-6l-s4096"
SMALL = {"vocab_size": 256, "d_model": 64, "attn_head_dim": 16, "n_heads": 4,
         "n_kv_heads": 2, "d_ff": 24, "n_experts": 16, "n_experts_held": 4,
         "top_k": 4, "n_layers": 4, "seq_len": 64,
         "expert_buffer_factor": 8.0}
ONE, DP2 = (1, 1, 1), (2, 1, 1)
REF = loader.load_code("reference", "sdar")
FAMILY = loader.load_code("families", "sdar")
CONTROLS = loader.load_code("tools", "sdar_controls")
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
REDUCED = {"num_hidden_layers": 6, "num_experts": 16, "vocab_size": 18992}
NEW_METRICS = ("attn_qknorm_rope_ms_per_step",)
# Accepted metrics whose ``workloads`` this cell joined: the expert block's
# readers over ``trace/moe.py`` (OLMoE's).
JOINED_METRICS = ("moe_ms_per_step", "moe_expert_matmul_ms_per_step",
                  "moe_route_dispatch_ms_per_step",
                  "moe_expert_matmul_roofline")


def small_family(mesh_shape=ONE, dtype="bfloat16"):
    config = {**loader.load_cell(CELL)["config"], **SMALL, "dtype": dtype}
    fam = FAMILY.Family(config, dict(zip(("dp", "pp", "mp"), mesh_shape)))
    n = int(np.prod(mesh_shape))
    mesh = create_mesh(fam.mesh_shape, devices=jax.devices()[:n])
    params = fam.init_params(jax.random.PRNGKey(0))
    # At hidden 64 the per-head norm's unit scale leaves scores of order 1
    # already; the scales are moved off 1 so that one that is dropped or
    # laid over the wrong axis shows.
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
def test_in_fp32_the_system_is_the_reference_on_every_layout(mesh_shape):
    """With the compute type fp32 nothing rounds differently and no router
    decision can flip: the mask's XLA path against the reference's explicit
    rule, the wrapped positions, the per-head norm, the sorted rows against
    the mask of experts, the weighted sum over the noised half — loss and
    every gradient leaf agree to fp32 round-off."""
    fam, mesh, params, batch = small_family(mesh_shape, "float32")
    d_loss, errs = against_reference(
        fam, params, batch, system(fam, mesh, params, batch))
    # 2 x 7 attention leaves, 2 x 5 of the expert MLPs, embedding, head,
    # final norm.
    assert len(errs) == 27
    assert d_loss <= 1e-5, d_loss
    assert max(errs.values()) <= 1e-5, errs


def test_in_bf16_the_system_is_inside_the_tolerances(bf16_system):
    fam, params, batch, sys_out = bf16_system
    d_loss, errs = against_reference(fam, params, batch, sys_out)
    assert d_loss <= REF.TOLERANCES["loss_abs"], d_loss
    assert max(errs.values()) <= REF.TOLERANCES["grad_rel_l2"], errs


# -- what the comparison sees -----------------------------------------------------

MASK_AND_LOSS = ("the_answer_leaks", "own_noised_block_dropped",
                 "positions_not_wrapped", "weights_ignored",
                 "qk_norm_over_all_features")


@pytest.mark.parametrize("control", MASK_AND_LOSS)
def test_in_fp32_every_control_shows(fp32_system, control):
    """A reference (standing in for a system) with one thing wrong is far
    from the system where nothing rounds."""
    fam, params, batch, sys_out = fp32_system
    d_loss, errs = under_control(control, fam, params, batch, sys_out)
    assert max(errs.values()) > 1e-2 or d_loss > 1e-2, (
        control, d_loss, max(errs.values()))


def test_the_sound_system_is_well_inside_the_limits(bf16_system):
    fam, params, batch, sys_out = bf16_system
    d_loss, sound = against_reference(fam, params, batch, sys_out)
    assert 4 * d_loss < REF.TOLERANCES["loss_abs"]
    assert 4 * max(sound.values()) < REF.TOLERANCES["grad_rel_l2"]
    assert REF.TOLERANCES["grad_rel_l2"] < 1.0     # a zero gradient reads 1


@pytest.mark.parametrize("control", MASK_AND_LOSS + (
    "matmuls_in_e4m3", "matmuls_in_e5m2"))
def test_tolerance_catches(bf16_system, control):
    """Under bf16 compute, with the limits the chip's readings set
    (``TOLERANCES``), every control reads not correct here as there: the
    family's seeded weights make a position its own token first
    (``Family.init_params``), so attention carries what the loss reads and a
    fault of four keys a query moves the attention leaves by tens of
    percent, where the sound system's worst leaf is a router's at 2 %."""
    fam, params, batch, sys_out = bf16_system
    d_loss, errs = under_control(control, fam, params, batch, sys_out)
    assert (max(errs.values()) > REF.TOLERANCES["grad_rel_l2"]
            or d_loss > REF.TOLERANCES["loss_abs"]), (d_loss, errs)


def test_a_bf16_head_is_not_told_from_the_system(bf16_system):
    """Log-probabilities carried in bf16 are off by up to half a spacing
    (0.031 near ln 18992) with no bias, and the loss is a weighted mean over
    the ~L / 2 masked positions: at the published sizes it moves by a draw
    of N(0, 0.018 sqrt(L ln 1000) / L) = N(0, 7e-4), the size of a sound
    difference, and the gradients pass the rounding straight through.  No
    limit that sound seeds pass refuses it (PERF.md section 7); what holds
    the head to fp32 is ``test_in_fp32_the_system_is_the_reference``."""
    spacing = 2.0 ** (np.floor(np.log2(np.log(18992.0))) - 7)
    assert spacing == 0.0625
    sigma = spacing / np.sqrt(12) * np.sqrt(4096 * np.log(1000.0)) / 4096
    assert sigma < REF.TOLERANCES["loss_abs"] < 3 * sigma
    fam, params, batch, sys_out = bf16_system
    d_loss, errs = under_control("a_bf16_head", fam, params, batch, sys_out)
    _d, sound = against_reference(fam, params, batch, sys_out)
    assert np.isfinite(d_loss)
    assert max(errs.values()) == pytest.approx(max(sound.values()), rel=1e-3)


@pytest.mark.parametrize("fp8", ["matmuls_in_e4m3", "matmuls_in_e5m2"])
def test_the_precision_below_bf16_is_far_from_the_system(bf16_system, fp8):
    """The configuration states bf16 compute; the reference with every
    matmul's operands rounded to an 8-bit float, the nearest precision
    below, must come out as not correct: its worst leaf is ten times the
    sound reading's and more."""
    fam, params, batch, sys_out = bf16_system
    _d, sound = against_reference(fam, params, batch, sys_out)
    d_loss, errs = under_control(fp8, fam, params, batch, sys_out)
    assert max(errs.values()) > 10 * max(sound.values()), (d_loss, errs)


def test_the_controls_tool_leaves_the_reference_as_it_was():
    before = {k: getattr(REF, k) for k in ("visible", "positions", "head_norm",
                                           "head", "matmul", "loss")}
    for name in CONTROLS.CONTROLS:
        with CONTROLS.patched(REF, name):
            pass
    assert {k: getattr(REF, k) for k in before} == before
    assert set(MASK_AND_LOSS) < set(CONTROLS.CONTROLS)
    assert CONTROLS.CELL == CELL


# -- the configuration's data and the family's arithmetic ---------------------------

def test_every_published_key_is_there_and_only_the_stated_ones_differ():
    cell = loader.load_cell(CELL)
    c = cell["config"]
    assert cell["config_entry"]["source"] == \
        "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"
    if CATALOG.is_file():
        row = next(json.loads(ln) for ln in CATALOG.read_text().splitlines()
                   if '"SDAR-30B-A3B-Chat"' in ln)
        assert row["source_url"] == cell["config_entry"]["source"]
        published = row["config"]
    else:                       # the catalog is the builder's, not the repo's
        published = {**{k: v for k, v in c.items() if k not in REDUCED},
                     **c["published"]}
    for key, value in published.items():
        assert c[key] == REDUCED.get(key, value), key
    assert sorted(c["reduced"]) == sorted(REDUCED)
    assert c["reduced"] == cell["config_entry"]["reduced"]
    assert c["published"] == {k: published[k] for k in REDUCED}
    # No width is cut, no head is cut.
    assert (c["hidden_size"], c["head_dim"], c["num_attention_heads"],
            c["num_key_value_heads"], c["moe_intermediate_size"],
            c["num_experts_per_tok"], c["intermediate_size"]) == (
                2048, 128, 32, 4, 768, 8, 6144)
    assert cell["entry"]["chips"] == 1
    for key in ("assumed", "departures", "deployment", "distorts",
                "reduced_why", "objective"):
        assert c[key], key
    assert "8 chips share each layer" in c["deployment"]
    assert any("block length 4" in a for a in c["assumed"])
    assert cell["traffic"]["global_batch"] == 1
    assert cell["traffic"]["gradient_check"] == "traced_run"
    assert cell["traffic"]["sized_by"]


def test_both_spellings_of_a_size_agree():
    c = loader.load_cell(CELL)["config"]
    for repo, published in [
            ("d_model", "hidden_size"), ("n_heads", "num_attention_heads"),
            ("n_kv_heads", "num_key_value_heads"),
            ("attn_head_dim", "head_dim"),
            ("n_experts_held", "num_experts"),
            ("top_k", "num_experts_per_tok"),
            ("d_ff", "moe_intermediate_size"), ("norm_eps", "rms_norm_eps"),
            ("router_renormalise", "norm_topk_prob"),
            ("tied_head", "tie_word_embeddings")]:
        assert c[repo] == c[published], (repo, published)
    assert c["n_layers"] == 2 * c["num_hidden_layers"]
    assert c["n_experts"] == c["published"]["num_experts"] == 128
    assert c["layer_pattern"] == "*E" and c["mlp_only_layers"] == []
    assert c["decoder_sparse_step"] == 1
    assert c["published"]["vocab_size"] == 8 * c["vocab_size"]
    assert c["published"]["num_experts"] == 8 * c["num_experts"]
    assert 2 * c["seq_len"] <= c["max_position_embeddings"]
    assert c["seq_len"] % c["diffusion_block"] == 0


def test_flop_arithmetic_is_a_copy_of_the_programs_today():
    cell = loader.load_cell(CELL)
    c = cell["config"]
    fam = FAMILY.Family(c, cell["traffic"]["mesh"])
    assert fam.tokens_per_seq == 4096                 # data tokens
    assert fam.flops_per_token() * c["seq_len"] == pytest.approx(
        tfm.train_flops_per_seq(fam.cfg), rel=1e-12)
    assert FAMILY.live_pairs(c) == 4096 ** 2 + 4 * 4096
    assert FAMILY.position_flops(c) == \
        2 * 18_874_368 + 2 * 262_144 + 2 * 4_718_592
    assert FAMILY.score_flops_per_position(c) == 2050 * 4 * 128 * 32
    layers = 6 * 2 * (FAMILY.position_flops(c)
                      + FAMILY.score_flops_per_position(c))
    head = 2 * 2048 * 18992
    assert fam.flops_per_token() == 3.0 * (layers + head)
    assert layers == pytest.approx(975.6e6, rel=1e-4)
    assert fam.flops_per_token() == pytest.approx(3.16e9, rel=2e-3)
    # The shares the cell's ``why`` states.
    total = layers + head
    scores = 12 * FAMILY.score_flops_per_position(c)
    assert scores / total == pytest.approx(0.38, abs=0.005)
    assert (scores + 12 * 2 * 18_874_368) / total == pytest.approx(0.81,
                                                                   abs=0.005)
    assert 12 * (2 * 262_144 + 2 * 4_718_592) / total == pytest.approx(
        0.11, abs=0.005)
    assert head / total == pytest.approx(0.07, abs=0.005)
    batch = cell["traffic"]["global_batch"]
    cost = fam.attention_cost(batch)
    calls = batch * 6 * 32
    pairs = 4096 ** 2 + 4 * 4096
    assert set(cost) == {"flops", "bytes", "moe_expert_matmul"}
    assert cost["flops"] == calls * 12.0 * pairs * 128
    assert cost["bytes"] == calls * (8 * 8192 * 128 * 2 + 2 * 8192 * 4)
    # The live pairs only: a third of the 2L x 2L square's FLOPs, two
    # thirds of the causal triangle's of 2L.
    assert pairs / 8192 ** 2 == pytest.approx(0.25, abs=1e-3)
    assert pairs / (8192 ** 2 / 2) == pytest.approx(0.5, abs=1e-3)
    assert cost["moe_expert_matmul"]["flops"] == pytest.approx(
        6 * 3.0 * batch * 8192 * 1.0 * 6 * 2048 * 768, rel=1e-12)


def test_the_family_refuses_another_pattern():
    c = {**loader.load_cell(CELL)["config"], **SMALL}
    with pytest.raises(ValueError, match="one attention and one expert"):
        FAMILY.Family({**c, "n_layers": 3}, dict(dp=1, pp=1, mp=1))
    with pytest.raises(ValueError, match="one attention and one expert"):
        FAMILY.Family({**c, "layer_pattern": "E*"}, dict(dp=1, pp=1, mp=1))


def test_a_program_without_the_fields_is_refused_in_words(monkeypatch):
    """The parent commit under this benchmark: the family says what is
    missing, ``run.py`` prints it and exits 1, and nothing hangs."""
    Old = tfm.TransformerConfig
    fields = tuple(f for f in Old._fields
                   if f not in ("diffusion_block", "head_qk_norm"))
    monkeypatch.setattr(tfm, "TransformerConfig",
                        type("TransformerConfig", (), {"_fields": fields}))
    c = {**loader.load_cell(CELL)["config"], **SMALL}
    with pytest.raises(loader.BenchmarkError, match="diffusion_block"):
        FAMILY.Family(c, dict(dp=1, pp=1, mp=1))


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_seeded_weights_send_this_rank_its_share_whatever_the_seed(seed):
    """``Family.init_params``: the mask token's eight are decided by a margin
    and one of them is this rank's first expert, so every layer has held
    rows (every masked position's, at the least), the sixteen together get
    the mean share, and nothing overflows the buffer."""
    config = {**loader.load_cell(CELL)["config"], **SMALL}
    fam = FAMILY.Family(config, dict(dp=1, pp=1, mp=1))
    mesh = create_mesh(fam.mesh_shape, devices=jax.devices()[:1])
    params = fam.init_params(jax.random.PRNGKey(seed))
    batch = fam.draw_batch(np.random.default_rng([seed, 1, 0]), 4)
    r = tfm.make_routing_fn(fam.cfg, fam.par, mesh)(params, *batch)
    per_expert = np.asarray(r["assignments"]).reshape(-1, SMALL["n_experts"])
    masked = int((batch[0] == fam.mask_id).sum())
    assert (per_expert[:, 0] >= masked).all(), (per_expert[:, 0], masked)
    mean = batch[0].size * SMALL["top_k"] * SMALL["n_experts_held"] \
        / SMALL["n_experts"]
    assert np.abs(np.asarray(r["held_rows"]) / mean - 1).max() < 0.2
    assert int(r["dropped"]) == 0
    # The embedding is at unit RMS: a position is its own token first.
    assert float(jnp.sqrt(jnp.mean(params["embed"] ** 2))) == pytest.approx(
        0.02 * SMALL["d_model"] ** 0.5, rel=0.05)


# -- the noise -----------------------------------------------------------------------

def test_the_batch_is_noised_block_by_block_from_the_seed():
    cell = loader.load_cell(CELL)
    fam = FAMILY.Family(cell["config"], cell["traffic"]["mesh"])
    assert fam.mask_id == 18991
    tokens, labels, weights = fam.draw_batch(
        np.random.default_rng([2147483659, 0, 7]), 2)
    again = fam.draw_batch(np.random.default_rng([2147483659, 0, 7]), 2)
    other = fam.draw_batch(np.random.default_rng([2147483659, 0, 8]), 2)
    assert all((a == b).all() for a, b in zip((tokens, labels, weights),
                                              again))
    assert (tokens != other[0]).any()
    assert tokens.shape == (2, 8192) and tokens.dtype == np.int32
    assert labels.shape == weights.shape == (2, 4096)
    assert labels.dtype == np.int32 and weights.dtype == np.float32
    noised, clean = tokens[:, :4096], tokens[:, 4096:]
    assert (clean == labels).all()
    assert labels.max() < fam.mask_id            # a label is never MASK
    masked = noised == fam.mask_id
    assert (noised[~masked] == clean[~masked]).all()
    assert ((weights > 0) == masked).all()
    by_block = weights.reshape(2, 1024, 4)
    top = by_block.max(-1, keepdims=True)
    assert ((by_block == 0) | (by_block == top)).all()   # one t a block
    assert weights.max() <= 1000.0 + 1e-3                # t >= 1e-3
    # Over 2048 blocks with t uniform: half the positions are masked.
    assert abs(masked.mean() - 0.5) < 0.03
    assert abs(weights.mean() - 1.0) < 0.1


# -- the cell's own per-layer metrics --------------------------------------------

FUSION = ('%fusion.7 = bf16[8192,2048]{1,0:T(8,128)(2,1)} '
          'fusion(bf16[8192,2048]{1,0} %p.1), kind=kLoop')
WHILE = ('%while.1 = (s32[]{:T(128)}) while((s32[]{:T(128)}) %tuple.1), '
         'condition=%cond, body=%body')
FWD = "jit(train_step)/jvp()/while/body/checkpoint/"
BWD = ("jit(train_step)/transpose(jvp())/while/body/checkpoint/"
       "rematted_computation/")


def kernel(name: str) -> str:
    return (f'%{name} = (bf16[1,32,8192,128]{{3,2,1,0}}) custom-call(%q), '
            'custom_call_target="tpu_custom_call"')


def grouped(name: str) -> str:
    return (f'%{name} = bf16[32768,768]{{1,0:T(8,128)(2,1)}} custom-call('
            '%get-tuple-element.4, %x.1, %copy.1), '
            'custom_call_target="tpu_custom_call", '
            'frontend_attributes={ragged_dot_tiling="512,512,512"}')


def synthetic_device():
    """Two whole steps of 200 ns."""
    meta = {1: (WHILE, ""),
            2: (kernel("hvd_flash_fwd_bd.3"), FWD + "hvd_attn/pallas_call"),
            3: (kernel("hvd_flash_fwd.1"), FWD + "hvd_attn/pallas_call"),
            4: (kernel("hvd_flash_bwd_dq_bd.2"), BWD + "hvd_attn/pallas_call"),
            5: (kernel("hvd_flash_bwd_dkv_bd.2"),
                BWD + "hvd_attn/pallas_call"),
            6: (FUSION, FWD + "hvd_attn/hvd_attn_rope/mul"),
            7: (FUSION, BWD + "hvd_attn/hvd_attn_qknorm/mul"),
            8: (FUSION, FWD + "hvd_mlp/hvd_moe_route/dot_general"),
            9: (grouped("ragged-dot-none.11"), "ragged-dot-none"),
            10: (FUSION, BWD + "hvd_mlp/mul"),
            11: (FUSION, "jit(step)/not_hvd_attn_rope/mul")}
    ops = []
    for t0 in (1000, 1200):
        ops.append((1, t0, t0 + 195))
        t = t0 + 5
        for mid, ns in [(2, 10), (3, 40), (4, 12), (5, 14), (6, 6), (7, 8),
                        (8, 30), (9, 11), (10, 5), (11, 7)]:
            ops.append((mid, t, t + ns))
            t += ns
    ops.append((2, 900, 990))                       # before the first step
    return ({R.OPS_LINE: ops,
             R.STEPS_LINE: [("s", 1000, 1200), ("s", 1200, 1400)]}, meta)


def test_classify_device_sorts_self_time_by_the_new_names():
    """The per-head norm and the rotation, wherever the scope sits in the
    path; a name that merely contains one is not it."""
    d = S.classify_device(*synthetic_device())
    assert dict(d["name_ns"]) == {"hvd_attn_rope": 12, "hvd_attn_qknorm": 16}
    assert S.classify_device({}, {}) == {"name_ns": {}}


def synthetic_layers(monkeypatch):
    from benchmark.trace import moe as M
    lines, meta = synthetic_device()
    device = {**S.classify_device(lines, meta), "n_programs": 2}
    expert = {**M.classify_device(lines, meta), "n_programs": 2}
    monkeypatch.setattr(S, "classified",
                        lambda layers: {"devices": {0: device}})
    monkeypatch.setattr(M, "classified",
                        lambda layers: {"devices": {0: expert}})
    cost = {"flops": 197e12 * 1.1e-9, "bytes": 1.0}    # least time 1.1 ns
    return device, {"attention": {"flops": 1.0, "bytes": 1.0,
                                  "moe_expert_matmul": cost},
                    "peaks": loader.load_peaks("TPU v5 lite"), "trace": {}}


def read_metric(layers, name, better="lower"):
    return loader.load_code("metrics", name).read(
        layers, {"name": name, "better": better})


def test_the_new_reader_over_a_synthetic_device(monkeypatch):
    device, layers = synthetic_layers(monkeypatch)
    assert read_metric(layers, "attn_qknorm_rope_ms_per_step") == \
        pytest.approx(14e-6)
    # A program without the per-head norm has nothing to report.
    del device["name_ns"]["hvd_attn_qknorm"]
    assert read_metric(layers, "attn_qknorm_rope_ms_per_step") is None


@pytest.mark.parametrize("name, better, value", [
    # hvd_mlp (30 + 5) and the grouped matmul (11), a step.
    ("moe_ms_per_step", "lower", 46e-6),
    ("moe_expert_matmul_ms_per_step", "lower", 11e-6),
    ("moe_route_dispatch_ms_per_step", "lower", 30e-6),
    ("moe_expert_matmul_roofline", "higher", 10.0)])
def test_the_expert_blocks_accepted_readers_read_this_cells_step(
        monkeypatch, name, better, value):
    """The held path runs under the scopes and grouped-matmul kernels that
    ``trace/moe.py`` reads for OLMoE, and the family's dict carries the cost
    ``moe_expert_matmul_roofline`` asks for: the cell joined those four
    metrics' ``workloads`` instead of bringing a sum of its own."""
    _device, layers = synthetic_layers(monkeypatch)
    assert read_metric(layers, name, better) == pytest.approx(value)


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


# The names the benchmark had before this cell, in order (PR 37's tree).
WORKLOADS_BEFORE = [
    "flagship-s8192-train-1chip", "flagship-s8192-train-dp2mp2",
    "bert-base-s512-train-1chip", "olmoe-1b-7b-s4096-train-1chip",
    "nemotron-3-super-s8192-train-1chip", "laguna-s-2.1-s8192-train-1chip"]
CONFIGS_BEFORE = ["flagship-12l-s8192", "bert-base-s512",
                  "olmoe-1b-7b-1l-s4096", "nemotron-3-super-120b-11l-s8192",
                  "laguna-s-2.1-5l-s8192"]
LAST_METRICS_BEFORE = ["host_gc_share", "host_pause_ms_max",
                       "idle_unexplained_ms_max"]


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
    assert (entry["config"], entry["chips"]) == (CONFIG, 1)
    for name in NEW_METRICS:
        m = loader.find(bench["per_layer"], name, "metric")
        assert m["workloads"] == [CELL]
        assert m["moves"] == "tokens_per_s_per_chip"
        assert m["source"] == "device_trace"
    for name in JOINED_METRICS:
        m = loader.find(bench["per_layer"], name, "metric")
        assert m["workloads"] == ["olmoe-1b-7b-s4096-train-1chip", CELL]
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


def test_the_cell_rehearses_on_the_cpu_at_its_own_small_preset(capsys):
    """Through ``benchmark/run.py`` with two whole layers kept: the loss
    and, traced, every gradient leaf against the reference, inside the
    limits."""
    rc = run.main(
        ["--workload", CELL, "--seed", "2147483999", "--seconds", "1",
         "--trace", "1"],
        rehearsal=run.Rehearsal(sizes=SMALL, traffic={"global_batch": 2}))
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    checks = [ln for ln in out if "reference check" in ln]
    assert len(checks) == 2 and all(ln.endswith("-> ok") for ln in checks)
    assert "over 27 leaves" in checks[1]
    line = json.loads(out[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["compared"]) == {"loss_abs_diff", "grad_rel_l2_worst"}
