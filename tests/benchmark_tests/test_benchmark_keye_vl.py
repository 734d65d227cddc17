"""The Keye-VL cell off the chip: the family against its plain reference at
a small size (fp32: the same numbers; bf16: inside what a control moves),
the controls of ``tools/keye_vl_controls.py``, the configuration's data and
the family's arithmetic, the traffic's positions against the rule, the share
of the experts against the uncut layer, and the cell's readers over a
synthetic device."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import loader                                     # noqa: E402
from benchmark.trace import keye_vl as K                         # noqa: E402
from benchmark.trace import reduce as R                          # noqa: E402
from horovod_tpu.models import transformer as tfm                # noqa: E402
from horovod_tpu.parallel.mesh import create_mesh                # noqa: E402

CELL = "keye-vl-2.0-30b-a3b-s16384-train-1chip"
CONFIG = "keye-vl-2.0-30b-a3b-4l-s16384"
SMALL = {"vocab_size": 256, "d_model": 64, "attn_head_dim": 16, "n_heads": 4,
         "n_kv_heads": 2, "d_ff": 24, "n_experts": 16, "n_experts_held": 4,
         "top_k": 4, "n_layers": 4, "seq_len": 64, "index_heads": 2,
         "index_head_dim": 8, "index_topk": 16, "rope_sections": [2, 2, 4],
         "image_spans": 2, "image_grid": [3, 5], "rope_theta": 100.0,
         "expert_buffer_factor": 8.0}
ONE, DP2 = (1, 1, 1), (2, 1, 1)
REF = loader.load_code("reference", "keye_vl")
FAMILY = loader.load_code("families", "keye_vl")
CONTROLS = loader.load_code("tools", "keye_vl_controls")
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
REDUCED = {"num_hidden_layers": 4, "num_experts": 16,
           "num_local_experts": 16, "vocab_size": 18992}
NEW_METRICS = ("sparse_attn_kernel_ms_per_step",
               "sparse_attn_kernel_roofline", "attn_index_ms_per_step",
               "attn_select_ms_per_step", "attn_index_loss_ms_per_step")
INDEXER = ("index_wq", "index_wk", "index_ww", "index_k_norm", "index_k_bias")


def small_family(mesh_shape=ONE, dtype="bfloat16", **sizes):
    config = {**loader.load_cell(CELL)["config"], **SMALL, "dtype": dtype,
              **sizes}
    fam = FAMILY.Family(config, dict(zip(("dp", "pp", "mp"), mesh_shape)))
    n = int(np.prod(mesh_shape))
    mesh = create_mesh(fam.mesh_shape, devices=jax.devices()[:n])
    params = fam.init_params(jax.random.PRNGKey(0))
    batch = fam.draw_batch(np.random.default_rng(5), 4)
    return fam, mesh, params, batch


def quick(fn, *args):
    """``fn(*args)`` as one program compiled without the CPU backend's
    optimisation passes: these programs are all compile and no run."""
    return jax.jit(fn).lower(*args).compile(
        compiler_options={"xla_backend_optimization_level": 0})(*args)


def system(fam, mesh, params, batch):
    return quick(jax.value_and_grad(fam.loss_fn(mesh)), params, *batch)


def against_reference(fam, params, batch, sys_out):
    """(|loss difference|, {leaf: relative L2 error of its gradient})."""
    args = fam.reference_args()
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = quick(jax.value_and_grad(
            lambda p, *b: REF.loss(p, *b, **args)),
            fam.to_reference(params), *batch)
    sys_loss, sys_grads = sys_out
    errs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.sqrt(jnp.sum((a - b) ** 2) / jnp.sum(b ** 2))),
        fam.to_reference(jax.device_get(sys_grads)), ref_grads)
    return (abs(float(sys_loss) - float(ref_loss)),
            {jax.tree_util.keystr(k): v for k, v in
             jax.tree_util.tree_leaves_with_path(errs)})


def under_control(name, fam, params, batch, sys_out):
    # The tool's own patches, a block of 8 keys where it takes 512.
    with CONTROLS.patched(REF, name, block=8):
        return against_reference(fam, params, batch, sys_out)


@pytest.fixture(scope="module")
def fp32_system():
    """One layer: a control's program is compiled for every one of them."""
    fam, mesh, params, batch = small_family(ONE, "float32", n_layers=2)
    return fam, params, batch, system(fam, mesh, params, batch)


# -- the system is the reference ------------------------------------------------

@pytest.mark.parametrize("mesh_shape", [ONE, DP2])
def test_in_fp32_the_system_is_the_reference_on_every_layout(mesh_shape):
    """With the compute type fp32 nothing rounds differently and no choice
    can flip: the counted selection against ``lax.top_k``, the visibility
    operand against the dense mask, three-stream positions, the loss tiled
    from the saved lse against the dense KL, the sorted rows against the
    mask of experts — loss and every gradient leaf agree to round-off."""
    fam, mesh, params, batch = small_family(mesh_shape, "float32")
    d_loss, errs = against_reference(
        fam, params, batch, system(fam, mesh, params, batch))
    # 2 x 12 attention leaves (five of them the indexer's), 2 x 5 of the
    # expert MLPs, embedding, head, final norm.
    assert len(errs) == 37
    assert d_loss <= 1e-5, d_loss
    assert max(errs.values()) <= 2e-5, errs


def test_in_bf16_the_system_is_near_the_reference():
    """The cell's limits are the chip's at the published widths (``REF.
    TOLERANCES``); at hidden 64 a flipped choice of key or expert weighs
    more, so this holds the rehearsal's size to a looser band."""
    fam, mesh, params, batch = small_family()
    d_loss, errs = against_reference(
        fam, params, batch, system(fam, mesh, params, batch))
    assert d_loss <= 5e-3, d_loss
    assert max(errs.values()) <= 0.5, errs


# -- what the comparison sees -----------------------------------------------------

# Each coarse control moves the loss or some leaf far past fp32's round-off;
# so do the fine ones, which on the chip hide under bf16's noise.
@pytest.mark.parametrize("control", [
    c for c in CONTROLS.CONTROLS if c != "none"])
def test_in_fp32_every_control_shows(fp32_system, control):
    fam, params, batch, sys_out = fp32_system
    d_loss, errs = under_control(control, fam, params, batch, sys_out)
    assert d_loss > 1e-4 or not all(v <= 1e-3 for v in errs.values()), (
        d_loss, errs)


def test_the_gradient_paths_are_told_apart_by_the_leaves_they_reach(
        fp32_system):
    """The indexer's input not detached: L_I reaches the leaves before it
    and nothing of the indexer's own changes.  The loss dropped: the
    indexer's leaves lose everything, the others nothing."""
    fam, params, batch, sys_out = fp32_system
    _, errs = under_control("indexer_input_not_detached", fam, params, batch,
                            sys_out)
    moved = {k for k, v in errs.items() if v > 1e-3}
    assert moved and not any(k.endswith(f"['{n}']") for k in moved
                             for n in INDEXER), moved
    d_loss, errs = under_control("index_loss_dropped", fam, params, batch,
                                 sys_out)
    assert d_loss > 1e-3
    # The reference's indexer gradients are exactly zero, so the relative
    # error is a division by zero; the other leaves read as before.
    assert all(not np.isfinite(v) for k, v in errs.items()
               if any(k.endswith(f"['{n}']") for n in INDEXER))
    assert all(v <= 2e-5 for k, v in errs.items()
               if not any(k.endswith(f"['{n}']") for n in INDEXER))


def test_the_controls_tool_leaves_the_reference_as_it_was():
    names = ("choose", "index_loss_of", "indexer_input", "heads_mean",
             "stream_sections", "position_streams", "attention_matmul",
             "matmul", "loss")
    before = {k: getattr(REF, k) for k in names}
    for name in CONTROLS.CONTROLS:
        with CONTROLS.patched(REF, name):
            pass
    assert {k: getattr(REF, k) for k in before} == before
    assert CONTROLS.CELL == CELL
    # Every control ISSUE 49 lists and the configuration's precision below
    # in every product, the two fine ones last.
    assert CONTROLS.CONTROLS[-2:] == ("topk_one_key_short",
                                      "ties_to_the_earlier_key")
    assert len(CONTROLS.CONTROLS) == 13


# -- the configuration's data and the family's arithmetic ---------------------------

def test_every_published_key_is_there_and_only_the_stated_ones_differ():
    cell = loader.load_cell(CELL)
    c = cell["config"]
    assert cell["config_entry"]["source"] == (
        "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
        "config.json")
    if CATALOG.is_file():
        row = next(json.loads(ln) for ln in CATALOG.read_text().splitlines()
                   if '"Keye-VL-2.0-30B-A3B"' in ln)
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
    # No width is cut, no head is cut, the indexer is whole.
    assert (c["hidden_size"], c["head_dim"], c["num_attention_heads"],
            c["num_key_value_heads"], c["moe_intermediate_size"],
            c["num_experts_per_tok"], c["intermediate_size"]) == (
                2048, 128, 32, 4, 768, 8, 6144)
    assert c["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    assert c["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert cell["entry"]["chips"] == 1
    for key in ("assumed", "departures", "deployment", "distorts",
                "reduced_why", "positions", "indexer", "index_loss"):
        assert c[key], key
    assert "8 chips share each layer" in c["deployment"]
    assert any("no vision tower" in d for d in c["departures"])
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
            ("n_experts_held", "num_local_experts"),
            ("top_k", "num_experts_per_tok"),
            ("d_ff", "moe_intermediate_size"), ("norm_eps", "rms_norm_eps"),
            ("router_renormalise", "norm_topk_prob"),
            ("tied_head", "tie_word_embeddings")]:
        assert c[repo] == c[published], (repo, published)
    sa = c["sa_config"]
    assert (c["index_heads"], c["index_head_dim"], c["index_topk"]) == (
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"])
    assert c["rope_sections"] == c["rope_scaling"]["mrope_section"]
    assert c["n_layers"] == 2 * c["num_hidden_layers"]
    assert c["n_experts"] == c["published"]["num_experts"] == 128
    assert c["layer_pattern"] == "SE" and c["mlp_only_layers"] == []
    assert c["published"]["vocab_size"] == 8 * c["vocab_size"]
    assert c["published"]["num_experts"] == 8 * c["num_experts"]
    assert c["seq_len"] <= c["max_position_embeddings"]
    assert c["seq_len"] == 8 * c["index_topk"]


def test_parameter_count_is_exact():
    cell = loader.load_cell(CELL)
    fam = FAMILY.Family(cell["config"], cell["traffic"]["mesh"])
    shapes = jax.eval_shape(fam.init_params, jax.random.PRNGKey(0))
    count = {k: sum(int(np.prod(a.shape))
                    for a in jax.tree_util.tree_leaves(v))
             for k, v in {**shapes, **shapes["layers"]}.items()
             if k != "layers"}
    indexer = sum(int(np.prod(shapes["layers"]["sel"][n].shape))
                  for n in INDEXER)
    assert indexer == 4 * 2_261_120
    assert count["sel"] - indexer == 4 * 18_876_672
    assert count["moe"] == 4 * 75_761_664
    assert count["embed"] == count["lm_head"] == 38_895_616
    assert sum(count.values()) == cell["config"]["parameters"] == 465_391_104
    # 16 bytes a parameter: 6.93 GiB, 43 % of a 16 GiB chip.
    assert 16 * 465_391_104 / 2 ** 30 == pytest.approx(6.93, abs=0.01)


def test_flop_arithmetic_is_the_programs_and_counts_the_chosen_pairs():
    cell = loader.load_cell(CELL)
    c = cell["config"]
    fam = FAMILY.Family(c, cell["traffic"]["mesh"])
    assert fam.flops_per_token() * c["seq_len"] == pytest.approx(
        tfm.train_flops_per_seq(fam.cfg), rel=1e-12)
    # ISSUE 49's count, M a token forward.
    parts = {k: v / 1e6 for k, v in FAMILY.layer_flops_per_token(c).items()}
    assert parts == pytest.approx({
        "projections": 37.75, "index_projections": 4.52,
        "index_scores": 16.78, "chosen_scores": 31.46, "router": 0.52,
        "held_experts": 9.44}, abs=0.005)
    assert sum(parts.values()) == pytest.approx(100.47, abs=0.01)
    assert fam.flops_per_token() / 1e9 == pytest.approx(1.439, abs=0.001)
    layers, total = 4 * sum(parts.values()), fam.flops_per_token() / 3e6
    # The shares the configuration's ``distorts`` states.
    assert 4 * (parts["index_scores"] + parts["chosen_scores"]) / total == \
        pytest.approx(0.40, abs=0.005)
    assert (layers - 4 * (parts["router"] + parts["held_experts"])) / total \
        == pytest.approx(0.75, abs=0.005)
    assert 4 * parts["held_experts"] / total == pytest.approx(0.08, abs=0.005)
    assert (total - layers) / total == pytest.approx(0.16, abs=0.005)
    # A brute-force count of the chosen pairs at a small size: a mask made
    # by the reference's own choice, whatever the scores.
    small = {**c, **SMALL}
    s, k = small["seq_len"], small["index_topk"]
    seen = np.tril(np.ones((s, s), bool))
    chosen = REF.choose(jnp.asarray(np.random.default_rng(0).normal(
        size=(s, s)), jnp.float32), jnp.asarray(seen), k)
    assert int(jnp.sum(chosen)) == FAMILY.chosen_pairs(small)
    assert int(seen.sum()) == FAMILY.causal_pairs(small)
    assert FAMILY.chosen_pairs(c) / c["seq_len"] == pytest.approx(1920.06,
                                                                  abs=0.01)
    batch = cell["traffic"]["global_batch"]
    cost = fam.attention_cost(batch)
    calls = batch * 4 * 32
    assert set(cost) == {"flops", "bytes", K.COST}
    assert cost["flops"] == cost[K.COST]["flops"] == \
        calls * 12.0 * FAMILY.chosen_pairs(c) * 128
    assert cost["bytes"] == cost[K.COST]["bytes"] == \
        calls * (8 * 16384 * 128 * 2 + 2 * 16384 * 4)
    # A held expert's rows a step, as the cell's ``why`` says.
    assert 16384 * 8 / 128 == 1024


def test_the_family_refuses_another_pattern_and_an_older_program(monkeypatch):
    c = {**loader.load_cell(CELL)["config"], **SMALL}
    with pytest.raises(ValueError, match="one selected attention"):
        FAMILY.Family({**c, "n_layers": 3}, dict(dp=1, pp=1, mp=1))
    with pytest.raises(ValueError, match="one selected attention"):
        FAMILY.Family({**c, "layer_pattern": "*E"}, dict(dp=1, pp=1, mp=1))
    # The parent commit under this benchmark: the family says what is
    # missing, ``run.py`` prints it and exits 1, and nothing hangs.
    fields = tuple(f for f in tfm.TransformerConfig._fields
                   if f not in ("index_topk", "rope_sections"))
    monkeypatch.setattr(tfm, "TransformerConfig",
                        type("TransformerConfig", (), {"_fields": fields}))
    with pytest.raises(loader.BenchmarkError, match="index_topk"):
        FAMILY.Family(c, dict(dp=1, pp=1, mp=1))


# -- the traffic ------------------------------------------------------------------------

def test_position_streams_follow_the_rule_token_by_token():
    spans = [(3, 2, 3), (12, 4, 2), (20, 1, 1)]
    got = FAMILY.position_streams(24, spans)
    want, value, at = np.zeros((3, 24), int), 0, 0
    for start, h, w in spans:
        while at < start:                      # text: one running value
            want[:, at], value, at = value, value + 1, at + 1
        for row in range(h):
            for col in range(w):
                want[:, at], at = (value, value + row, value + col), at + 1
        value += max(h, w)
    while at < 24:
        want[:, at], value, at = value, value + 1, at + 1
    np.testing.assert_array_equal(got, want)
    assert got.dtype == np.int32
    # The worked case: text 0 1 2, a 2 x 3 grid after value 2, text from 6.
    assert got[:, :10].tolist() == [[0, 1, 2, 3, 3, 3, 3, 3, 3, 6],
                                    [0, 1, 2, 3, 3, 3, 4, 4, 4, 6],
                                    [0, 1, 2, 3, 4, 5, 3, 4, 5, 6]]
    with pytest.raises(ValueError, match="overlap"):
        FAMILY.position_streams(24, [(3, 2, 3), (5, 2, 2)])


def test_the_batch_brings_ids_labels_and_four_image_spans_from_the_seed():
    cell = loader.load_cell(CELL)
    fam = FAMILY.Family(cell["config"], cell["traffic"]["mesh"])
    tokens, labels, positions = fam.draw_batch(
        np.random.default_rng([2147483700, 0, 3]), 1)
    again = fam.draw_batch(np.random.default_rng([2147483700, 0, 3]), 1)
    assert all((a == b).all() for a, b in zip((tokens, labels, positions),
                                              again))
    assert tokens.shape == labels.shape == (1, 16384)
    assert positions.shape == (1, 3, 16384) and positions.dtype == np.int32
    assert tokens.min() >= 0 and tokens.max() < 18992
    np.testing.assert_array_equal(labels, np.roll(tokens, -1, axis=1))
    t, h, w = positions[0]
    image = (t != h) | (t != w)
    # Four spans of 256 .. 1,024 positions (a grid's first cell reads as
    # text: its three are equal), text before, between and after.
    assert 4 * 256 - 4 <= image.sum() <= 4 * 1024
    starts = np.flatnonzero(np.diff(np.concatenate([[0], image])) == 1)
    assert 4 <= len(starts) and not image[0] and not image[-1]
    # The temporal stream never falls, and text goes on past the grid.
    assert (np.diff(t) >= 0).all() and t[-1] < 16384
    assert cell["config"]["image_spans"] == 4
    assert cell["config"]["image_grid"] == [16, 32]
    spans = FAMILY.draw_spans(np.random.default_rng(1), 16384, 4, (16, 32))
    assert len(spans) == 4 and all(16 <= h <= 32 and 16 <= w <= 32
                                   for _, h, w in spans)
    assert all(a + ha * wa < b for (a, ha, wa), (b, _, _) in
               zip(spans, spans[1:])) and spans[0][0] >= 1


# -- the share ----------------------------------------------------------------------------

def test_the_eight_ranks_expert_parts_add_up_to_the_uncut_layer():
    """What the guide asks of a share: at a small size, the parts of an
    expert block's result that the ranks give (rank r holds experts r x
    held .. (r + 1) x held - 1: here by handing the program the router's
    columns and the experts rolled so that they come first), with what
    every rank computes alike (the norm, the router, the attention and the
    indexer before it) counted once, add up to the reference's layer over
    all the experts."""
    c = {**loader.load_cell(CELL)["config"], **SMALL, "dtype": "float32"}
    ranks, held, e = 4, SMALL["n_experts_held"], SMALL["n_experts"]
    whole = FAMILY.Family({**c, "n_experts_held": e}, dict(dp=1, pp=1, mp=1))
    part = FAMILY.Family(c, dict(dp=1, pp=1, mp=1))
    params = whole.init_params(jax.random.PRNGKey(0))
    lp = jax.tree_util.tree_map(lambda a: a[0, 0, 0],
                                params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, c["d_model"]))

    def parts_whole_and_reference(lp, x):
        total = jnp.zeros_like(x)
        for r in range(ranks):
            mine = {**lp, "gate": jnp.roll(lp["gate"], -r * held, axis=-1),
                    **{k: jnp.roll(lp[k], -r * held, axis=0)[:held]
                       for k in ("w_gate", "w_up", "w_down")}}
            total = total + tfm._expert_mixer(part.cfg, mine, x)[0]
        h = REF.rmsnorm(x.reshape(-1, c["d_model"]), lp["ln"], c["norm_eps"])
        want = REF.experts(h, REF.route(h, lp["gate"], c["top_k"]),
                           lp["w_gate"], lp["w_up"], lp["w_down"])
        return total, tfm._expert_mixer(whole.cfg, lp, x)[0], want

    with jax.default_matmul_precision("highest"):
        total, uncut, want = quick(parts_whole_and_reference, lp, x)
    np.testing.assert_allclose(total.reshape(want.shape), want, atol=2e-6)
    # ... and the uncut program is that layer too.
    np.testing.assert_allclose(uncut.reshape(want.shape), want, atol=2e-6)


# -- the cell's own per-layer metrics --------------------------------------------

FUSION = ('%fusion.7 = bf16[16384,2048]{1,0:T(8,128)(2,1)} '
          'fusion(bf16[16384,2048]{1,0} %p.1), kind=kLoop')
WHILE = ('%while.1 = (s32[]{:T(128)}) while((s32[]{:T(128)}) %tuple.1), '
         'condition=%cond, body=%body')
FWD = "jit(train_step)/jvp()/while/body/checkpoint/hvd_attn/"
BWD = ("jit(train_step)/transpose(jvp())/while/body/checkpoint/"
       "rematted_computation/hvd_attn/")


def kernel(name: str) -> str:
    return (f'%{name} = (bf16[1,16384,4096]{{2,1,0}}) custom-call(%q), '
            'custom_call_target="tpu_custom_call"')


def synthetic_device():
    """Two whole steps of 200 ns."""
    meta = {1: (WHILE, ""),
            2: (kernel("hvd_flash_fwd_sel.3"), FWD + "pallas_call"),
            3: (kernel("hvd_flash_fwd.1"), FWD + "pallas_call"),
            4: (kernel("hvd_flash_bwd_dq_sel.2"), BWD + "pallas_call"),
            5: (kernel("hvd_flash_bwd_dkv_sel.2"), BWD + "pallas_call"),
            6: (FUSION, FWD + "hvd_attn_index/dot_general"),
            7: (FUSION, FWD + "hvd_attn_index/while/body/hvd_attn_select/ge"),
            8: (FUSION, FWD + "hvd_attn_index_loss/while/body/exp"),
            9: (FUSION, BWD + "hvd_attn_index_loss/while/body/mul"),
            10: (FUSION, FWD + "hvd_attn_index/hvd_attn_rope/mul"),
            11: (FUSION, "jit(step)/not_hvd_attn_index/mul")}
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


def synthetic_layers(monkeypatch):
    lines, meta = synthetic_device()
    device = {**K.classify_device(lines, meta), "n_programs": 2}
    monkeypatch.setattr(K, "classified",
                        lambda layers: {"devices": {0: device}})
    cost = {"flops": 197e12 * 3.6e-9, "bytes": 1.0}    # least time 3.6 ns
    return device, {"attention": {"flops": 1.0, "bytes": 1.0, K.COST: cost},
                    "peaks": loader.load_peaks("TPU v5 lite"), "trace": {}}


def read_metric(layers, name, better="lower"):
    return loader.load_code("metrics", name).read(
        layers, {"name": name, "better": better})


def test_classify_device_sorts_self_time_by_the_new_names():
    """The selected kernels by their whole name (a plain forward is not
    one), the scopes wherever they sit in the path, the ranking inside the
    indexer's; a name that merely contains one is not it."""
    d = K.classify_device(*synthetic_device())
    assert dict(d["kernel_ns"]) == {
        "hvd_flash_fwd_sel": 20, "hvd_flash_bwd_dq_sel": 24,
        "hvd_flash_bwd_dkv_sel": 28}
    assert dict(d["name_ns"]) == {
        "hvd_attn_index": 2 * (6 + 8 + 5), "hvd_attn_select": 16,
        "hvd_attn_index_loss": 2 * (30 + 11)}
    assert K.classify_device({}, {}) == {"name_ns": {}, "kernel_ns": {}}


@pytest.mark.parametrize("name, better, value", [
    ("sparse_attn_kernel_ms_per_step", "lower", 36e-6),
    ("sparse_attn_kernel_roofline", "higher", 10.0),
    ("attn_index_ms_per_step", "lower", 19e-6),
    ("attn_select_ms_per_step", "lower", 8e-6),
    ("attn_index_loss_ms_per_step", "lower", 41e-6)])
def test_the_new_readers_over_a_synthetic_device(monkeypatch, name, better,
                                                 value):
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
    K._classified.cache_clear()
    out = K.classified(layers)
    assert out is not None and sorted(out["devices"]) == [0, 1, 2, 3]
    assert not any(d["name_ns"] or d["kernel_ns"]
                   for d in out["devices"].values())
    assert loader.load_code("metrics", name).read(
        layers, {"name": name, "better": "lower"}) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_an_untraced_run_gives_no_value(name):
    layers = {"trace": None, "attention": None, "peaks": None}
    assert loader.load_code("metrics", name).read(
        layers, {"name": name, "better": "lower"}) is None


# -- the benchmark's data -------------------------------------------------------------

WORKLOADS_BEFORE = [
    "flagship-s8192-train-1chip", "flagship-s8192-train-dp2mp2",
    "bert-base-s512-train-1chip", "olmoe-1b-7b-s4096-train-1chip",
    "nemotron-3-super-s8192-train-1chip", "laguna-s-2.1-s8192-train-1chip",
    "sdar-30b-a3b-s4096-train-1chip", "lfm2-24b-a2b-s32768-train-1chip",
    "smallthinker-21b-a3b-s16384-train-1chip"]
LAST_METRICS_BEFORE = ["short_conv_gate_roofline",
                       "routed_expert_blocks_ms_per_step",
                       "attn_rope_ms_per_step"]


def test_the_benchmark_holds_this_cell_and_every_name_it_had():
    """No position or count is pinned: later PRs append too.  This cell's
    entries exist, and every name the parent had is still there, in the
    parent's order."""
    bench = loader.load_benchmark()

    def names(key):
        return [e["name"] for e in bench[key]]

    def in_order(had, now):
        return [n for n in now if n in set(had)] == had

    assert in_order(WORKLOADS_BEFORE, names("workloads"))
    assert in_order(LAST_METRICS_BEFORE, names("per_layer"))
    assert names("workloads").index(CELL) > names("workloads").index(
        WORKLOADS_BEFORE[-1])
    assert CONFIG in names("configs")
    entry = loader.find(bench["workloads"], CELL, "workload")
    assert (entry["config"], entry["chips"], entry["traffic"]) == (
        CONFIG, 1, "train-b1-1chip-keye-vl")
    for name in NEW_METRICS:
        m = loader.find(bench["per_layer"], name, "metric")
        assert CELL in m["workloads"]
        assert m["moves"] == "tokens_per_s_per_chip"
        assert m["source"] == "device_trace"
        assert names("per_layer").index(name) > names("per_layer").index(
            LAST_METRICS_BEFORE[-1])
    for m in bench["end_to_end"]:
        if "workloads" in m:
            assert in_order(WORKLOADS_BEFORE, m["workloads"])
            assert CELL in m["workloads"]
    cell = loader.load_cell(CELL)
    assert {m["name"] for m in cell["per_layer"]} >= set(NEW_METRICS) | {
        "attn_kernel_ms_per_step", "attn_kernel_roofline",
        "attn_fwd_kernel_calls_per_step", "head_ms_per_step", "peak_hbm_gb"}
    assert {m["name"] for m in cell["end_to_end"]} == {
        "tokens_per_s_per_chip", "mfu", "setup_s"}
    assert len(json.dumps(bench)) < 64 * 1024
