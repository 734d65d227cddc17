"""Laguna-S-2.1 (``laguna``): the system against the benchmark's plain
reference at a small size on the CPU (the published pattern — a leading full
layer with the dense MLP, then three sliding layers and a full one with
experts — at hidden 64: 4 / 6 query heads on 2 kv heads of 16, a window of
16, 4 of 32 experts held at width 24, top-5, a shared expert of 24, sequence
64), the faults and the lower precisions the tolerances must catch, the
configuration's data and the family's arithmetic, and the readers of the
cell's own per-layer metrics.  On the chip ``benchmark/run.py`` makes the
same comparison at the published widths."""

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
from benchmark.trace import reduce as R           # noqa: E402
from horovod_tpu.models import transformer as tfm          # noqa: E402
from horovod_tpu.parallel.mesh import create_mesh          # noqa: E402

CELL = "laguna-s-2.1-s8192-train-1chip"
SMALL = {"vocab_size": 256, "d_model": 64, "attn_head_dim": 16, "n_heads": 4,
         "window_heads": 6, "n_kv_heads": 2, "attn_window": 16, "d_ff": 24,
         "dense_ff": 96, "shared_expert_ff": 24, "n_experts": 32,
         "n_experts_held": 4, "top_k": 5, "seq_len": 64,
         "expert_buffer_factor": 8.0}
ONE, DP2 = (1, 1, 1), (2, 1, 1)
REF = loader.load_code("reference", "laguna")
FAMILY = loader.load_code("families", "laguna")
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
REDUCED = {"num_hidden_layers": 5, "num_attention_heads": 24,
           "num_key_value_heads": 4, "num_experts": 8, "vocab_size": 12544,
           "num_attention_heads_per_layer": [24, 36, 36, 36, 24],
           "layer_types": ["full_attention"] + ["sliding_attention"] * 3
           + ["full_attention"],
           "mlp_layer_types": ["dense"] + ["sparse"] * 4,
           "gating_types": ["per_head"] * 5}


def small_config(dtype="bfloat16"):
    c = loader.load_cell(CELL)["config"]
    rope = c["rope_parameters"]
    # The original context at the sequence's scale, so that the ramp between
    # kept and divided frequencies lies inside the 8 rotary pairs.
    return {**c, **SMALL, "dtype": dtype, "rope_parameters": {
        **rope, "full_attention": {
            **rope["full_attention"], "original_max_position_embeddings": 16}}}


def small_family(mesh_shape=ONE, dtype="bfloat16"):
    config = small_config(dtype)
    assert (config["leading_pattern"], config["layer_pattern"],
            config["n_layers"]) == ("*D", "WEWEWE*E", 10)
    fam = FAMILY.Family(config, dict(zip(("dp", "pp", "mp"), mesh_shape)))
    n = int(np.prod(mesh_shape))
    mesh = create_mesh(fam.mesh_shape, devices=jax.devices()[:n])
    params = fam.init_params(jax.random.PRNGKey(0))
    # At hidden 64 the 0.02 initialisation leaves attention near uniform and
    # the gates at a half, where a wrong mask, rotation or gate barely
    # shows.  Widen q, k and the gates.
    layers = params["layers"]
    for blk in (layers["attn"], layers["swa"], layers["leading"]["attn"]):
        blk["wq"], blk["wk"] = blk["wq"] * 8.0, blk["wk"] * 8.0
        blk["w_head_gate"] = blk["w_head_gate"] * 20.0
    batch = fam.draw_batch(np.random.default_rng(5), 4)
    return fam, mesh, params, batch


def system(fam, mesh, params, batch):
    return jax.jit(jax.value_and_grad(fam.loss_fn(mesh)))(params, *batch)


def against_reference(fam, params, batch, sys_out, **args):
    """(|loss difference|, {leaf: relative L2 error of its gradient})."""
    args = {**fam.reference_args(), **args}
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
    decision can flip: the banded kernels' XLA path against the explicit
    mask, the sorted rows against the mask of experts, the stacked blocks
    against the list of layers — loss and every gradient leaf agree to fp32
    round-off."""
    fam, mesh, params, batch = small_family(mesh_shape, "float32")
    d_loss, errs = against_reference(
        fam, params, batch, system(fam, mesh, params, batch))
    # 5 x 6 attention leaves, 4 of the dense MLP, 4 x 8 of the expert MLPs,
    # embedding, head, final norm.
    assert len(errs) == 69
    assert d_loss <= 1e-5, d_loss
    assert max(errs.values()) <= 1e-5, errs


def test_in_bf16_the_system_is_inside_the_tolerances(bf16_system):
    fam, params, batch, sys_out = bf16_system
    d_loss, errs = against_reference(fam, params, batch, sys_out)
    assert d_loss <= REF.TOLERANCES["loss_abs"], d_loss
    assert max(errs.values()) <= REF.TOLERANCES["grad_rel_l2"], errs


# -- what the comparison sees -----------------------------------------------------

def no_gate(block):
    def wrong(h, lp, **kw):
        return block(h, {**lp, "wg": 0.0 * lp["wg"]}, **kw)
    return wrong


def not_renormalised(h, router, top_k, scale):
    p = jax.nn.softmax(REF.matmul(h, router), axis=-1)
    kth = jax.lax.top_k(p, top_k)[0][:, -1:]
    return jnp.where(p >= kth, p, 0.0) * scale


FAULTS = {
    "a_window_of_one_key_less": dict(window=15),
    "a_window_of_one_key_more": dict(window=17),
    "top_4_of_5": dict(top_k=4),
    "no_routed_scaling": dict(router_scale=1.0),
    "the_whole_head_rotated_in_a_full_layer": dict(
        full_rope=(500000.0, 1.0, 128.0, 16, 32.0, 1.0, 1.4852030263919618)),
    "no_yarn_scaling": dict(
        full_rope=(500000.0, 0.5, 1.0, 16, 32.0, 1.0, 1.0)),
    "no_attention_factor": dict(
        full_rope=(500000.0, 0.5, 128.0, 16, 32.0, 1.0, 1.0)),
    "the_full_layers_theta_in_a_sliding_layer": dict(sliding_theta=500000.0),
    "every_layer_full": dict(layer_types=("full",) * 5),
}
PATCHES = {
    "gates_of_a_half": lambda mp: mp.setattr(
        REF, "attention_block", no_gate(REF.attention_block)),
    "weights_not_renormalised": lambda mp: mp.setattr(
        REF, "route", not_renormalised),
    "an_ungated_dense_and_shared_mlp": lambda mp: mp.setattr(
        REF, "swiglu", lambda h, w1, w3, w2: REF.matmul(
            jax.nn.silu(REF.matmul(h, w1)), w2)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS) + sorted(PATCHES))
def test_in_fp32_every_fault_shows(monkeypatch, fp32_system, fault):
    """A reference (standing in for a system) with one thing wrong is far
    from the system where nothing rounds: a window off by one key, either
    way, among them.  This is what refuses a fault smaller than the chip's
    limits (``TOLERANCES``: set by the router's flips under bf16)."""
    fam, params, batch, sys_out = fp32_system
    if fault in PATCHES:
        PATCHES[fault](monkeypatch)
    _d, errs = against_reference(fam, params, batch, sys_out,
                                 **FAULTS.get(fault, {}))
    assert max(errs.values()) > 1e-2, (fault, max(errs.values()))


@pytest.mark.parametrize("fault", [
    "a_window_of_one_key_less", "a_window_of_one_key_more", "top_4_of_5",
    "every_layer_full", "gates_of_a_half", "weights_not_renormalised"])
def test_tolerance_catches(monkeypatch, bf16_system, fault):
    fam, params, batch, sys_out = bf16_system
    if fault in PATCHES:
        PATCHES[fault](monkeypatch)
    _d, errs = against_reference(fam, params, batch, sys_out,
                                 **FAULTS.get(fault, {}))
    assert max(errs.values()) > REF.TOLERANCES["grad_rel_l2"], errs


def rounded_to(dtype):
    def f(x):             # the value rounded, the gradient passed through
        return x + jax.lax.stop_gradient(
            x.astype(dtype).astype(jnp.float32) - x)
    return f


@pytest.mark.parametrize("fp8", ["float8_e4m3fn", "float8_e5m2"])
def test_tolerance_refuses_the_precision_below_bf16(
        monkeypatch, bf16_system, fp8):
    """The configuration states bf16 compute; the reference with every
    matmul's operands rounded to an 8-bit float, the nearest precision below,
    must come out as not correct."""
    fam, params, batch, sys_out = bf16_system
    exact, to_fp8 = REF.matmul, rounded_to(fp8)
    monkeypatch.setattr(REF, "matmul",
                        lambda a, b: exact(to_fp8(a), to_fp8(b)))
    d_loss, errs = against_reference(fam, params, batch, sys_out)
    assert (max(errs.values()) > REF.TOLERANCES["grad_rel_l2"]
            or d_loss > REF.TOLERANCES["loss_abs"]), (d_loss, errs)


# -- the configuration's data and the family's arithmetic ---------------------------

def test_every_published_key_is_there_and_only_the_stated_ones_differ():
    cell = loader.load_cell(CELL)
    c = cell["config"]
    assert cell["config_entry"]["source"] == \
        "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json"
    if CATALOG.is_file():
        row = next(json.loads(ln) for ln in CATALOG.read_text().splitlines()
                   if '"Laguna-S-2.1"' in ln)
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
    # The cut lists are the first five entries of the published ones.
    for key in ("layer_types", "mlp_layer_types", "gating_types"):
        assert c[key] == c["published"][key][:5], key
    assert c["published"]["num_attention_heads_per_layer"][:5] == [
        48, 72, 72, 72, 48]
    assert c["published"]["layer_types"] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention"] * 12
    # No width is cut.
    assert (c["hidden_size"], c["head_dim"], c["intermediate_size"],
            c["moe_intermediate_size"], c["shared_expert_intermediate_size"],
            c["sliding_window"], c["num_experts_per_tok"]) == (
                3072, 128, 12288, 1024, 1024, 512, 10)
    assert c["rope_parameters"] == published["rope_parameters"]
    assert cell["entry"]["chips"] == 1
    for key in ("assumed", "departures", "deployment", "distorts",
                "reduced_why"):
        assert c[key], key
    assert "32 chips share each layer" in c["deployment"]
    assert cell["traffic"]["global_batch"] == 3
    assert cell["traffic"]["gradient_check"] == "traced_run"


def test_both_spellings_of_a_size_agree():
    c = loader.load_cell(CELL)["config"]
    for repo, published in [
            ("d_model", "hidden_size"), ("n_heads", "num_attention_heads"),
            ("n_kv_heads", "num_key_value_heads"),
            ("attn_head_dim", "head_dim"), ("attn_window", "sliding_window"),
            ("n_experts_held", "num_experts"),
            ("top_k", "num_experts_per_tok"),
            ("d_ff", "moe_intermediate_size"),
            ("dense_ff", "intermediate_size"),
            ("shared_expert_ff", "shared_expert_intermediate_size"),
            ("router_scale", "moe_routed_scaling_factor")]:
        assert c[repo] == c[published], (repo, published)
    assert c["n_layers"] == 2 * c["num_hidden_layers"]
    assert c["n_experts"] == c["published"]["num_experts"] == 256
    assert c["window_heads"] == c["num_attention_heads_per_layer"][1] == 36
    assert c["n_heads"] == c["num_attention_heads_per_layer"][0] == 24
    # The two patterns are layer_types and mlp_layer_types as letters.
    letters = "".join(
        {"full_attention": "*", "sliding_attention": "W"}[a]
        + {"dense": "D", "sparse": "E"}[m]
        for a, m in zip(c["layer_types"], c["mlp_layer_types"]))
    assert letters == c["leading_pattern"] + c["layer_pattern"]
    assert c["mlp_only_layers"] == [0] and c["leading_pattern"] == "*D"
    # The published groups of query heads a key / value head stay.
    pub = c["published"]
    assert c["n_heads"] // c["n_kv_heads"] == \
        pub["num_attention_heads"] // pub["num_key_value_heads"] == 6
    assert c["window_heads"] // c["n_kv_heads"] == 72 // 8 == 9


def test_parameter_count_is_exact():
    cell = loader.load_cell(CELL)
    c = cell["config"]
    fam = FAMILY.Family(c, cell["traffic"]["mesh"])
    shapes = jax.eval_shape(fam.init_params, jax.random.PRNGKey(0))
    sizes = {jax.tree_util.keystr(k): int(np.prod(v.shape)) for k, v in
             jax.tree_util.tree_leaves_with_path(shapes)}

    def total(*parts):
        return sum(n for k, n in sizes.items()
                   if all(f"['{p}']" in k for p in parts))

    assert total("leading", "attn") == total("layers", "attn") \
        - total("leading", "attn") == 22_096_896
    assert total("swa") == 3 * 31_570_944
    assert total("dense") == 113_249_280
    assert total("moe") == 4 * 85_724_160
    assert sizes["['embed']"] == sizes["['lm_head']"] == 12544 * 3072
    assert sum(sizes.values()) == c["parameters"] == 672_125_952
    assert "['pos']" not in sizes
    assert not [k for k in sizes if "router_bias" in k]


def test_flop_arithmetic_is_a_copy_of_the_programs_today():
    cell = loader.load_cell(CELL)
    c = cell["config"]
    fam = FAMILY.Family(c, cell["traffic"]["mesh"])
    assert fam.flops_per_token() * c["seq_len"] == pytest.approx(
        tfm.train_flops_per_seq(fam.cfg), rel=1e-12)
    per = FAMILY.block_flops_per_token(c)
    pairs = 512 * 8192 - 512 * 511 / 2
    assert FAMILY.band_pairs(c) == pairs == 4_063_488
    assert per["*"] == 2 * 3072 * 128 * 56 + 2 * 3072 * 24 \
        + 4 * 4096 * 24 * 128
    assert per["W"] == pytest.approx(2 * 3072 * 128 * 80 + 2 * 3072 * 36
                                     + 4 * pairs / 8192 * 36 * 128)
    assert per["D"] == 6 * 3072 * 12288
    assert per["E"] == 2 * 3072 * 256 + 6 * 3072 * 1024 \
        + 0.3125 * 6 * 3072 * 1024
    head = 2 * 3072 * 12544
    total = 2 * per["*"] + 3 * per["W"] + per["D"] + 4 * per["E"] + head
    assert fam.flops_per_token() == pytest.approx(3.0 * total)
    assert fam.flops_per_token() == pytest.approx(2.444e9, rel=1e-3)
    # The shares the cell's ``why`` states: attention blocks half, the dense
    # MLP 28 %, routed experts 3 %.
    assert (2 * per["*"] + 3 * per["W"]) / total == pytest.approx(0.50, abs=0.01)
    assert per["D"] / total == pytest.approx(0.28, abs=0.005)
    assert 4 * 0.3125 * 6 * 3072 * 1024 / total == pytest.approx(0.03, abs=0.002)
    batch = cell["traffic"]["global_batch"]
    cost = fam.attention_cost(batch)
    win, full = (batch * 3 * 36 * 12.0 * pairs * 128,
                 batch * 2 * 24 * 12.0 * 8192 ** 2 / 2 * 128)
    assert cost["window_attention"]["flops"] == pytest.approx(win, rel=1e-12)
    assert cost["flops"] == pytest.approx(win + full, rel=1e-12)
    assert cost["bytes"] == batch * (3 * 36 + 2 * 24) * (
        12 * 8192 * 128 * 2 + 2 * 8192 * 4)
    # The windowed kernels' least time: 10.27 ms of FLOPs against 9.98 of
    # bytes a step.
    assert cost["window_attention"]["flops"] / 197e12 == pytest.approx(
        10.27e-3, rel=0.01)
    assert cost["window_attention"]["bytes"] / 819e9 == pytest.approx(
        9.98e-3, rel=0.01)
    tokens = batch * c["seq_len"]
    assert cost["moe_expert_matmul"]["flops"] == pytest.approx(
        4 * 3.0 * tokens * 0.3125 * 6 * 3072 * 1024, rel=1e-12)


def test_a_rehearsals_depth_is_a_windowed_layer_with_its_experts():
    assert FAMILY.patterns_at_depth("*D", "WEWEWE*E", 10) == ("*D", "WEWEWE*E")
    assert FAMILY.patterns_at_depth("*D", "WEWEWE*E", 18) == ("*D", "WEWEWE*E")
    assert FAMILY.patterns_at_depth("*D", "WEWEWE*E", 2) == ("", "WE")
    assert FAMILY.patterns_at_depth("*D", "WEWEWE*E", 4) == ("", "WEWE")
    with pytest.raises(ValueError, match="one attention and one MLP"):
        FAMILY.Family({**small_config(), "n_layers": 3}, dict(dp=1, pp=1, mp=1))


def test_the_cell_rehearses_on_the_cpu_at_its_own_small_preset(capsys):
    """Through ``benchmark/run.py`` with the pattern kept (the suite's common
    rehearsal, ``test_benchmark_run_cpu.py``, cuts every cell to two
    blocks): the loss and, traced, every gradient leaf against the
    reference, inside the limits."""
    small = small_config()
    rc = run.main(
        ["--workload", CELL, "--seed", "2147483999", "--seconds", "1",
         "--trace", "1"],
        rehearsal=run.Rehearsal(sizes={k: small[k] for k in (
            *SMALL, "rope_parameters")}))
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    checks = [ln for ln in out if "reference check" in ln]
    assert len(checks) == 2 and all(ln.endswith("-> ok") for ln in checks)
    assert "over 69 leaves" in checks[1]
    line = json.loads(out[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["compared"]) == {"loss_abs_diff", "grad_rel_l2_worst"}


# -- the cell's own per-layer metrics --------------------------------------------

FUSION = ('%fusion.7 = bf16[16384,3072]{1,0:T(8,128)(2,1)} '
          'fusion(bf16[16384,3072]{1,0} %p.1), kind=kLoop')
WHILE = ('%while.1 = (s32[]{:T(128)}) while((s32[]{:T(128)}) %tuple.1), '
         'condition=%cond, body=%body')
FWD = "jit(train_step)/jvp()/while/body/checkpoint/"
BWD = ("jit(train_step)/transpose(jvp())/while/body/checkpoint/"
       "rematted_computation/")
NEW_METRICS = ("window_attn_kernel_ms_per_step",
               "window_attn_kernel_roofline", "attn_rope_gate_ms_per_step",
               "dense_mlp_ms_per_step", "expert_block_ms_per_step")


def kernel(name: str) -> str:
    return (f'%{name} = (bf16[2,36,8192,128]{{3,2,1,0}}) custom-call(%q), '
            'custom_call_target="tpu_custom_call"')


def grouped(name: str) -> str:
    return (f'%{name} = bf16[20480,1024]{{1,0:T(8,128)(2,1)}} custom-call('
            '%get-tuple-element.4, %x.1, %copy.1), '
            'custom_call_target="tpu_custom_call", '
            'frontend_attributes={ragged_dot_tiling="512,512,512"}')


def synthetic_device():
    """Two whole steps of 200 ns."""
    meta = {1: (WHILE, ""),
            2: (kernel("hvd_flash_fwd_win.3"), FWD + "hvd_attn/pallas_call"),
            3: (kernel("hvd_flash_fwd.1"), FWD + "hvd_attn/pallas_call"),
            4: (kernel("hvd_flash_bwd_dq_win.2"), BWD + "hvd_attn/pallas_call"),
            5: (kernel("hvd_flash_bwd_dkv_win.2"), BWD + "hvd_attn/pallas_call"),
            6: (FUSION, FWD + "hvd_attn/hvd_attn_rope/mul"),
            7: (FUSION, BWD + "hvd_attn/hvd_attn_gate/dot_general"),
            8: (FUSION, FWD + "hvd_mlp/hvd_mlp_dense/dot_general"),
            9: (FUSION, FWD + "hvd_mlp/hvd_moe_shared/dot_general"),
            10: (grouped("ragged-dot-none.11"), "ragged-dot-none"),
            11: (FUSION, BWD + "hvd_mlp/mul"),
            12: (FUSION, "jit(step)/not_hvd_attn_rope/mul")}
    ops = []
    for t0 in (1000, 1200):
        ops.append((1, t0, t0 + 195))
        t = t0 + 5
        for mid, ns in [(2, 10), (3, 40), (4, 12), (5, 14), (6, 6), (7, 8),
                        (8, 30), (9, 9), (10, 11), (11, 5), (12, 7)]:
            ops.append((mid, t, t + ns))
            t += ns
    ops.append((2, 900, 990))                       # before the first step
    return ({R.OPS_LINE: ops,
             R.STEPS_LINE: [("s", 1000, 1200), ("s", 1200, 1400)]}, meta)


def test_classify_device_sorts_self_time_by_the_new_names():
    """The windowed kernels by their whole names (the full call's
    ``hvd_flash_fwd`` is not one); rotation and gate; the dense block apart
    from the expert blocks, which take the grouped matmul by name."""
    d = L.classify_device(*synthetic_device())
    assert dict(d["kernel_ns"]) == {"hvd_flash_fwd_win": 20,
                                    "hvd_flash_bwd_dq_win": 24,
                                    "hvd_flash_bwd_dkv_win": 28}
    assert dict(d["name_ns"]) == {
        "hvd_attn_rope": 12, "hvd_attn_gate": 16, "hvd_mlp_dense": 60,
        L.EXPERT_BLOCKS: 2 * (9 + 11 + 5), "hvd_moe_shared": 18}
    assert L.classify_device({}, {}) == {"name_ns": {}, "kernel_ns": {}}
    assert L.window_kernel_of(kernel("hvd_flash_fwd.1")) is None
    assert L.window_kernel_of(kernel("hvd_flash_fwd_win")) == \
        "hvd_flash_fwd_win"
    assert L.window_kernel_of(FUSION) is None


def test_the_readers_over_a_synthetic_device(monkeypatch):
    lines, meta = synthetic_device()
    device = {**L.classify_device(lines, meta), "n_programs": 2}
    monkeypatch.setattr(L, "classified",
                        lambda layers: {"devices": {0: device}})
    cost = {"flops": 197e12 * 9e-9, "bytes": 1.0}      # least time 9 ns
    layers = {"attention": {"flops": 1.0, "bytes": 1.0,
                            "window_attention": cost},
              "peaks": loader.load_peaks("TPU v5 lite"), "trace": {}}

    def read(name, better="lower"):
        return loader.load_code("metrics", name).read(
            layers, {"name": name, "better": better})

    assert read("window_attn_kernel_ms_per_step") == pytest.approx(36e-6)
    assert read("window_attn_kernel_roofline", "higher") == pytest.approx(25.0)
    assert read("attn_rope_gate_ms_per_step") == pytest.approx(14e-6)
    assert read("dense_mlp_ms_per_step") == pytest.approx(30e-6)
    assert read("expert_block_ms_per_step") == pytest.approx(25e-6)
    # Another family's dict has no windowed cost; a program with no dense
    # block's name has no split of hvd_mlp to report.
    layers["attention"].pop("window_attention")
    assert read("window_attn_kernel_roofline", "higher") is None
    del device["name_ns"]["hvd_mlp_dense"]
    assert read("expert_block_ms_per_step") is None
    assert read("dense_mlp_ms_per_step") is None
    del device["name_ns"]["hvd_attn_gate"]
    assert read("attn_rope_gate_ms_per_step") is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_names_gives_no_value(
        tmp_path, monkeypatch, name):
    """The parent's program on a traced run of any cell has none of the
    names: the readers find the trace and return nothing, and do not
    raise."""
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    from test_benchmark_trace_moe import NAMED, fake_layers
    layers = fake_layers(tmp_path, monkeypatch, NAMED)
    L._classified.cache_clear()
    layers["attention"]["window_attention"] = {"flops": 1e12, "bytes": 1e9}
    out = L.classified(layers)
    assert out is not None and sorted(out["devices"]) == [0, 1, 2, 3]
    assert not any(d["kernel_ns"] for d in out["devices"].values())
    assert loader.load_code("metrics", name).read(
        layers, {"name": name, "better": "lower"}) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_an_untraced_run_gives_no_value(name):
    layers = {"trace": None, "attention": None, "peaks": None}
    assert loader.load_code("metrics", name).read(
        layers, {"name": name, "better": "lower"}) is None


def test_the_benchmark_grew_by_appended_entries_only():
    bench = loader.load_benchmark()
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    assert len(bench["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert [m["name"] for m in bench["per_layer"]][-5:] == list(NEW_METRICS)
    for m in bench["per_layer"][-5:]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "tokens_per_s_per_chip"
    for m in bench["end_to_end"]:
        if "workloads" in m:
            assert m["workloads"][-1] == CELL
    cell = loader.load_cell(CELL)
    assert {m["name"] for m in cell["per_layer"]} >= set(NEW_METRICS) | {
        "attn_kernel_ms_per_step", "attn_kernel_roofline",
        "attn_fwd_kernel_calls_per_step", "peak_hbm_gb"}
