"""Nemotron 3 Super (``nemotron_h``): the system against the benchmark's
plain reference at a small size on the CPU (the published period of 11
blocks at hidden 64: 4 Mamba heads of 8 with state 16 in chunks of 16, 4
query heads on 1 kv head of 16, 4 of 32 experts held at width 24 in a latent
space of 32, top-6, a shared expert of 48, sequence 64), the faults and the
lower precisions the tolerances must catch, the configuration's data and the
family's arithmetic, and the readers of the cell's own per-layer metrics.
On the chip ``benchmark/run.py`` makes the same comparison at the published
widths."""

from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import loader                      # noqa: E402
from benchmark.trace import reduce as R           # noqa: E402
from benchmark.trace import ssm as SSM            # noqa: E402
from horovod_tpu.models import transformer as tfm          # noqa: E402
from horovod_tpu.parallel.mesh import create_mesh          # noqa: E402

CELL = "nemotron-3-super-s8192-train-1chip"
SMALL = {"vocab_size": 256, "d_model": 64, "attn_head_dim": 16,
         "ssm_heads": 4, "ssm_head_dim": 8, "ssm_state": 16, "ssm_chunk": 16,
         "d_ff": 24, "moe_latent": 32, "shared_expert_ff": 48,
         "n_experts": 32, "n_experts_held": 4, "top_k": 6, "seq_len": 64,
         "expert_buffer_factor": 8.0}
ONE, DP2 = (1, 1, 1), (2, 1, 1)
REF = loader.load_code("reference", "nemotron_h")
FAMILY = loader.load_code("families", "nemotron_h")
# What the catalog's row gives for the published model (config.json).
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
REDUCED = {"num_hidden_layers": 11, "hybrid_override_pattern": "EMEMEMEMEM*",
           "mamba_num_heads": 16, "n_groups": 1, "num_attention_heads": 4,
           "num_key_value_heads": 1, "n_routed_experts": 8,
           "vocab_size": 16384, "num_nextn_predict_layers": 0}


def small_family(mesh_shape=ONE, dtype="bfloat16"):
    cell = loader.load_cell(CELL)
    config = {**cell["config"], **SMALL, "dtype": dtype}
    assert config["layer_pattern"] == "EMEMEMEMEM*" and config["n_layers"] == 11
    fam = FAMILY.Family(config, dict(zip(("dp", "pp", "mp"), mesh_shape)))
    n = int(np.prod(mesh_shape))
    mesh = create_mesh(fam.mesh_shape, devices=jax.devices()[:n])
    params = fam.init_params(jax.random.PRNGKey(0))
    # As test_benchmark_olmoe.py: at hidden 64 the 0.02 initialisation leaves
    # attention near uniform, where a wrong head or mask barely shows.
    # Widen q and k.
    for k in ("wq", "wk"):
        params["layers"]["attn"][k] = params["layers"]["attn"][k] * 8.0
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
    """The system's loss and gradients on one device in bf16, once for the
    tests that only vary what it is compared with."""
    fam, mesh, params, batch = small_family()
    return fam, params, batch, system(fam, mesh, params, batch)


# -- the system is the reference ------------------------------------------------

@pytest.mark.parametrize("mesh_shape", [ONE, DP2])
def test_in_fp32_the_system_is_the_reference_on_every_layout(mesh_shape):
    """With the compute type fp32 nothing rounds differently and no router
    decision can flip: the chunked scan against the position-by-position
    recurrence, the sorted rows against the mask, the kernels' layout
    against blocks of queries — loss and every gradient leaf agree to fp32
    round-off."""
    fam, mesh, params, batch = small_family(mesh_shape, "float32")
    d_loss, errs = against_reference(
        fam, params, batch, system(fam, mesh, params, batch))
    assert len(errs) == 25          # the bias is a buffer, not compared
    assert d_loss <= 2e-6, d_loss
    assert max(errs.values()) <= 1e-4, errs


def test_in_bf16_the_system_is_inside_the_tolerances(bf16_system):
    fam, params, batch, sys_out = bf16_system
    d_loss, errs = against_reference(fam, params, batch, sys_out)
    assert d_loss <= REF.TOLERANCES["loss_abs"], d_loss
    assert max(errs.values()) <= REF.TOLERANCES["grad_rel_l2"], errs


def test_the_balanced_bias_moves_both_sides_alike(monkeypatch):
    """The family balances the router's correction bias at initialisation
    and hands it to the reference as the last row of ``gate`` (a buffer,
    outside the gradient): with it the reference equals the system, with
    that row ignored it does not.  (``tests/test_nemotron_layers.py`` has
    the balancer itself.)"""
    fam, mesh, params, batch = small_family(ONE, "float32")
    bias = params["layers"]["moe"]["router_bias"]
    assert bias.shape == (1, 1, 5, 32) and float(jnp.abs(bias).max()) > 0.01
    ref_gate = fam.to_reference(params)["layers"]["moe"]["gate"]
    assert ref_gate.shape == (1, 5, 65, 32)
    assert (np.asarray(ref_gate[..., -1, :]) == np.asarray(bias[0])).all()
    sys_out = system(fam, mesh, params, batch)
    d_loss, errs = against_reference(fam, params, batch, sys_out)
    assert d_loss <= 2e-6 and max(errs.values()) <= 1e-4, (d_loss, errs)
    route = REF.route
    monkeypatch.setattr(REF, "route", lambda h, wr, b, *a: route(
        h, wr, 0.0 * b, *a))
    _d, errs = against_reference(fam, params, batch, sys_out)
    assert max(errs.values()) > 0.05, errs


# -- what the tolerances refuse ---------------------------------------------------

def not_renormalised(route):
    def wrong(h, wr, bias, top_k, scale, renormalise):
        return route(h, wr, bias, top_k, scale, False)
    return wrong


def acausal_conv(x, w, b):
    """Taps on the future instead of the past."""
    return REF_CONV(x[::-1], w, b)[::-1]


REF_CONV = REF.causal_conv
FAULTS = {
    "weights_not_renormalised":
        lambda mp: mp.setattr(REF, "route", not_renormalised(REF.route)),
    "a_conv_that_reads_the_future":
        lambda mp: mp.setattr(REF, "causal_conv", acausal_conv),
    "no_squared_relu":
        lambda mp: mp.setattr(REF, "relu2", lambda x: jnp.maximum(x, 0.0)),
    "a_recurrence_without_decay":
        lambda mp: mp.setattr(REF, "carried", lambda s: s * 1.05),
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


def test_tolerance_catches_top_21(bf16_system):
    fam, params, batch, sys_out = bf16_system
    _d_loss, errs = against_reference(fam, params, batch, sys_out, top_k=5)
    assert max(errs.values()) > 1.5 * REF.TOLERANCES["grad_rel_l2"], errs


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
    _d_loss, errs = against_reference(fam, params, batch, sys_out)
    assert max(errs.values()) > REF.TOLERANCES["grad_rel_l2"], errs


@pytest.mark.parametrize("fp8", ["float8_e4m3fn", "float8_e5m2"])
def test_tolerance_refuses_an_8_bit_carried_state(
        monkeypatch, bf16_system, fp8):
    """The configuration states an fp32 state.  With the recurrence's state
    rounded to an 8-bit float as it is carried from a position to the next,
    the decay's and the step's gradients are far outside the limit.  (A bf16
    state moves no leaf by more than a few tenths of a percent at any size
    tried, under the bf16 matmuls' own noise: no limit could refuse it
    without refusing the system.  The note beside TOLERANCES says so.)"""
    fam, params, batch, sys_out = bf16_system
    monkeypatch.setattr(REF, "carried", rounded_to(fp8))
    _d_loss, errs = against_reference(fam, params, batch, sys_out)
    assert max(errs["['layers']['ssm']['a_log']"],
               errs["['layers']['ssm']['dt_bias']"]) \
        > REF.TOLERANCES["grad_rel_l2"], errs


# -- the configuration's data and the family's arithmetic ---------------------------

def test_every_published_key_is_there_and_only_the_stated_ones_differ():
    cell = loader.load_cell(CELL)
    c = cell["config"]
    for key, value in PUBLISHED.items():
        assert c[key] == REDUCED.get(key, value), key
    assert sorted(c["reduced"]) == sorted(REDUCED)
    assert c["reduced"] == cell["config_entry"]["reduced"]
    assert c["published"] == {
        k: PUBLISHED.get(k, c["published"][k]) for k in REDUCED}
    whole = c["published"]["hybrid_override_pattern"]
    assert (len(whole), whole.count("M"), whole.count("E"),
            whole.count("*")) == (88, 40, 40, 8)
    # One whole period of the published pattern, layers 26-36 from 0.
    assert whole[26:37] == c["hybrid_override_pattern"]
    # No width is cut.
    assert (c["hidden_size"], c["moe_latent_size"],
            c["moe_intermediate_size"],
            c["moe_shared_expert_intermediate_size"], c["head_dim"],
            c["mamba_head_dim"], c["ssm_state_size"], c["conv_kernel"],
            c["chunk_size"], c["num_experts_per_tok"]) == (
                4096, 1024, 2688, 5376, 128, 64, 128, 4, 128, 22)
    assert cell["entry"]["chips"] == 1
    for key in ("assumed", "departures", "deployment", "distorts"):
        assert c[key], key
    assert "64 chips share each layer" in c["deployment"]


def test_both_spellings_of_a_size_agree():
    c = loader.load_cell(CELL)["config"]
    for repo, published in [
            ("d_model", "hidden_size"), ("n_layers", "num_hidden_layers"),
            ("layer_pattern", "hybrid_override_pattern"),
            ("n_heads", "num_attention_heads"),
            ("n_kv_heads", "num_key_value_heads"),
            ("attn_head_dim", "head_dim"), ("ssm_heads", "mamba_num_heads"),
            ("ssm_head_dim", "mamba_head_dim"), ("ssm_groups", "n_groups"),
            ("ssm_state", "ssm_state_size"), ("ssm_conv", "conv_kernel"),
            ("ssm_chunk", "chunk_size"),
            ("n_experts_held", "n_routed_experts"),
            ("top_k", "num_experts_per_tok"),
            ("d_ff", "moe_intermediate_size"),
            ("moe_latent", "moe_latent_size"),
            ("shared_expert_ff", "moe_shared_expert_intermediate_size"),
            ("router_scale", "routed_scaling_factor"),
            ("rms_norm_eps", "layer_norm_epsilon")]:
        assert c[repo] == c[published], (repo, published)
    assert c["n_experts"] == c["published"]["n_routed_experts"] == 512


def test_parameter_count_is_exact():
    cell = loader.load_cell(CELL)
    c = cell["config"]
    fam = FAMILY.Family(c, cell["traffic"]["mesh"])
    shapes = jax.eval_shape(fam.init_params, jax.random.PRNGKey(0))
    sizes = {jax.tree_util.keystr(k): int(np.prod(v.shape)) for k, v in
             jax.tree_util.tree_leaves_with_path(shapes)}
    by_kind = {kind: sum(n for k, n in sizes.items() if f"['{kind}']" in k)
               for kind in ("ssm", "attn", "moe")}
    assert by_kind == {"ssm": 5 * 13_708_592, "attn": 5_246_976,
                       "moe": 5 * (54_530_048 + 512 + 8 * 5_505_024)}
    assert sum(sizes.values()) == c["parameters"] == 700_865_520
    assert sizes["['embed']"] == sizes["['lm_head']"] == 16384 * 4096
    assert "['pos']" not in sizes


def test_flop_arithmetic_is_a_copy_of_the_programs_today():
    cell = loader.load_cell(CELL)
    c = cell["config"]
    fam = FAMILY.Family(c, cell["traffic"]["mesh"])
    assert fam.flops_per_token() * c["seq_len"] == pytest.approx(
        tfm.train_flops_per_seq(fam.cfg), rel=1e-12)
    per = FAMILY.block_flops_per_token(c)
    # M: 2 x 4096 x 2320 + 2 x 1024 x 4096 + conv + scan (0.82 M)
    assert per["ssm_scan"] == 2 * 128 * 128 + 2 * 128 * 64 * 16 \
        + 4 * 64 * 128 * 16 == 819_200
    assert per["M"] == 19_005_440 + 8_388_608 + 10_240 + 819_200
    assert per["*"] == 2 * 4096 * 128 * 10 + 2 * 8192 * 4 * 128
    assert per["E"] == pytest.approx(
        4_194_304 + 16_777_216 + 88_080_384 + 0.34375 * 11_010_048)
    total = 5 * per["M"] + per["*"] + 5 * per["E"] + 2 * 4096 * 16384
    assert fam.flops_per_token() == pytest.approx(3.0 * total)
    # The shares the cell's ``why`` states: shared expert about half, head
    # 16 %, attention 2 %, routed experts 2 %.
    assert 5 * 88_080_384 / total == pytest.approx(0.51, abs=0.01)
    assert 2 * 4096 * 16384 / total == pytest.approx(0.156, abs=0.005)
    assert per["*"] / total == pytest.approx(0.022, abs=0.002)
    assert 5 * 0.34375 * 11_010_048 / total == pytest.approx(0.022, abs=0.002)
    batch = cell["traffic"]["global_batch"]
    cost = fam.attention_cost(batch)
    assert cost["flops"] == pytest.approx(
        batch * 4 * 0.5 * 12.0 * 8192 ** 2 * 128, rel=1e-12)
    tokens = batch * c["seq_len"]
    assert cost["ssm_scan"]["flops"] == 5 * tokens * 3 * 819_200
    assert cost["moe_expert_matmul"]["flops"] == pytest.approx(
        5 * 3.0 * tokens * 0.34375 * 4 * 1024 * 2688, rel=1e-12)
    # At its least traffic the scan is bound by bytes, 1.40 ms against
    # 1.02 ms of FLOPs a step: ~0.8 MFLOP a token and block is little work.
    assert cost["ssm_scan"]["bytes"] / 819e9 == pytest.approx(1.40e-3, 0.01)
    assert cost["ssm_scan"]["flops"] / 197e12 == pytest.approx(1.02e-3, 0.01)


def test_a_rehearsals_depth_keeps_an_expert_block_and_attention():
    assert FAMILY.period_at_depth("EMEMEMEMEM*", 11) == "EMEMEMEMEM*"
    assert FAMILY.period_at_depth("EMEMEMEMEM*", 22) == "EMEMEMEMEM*"
    assert FAMILY.period_at_depth("EMEMEMEMEM*", 2) == "E*"
    assert FAMILY.period_at_depth("EMEMEMEMEM*", 3) == "EM*"


# -- the cell's own per-layer metrics --------------------------------------------

FUSION = ('%fusion.7 = bf16[16384,1280]{1,0:T(8,128)(2,1)} '
          'fusion(bf16[16384,1280]{1,0} %p.1), kind=kLoop')
WHILE = ('%while.1 = (s32[]{:T(128)}) while((s32[]{:T(128)}) %tuple.1), '
         'condition=%cond, body=%body')
FWD = "jit(train_step)/jvp()/while/body/checkpoint/"
BWD = ("jit(train_step)/transpose(jvp())/while/body/checkpoint/"
       "rematted_computation/")
NEW_METRICS = ("ssm_ms_per_step", "ssm_scan_ms_per_step", "ssm_scan_roofline",
               "latent_moe_ms_per_step", "moe_shared_expert_ms_per_step")


def test_classify_device_sorts_self_time_by_the_new_names():
    """Two whole steps of 100 ns.  The scan and the conv count under their
    own names and under the block's; the latent projections and the shared
    expert are inside ``hvd_mlp``, which is not this module's to sum."""
    meta = {1: (WHILE, ""),
            2: (FUSION, FWD + "hvd_ssm/dot_general"),
            3: (FUSION, FWD + "hvd_ssm/hvd_ssm_conv/mul"),
            4: (FUSION, BWD + "hvd_ssm/hvd_ssm_scan/dot_general"),
            5: (FUSION, FWD + "hvd_mlp/hvd_moe_latent/dot_general"),
            6: (FUSION, BWD + "hvd_mlp/hvd_moe_shared/dot_general"),
            7: (FUSION, FWD + "hvd_mlp/mul"),
            8: (FUSION, "jit(step)/not_hvd_ssm/mul")}
    ops = []
    for t0 in (1000, 1100):
        ops += [(1, t0, t0 + 95), (2, t0 + 5, t0 + 15), (3, t0 + 15, t0 + 20),
                (4, t0 + 20, t0 + 50), (5, t0 + 50, t0 + 58),
                (6, t0 + 58, t0 + 80), (7, t0 + 80, t0 + 85),
                (8, t0 + 85, t0 + 90)]
    ops.append((4, 900, 990))                       # before the first step
    lines = {R.OPS_LINE: ops,
             R.STEPS_LINE: [("s", 1000, 1100), ("s", 1100, 1200)]}
    d = SSM.classify_device(lines, meta)
    assert dict(d["name_ns"]) == {
        "hvd_ssm": 2 * (10 + 5 + 30), "hvd_ssm_conv": 10, "hvd_ssm_scan": 60,
        "hvd_moe_latent": 16, "hvd_moe_shared": 44}
    assert SSM.classify_device({}, {}) == {"name_ns": {}}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_names_gives_no_value(
        tmp_path, monkeypatch, name):
    """The parent's program on a traced run of any cell has none of the
    names: the readers find the trace and return nothing, and do not
    raise (``latent_moe_ms_per_step`` reads any family's ``hvd_mlp``)."""
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    from test_benchmark_trace_moe import NAMED, fake_layers
    layers = fake_layers(tmp_path, monkeypatch, NAMED)
    SSM._classified.cache_clear()
    layers["attention"]["ssm_scan"] = {"flops": 1e12, "bytes": 1e9}
    out = SSM.classified(layers)
    assert out is not None and sorted(out["devices"]) == [0, 1, 2, 3]
    assert not any(d["name_ns"] for d in out["devices"].values())
    value = loader.load_code("metrics", name).read(
        layers, {"name": name, "better": "lower"})
    if name == "latent_moe_ms_per_step":
        assert value > 0
    else:
        assert value is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_an_untraced_run_gives_no_value(name):
    layers = {"trace": None, "attention": None, "peaks": None}
    assert loader.load_code("metrics", name).read(
        layers, {"name": name, "better": "lower"}) is None


def test_scan_roofline_takes_its_cost_from_the_attention_dict():
    read = loader.load_code("metrics", "ssm_scan_roofline")
    layers = {"attention": {"flops": 1.0, "bytes": 1.0},
              "peaks": {"flops_per_s_bf16": 197e12,
                        "hbm_bytes_per_s": 819e9}, "trace": None}
    assert read.least_seconds(layers) is None       # another family's dict
    layers["attention"]["ssm_scan"] = {"flops": 197e12 / 2, "bytes": 819e9}
    assert read.least_seconds(layers) == (1.0, "bytes")
    assert read.read(layers, {"better": "higher"}) is None      # no trace
