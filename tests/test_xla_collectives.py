"""Compiled-plane quantized + topology-scheduled collectives (ISSUE 20).

ops/xla_collectives.py must give the GSPMD plane the eager wire: jit-pure
lowering (no host callbacks), analytically-bounded quantization error at
N ranks, error-feedback convergence parity against fp32, bit-identity
when the wire is off, a checkpointable residual, hierarchical cross-byte
arithmetic matching the eager formula, and schedule selection that
honors the PR 11 dispatch table and the explicit pins.
"""

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.compat import shard_map
from horovod_tpu.core.config import Config
from horovod_tpu.core.state import global_state
from horovod_tpu.ops import collective as C
from horovod_tpu.ops import dispatch as D
from horovod_tpu.ops import gspmd as G
from horovod_tpu.ops import quantization as Q
from horovod_tpu.ops import xla_collectives as XC

N = 8


def _mesh(axes=("data",)):
    devs = np.array(jax.devices()[:N])
    if len(axes) > 1:
        devs = devs.reshape(N // 2, 2)
    return Mesh(devs, axes)


def _shmap(mesh, fn, in_specs, out_specs):
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


@pytest.fixture
def cfg():
    """A writable session config, restored afterwards."""
    old = global_state.config
    c = Config.from_env()
    global_state.config = c
    D.reset()
    try:
        yield c
    finally:
        global_state.config = old
        D.reset()


# ---------------------------------------------------------------------------
# lowering purity: the schedule is burned in, no host callbacks
# ---------------------------------------------------------------------------

def test_quantized_allreduce_lowering_has_no_host_callbacks(cfg):
    mesh = _mesh()
    spec = Q.QuantSpec(bits=8, block=256)

    def body(x):
        return XC.allreduce_scheduled(x, C.Average, "data", spec=spec)

    fn = jax.jit(_shmap(mesh, body, in_specs=(P("data"),),
                        out_specs=P("data")))
    x = jnp.linspace(-1.0, 1.0, N * 512).reshape(N, 512)
    text = fn.lower(x).as_text()
    for marker in ("callback", "CallbackHlo", "python_callable"):
        assert marker not in text, f"host {marker} leaked into lowering"
    # And the wire ops are actually there.
    assert "all_to_all" in text and "all_gather" in text


# ---------------------------------------------------------------------------
# N-rank analytic error bound under shard_map
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits,qmax", [(8, 127.0), (4, 7.0)])
def test_allreduce_error_within_analytic_bound(bits, qmax):
    """Two-pass quantized Average at 8 ranks: per-element error is
    bounded by the sum of each rank's first-pass half-step (averaged)
    plus the second pass's half-step — scale = block_absmax / qmax."""
    mesh = _mesh()
    spec = Q.QuantSpec(bits=bits, block=256)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((N, 2048)).astype(np.float32)

    def body(v):
        return XC.allreduce_scheduled(v[0], C.Average, "data", spec=spec)

    out = np.asarray(jax.jit(_shmap(
        mesh, body, in_specs=(P("data"),), out_specs=P()))(x))
    exact = x.mean(axis=0)

    # Loose uniform bound from the ranks' global absmax (every block's
    # scale is <= absmax/qmax; quantization error <= scale/2).
    first = sum(np.abs(x[i]).max() / qmax / 2.0 for i in range(N)) / N
    second = (np.abs(exact).max() + first) / qmax / 2.0
    bound = first + second
    err = np.abs(out - exact).max()
    assert err <= bound, (err, bound)
    assert err > 0.0  # it IS a lossy wire


def test_allgather_nested_matches_flat_layout():
    """The hierarchical (cross-first, local-outer) compressed gather
    must produce the same global layout as the flat joint-axis gather —
    the P(("local","cross")) dim-0 convention."""
    mesh = _mesh(axes=("local", "cross"))
    spec = Q.QuantSpec(bits=8, block=64)
    rng = np.random.default_rng(1)
    x = rng.standard_normal((N, 96)).astype(np.float32)

    def body(nested):
        def inner(v):
            return Q.compressed_allgather(v[0], ("local", "cross"),
                                          spec=spec, nested=nested)
        return inner

    specs = dict(in_specs=(P(("local", "cross")),), out_specs=P())
    flat = np.asarray(jax.jit(_shmap(mesh, body(False), **specs))(x))
    nested = np.asarray(jax.jit(_shmap(mesh, body(True), **specs))(x))
    np.testing.assert_array_equal(flat, nested)
    # One qdq round trip per shard, in rank order.
    want = np.concatenate([np.asarray(Q.qdq(jnp.asarray(x[i]), spec))
                           for i in range(N)])
    np.testing.assert_allclose(flat.reshape(-1), want, rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# EF convergence parity + bit-identity (make_zero_train_step)
# ---------------------------------------------------------------------------

def _toy_problem():
    rng = np.random.default_rng(2)
    params = {"w": jnp.asarray(rng.standard_normal((6, 3)) * 0.3,
                               jnp.float32),
              "b": jnp.zeros((3,), jnp.float32)}
    x = jnp.asarray(rng.standard_normal((32, 6)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((32, 3)), jnp.float32)

    def loss_fn(p, batch):
        bx, by = batch
        return jnp.mean((bx @ p["w"] + p["b"] - by) ** 2)

    return params, (x, y), loss_fn


def _run_gspmd(stage, compression, steps=25, axis="data",
               mesh_axes=("data",)):
    mesh = _mesh(axes=mesh_axes)
    params, batch, loss_fn = _toy_problem()
    fns = G.make_zero_train_step(loss_fn, optax.adam(5e-2), mesh,
                                 stage=stage, axis=axis,
                                 compression=compression)
    params, state = fns.init(params)
    loss = None
    for _ in range(steps):
        params, state, loss = fns.step(params, state, batch)
    return float(loss), params, state


@pytest.mark.parametrize("stage", [2, 3])
def test_zero_step_compression_none_is_bit_identical(stage, cfg):
    l0, p0, _ = _run_gspmd(stage, None)
    l1, p1, _ = _run_gspmd(stage, "none")
    assert l0 == l1
    for a, b in zip(jax.tree_util.tree_leaves(p0),
                    jax.tree_util.tree_leaves(p1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("stage", [2, 3])
def test_zero_step_int8_ef_convergence_parity(stage, cfg):
    """Seeded toy run: int8 + error feedback lands within 1% of the
    fp32 loss (the acceptance bar), and the residual is live."""
    l_fp, _, _ = _run_gspmd(stage, None)
    l_q, _, state = _run_gspmd(stage, hvd.Compression.int8)
    assert abs(l_q - l_fp) <= 0.01 * max(abs(l_fp), 1e-12), (l_q, l_fp)
    res = jax.tree_util.tree_leaves(state.residual)
    assert res and any(np.abs(np.asarray(r)).max() > 0 for r in res)


def test_zero_step_session_knob_drives_wire(cfg):
    """compression=None resolves through HVD_TPU_COMPRESSION: with the
    session knob at int8 the state carries a residual; at none the raw
    optax state comes back (no _ZeroState wrap)."""
    from horovod_tpu.optimizers import _ZeroState
    cfg.compression = "int8"
    _, _, state = _run_gspmd(2, None, steps=2)
    assert isinstance(state, _ZeroState) and state.residual is not None
    cfg.compression = "none"
    _, _, state = _run_gspmd(2, None, steps=2)
    assert not isinstance(state, _ZeroState)


def test_zero_step_hierarchical_axis_converges(cfg):
    """Tuple ("local","cross") axis with the hierarchical schedule
    pinned on: still within 2% of flat fp32."""
    cfg.hierarchical_allreduce = True
    l_fp, _, _ = _run_gspmd(3, None, axis=("local", "cross"),
                            mesh_axes=("local", "cross"))
    l_q, _, _ = _run_gspmd(3, hvd.Compression.int8,
                           axis=("local", "cross"),
                           mesh_axes=("local", "cross"))
    assert abs(l_q - l_fp) <= 0.02 * max(abs(l_fp), 1e-12), (l_q, l_fp)


def test_zero_step_records_wire_metrics(cfg):
    before_raw = C._collective_metrics("gspmd")[3].value
    before_sent = C._collective_metrics("gspmd")[4].value
    _run_gspmd(2, hvd.Compression.int8, steps=4)
    d_raw = C._collective_metrics("gspmd")[3].value - before_raw
    d_sent = C._collective_metrics("gspmd")[4].value - before_sent
    assert d_raw > 0 and d_sent > 0
    # Tiny padded tensors still beat 2x on the int8 wire.
    assert d_raw / d_sent > 2.0


# ---------------------------------------------------------------------------
# checkpointed residual round-trip
# ---------------------------------------------------------------------------

def test_residual_checkpoint_round_trip(cfg):
    from horovod_tpu.checkpoint import zero as ckz
    mesh = _mesh()
    _, _, state = _run_gspmd(2, hvd.Compression.int8, steps=3)
    assert any(np.abs(np.asarray(r)).max() > 0
               for r in jax.tree_util.tree_leaves(state.residual))
    with tempfile.TemporaryDirectory() as root:
        ckz.save_zero_state(root, state, step=3, mesh=mesh,
                            axis_name="data")
        back = ckz.restore_zero_state(root, state, mesh=mesh,
                                      axis_name="data")
    for a, b in zip(jax.tree_util.tree_leaves(state.residual),
                    jax.tree_util.tree_leaves(back.residual)):
        av, bv = np.asarray(a).reshape(-1), np.asarray(b).reshape(-1)
        np.testing.assert_array_equal(av, bv[: av.size])
    # Dense GSPMD moments round-trip with their shapes intact.
    for a, b in zip(jax.tree_util.tree_leaves(state.inner),
                    jax.tree_util.tree_leaves(back.inner)):
        assert np.asarray(a).shape == np.asarray(b).shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# hierarchical wire-byte arithmetic goldens
# ---------------------------------------------------------------------------

def test_flat_wire_ratios_at_block_256():
    """fp32 bytes over wire bytes at one fp32 scale per 256 values:
    4 / (1 + 4/256) = 3.9385 for int8 and 4 / (1/2 + 4/256) = 7.7576 for
    int4 — the exact ratios the docs quote (compression.md,
    collectives.md, parallel.md; ROADMAP S7)."""
    n = 1 << 20
    raw8, sent8 = XC.allreduce_wire_bytes(n, Q.QuantSpec(8, 256))
    raw4, sent4 = XC.allreduce_wire_bytes(n, Q.QuantSpec(4, 256))
    assert raw8 / sent8 == pytest.approx(3.9385, abs=5e-5)
    assert raw4 / sent4 == pytest.approx(7.7576, abs=5e-5)
    # bf16 cast wire is exactly 2x.
    rawc, sentc = XC.allreduce_wire_bytes(n, wire_dtype=jnp.bfloat16)
    assert rawc / sentc == 4 / 2


def test_hierarchical_cross_bytes_match_eager_formula():
    """The compiled plan's cross-host bytes must equal the eager
    compressed_allreduce_hierarchical arithmetic: phase-2 moves the 1/L
    shard on the wire, so cross_flat / cross == L exactly when padding
    aligns — the local-size x wire-format reduction."""
    spec = Q.QuantSpec(bits=8, block=256)
    L, Cx = 4, 2
    n = 1 << 20  # aligned: n % (L*block) == 0, shard % (C*block) == 0
    got = XC.hierarchical_allreduce_wire_bytes(n, L, Cx, spec)
    npad = n  # already aligned
    shard = npad // L
    assert got["raw"] == 2 * 4 * n
    assert got["local"] == 2 * Q.wire_bytes(npad, spec)
    assert got["cross"] == 2 * Q.wire_bytes(shard, spec)
    assert got["sent"] == got["local"] + got["cross"]
    assert got["cross_flat"] == 2 * Q.wire_bytes(npad, spec)
    assert got["cross_flat"] / got["cross"] == pytest.approx(L, rel=1e-3)
    # Misaligned payloads pad up, never under-count.
    odd = XC.hierarchical_allreduce_wire_bytes(n + 13, L, Cx, spec)
    assert odd["cross"] >= got["cross"]
    assert odd["local"] >= got["local"]


def test_plan_allreduce_step_selects_hier_per_bucket(cfg):
    """plan_allreduce_step applies the same per-payload verdict the
    trace does: with a table that says hier everywhere, every leaf with
    a real (local, cross) split prices hierarchically."""
    spec = Q.QuantSpec(bits=8, block=256)
    sizes = [1 << 18, 1 << 12]
    D.set_active(D.constant_table({"allreduce": True}), reason="test")
    hier = XC.plan_allreduce_step(sizes, local_size=4, cross_size=2,
                                  spec=spec)
    D.reset()
    flat = XC.plan_allreduce_step(sizes, local_size=4, cross_size=2,
                                  spec=spec)
    assert flat.raw == hier.raw == sum(2 * 4 * n for n in sizes)
    assert flat.sent == sum(2 * Q.wire_bytes(n, spec) for n in sizes)
    want = sum(XC.hierarchical_allreduce_wire_bytes(n, 4, 2, spec)["sent"]
               for n in sizes)
    assert hier.sent == want
    # No (local, cross) split -> hier verdict cannot apply.
    D.set_active(D.constant_table({"allreduce": True}), reason="test")
    assert XC.plan_allreduce_step(sizes, spec=spec).sent == flat.sent


# ---------------------------------------------------------------------------
# schedule selection precedence: table > pin > legacy bool > flat
# ---------------------------------------------------------------------------

def test_choose_schedule_precedence(cfg):
    # Default: flat.
    assert XC.choose_schedule("allreduce", 1 << 20) == "flat"
    # Legacy bool.
    cfg.hierarchical_allreduce = True
    assert XC.choose_schedule("allreduce", 1 << 20) == "hier"
    # Explicit pin overrides the bool.
    cfg.hierarchical_allreduce_pin = False
    assert XC.choose_schedule("allreduce", 1 << 20) == "flat"
    # Active probed table overrides both, per bucket.
    table = D.constant_table({"allreduce": True, "allgather": False},
                             source="probe")
    D.set_active(table, reason="test")
    assert XC.choose_schedule("allreduce", 1 << 20) == "hier"
    assert XC.choose_schedule("allgather", 1 << 20) == "flat"
    D.reset()
    assert XC.choose_schedule("allreduce", 1 << 20) == "flat"


# ---------------------------------------------------------------------------
# quantized stage-3 gather opt-in (shard_map plane)
# ---------------------------------------------------------------------------

def test_quantized_gather_opt_in_value(cfg):
    """gather_in_forward(quantize_gather=True) gathers one qdq round
    trip of the concatenated bucket — lossy, bounded, opt-in."""
    from horovod_tpu.ops import overlap
    mesh = _mesh()
    rng = np.random.default_rng(3)
    full = {"w": jnp.asarray(rng.standard_normal((N * 2, 3)),
                             jnp.float32)}
    likes = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), full)
    comp = hvd.Compression.int8

    def run(quantize_gather):
        def body(p):
            # Flat per-rank shard of each leaf (sizes divide N here).
            my = jax.tree_util.tree_map(
                lambda l: l.reshape(N, -1)[jax.lax.axis_index("data")]
                .reshape(-1), p)
            return overlap.gather_in_forward(
                my, likes, axis_name="data",
                compression=comp, quantize_gather=quantize_gather)
        return jax.jit(_shmap(mesh, body, in_specs=(P(),),
                              out_specs=P()))(full)

    exact = run(False)
    quant = run(True)
    np.testing.assert_array_equal(np.asarray(exact["w"]),
                                  np.asarray(full["w"]))
    qw = np.asarray(quant["w"])
    assert not np.array_equal(qw, np.asarray(full["w"]))
    scale = np.abs(np.asarray(full["w"])).max() / 127.0
    assert np.abs(qw - np.asarray(full["w"])).max() <= scale


# ---------------------------------------------------------------------------
# MoE dispatch primitive (delegated into this layer)
# ---------------------------------------------------------------------------

def test_all_to_all_wire_quantized_close_to_fp32():
    from horovod_tpu.parallel import moe as moe_lib
    assert moe_lib._all_to_all_wire is not None
    mesh = _mesh()
    spec = Q.QuantSpec(bits=8, block=64)
    rng = np.random.default_rng(4)
    x = rng.standard_normal((N, N, 16)).astype(np.float32)

    def body(quant):
        def inner(v):
            return XC.all_to_all_wire(v[0], "data", quant)
        return inner

    specs = dict(in_specs=(P("data"),), out_specs=P("data"))
    fp = np.asarray(jax.jit(_shmap(mesh, body(None), **specs))(x))
    qt = np.asarray(jax.jit(_shmap(mesh, body(spec), **specs))(x))
    assert fp.shape == qt.shape
    scale = np.abs(x).max() / 127.0
    assert np.abs(fp - qt).max() <= scale
    assert not np.array_equal(fp, qt)
