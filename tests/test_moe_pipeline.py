"""Third mesh dimensions (ISSUE 16): MoE expert parallelism and 1F1B
pipeline parallelism as first-class workload classes.

Routing/capacity goldens with dropped-token accounting, the (dp, ep)
MoE workload vs its no-capacity serial oracle and vs the FLOPs-matched
dense baseline, quantized-dispatch convergence parity, 1F1B-vs-GPipe
bit parity (including the n_micro < n_stages corner), the 3-axis
(2, 2, 2) → (2, 2, 1) checkpoint-reshard drill on disk AND through the
peer tier, and the pipeline_bubble attribution component.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import checkpoint as ckpt
from horovod_tpu.compat import shard_map
from horovod_tpu.models import moe_transformer as moet
from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel import moe as moe_lib
from horovod_tpu.parallel import pipeline as pp_lib
from horovod_tpu.parallel.mesh import create_mesh

R = importlib.import_module("horovod_tpu.checkpoint.reshard")


class _SGD:
    def update(self, grads, state, params):
        return jax.tree_util.tree_map(lambda g: -0.1 * g, grads), state


# ---------------------------------------------------------------------------
# Routing / capacity goldens
# ---------------------------------------------------------------------------

def test_expert_capacity_clamps_to_one():
    # The ISSUE-16 edge case: tiny token counts or small factors round
    # the per-expert buffer to zero — the clamp keeps dispatch legal.
    assert moe_lib.expert_capacity(2, 8, 0.1) == 1
    assert moe_lib.expert_capacity(1, 64, 1.0) == 1
    # And the ordinary arithmetic: ceil(T*k/E * f).
    assert moe_lib.expert_capacity(128, 8, 1.25, top_k=1) == 20
    assert moe_lib.expert_capacity(128, 8, 1.25, top_k=2) == 40


def test_top_k_routing_golden_positions_and_drops():
    """4 tokens, 2 experts, capacity 2: sequential slot assignment with
    overflow dropped, combine weighted by the raw softmax probs."""
    logits = jnp.array([[2.0, 0.0],    # t0 -> e0 (slot 0)
                        [2.0, 0.0],    # t1 -> e0 (slot 1)
                        [2.0, 0.0],    # t2 -> e0 FULL -> dropped
                        [0.0, 2.0]],   # t3 -> e1 (slot 0)
                       jnp.float32)
    info = moe_lib.top_k_routing(logits, capacity=2, top_k=1)
    d = np.asarray(info.dispatch)
    assert d[0, 0, 0] == 1.0 and d[1, 0, 1] == 1.0 and d[3, 1, 0] == 1.0
    assert d[2].sum() == 0.0                       # t2 dropped
    assert float(info.dropped) == 1.0
    p0 = float(jax.nn.softmax(logits[0])[0])
    assert np.asarray(info.combine)[0, 0, 0] == pytest.approx(p0)


def test_top_k2_second_choice_counts_after_first():
    """top-2: every token's 2nd choice lands AFTER all 1st choices in
    the capacity order, and dropped counts reflect both slots."""
    t, e = 8, 2
    logits = jnp.stack([jnp.linspace(1.0, 2.0, t),
                        jnp.linspace(2.0, 1.0, t)], axis=1)
    cap = 3
    info = moe_lib.top_k_routing(logits, capacity=cap, top_k=2)
    d = np.asarray(info.dispatch)
    # 16 routes into 2*3 slots -> exactly 10 dropped.
    assert float(info.dropped) == t * 2 - e * cap
    assert d.sum() == e * cap
    # No slot double-booked.
    assert (d.sum(axis=0) <= 1.0 + 1e-6).all()
    with pytest.raises(ValueError):
        moe_lib.top_k_routing(logits, capacity=cap, top_k=3)


# ---------------------------------------------------------------------------
# (dp, ep) MoE workload: oracle, dense-baseline and quantized parity
# ---------------------------------------------------------------------------

_MOE_CFG = moet.MoEConfig(
    vocab_size=61, d_model=32, n_heads=4, d_ff=48, n_layers=2,
    seq_len=16, n_experts=8, top_k=2, capacity_factor=8.0,
    aux_weight=0.01, dtype=jnp.float32, remat=False)
_MOE_PAR = moet.MoEParallelConfig(dp=2, ep=4)


def _moe_fixture(cfg=_MOE_CFG, par=_MOE_PAR, batch=8):
    hvd.init()
    mesh = create_mesh({"dp": par.dp, "ep": par.ep})
    params = moet.init_params(jax.random.PRNGKey(0), cfg, par)
    tokens, labels = moet.synthetic_batch(jax.random.PRNGKey(1), cfg,
                                          batch)
    return mesh, params, tokens, labels


def test_moe_sharded_forward_matches_no_capacity_oracle():
    """At a capacity factor where nothing drops, the (dp=2, ep=4)
    sharded forward equals the per-token-routed serial oracle — pinning
    the dispatch/combine all_to_all math end to end."""
    mesh, params, tokens, labels = _moe_fixture()
    total, m = jax.jit(moet.make_loss_fn(_MOE_CFG, _MOE_PAR, mesh))(
        params, tokens, labels)
    assert float(m["dropped"]) == 0.0
    # Routed counts accumulate per layer: T * top_k * n_layers.
    assert float(m["routed"]) == \
        tokens.size * _MOE_CFG.top_k * _MOE_CFG.n_layers
    oracle = moet.serial_forward_loss(_MOE_CFG, params, tokens, labels)
    assert float(m["ce"]) == pytest.approx(float(oracle), rel=1e-5)
    # Total = ce + aux_weight * aux, all replicated scalars.
    assert float(total) == pytest.approx(
        float(m["ce"]) + _MOE_CFG.aux_weight * float(m["aux"]), rel=1e-6)


def test_moe_tight_capacity_drops_and_stays_finite():
    cfg = _MOE_CFG._replace(capacity_factor=0.5)
    mesh, params, tokens, labels = _moe_fixture(cfg)
    total, m = jax.jit(moet.make_loss_fn(cfg, _MOE_PAR, mesh))(
        params, tokens, labels)
    assert np.isfinite(float(total))
    assert 0 < float(m["dropped"]) < float(m["routed"])


def test_moe_train_step_learns_and_shards_experts_over_ep():
    mesh, params, tokens, labels = _moe_fixture()
    step, shard_params = moet.make_train_step(_MOE_CFG, _MOE_PAR, mesh,
                                              _SGD())
    p = shard_params(params)
    losses = []
    st = ()
    for _ in range(3):
        p, st, loss, m = step(p, st, tokens, labels)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    spec = tuple(p["layers"]["w_in"].sharding.spec)
    assert spec[:2] == (None, "ep")      # experts stay sharded over ep


def test_moe_quantized_dispatch_convergence_parity():
    """int8 block-scaled dispatch wire: same trajectory as fp32 within
    a tight relative band, step by step."""
    cfg8 = _MOE_CFG._replace(dispatch_bits=8, dispatch_block=32)
    mesh, _params, tokens, labels = _moe_fixture()
    traj = {}
    for name, cfg in (("fp32", _MOE_CFG), ("int8", cfg8)):
        # Fresh (identically seeded) init per arm: the donating train
        # step consumes the device_put'ed tree, which can alias the
        # source arrays.
        params = moet.init_params(jax.random.PRNGKey(0), cfg, _MOE_PAR)
        step, shard_params = moet.make_train_step(cfg, _MOE_PAR, mesh,
                                                  _SGD())
        p, st, losses = shard_params(params), (), []
        for _ in range(4):
            p, st, loss, _m = step(p, st, tokens, labels)
            losses.append(float(loss))
        traj[name] = losses
    assert traj["fp32"][-1] < traj["fp32"][0]
    assert traj["int8"][-1] < traj["int8"][0]
    for a, b in zip(traj["fp32"], traj["int8"]):
        assert b == pytest.approx(a, rel=2e-2)


def test_moe_matches_dense_baseline_at_equal_flops():
    """Seeded MoE run vs the FLOPs-matched dense baseline: equal
    audited per-token compute, both trajectories decrease, final CE in
    the same band (loss parity at equal FLOPs — the MoE claim)."""
    cfg = _MOE_CFG._replace(top_k=1, capacity_factor=2.0)
    dense_cfg = moet.flops_matched_dense_config(cfg)
    assert dense_cfg.d_ff == cfg.top_k * cfg.d_ff
    # Audited accounting: identical up to the 2*d*E router term.
    gate = 3.0 * cfg.seq_len * cfg.n_layers * 2.0 * cfg.d_model * \
        cfg.n_experts
    assert moet.train_flops_per_seq(cfg) - gate == pytest.approx(
        tfm.train_flops_per_seq(dense_cfg))

    mesh, params, tokens, labels = _moe_fixture(cfg)
    step, shard_params = moet.make_train_step(cfg, _MOE_PAR, mesh,
                                              _SGD())
    p, st = shard_params(params), ()
    for _ in range(6):
        p, st, loss, m = step(p, st, tokens, labels)
    moe_ce = float(m["ce"])

    d_par = tfm.ParallelConfig(dp=8)
    d_mesh = create_mesh({"dp": 8, "pp": 1, "mp": 1})
    d_params = tfm.init_params(jax.random.PRNGKey(0), dense_cfg, d_par)
    d_step, d_shard = tfm.make_train_step(dense_cfg, d_par, d_mesh,
                                          _SGD())
    dp, dst = d_shard(d_params), ()
    d0 = None
    for _ in range(6):
        dp, dst, d_loss = d_step(dp, dst, tokens, labels)
        d0 = float(d_loss) if d0 is None else d0
    dense_ce = float(d_loss)
    assert dense_ce < d0
    assert moe_ce == pytest.approx(dense_ce, rel=0.15)


# ---------------------------------------------------------------------------
# 1F1B schedule: bubble arithmetic and GPipe bit parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_stages,n_micro",
                         [(2, 3), (4, 8), (4, 2), (2, 1), (1, 4), (8, 8)])
def test_1f1b_schedule_bubble_matches_analytic(n_stages, n_micro):
    sched = pp_lib.build_1f1b_schedule(n_stages, n_micro)
    assert sched.measured_bubble == pytest.approx(
        pp_lib.bubble_fraction(n_stages, n_micro), abs=1e-9)
    # The whole point of 1F1B: the stash is bounded by the stage count,
    # not the microbatch count.
    assert sched.stash_depth <= n_stages


def test_1f1b_matches_gpipe_loss_and_grads():
    """Flagship transformer on (dp, pp, mp) = (2, 2, 2): the 1F1B
    schedule's loss is bit-identical to GPipe's (the forward IS the
    GPipe tick loop) and grads agree to summation-order tolerance."""
    hvd.init()
    cfg = tfm.TransformerConfig(
        vocab_size=61, d_model=32, n_heads=4, d_ff=48, n_layers=2,
        seq_len=16, dtype=jnp.float32, remat=False)
    mesh = create_mesh({"dp": 2, "pp": 2, "mp": 2})
    par_g = tfm.ParallelConfig(dp=2, pp=2, mp=2, n_microbatches=4,
                               pp_schedule="gpipe")
    par_f = par_g._replace(pp_schedule="1f1b")
    params = tfm.init_params(jax.random.PRNGKey(5), cfg, par_g)
    tokens, labels = tfm.synthetic_batch(jax.random.PRNGKey(6), cfg, 8)
    lg, gg = jax.value_and_grad(tfm.make_loss_fn(cfg, par_g, mesh))(
        params, tokens, labels)
    lf, gf = jax.value_and_grad(tfm.make_loss_fn(cfg, par_f, mesh))(
        params, tokens, labels)
    assert float(lg) == float(lf)                  # bit parity
    for a, b in zip(jax.tree_util.tree_leaves(gg),
                    jax.tree_util.tree_leaves(gf)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=1e-4)


def test_1f1b_short_batch_loss_correct():
    """n_micro=1 < pp=2: the short-batch corner must be numerically
    correct, not refused — and CORRECT means equal to the unsharded
    serial oracle, not just self-consistent.  Forward-only on the full
    transformer (the grad compile for this geometry is covered by the
    toy-stage drill below — two extra pipelined-grad compiles of the
    flagship model would bust the tier-1 wall budget)."""
    hvd.init()
    cfg = tfm.TransformerConfig(
        vocab_size=61, d_model=32, n_heads=4, d_ff=48, n_layers=2,
        seq_len=16, dtype=jnp.float32, remat=False)
    mesh = create_mesh({"dp": 2, "pp": 2, "mp": 2})
    par_g = tfm.ParallelConfig(dp=2, pp=2, mp=2, n_microbatches=1,
                               pp_schedule="gpipe")
    par_f = par_g._replace(pp_schedule="1f1b")
    params = tfm.init_params(jax.random.PRNGKey(5), cfg, par_g)
    tokens, labels = tfm.synthetic_batch(jax.random.PRNGKey(6), cfg, 8)
    lg = tfm.make_loss_fn(cfg, par_g, mesh)(params, tokens, labels)
    lf = tfm.make_loss_fn(cfg, par_f, mesh)(params, tokens, labels)
    assert float(lg) == float(lf)                  # bit parity
    oracle = tfm.serial_forward_loss(cfg, params, tokens, labels)
    assert float(lg) == pytest.approx(float(oracle), rel=1e-5)


@pytest.mark.parametrize("n_micro", [2, 1])
def test_1f1b_short_batch_toy_grads_match_gpipe(n_micro):
    """Backward parity in the n_micro < n_stages regime, where the
    1F1B slot table is fill/drain-only: toy tanh stages over pp=4 keep
    the grad compile cheap while exercising the same replay machinery
    as the flagship model."""
    hvd.init()
    mesh = create_mesh({"dp": 2, "pp": 4})
    d = 4
    ws = jax.random.normal(jax.random.PRNGKey(7), (4, d, d)) * 0.5
    x = jax.random.normal(jax.random.PRNGKey(8), (n_micro, 2, d))

    def stage_fn(w, a):
        return jnp.tanh(a @ w)

    def loss(schedule):
        apply = (pp_lib.pipeline_apply if schedule == "gpipe"
                 else pp_lib.pipeline_apply_1f1b)

        def inner(w_stage, xs):
            out = apply(stage_fn, w_stage[0], xs, axis_name="pp")
            mask = pp_lib.last_stage_mask("pp")
            return jnp.sum((jax.lax.psum(out * mask, "pp")) ** 2)[None]

        def fn(w, xs):
            return jax.jit(shard_map(
                inner, mesh=mesh, in_specs=(P("pp"), P(None)),
                out_specs=P("pp"), check_vma=False))(w, xs)[0]

        return jax.value_and_grad(fn)(ws, x)

    lg, gg = loss("gpipe")
    lf, gf = loss("1f1b")
    assert float(lg) == float(lf)
    np.testing.assert_allclose(np.asarray(gg), np.asarray(gf),
                               atol=1e-6, rtol=1e-6)


def test_unknown_pp_schedule_refused():
    hvd.init()
    cfg = tfm.TransformerConfig(vocab_size=61, d_model=32, n_heads=4,
                                d_ff=48, n_layers=2, seq_len=16,
                                dtype=jnp.float32, remat=False)
    mesh = create_mesh({"dp": 2, "pp": 2, "mp": 2})
    par = tfm.ParallelConfig(dp=2, pp=2, mp=2, pp_schedule="zigzag")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, par)
    tokens, labels = tfm.synthetic_batch(jax.random.PRNGKey(1), cfg, 8)
    with pytest.raises(ValueError, match="pp_schedule"):
        tfm.make_loss_fn(cfg, par, mesh)(params, tokens, labels)


# ---------------------------------------------------------------------------
# 3-axis checkpoint reshard: (dp, mp, ep/pp) tuples
# ---------------------------------------------------------------------------

def test_mesh_reshard_three_axis_roundtrip_and_degradation():
    x = np.arange(37, dtype=np.float64) * 0.5 - 3.0
    shards = [R.mesh_shard_of(x, (2, 2, 2), *rk)
              for rk in np.ndindex(2, 2, 2)]
    np.testing.assert_array_equal(
        R.reassemble_mesh(shards, x.size, (2, 2, 2)), x)
    # (2,2,2) -> (2,2,1): equals sharding the logical value directly.
    out = R.reshard_mesh(shards, x.size, (2, 2, 2), (2, 2, 1))
    for rk, s in zip(np.ndindex(2, 2, 1), out):
        np.testing.assert_array_equal(s, R.mesh_shard_of(x, (2, 2, 1),
                                                         *rk))
    # Trailing size-1 axes degrade exactly to the lower-dim layout.
    for rk in np.ndindex(2, 3):
        np.testing.assert_array_equal(
            R.mesh_shard_of(x, (2, 3, 1), rk[0], rk[1], 0),
            R.mesh_shard_of(x, (2, 3), *rk))
    # Cross-rank-count: back to a flat world of 4.
    flat = R.reshard_mesh(shards, x.size, (2, 2, 2), (4,))
    np.testing.assert_array_equal(R.reassemble(flat, x.size), x)


def _mesh3(shape, axes):
    devs = np.array(jax.devices()[:int(np.prod(shape))]).reshape(shape)
    return Mesh(devs, axes)


_DRILL_PARAMS = {"w": jnp.linspace(-1.0, 1.0, 12).reshape(4, 3),
                 "b": jnp.linspace(0.5, 2.0, 16)}


def _drill_loss(p, x):
    return jnp.sum((x @ p["w"]) ** 2) * 1e-3 + jnp.sum(p["b"] ** 2) * 1e-2


def _train3(mesh, axes, steps, start=None):
    """Stage-3 train over the PRODUCT of ``axes``; returns tx, states."""
    tx = hvd.ZeroShardedOptimizer(optax.adamw(1e-2, weight_decay=1e-3),
                                  stage=3, axis_name=axes)
    world = int(np.prod([mesh.shape[a] for a in axes]))
    if start is None:
        ps = ckpt.zero_shard_params(tx, _DRILL_PARAMS, mesh=mesh,
                                    axis_name=axes)
        ost = ckpt.zero_init(tx, ps, mesh=mesh, axis_name=axes)
    else:
        ps, ost = start
    ps_specs = ckpt.zero_state_specs(ps, axis_name=axes)
    ost_specs = ckpt.zero_state_specs(ost, axis_name=axes)

    def step(pstate, ostate, x):
        x = x[0]
        for _ in range(steps):
            def lf(shards):
                return _drill_loss(tx.gather_params(shards,
                                                    _DRILL_PARAMS), x)
            g = jax.grad(lf)(pstate.inner)
            u, ostate = tx.update(g, ostate, pstate)
            pstate = tx.apply_updates(pstate, u)
        return pstate, ostate

    fn = jax.jit(shard_map(step, mesh=mesh,
                           in_specs=(ps_specs, ost_specs, P(axes)),
                           out_specs=(ps_specs, ost_specs),
                           check_vma=False))
    batch = jnp.arange(world * 4, dtype=jnp.float32).reshape(world, 1, 4)
    return tx, fn(ps, ost, batch)


def _logical(state, mesh, axes):
    ext = ckpt.extract_zero_state(state, mesh=mesh, axis_name=axes)
    out = {}
    for i, spec in enumerate(ext.specs):
        if spec.kind == ckpt.SHARDED:
            shards = [ext.rank_values[r][i] for r in range(ext.world)]
            out[spec.path] = np.concatenate(
                [np.asarray(s).reshape(-1) for s in shards]
            )[:spec.true_size]
        else:
            out[spec.path] = np.asarray(ext.rank_values[0][i])
    return out


@pytest.mark.timeout(120)
def test_three_axis_mesh_change_restores_bit_identical(tmp_path):
    """THE 3-axis drill: stage-3 train on (dp, mp, ep) = (2, 2, 2) at
    world 8 -> commit -> restore at the shrunk (2, 2, 1) world-4 mesh;
    every restored logical element equals the committed step exactly
    (float ==), on disk AND through the peer (disk-free) tier — and the
    restored state trains on at the new geometry."""
    hvd.init()
    axes8 = ("data", "model", "expert")
    mesh8 = _mesh3((2, 2, 2), axes8)
    tx, (ps, ost) = _train3(mesh8, axes8, steps=3)
    proot, oroot = str(tmp_path / "params"), str(tmp_path / "opt")
    ckpt.save_zero_state(proot, ps, step=3, mesh=mesh8, axis_name=axes8)
    ckpt.save_zero_state(oroot, ost, step=3, mesh=mesh8, axis_name=axes8)
    committed_p = _logical(ps, mesh8, axes8)
    committed_o = _logical(ost, mesh8, axes8)

    # Peer (disk-free) replication of the same committed step.
    from horovod_tpu import recovery as rec
    ext_p = ckpt.extract_zero_state(ps, mesh=mesh8, axis_name=axes8)
    rec.replicate("params3ax", 3, ext_p, stride=1, push=False)
    rec.seal_commit("params3ax", 3)

    axes4 = ("data", "model", "expert")
    mesh4 = _mesh3((2, 2, 1), axes4)
    tx4 = hvd.ZeroShardedOptimizer(
        optax.adamw(1e-2, weight_decay=1e-3), stage=3, axis_name=axes4)
    like_p = ckpt.zero_shard_params(tx4, _DRILL_PARAMS, mesh=mesh4,
                                    axis_name=axes4)
    like_o = ckpt.zero_init(tx4, like_p, mesh=mesh4, axis_name=axes4)
    r_p = ckpt.restore_zero_state(proot, like_p, mesh=mesh4,
                                  axis_name=axes4)
    r_o = ckpt.restore_zero_state(oroot, like_o, mesh=mesh4,
                                  axis_name=axes4)
    for got, want in ((_logical(r_p, mesh4, axes4), committed_p),
                      (_logical(r_o, mesh4, axes4), committed_o)):
        assert set(got) == set(want)
        for k in want:
            np.testing.assert_array_equal(got[k], want[k])

    # Peer restore at the SAME shrunk mesh: bit-identical too.
    peer_p, _extra, _rep = rec.peer_restore("params3ax", like_p,
                                            mesh=mesh4, axis_name=axes4)
    got = _logical(peer_p, mesh4, axes4)
    for k in committed_p:
        np.testing.assert_array_equal(got[k], committed_p[k])

    # The restored layouts are live: one more step at the new mesh.
    _train3(mesh4, axes4, steps=1, start=(r_p, r_o))


# ---------------------------------------------------------------------------
# pipeline_bubble attribution component
# ---------------------------------------------------------------------------

def test_attribution_pipeline_bubble_component():
    from horovod_tpu.metrics.attribution import StepAttribution
    from horovod_tpu.metrics.registry import MetricsRegistry
    from horovod_tpu import metrics
    assert "pipeline_bubble" in metrics.COMPONENTS
    assert "pipeline_bubble" in metrics.WALL_COMPONENTS
    reg = MetricsRegistry()
    eng = StepAttribution(reg)
    eng.close_step(0, 0.1)
    eng.note_pipeline_bubble(0.03)
    rec = eng.close_step(1, 0.1)
    comps = rec["components"]
    assert comps["pipeline_bubble"] == pytest.approx(0.03)
    # Bubble is carved out of the residual: compute absorbs the rest.
    assert comps["compute"] == pytest.approx(0.07)
    assert sum(rec["shares"].values()) == pytest.approx(1.0)
    flat = reg.scalars()
    assert flat["hvd_step_attribution_seconds{component=pipeline_bubble}"
                ] == pytest.approx(0.03)


def test_note_bubble_credits_analytic_fraction():
    # note_bubble charges bubble_fraction * span into the live engine.
    credited = pp_lib.note_bubble(4, 8, 1.1)
    assert credited == pytest.approx(pp_lib.bubble_fraction(4, 8) * 1.1)
    assert pp_lib.note_bubble(4, 8, -1.0) == 0.0


def test_drift_diagnoser_knows_pipeline_bubble():
    from horovod_tpu.debug.regression import COMPONENT_SUBSYSTEMS
    assert "pipeline_bubble" in COMPONENT_SUBSYSTEMS


# ---------------------------------------------------------------------------
# Expert-parallel serving
# ---------------------------------------------------------------------------

_SRV_CFG = tfm.TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, d_ff=64, n_layers=2,
    seq_len=64, n_experts=4, top_k=2, dtype=jnp.float32, remat=False)


def _srv_params():
    return tfm.init_params(jax.random.PRNGKey(3), _SRV_CFG,
                           tfm.ParallelConfig())


def test_moe_prefill_and_decode_match_per_token_oracle():
    """MoE serving: prefill logits and a decode step both reproduce the
    per-token-routed oracle's next-token distribution (the router runs
    per token at decode; no capacity at inference)."""
    params = _srv_params()
    toks = jax.random.randint(jax.random.PRNGKey(4), (16,), 0,
                              _SRV_CFG.vocab_size, jnp.int32)
    kv = tfm.init_kv_pages(_SRV_CFG, 5, 4)
    table = jnp.arange(1, 5, dtype=jnp.int32)[None]
    logits_p, kv = tfm.chunk_forward(_SRV_CFG, params, toks[None],
                                     jnp.zeros((1,), jnp.int32), kv, table)
    logits_p = logits_p[0, 11]
    flat = {"embed": params["embed"], "pos": params["pos"],
            "final_norm": params["final_norm"],
            "layers": tfm._flat_layers(params)}
    ocfg = moet.MoEConfig(
        vocab_size=64, d_model=32, n_heads=4, d_ff=64, n_layers=2,
        seq_len=64, n_experts=4, top_k=2, dtype=jnp.float32)
    oracle = moet.serial_forward_logits(ocfg, flat, toks[None, :12])
    np.testing.assert_allclose(np.asarray(logits_p),
                               np.asarray(oracle[0, -1]), atol=2e-4)
    assert int(jnp.argmax(logits_p)) == int(jnp.argmax(oracle[0, -1]))

    ld, kv = tfm.chunk_forward(_SRV_CFG, params, toks[12][None, None],
                               jnp.array([12], jnp.int32), kv, table)
    ld = ld[:, 0]
    oracle13 = moet.serial_forward_logits(ocfg, flat, toks[None, :13])
    np.testing.assert_allclose(np.asarray(ld[0]),
                               np.asarray(oracle13[0, -1]), atol=2e-4)
    assert int(jnp.argmax(ld[0])) == int(jnp.argmax(oracle13[0, -1]))


def test_decode_engine_serves_moe_config():
    """The continuous-batching engine accepts an MoE config end to end:
    admit -> greedy decode -> finish, one compiled decode trace."""
    from horovod_tpu.serving import DecodeEngine, Request
    eng = DecodeEngine(_SRV_CFG, _srv_params(), slots=2, page_tokens=8,
                       max_len=_SRV_CFG.seq_len)
    evs = eng.admit(Request(id="m", prompt=[1, 2, 3], max_new_tokens=5))
    toks = [e.token for e in evs if e.kind == "token"]
    while not any(e.kind == "finish" for e in evs):
        evs = eng.step()
        toks += [e.token for e in evs if e.kind == "token"]
    assert len(toks) == 5
    assert all(0 <= t < _SRV_CFG.vocab_size for t in toks)
    assert eng.decode_traces == 1
