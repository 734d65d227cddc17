"""Topology-probed per-payload schedule dispatch (ISSUE 11): bucket and
table goldens, probe determinism under a fixed seed, the autotune
crossover-shift refinement, schedule annotation on the op stream and the
overlap scheduler's per-bucket dispatch, and the compiled-plane
compositions — quantized hierarchical allreduce against its analytic
bound and Adasum-on-quantized-hierarchical convergence parity on the
toy quadratic."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.compat import shard_map
from horovod_tpu.ops import dispatch as D
from horovod_tpu.ops.dispatch import (
    DispatchTable, ProbeMeasurement, bucket_of, build_table,
    constant_table, run_probe, N_BUCKETS, PAYLOAD_BUCKET_BOUNDS)

N = 8


@pytest.fixture(autouse=True)
def _clean_table():
    """Every test starts and ends with no active table — the module
    global must not leak annotations into unrelated suites."""
    D.reset()
    yield
    D.reset()


def _mesh_2x4():
    devices = jax.devices()[:8]
    return jax.sharding.Mesh(np.array(devices).reshape(2, 4),
                             ("cross", "local"))


# ---------------------------------------------------------------------------
# buckets + table goldens
# ---------------------------------------------------------------------------

def test_bucket_arithmetic_goldens():
    assert bucket_of(1) == 0
    assert bucket_of(16 << 10) == 0
    assert bucket_of((16 << 10) + 1) == 1
    assert bucket_of(1 << 20) == 2
    assert bucket_of(8 << 20) == 3
    assert bucket_of(64 << 20) == 4
    assert bucket_of(1 << 30) == N_BUCKETS - 1
    assert len(D.BUCKET_LABELS) == N_BUCKETS


def _canned_measurements():
    return [
        # allreduce: flat wins small, hier wins large (the 1810.11112
        # crossover shape).
        ProbeMeasurement("allreduce", "flat", 64 << 10, 0.001),
        ProbeMeasurement("allreduce", "hier", 64 << 10, 0.002),
        ProbeMeasurement("allreduce", "flat", 8 << 20, 0.020),
        ProbeMeasurement("allreduce", "hier", 8 << 20, 0.010),
        # allgather: flat wins everywhere probed.
        ProbeMeasurement("allgather", "flat", 128 << 10, 0.001),
        ProbeMeasurement("allgather", "hier", 128 << 10, 0.003),
    ]


def test_build_table_golden_crossover():
    t = build_table(_canned_measurements())
    # Buckets nearest 64KB stay flat; buckets nearest 8MB go hier.
    assert t.allreduce == ("flat", "flat", "flat", "hier", "hier", "hier")
    assert t.allgather == ("flat",) * N_BUCKETS
    assert t.source == "probe"
    assert t.choose("allreduce", 4 << 10) == "flat"
    assert t.choose("allreduce", 32 << 20) == "hier"
    assert t.crossover_bytes("allreduce") == PAYLOAD_BUCKET_BOUNDS[2]
    assert t.crossover_bytes("allgather") is None


def test_build_table_pins_override_measurements():
    t = build_table(_canned_measurements(),
                    pins={"allreduce": True, "allgather": False})
    assert set(t.allreduce) == {"hier"}
    assert set(t.allgather) == {"flat"}


def test_build_table_fallback_for_unprobed_kind():
    ms = [m for m in _canned_measurements() if m.kind == "allreduce"]
    t = build_table(ms, fallback={"allgather": True})
    assert set(t.allgather) == {"hier"}       # legacy global honored
    assert t.allreduce[0] == "flat"           # probed kind still probed


def test_build_table_incomplete_arm_ignored():
    # A size with only one schedule measured cannot be compared and
    # must not decide anything.
    ms = [ProbeMeasurement("allreduce", "hier", 8 << 20, 0.001)]
    t = build_table(ms)
    assert set(t.allreduce) == {"flat"}       # falls back to default


def test_encode_decode_roundtrip():
    t = build_table(_canned_measurements())
    t2 = DispatchTable.decode(t.encode(), source="probe")
    assert t2.allreduce == t.allreduce and t2.allgather == t.allgather
    with pytest.raises(ValueError):
        DispatchTable.decode(np.zeros(3, np.int8))


def test_shifted_moves_crossover_and_clamps():
    t = build_table(_canned_measurements())
    up = t.shifted({"allreduce": 1})
    assert up.allreduce == ("flat", "flat", "hier", "hier", "hier", "hier")
    assert up.source == "autotune"
    down = t.shifted({"allreduce": -1})
    assert down.allreduce == ("flat", "flat", "flat", "flat", "hier",
                              "hier")
    assert t.shifted({"allreduce": 0}).allreduce == t.allreduce
    # Clamped at the edges: repeated shifts saturate, never wrap.
    sat = t.shifted({"allreduce": 1}).shifted({"allreduce": 1}) \
           .shifted({"allreduce": 1})
    assert sat.allreduce[0] == "flat" or set(sat.allreduce) == {"hier"}
    # A constant table is shift-invariant (pinned kinds stay pinned).
    c = constant_table({"allreduce": True})
    assert c.shifted({"allreduce": -1}).allreduce == c.allreduce


def test_to_native_shape():
    t = build_table(_canned_measurements())
    bounds, choices = t.to_native("allreduce")
    assert len(bounds) == len(choices) == N_BUCKETS
    assert bounds[:-1] == list(PAYLOAD_BUCKET_BOUNDS)
    assert bounds[-1] == (1 << 63) - 1
    assert choices == [0, 0, 0, 1, 1, 1]


# ---------------------------------------------------------------------------
# probe determinism (fake controller + injected timer: the plan, names,
# payload draws and resulting measurements are pure in the seed)
# ---------------------------------------------------------------------------

class _FakeController:
    def __init__(self, rank=0, size=4, local_sizes=None):
        self._rank, self._size = rank, size
        # Per-rank local sizes the topology-agreement allgather returns
        # (None = homogeneous: echo the caller's contribution).
        self._local_sizes = local_sizes
        self.table_calls = []
        self.ops = []

    def rank(self):
        return self._rank

    def size(self):
        return self._size

    def barrier(self):
        pass

    def allgather(self, arr, name=None):
        if self._local_sizes is not None:
            return np.asarray(self._local_sizes, dtype=np.int32)
        return np.tile(np.asarray(arr), self._size)

    def set_schedule_table(self, kind, bounds, choices):
        self.table_calls.append((kind, tuple(bounds), tuple(choices)))


def _fake_run(ctl):
    def run(kind, arr, name):
        ctl.ops.append((kind, name, arr.size, float(np.sum(arr))))
    return run


def _counting_timer():
    t = [0.0]

    def timer():
        t[0] += 0.001
        return t[0]
    return timer


def test_probe_deterministic_under_fixed_seed():
    runs = []
    for _ in range(2):
        ctl = _FakeController()
        ms = run_probe(ctl, ("allreduce", "allgather"), seed=7, reps=2,
                       runner=_fake_run(ctl), timer=_counting_timer())
        runs.append((ms, ctl.ops, ctl.table_calls))
    assert runs[0] == runs[1]
    # ... and the built tables are identical too.
    assert build_table(runs[0][0]) == build_table(runs[1][0])


def test_probe_seed_changes_payload_contents_not_plan():
    a, b = _FakeController(), _FakeController()
    run_probe(a, ("allreduce",), seed=1, reps=1, runner=_fake_run(a),
              timer=_counting_timer())
    run_probe(b, ("allreduce",), seed=2, reps=1, runner=_fake_run(b),
              timer=_counting_timer())
    assert [(k, n, s) for k, n, s, _ in a.ops] == \
        [(k, n, s) for k, n, s, _ in b.ops]     # same op sequence
    assert [c for *_, c in a.ops] != [c for *_, c in b.ops]  # new draws


def test_probe_pins_whole_range_per_arm_on_rank0_only():
    ctl = _FakeController(rank=0)
    run_probe(ctl, ("allreduce",), reps=1, runner=_fake_run(ctl),
              timer=_counting_timer())
    assert ctl.table_calls == [
        ("allreduce", ((1 << 63) - 1,), (0,)),
        ("allreduce", ((1 << 63) - 1,), (1,))]
    other = _FakeController(rank=2)
    run_probe(other, ("allreduce",), reps=1, runner=_fake_run(other),
              timer=_counting_timer())
    assert other.table_calls == []


def test_probe_allgather_keys_table_on_gathered_bytes():
    ctl = _FakeController(size=4)
    ms = run_probe(ctl, ("allgather",), reps=1, runner=_fake_run(ctl),
                   timer=_counting_timer())
    contributions = D.PROBE_PAYLOADS["allgather"]
    assert sorted({m.nbytes for m in ms}) == \
        sorted(c * 4 for c in contributions)


# ---------------------------------------------------------------------------
# annotation: op stream + per-bucket overlap dispatch
# ---------------------------------------------------------------------------

def test_annotate_without_table_is_none():
    assert D.annotate("allreduce", 1024) is None
    D.set_active(build_table(_canned_measurements()))
    assert D.annotate("allreduce", 1024) == "flat"
    assert D.annotate("allreduce", 32 << 20) == "hier"
    assert D.annotate("broadcast", 1024) is None   # no flat/hier choice
    assert D.annotate("allreduce", None) is None


def test_op_range_flight_event_carries_schedule():
    from horovod_tpu.debug import flight
    hvd.init()
    D.set_active(build_table(_canned_measurements()))
    big = np.zeros((32 << 20) // 4, np.float32)
    small = np.zeros(64, np.float32)
    hvd.allreduce(small, name="disp.small")
    hvd.allreduce(big, name="disp.big")
    evs = {e["name"]: e for e in flight.snapshot()
           if e["kind"] == "collective.enqueue"
           and str(e.get("name", "")).startswith("disp.")}
    assert evs["disp.small"]["schedule"] == "flat"
    assert evs["disp.big"]["schedule"] == "hier"


def test_op_range_allgather_annotates_gathered_bytes(monkeypatch):
    """The table keys on the FULL gathered payload (what the
    coordinator stamps from), so the annotation must scale the per-rank
    contribution by the communicator size — a 512KB contribution at
    world 4 is a 2MB wire payload and can sit on the other side of a
    crossover."""
    from horovod_tpu.debug import flight
    from horovod_tpu.ops import collective as C
    hvd.init()
    ms = _canned_measurements() + [
        ProbeMeasurement("allgather", "flat", 8 << 20, 0.020),
        ProbeMeasurement("allgather", "hier", 8 << 20, 0.010)]
    D.set_active(build_table(ms))   # allgather crossover at 1MB too
    monkeypatch.setattr(C, "communicator_size", lambda: 4)
    x = np.zeros((512 << 10) // 4, np.float32)   # 512KB -> 2MB gathered
    with C._op_range("allgather", "disp.ag", x):
        pass
    ev = [e for e in flight.snapshot()
          if e["kind"] == "collective.enqueue"
          and e.get("name") == "disp.ag"][-1]
    assert ev["schedule"] == "hier"   # 2MB bucket, not 512KB's "flat"
    assert D.annotate("allgather", x.nbytes) == "flat"  # per-rank view


def test_op_range_schedule_seconds_metric():
    from horovod_tpu.metrics.registry import registry
    hvd.init()
    D.set_active(build_table(_canned_measurements()))
    c = registry().counter(
        "hvd_collective_schedule_seconds_total", "x",
        kind="allreduce", schedule="hier")
    before = c.value
    hvd.allreduce(np.zeros((32 << 20) // 4, np.float32), name="disp.m")
    assert c.value > before


def test_overlap_buckets_annotate_per_bucket_schedules():
    """A small early bucket and a large late bucket legitimately pick
    different schedules from one table — the per-bucket dispatch the
    tentpole promises, visible on the bucket-launch flight events."""
    from horovod_tpu.debug import flight
    from horovod_tpu.ops.overlap import EagerBucketQueue, plan_buckets
    hvd.init()
    D.set_active(build_table(_canned_measurements()))
    leaves = [np.zeros((512 << 10) // 4, np.float32),  # 512KB -> flat
              np.zeros((32 << 20) // 4, np.float32)]   # 32MB -> hier
    plan = plan_buckets(leaves, bucket_bytes=1 << 20)
    q = EagerBucketQueue(plan, op=hvd.Sum, name="disp.ol")
    for bi, idxs in enumerate(plan.buckets):
        q.launch(bi, [leaves[i] for i in idxs])
    q.finish()
    scheds = {e["bytes"]: e.get("schedule")
              for e in flight.snapshot()
              if e["kind"] == "overlap.bucket_launch"
              and str(e.get("name", "")).startswith("disp.ol")}
    assert scheds[512 << 10] == "flat"
    assert scheds[32 << 20] == "hier"


# ---------------------------------------------------------------------------
# autotune refinement: crossover shifts over the probe-seeded table
# ---------------------------------------------------------------------------

def test_parameter_manager_dispatch_shift_mode():
    from horovod_tpu.autotune import ParameterManager
    applied = []
    pm = ParameterManager(lambda *a: applied.append(a), max_samples=6,
                          warmup_samples=0, steps_per_sample=1,
                          initial_toggles=(0, 0, True),
                          tune_toggles=(True, True, False),
                          dispatch_shifts=True)
    # Slots 2/3 of current are shift ints, warm start 0.
    assert pm.current[2] == 0 and pm.current[3] == 0
    while not pm.frozen:
        pm.record_bytes(1 << 20)
    shifts_ar = {a[2] for a in applied}
    shifts_ag = {a[3] for a in applied}
    # The bootstrap plan demonstrably tries every shift of each tunable
    # dim against the warm start before EI takes over.
    assert shifts_ar == {-1, 0, 1}
    assert shifts_ag == {-1, 0, 1}
    assert all(isinstance(a[2], int) and not isinstance(a[2], bool)
               for a in applied)
    assert pm.current[2] in (-1, 0, 1)


def test_parameter_manager_shift_pins():
    from horovod_tpu.autotune import ParameterManager
    applied = []
    pm = ParameterManager(lambda *a: applied.append(a), max_samples=3,
                          warmup_samples=0, steps_per_sample=1,
                          initial_toggles=(0, 0, True),
                          tune_toggles=(False, True, False),
                          dispatch_shifts=True)
    while not pm.frozen:
        pm.record_bytes(1 << 20)
    assert {a[2] for a in applied} == {0}          # pinned at warm start
    assert {a[3] for a in applied} == {-1, 0, 1}   # tunable explores


def test_parameter_manager_bool_mode_unchanged():
    from horovod_tpu.autotune import ParameterManager
    pm = ParameterManager(lambda *a: None, max_samples=2,
                          initial_toggles=(False, True, True))
    assert pm.current[2] is False and pm.current[3] is True


def test_controller_apply_tuned_shifts_table(monkeypatch):
    """_apply_tuned in dispatch mode installs the SHIFTED per-bucket
    tables and the cache toggle alone — never the whole-range
    set_tuned_toggles that would clobber the probe's table."""
    from horovod_tpu.native.controller import NativeController
    base = build_table(_canned_measurements())
    calls = {"tables": [], "cache": [], "toggles": []}

    class FakeCtl:
        _dispatch_table = base
        _apply_tuned = NativeController._apply_tuned

        class _lib:  # noqa: N801 — mimic the ctypes surface
            @staticmethod
            def hvd_native_set_params(f, c):
                pass

            @staticmethod
            def hvd_native_set_cache_enabled(v):
                calls["cache"].append(v)

            @staticmethod
            def hvd_native_set_tuned_toggles(a, b, c):
                calls["toggles"].append((a, b, c))

            @staticmethod
            def hvd_native_set_wire_compression(code):
                pass

        def set_schedule_table(self, kind, bounds, choices):
            calls["tables"].append((kind, tuple(choices)))

    FakeCtl()._apply_tuned(1 << 22, 2.0, 1, 0, True)
    assert calls["toggles"] == []
    assert calls["cache"] == [1]
    shifted = dict(calls["tables"])
    assert shifted["allreduce"] == (0, 0, 1, 1, 1, 1)   # crossover -1 bucket
    assert shifted["allgather"] == (0,) * N_BUCKETS
    active = D.active_table()
    assert active is not None and active.source == "autotune"


# ---------------------------------------------------------------------------
# config: pins + probe knobs
# ---------------------------------------------------------------------------

def test_config_pin_tristate(monkeypatch):
    from horovod_tpu.core.config import Config
    monkeypatch.delenv("HVD_TPU_HIERARCHICAL_ALLREDUCE", raising=False)
    monkeypatch.delenv("HOROVOD_HIERARCHICAL_ALLREDUCE", raising=False)
    monkeypatch.delenv("HVD_TPU_HIERARCHICAL_ALLGATHER", raising=False)
    monkeypatch.delenv("HOROVOD_HIERARCHICAL_ALLGATHER", raising=False)
    cfg = Config.from_env()
    assert cfg.hierarchical_allreduce_pin is None
    assert cfg.hierarchical_allgather_pin is None
    monkeypatch.setenv("HVD_TPU_HIERARCHICAL_ALLREDUCE", "0")
    monkeypatch.setenv("HVD_TPU_HIERARCHICAL_ALLGATHER", "1")
    cfg = Config.from_env()
    assert cfg.hierarchical_allreduce_pin is False
    assert cfg.hierarchical_allgather_pin is True
    assert cfg.schedule_probe is True
    monkeypatch.setenv("HVD_TPU_SCHEDULE_PROBE", "0")
    monkeypatch.setenv("HVD_TPU_SCHEDULE_PROBE_SEED", "5")
    monkeypatch.setenv("HVD_TPU_SCHEDULE_PROBE_REPS", "0")
    cfg = Config.from_env()
    assert cfg.schedule_probe is False
    assert cfg.schedule_probe_seed == 5
    assert cfg.schedule_probe_reps == 1   # floored


def test_bootstrap_pins_bypass_probe():
    """Pinned kinds never probe: with both kinds pinned the bootstrap
    installs the constant table without a single collective."""
    from horovod_tpu.core.config import Config
    cfg = Config()
    cfg.hierarchical_allreduce_pin = True
    cfg.hierarchical_allgather_pin = False
    ctl = _FakeController(size=4)
    ctl.broadcast = lambda *a, **k: pytest.fail("probe ran")
    table = D.bootstrap(ctl, cfg, local_size=2)
    assert set(table.allreduce) == {"hier"}
    assert set(table.allgather) == {"flat"}
    assert table.source == "pin"
    # Rank 0 installed the native tables for both kinds.
    assert {k for k, *_ in ctl.table_calls} == {"allreduce", "allgather"}


def test_bootstrap_degenerate_topology_is_flat():
    """local_size == world (or 1): the native layer degenerates
    hierarchical to flat, and the mirror must record the EFFECTIVE
    schedule — no probe, no native install."""
    from horovod_tpu.core.config import Config
    ctl = _FakeController(size=4)
    table = D.bootstrap(ctl, Config(), local_size=4)
    assert set(table.allreduce) == {"flat"}
    assert ctl.table_calls == []
    assert D.active_table() is table


def test_bootstrap_heterogeneous_local_sizes_skip_probe():
    """Heterogeneous host layouts (the elastic 2+1+1 shape that stalled
    the cascade drill, and the adversarial 3+2+1 where a 2-slot rank's
    local arithmetic ALONE would say 'probe'): the topology-agreement
    allgather makes every rank see the same local-size vector, and a
    non-homogeneous one must skip the probe on ALL ranks — a split
    decision strands half the fleet inside probe collectives."""
    from horovod_tpu.core.config import Config
    for layout, my_local in (([2, 2, 1, 1], 2),   # elastic cascade shape
                             ([3, 3, 3, 2, 2, 1], 2)):  # 2*cross==world
        ctl = _FakeController(size=len(layout), local_sizes=layout)
        table = D.bootstrap(ctl, Config(), local_size=my_local)
        assert set(table.allreduce) == {"flat"}, layout
        assert set(table.allgather) == {"flat"}, layout
        assert ctl.table_calls == [], layout   # no probe arm ever pinned


# ---------------------------------------------------------------------------
# compiled plane: quantized hierarchical allreduce (2 x 4 mesh)
# ---------------------------------------------------------------------------

def _analytic_bound_hier(xs, qmax, L, crossP):
    """Worst-case |compressed-hier - exact| per element, global-absmax
    coarsening like test_quantization._analytic_bound: phase 1 rounds
    each rank's contribution once; phase 2 rounds the node-sum shard
    twice more (its two passes); phase 3 rounds the result once."""
    world = L * crossP
    pass1 = sum(np.abs(xs[r]).max() for r in range(world)) / (2 * qmax)
    reduced = np.abs(xs.sum(0)).max() + pass1
    return pass1 + 3 * reduced / (2 * qmax)


@pytest.mark.parametrize("bits,qmax", [(8, 127), (4, 7)])
def test_quantized_hierarchical_allreduce_within_bound(bits, qmax):
    mesh = _mesh_2x4()
    rng = np.random.RandomState(2)
    xs = (rng.randn(N, 700) * 2).astype(np.float32)
    comp = hvd.Compression.int8 if bits == 8 else hvd.Compression.int4
    out = np.asarray(jax.jit(shard_map(
        lambda t: hvd.allreduce(t, op=hvd.Sum, compression=comp,
                                axis_name=("local", "cross")),
        mesh=mesh, in_specs=P(("cross", "local")),
        out_specs=P(("cross", "local")), check_vma=False))(
            jnp.asarray(xs)))
    exact = xs.sum(0)
    err = np.abs(out[0] - exact).max()
    assert err <= _analytic_bound_hier(xs, qmax, 4, 2)
    assert err > 0   # the wire is actually quantized
    # Every rank holds the identical result (it IS an allreduce).
    for r in range(N):
        np.testing.assert_array_equal(out[r], out[0])


def test_quantized_hierarchical_average_and_cast_wire():
    mesh = _mesh_2x4()
    rng = np.random.RandomState(3)
    xs = rng.randn(N, 260).astype(np.float32)
    out = np.asarray(jax.jit(shard_map(
        lambda t: hvd.allreduce(t, op=hvd.Average,
                                compression=hvd.Compression.int8,
                                axis_name=("local", "cross")),
        mesh=mesh, in_specs=P(("cross", "local")),
        out_specs=P(("cross", "local")), check_vma=False))(
            jnp.asarray(xs)))
    np.testing.assert_allclose(out[0], xs.mean(0), atol=0.05)
    # bf16 cast wire rides the same two-level schedule.
    out2 = np.asarray(jax.jit(shard_map(
        lambda t: hvd.allreduce(t, op=hvd.Sum,
                                compression=hvd.Compression.bf16,
                                axis_name=("local", "cross")),
        mesh=mesh, in_specs=P(("cross", "local")),
        out_specs=P(("cross", "local")), check_vma=False))(
            jnp.asarray(xs)))
    np.testing.assert_allclose(out2[0], xs.sum(0), rtol=0.02, atol=0.15)


def test_quantized_hierarchical_degenerate_axis_falls_back():
    devices = jax.devices()[:8]
    mesh = jax.sharding.Mesh(np.array(devices).reshape(8, 1),
                             ("cross", "local"))
    rng = np.random.RandomState(4)
    xs = rng.randn(N, 130).astype(np.float32)
    out = np.asarray(jax.jit(shard_map(
        lambda t: hvd.allreduce(t, op=hvd.Sum,
                                compression=hvd.Compression.int8,
                                axis_name=("local", "cross")),
        mesh=mesh, in_specs=P(("cross", "local")),
        out_specs=P(("cross", "local")), check_vma=False))(
            jnp.asarray(xs)))
    exact = xs.sum(0)
    assert np.abs(out[0] - exact).max() <= \
        np.abs(exact).max() / (2 * 127) * 20


def test_hierarchical_cross_bytes_shrink_by_local_and_wire():
    """The headline arithmetic: cross-node bytes per member are the
    SHARD's wire bytes — 1/L of the tensor, in the compressed format —
    so the reduction vs flat fp32 is local_size x compression."""
    from horovod_tpu.ops.quantization import QuantSpec, wire_bytes
    n, L = 1 << 20, 4
    spec = QuantSpec(8, 256)
    flat_fp32 = n * 4
    hier_wire = wire_bytes(n // L, spec)
    assert flat_fp32 / hier_wire > 3.9 * L   # ~4x wire x 4x local


# ---------------------------------------------------------------------------
# Adasum on quantized hierarchical reduction: convergence parity
# ---------------------------------------------------------------------------

def _adasum_quadratic_descent(comp, steps=80, lr=0.5, dim=33):
    """Distributed toy quadratic: rank r owns f_r(w) = ||w - c_r||^2/2;
    each step combines the per-rank gradients with hierarchical Adasum
    (optionally on the quantized wire) and descends."""
    mesh = _mesh_2x4()
    rng = np.random.RandomState(0)
    cs = rng.randn(N, dim).astype(np.float32)
    f = jax.jit(shard_map(
        lambda w, c: hvd.allreduce(w - c.reshape(-1), op=hvd.Adasum,
                                   axis_name=("local", "cross"),
                                   compression=comp),
        mesh=mesh, in_specs=(P(), P(("cross", "local"))),
        out_specs=P(("cross", "local")), check_vma=False))
    w = jnp.zeros(dim, jnp.float32)
    for _ in range(steps):
        g = f(w, jnp.asarray(cs)).reshape(N, dim)[0]
        # One collective program in flight at a time: queued behind each
        # other on a busy host, the eight virtual devices' threads of step
        # k + 1 can hold the pool while step k waits at its rendezvous, and
        # XLA aborts the process when that wait times out.
        w = jax.block_until_ready(w - lr * g)
    w = np.asarray(w)
    loss = 0.5 * np.mean(np.sum((w[None] - cs) ** 2, axis=1))
    return w, float(loss)


def test_adasum_quantized_hierarchical_convergence_parity():
    w_fp, loss_fp = _adasum_quadratic_descent(None)
    w_q, loss_q = _adasum_quadratic_descent(hvd.Compression.int8)
    # Both converge to the consensus optimum; the quantized-wire run
    # lands within the PR 5 error-feedback bar (~1% of fp32).
    assert abs(loss_q - loss_fp) / loss_fp < 0.01
    assert np.linalg.norm(w_q - w_fp) / np.linalg.norm(w_fp) < 0.01


def test_adasum_flat_compression_raises():
    mesh = _mesh_2x4()
    with pytest.raises(ValueError, match="Adasum"):
        jax.jit(shard_map(
            lambda t: hvd.allreduce(t, op=hvd.Adasum, axis_name="cross",
                                    compression=hvd.Compression.int8),
            mesh=mesh, in_specs=P(("cross", "local")),
            out_specs=P(("cross", "local")), check_vma=False))(
                jnp.zeros((8, 16), jnp.float32))


def test_adasum_hierarchical_quantized_matches_plain_closely():
    mesh = _mesh_2x4()
    rng = np.random.RandomState(5)
    xs = rng.randn(N, 95).astype(np.float32)

    def run(comp):
        return np.asarray(jax.jit(shard_map(
            lambda t: hvd.allreduce(t, op=hvd.Adasum,
                                    axis_name=("local", "cross"),
                                    compression=comp),
            mesh=mesh, in_specs=P(("cross", "local")),
            out_specs=P(("cross", "local")), check_vma=False))(
                jnp.asarray(xs)))[0]

    plain = run(None)
    quant = run(hvd.Compression.int8)
    assert np.abs(quant - plain).max() / (np.abs(plain).max() + 1e-9) \
        < 0.05
