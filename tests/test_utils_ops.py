"""Standalone sync-BN op and checkpoint helpers."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from horovod_tpu.compat import shard_map

import horovod_tpu as hvd
from horovod_tpu.ops.sync_batch_norm import sync_batch_norm
from horovod_tpu.utils import checkpoint as ckpt


def test_sync_batch_norm_matches_global():
    hvd.init()
    mesh = hvd.mesh()
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 4))
    scale = jnp.ones((4,))
    bias = jnp.zeros((4,))
    rm = jnp.zeros((4,))
    rv = jnp.ones((4,))

    def fn(x, s, b, m, v):
        out, nm, nv = sync_batch_norm(x, s, b, m, v, axis_name="data")
        return out, nm, nv

    out, nm, nv = jax.jit(shard_map(
        fn, mesh=mesh, in_specs=(P("data"), P(), P(), P(), P()),
        out_specs=(P("data"), P(), P()), check_vma=False))(
            x, scale, bias, rm, rv)
    # Global-batch BN oracle.
    mean = x.mean(0)
    var = x.var(0)
    expected = (x - mean) / jnp.sqrt(var + 1e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(nm), 0.1 * np.asarray(mean),
                               rtol=1e-4, atol=1e-6)


def test_checkpoint_roundtrip(tmp_path):
    hvd.init()
    state = {"w": jnp.arange(6.0).reshape(2, 3),
             "opt": {"m": jnp.ones((4,))}}
    path = str(tmp_path / "ckpt")
    ckpt.save_checkpoint(path, state, step=7)
    assert ckpt.latest_step(str(tmp_path), "ckpt") == 7
    restored = ckpt.restore_checkpoint(path, target=state, step=7)
    np.testing.assert_allclose(np.asarray(restored["w"]),
                               np.asarray(state["w"]))
    np.testing.assert_allclose(np.asarray(restored["opt"]["m"]), 1.0)


def test_checkpoint_nonzero_rank_skips(tmp_path):
    path = str(tmp_path / "nope")
    ckpt.save_checkpoint(path, {"a": np.ones(2)}, rank=1)
    import os
    assert not os.path.exists(path) and not os.path.exists(path + ".pkl")


# ---------------------------------------------------------------------------
# Profiler trace ranges (NVTX-analog, utils/profiler.py)
# ---------------------------------------------------------------------------

def test_op_range_is_emitted_whatever_the_environment_holds(monkeypatch,
                                                            tmp_path):
    """The host ranges have no knob: with no trace being taken a range is a
    safe no-op, and under a trace it is on the host timeline even where the
    reference's ``HOROVOD_DISABLE_NVTX_RANGES`` is set (a TraceAnnotation
    costs nothing worth a knob when no trace is being taken)."""
    from jax.profiler import ProfileData
    from horovod_tpu.utils import profiler
    with profiler.op_range("hvd.allreduce.x", 128):
        y = 1 + 1
    assert y == 2
    assert not hasattr(profiler, "_enabled")
    monkeypatch.setenv("HOROVOD_DISABLE_NVTX_RANGES", "1")
    with profiler.trace(str(tmp_path)):
        with profiler.op_range("hvd.allreduce.x", 128):
            pass
        with profiler.host_span("hvd.plain"):
            pass
    import glob
    import os
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert {"hvd.allreduce.x#bytes=128", "hvd.plain"} <= names


def test_eager_collectives_pass_through_ranges():
    import numpy as np
    import horovod_tpu as hvd
    hvd.init()
    out = hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum, name="prof1")
    np.testing.assert_allclose(np.asarray(out), 1.0)


def test_trace_capture_writes_logdir(tmp_path):
    import jax.numpy as jnp
    from horovod_tpu.utils import profiler
    with profiler.trace(str(tmp_path)):
        (jnp.ones(8) * 2).block_until_ready()
    import os
    found = [f for _, _, fs in os.walk(tmp_path) for f in fs]
    assert found, "no trace files captured"
