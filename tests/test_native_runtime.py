"""Native (C++) eager-path runtime: N real processes on localhost exchanging
through the TCP controller + ring data plane — the reference's
Gloo-on-loopback test strategy (SURVEY.md §4: cheap real backend, rank-seeded
closed-form tensors)."""

import json
import multiprocessing as mp
import os
import socket
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _worker(rank, size, port, fn_name, out_queue):
    sys.path.insert(0, REPO)
    os.environ["HVD_TPU_CYCLE_TIME"] = "1"
    from horovod_tpu.native.controller import NativeController
    ctl = NativeController(rank, size, f"127.0.0.1:{port}")
    try:
        result = globals()[fn_name](ctl, rank, size)
        out_queue.put((rank, "ok", result))
    except Exception as e:  # noqa: BLE001
        out_queue.put((rank, "error", repr(e)))
    finally:
        ctl.shutdown()


def _run(fn_name, size=4):
    port = _free_port()
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [ctx.Process(target=_worker, args=(r, size, port, fn_name, q))
             for r in range(size)]
    for p in procs:
        p.start()
    results = {}
    for _ in range(size):
        rank, status, payload = q.get(timeout=120)
        assert status == "ok", f"rank {rank}: {payload}"
        results[rank] = payload
    for p in procs:
        p.join(timeout=30)
        assert p.exitcode == 0
    return results


# --- per-worker bodies (must be top-level for spawn pickling) --------------

def body_allreduce(ctl, rank, size):
    x = np.full((16, 3), float(rank + 1), dtype=np.float32)
    out = ctl.allreduce(x, op=1)  # SUM
    expected = sum(range(1, size + 1))
    np.testing.assert_allclose(out, expected)
    avg = ctl.allreduce(x, op=0)  # AVERAGE
    np.testing.assert_allclose(avg, expected / size)
    mx = ctl.allreduce(x.astype(np.float64), op=4)  # MAX
    np.testing.assert_allclose(mx, size)
    ints = ctl.allreduce(np.full((5,), rank + 1, dtype=np.int64), op=1)
    np.testing.assert_array_equal(ints, expected)
    return True


def body_allreduce_bf16ish(ctl, rank, size):
    x = np.full((8,), float(rank + 1), dtype=np.float16)
    out = ctl.allreduce(x, op=1)
    np.testing.assert_allclose(out.astype(np.float32),
                               sum(range(1, size + 1)))
    return True


def body_fusion(ctl, rank, size):
    # Multiple tensors in flight fuse into one negotiated response set.
    handles = {}
    for i in range(8):
        x = np.full((64,), float(rank + i), dtype=np.float32)
        handles[i] = ctl.allreduce(x, op=1, name=f"fuse.{i}")
    for i, out in handles.items():
        expected = sum(r + i for r in range(size))
        np.testing.assert_allclose(out, expected)
    return True


def body_allgather(ctl, rank, size):
    # Unequal first dims: rank r contributes r+1 rows valued r.
    x = np.full((rank + 1, 2), float(rank), dtype=np.float32)
    out = ctl.allgather(x)
    expected_rows = sum(r + 1 for r in range(size))
    assert out.shape == (expected_rows, 2)
    off = 0
    for r in range(size):
        np.testing.assert_allclose(out[off:off + r + 1], float(r))
        off += r + 1
    return True


def body_broadcast(ctl, rank, size):
    for root in (0, size - 1):
        x = np.full((7,), float(rank * 10), dtype=np.float32)
        out = ctl.broadcast(x, root_rank=root, name=f"bc.{root}")
        np.testing.assert_allclose(out, float(root * 10))
    return True


def body_alltoall(ctl, rank, size):
    # Rank r sends (d+1) rows valued r*size+d to rank d.
    rows = []
    splits = []
    for d in range(size):
        rows.append(np.full((d + 1, 2), float(rank * size + d),
                            dtype=np.float32))
        splits.append(d + 1)
    x = np.concatenate(rows, axis=0)
    out, recv_splits = ctl.alltoall(x, splits=splits)
    # Rank receives (rank+1) rows from each source valued src*size+rank.
    assert list(recv_splits) == [rank + 1] * size
    off = 0
    for src in range(size):
        np.testing.assert_allclose(out[off:off + rank + 1],
                                   float(src * size + rank))
        off += rank + 1
    return True


def body_barrier_join(ctl, rank, size):
    ctl.barrier()
    last = ctl.join()
    assert last == size - 1
    return True


def body_adasum(ctl, rank, size):
    # Identical vectors → adasum = the vector (parallel gradients average).
    x = np.array([3.0, -1.0, 2.0], dtype=np.float32)
    out = ctl.allreduce(x, op=2)  # ADASUM
    np.testing.assert_allclose(out, x, rtol=1e-5)
    return True


def body_shape_mismatch_error(ctl, rank, size):
    # Mismatched shapes across ranks must produce a coordinator error
    # (reference controller.cc:482-706 validation).
    x = np.zeros((rank + 1,), dtype=np.float32)  # different shape per rank
    try:
        ctl.allreduce(x, op=1, name="bad.shape")
    except Exception as e:  # noqa: BLE001
        assert "mismatched shape" in str(e)
        return True
    raise AssertionError("expected shape-mismatch error")


def body_join_with_pending(ctl, rank, size):
    # Ranks 0..size-2 allreduce; last rank joins instead. Joined rank
    # participates with zero proxies (reference operations.cc:1202-1226).
    if rank == size - 1:
        last = ctl.join()
        assert last == size - 1
        return True
    x = np.full((4,), float(rank + 1), dtype=np.float32)
    out = ctl.allreduce(x, op=1, name="with.join")
    np.testing.assert_allclose(out, sum(range(1, size)))
    last = ctl.join()
    assert last == size - 1
    return True


# --- tests -----------------------------------------------------------------

@pytest.mark.parametrize("body", [
    "body_allreduce", "body_allreduce_bf16ish", "body_fusion",
    "body_allgather", "body_broadcast", "body_alltoall",
    "body_barrier_join", "body_adasum", "body_shape_mismatch_error",
    "body_join_with_pending",
])
def test_native_4proc(body):
    _run(body, size=4)


def test_native_2proc_allreduce():
    _run("body_allreduce", size=2)


def test_ensure_built_always_runs_make(monkeypatch):
    """A library already on disk is not trusted: make runs every time (a
    no-op when it is current), so a stale binary is rebuilt from the
    tracked sources."""
    import subprocess

    from horovod_tpu.native import controller
    controller._ensure_built()
    assert os.path.exists(controller._lib_path())
    calls = []
    real_run = subprocess.run

    def spy(cmd, *args, **kwargs):
        calls.append(list(cmd))
        return real_run(cmd, *args, **kwargs)

    monkeypatch.setattr(controller.subprocess, "run", spy)
    assert controller._ensure_built() == controller._lib_path()
    assert [c[:2] for c in calls] == [["make", "-C"]]
