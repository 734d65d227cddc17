"""Launcher package + the in-process ``run()`` API.

``horovod_tpu.runner.run(fn, ...)`` is the programmatic launcher the
reference exposes as ``horovod.run`` (runner/__init__.py:92): it spawns
``np`` local worker processes, establishes the same env contract as the
``hvdrun`` CLI, executes ``fn`` in each as a rank, and returns the results
ordered by rank.
"""

from __future__ import annotations

import multiprocessing as _mp
import os
import socket
from typing import Any, Callable, List, Optional

from .hosts import HostInfo, get_host_assignments, slot_env


def _worker_main(fn, args, kwargs, env, q, rank):
    os.environ.update(env)
    # Unpickling fn already imported its module — and with it jax, which
    # reads JAX_PLATFORMS once at import — so the env above is too late to
    # pick the platform; the in-process config update is not.
    from .bootstrap import apply_platform
    apply_platform()
    try:
        q.put((rank, True, fn(*args, **kwargs)))
    except Exception as e:  # surface the failure to the parent
        q.put((rank, False, repr(e)))


def run(fn: Callable, args: tuple = (), kwargs: Optional[dict] = None,
        np: int = 1, hosts: Optional[str] = None,
        use_mpi: Optional[bool] = None,
        use_gloo: Optional[bool] = None,
        controller_port: int = 28500,
        env: Optional[dict] = None,
        work_dir: Optional[str] = None,
        worker_platform: str = "cpu") -> List[Any]:
    """Run ``fn`` as ``np`` distributed ranks and return the list of
    per-rank results (rank order).

    Without ``hosts``: ``np`` local processes (multiprocessing spawn).
    With ``hosts`` ("h1:2,h2:2" like hvdrun -H): ``fn`` is cloudpickled
    into ``work_dir`` (must be visible on every host — defaults to a
    local temp dir, correct for localhost slot lists) and executed
    through the same launcher/ssh machinery as ``hvdrun``, the reference's
    per-host fn semantics (runner/__init__.py:92).

    ``use_mpi``/``use_gloo`` are accepted for reference signature
    compatibility; the controller here is always the TCP (gloo-analog)
    one — there is no MPI dependency on TPU VMs.
    """
    del use_mpi, use_gloo
    kwargs = kwargs or {}
    if hosts is not None:
        return _run_on_hosts(fn, args, kwargs, np, hosts, controller_port,
                             env, work_dir, worker_platform)
    hostname = socket.gethostname()
    slots = get_host_assignments([HostInfo(hostname, np)], np)
    controller_addr = f"{hostname}:{controller_port}"

    ctx = _mp.get_context("spawn")
    q = ctx.Queue()
    procs = []
    for slot in slots:
        wenv = slot_env(slot, controller_addr)
        # In-process runs stay on CPU: worker processes must not race for
        # the single TPU chip the parent may hold.
        wenv.setdefault("HVD_TPU_WORKER_PLATFORM", "cpu")
        wenv.setdefault("HVD_TPU_WORKER_CPU_DEVICES", "1")
        wenv.update(env or {})
        p = ctx.Process(target=_worker_main,
                        args=(fn, args, kwargs, wenv, q, slot.rank))
        p.start()
        procs.append(p)

    import queue as _queue
    results: dict = {}
    try:
        while len(results) < len(procs):
            try:
                rank, ok, value = q.get(timeout=1.0)
            except _queue.Empty:
                # Any worker that exited before reporting — crash, spawn
                # re-import failure (stdin/REPL callers), sys.exit(0), or
                # an unpicklable return value — would otherwise hang this
                # loop forever.  Drain stragglers already in the queue
                # before declaring the run dead.
                if not q.empty():
                    continue
                lost = [(r, p.exitcode) for r, p in enumerate(procs)
                        if not p.is_alive() and r not in results]
                if lost:
                    raise RuntimeError(
                        f"worker(s) {lost} (rank, exitcode) exited before "
                        "reporting a result. Note: run(fn) uses spawn, so "
                        "it must be called from an importable module (not "
                        "stdin/REPL), fn must be module-level, and its "
                        "return value picklable.")
                continue
            if not ok:
                raise RuntimeError(f"rank {rank} failed: {value}")
            results[rank] = value
    finally:
        for p in procs:
            p.join(timeout=30)
            if p.is_alive():
                p.terminate()
    return [results[r] for r in sorted(results)]


def _run_on_hosts(fn, args, kwargs, np_, hosts, controller_port, env,
                  work_dir, worker_platform):
    """Spawn fn-workers across a host list through the launcher machinery
    (rendezvous + slot env + ssh/local exec), collecting per-rank result
    pickles from the shared work dir.  ``worker_platform`` defaults to
    "cpu": the calling process may already hold the local accelerator
    (the same guard the local multiprocessing path applies); pass "auto"
    to let workers partition/inherit chips."""
    import shutil
    import sys
    import tempfile

    from . import exec as exec_mod
    from .fnpickle import collect_results, dump_payload
    from .hosts import parse_hosts
    from .launch import _controller_addr, start_rendezvous

    host_infos = parse_hosts(hosts)
    slots = get_host_assignments(host_infos, np_)
    controller_addr = _controller_addr(host_infos, controller_port)

    own_tmp = work_dir is None
    work_dir = work_dir or tempfile.mkdtemp(prefix="hvd_run_")
    payload_path, results_dir = dump_payload(work_dir, fn, args, kwargs)

    rendezvous, extra_env = start_rendezvous(host_infos)
    extra_env.update(env or {})
    command = [sys.executable, "-m", "horovod_tpu.runner.fn_exec",
               payload_path, results_dir]
    try:
        workers = exec_mod.launch_workers(slots, command, controller_addr,
                                          extra_env=extra_env,
                                          platform_policy=worker_platform)
        rc = exec_mod.wait_all(workers)
        if rc != 0:
            raise RuntimeError(f"run(fn) workers failed (exit {rc})")
        results = collect_results(results_dir)
        if len(results) != len(slots):
            raise RuntimeError(
                f"collected {len(results)} results for {len(slots)} ranks "
                f"(work_dir {work_dir} must be visible on every host)")
        return results
    finally:
        rendezvous.stop()
        if own_tmp:
            shutil.rmtree(work_dir, ignore_errors=True)
