"""Worker bootstrap: apply the per-slot accelerator platform *before* the
user script runs, then exec it in-process.

Why this exists: the launcher partitions a host's TPU chips among its worker
processes via env (``TPU_VISIBLE_DEVICES`` et al. — the TPU analog of the
reference's per-slot env contract, gloo_run.py:64-75), and two things must
then happen inside the worker before the *user's* first jax call: joining
the launcher-declared ``jax.distributed`` world, and — for CPU workers —
pinning the platform.  The pin is an in-process ``jax.config.update``
because jax reads ``JAX_PLATFORMS`` once, at import: ``runner.run()``'s
spawned children import the user's module (and with it jax) before their
slot env is applied, so only the config update reaches them, and the
launcher uses the same mechanism for both paths.  So the launcher rewrites
``python train.py ...`` into ``python -m horovod_tpu.runner.bootstrap --
train.py ...`` whenever either is needed.

Env contract (set by the launcher, see runner/launch.py):
  HVD_TPU_WORKER_PLATFORM      "cpu" | "tpu" | unset (inherit)
  HVD_TPU_WORKER_CPU_DEVICES   device count for the cpu platform (default 1)
"""

from __future__ import annotations

import os
import runpy
import sys


def apply_platform() -> None:
    """Pin jax to the slot's platform before any backend init.  A no-op
    when jax is absent (non-JAX workers) or the platform is inherited;
    fatal when the pin cannot take effect — a worker meant for the CPU
    that keeps the default platform would take every chip on the host."""
    plat = os.environ.get("HVD_TPU_WORKER_PLATFORM")
    if not plat or plat == "inherit":
        return
    try:
        import jax
    except ImportError:
        return
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        # jax.config.update("jax_platforms") after backend init is
        # silently ignored, so look at what the backend already is.
        if jax.default_backend() == plat:
            return
        print(f"[hvd_tpu bootstrap] cannot pin worker to {plat!r}: a jax "
              f"backend ({jax.default_backend()}) was initialized before "
              "the launcher's platform could be applied", file=sys.stderr)
        raise SystemExit(1)
    jax.config.update("jax_platforms", plat)
    if plat == "cpu":
        n = int(os.environ.get("HVD_TPU_WORKER_CPU_DEVICES", "1"))
        jax.config.update("jax_num_cpu_devices", n)


def apply_jax_distributed() -> None:
    """Join the launcher-declared JAX world (chip-partitioned workers):
    compiled multi-process programs and the eager on-device ICI plane both
    need jax.distributed before backend init."""
    addr = os.environ.get("HVD_TPU_JAX_COORD_ADDR")
    if not addr:
        return
    try:
        import jax
        jax.distributed.initialize(
            coordinator_address=addr,
            num_processes=int(os.environ["HVD_TPU_JAX_NUM_PROCS"]),
            process_id=int(os.environ["HVD_TPU_JAX_PROC_ID"]))
    except Exception as e:
        # A launcher-declared world that fails to form must be fatal: a
        # worker silently falling back to single-process would reduce over
        # the wrong world while its peers hang waiting for it.
        print(f"[hvd_tpu bootstrap] jax.distributed.initialize failed: {e}",
              file=sys.stderr)
        raise SystemExit(1)


# True when the current jax world's client was built by _raw_init_world
# (shutdown_on_destruction=False: dropping the client is silent).
_RAW_WORLD = False


def _raw_init_world(addr: str, num_processes: int, process_id: int,
                    timeout: int = 60) -> bool:
    """Build the jax distributed client/service directly with ELASTIC
    semantics the public initialize() does not expose:
    ``shutdown_on_destruction=False`` (a worker whose coordinator died
    must exit silently, not LOG(FATAL) from the client destructor's
    ShutdownTask RPC) and a no-op missed-heartbeat callback (heartbeat
    loss is the elastic NORMAL case, surfaced via collective errors and
    handled by restore + re-init — not grounds for process suicide).
    Returns False when the private jaxlib API has drifted (caller falls
    back to the public path)."""
    global _RAW_WORLD
    from jax._src import distributed as _jd
    try:
        from jaxlib import _jax as _jaxlib
        # Client first: constructing the service binds the coordinator
        # port, and leaking a bound service on client-construction API
        # drift would make the public-API fallback fail with
        # address-in-use on rank 0.
        client = _jaxlib.get_distributed_runtime_client(
            addr, process_id, init_timeout=timeout,
            use_compression=True,
            shutdown_on_destruction=False, recoverable=True)
        service = None
        if process_id == 0:
            bind = "[::]:" + addr.rsplit(":", 1)[1]
            service = _jaxlib.get_distributed_runtime_service(
                bind, num_processes)
    except (ImportError, AttributeError, TypeError):
        return False  # private API drift: public fallback
    # Connect BEFORE publishing into jax's global state: a failed connect
    # (peer missing, port taken) must not leave a half-initialized world
    # behind — dropping the locals unbinds the service and silently
    # drops the never-connected client (shutdown_on_destruction=False).
    client.connect()  # real errors propagate to the caller
    st = _jd.global_state
    st.coordinator_address = addr
    st.process_id = process_id
    st.num_processes = num_processes
    st.service = service
    st.client = client
    _RAW_WORLD = True
    return True


def teardown_jax_world() -> None:
    """Tear down the current jax.distributed world (ordered
    client/service teardown + backend and cache clears).  Safe no-op
    when no world exists.  Used by the elastic init path both before a
    rebuild and when a round no longer declares a jax world (e.g. the
    host set stopped being all-local): survivors must NOT keep a stale
    world — its process count is wrong and its error-poll thread would
    LOG(FATAL) when old peers die."""
    global _RAW_WORLD
    import jax
    from jax._src import distributed as _jd
    st = _jd.global_state
    if st.client is not None:
        if _RAW_WORLD:
            # Ordered teardown.  The client's error-poll thread
            # LOG(FATAL)s the process the moment its gRPC channel to the
            # coordinator breaks, so: (1) every process explicitly
            # disconnects its client FIRST, while the old service is
            # still up (clean ShutdownTask; stops the poll thread); a
            # failure here means the old coordinator is already dead and
            # this process is doomed anyway — swallow and hope the reset
            # outruns the poll thread.  (2) The old coordinator delays
            # its service teardown so peers' disconnects land before the
            # service starts cancelling calls.  Coordinator death itself
            # is NOT survivable in-process (the poll fatal fires within
            # ~1s); the driver's cascade leniency respawns the round.
            try:
                st.client.shutdown()
            except Exception as e:  # noqa: BLE001 — coordinator gone
                print(f"[hvd_tpu bootstrap] old jax client shutdown: {e}",
                      file=sys.stderr)
            st.client = None
            if st.service is not None:
                import time as _time
                _time.sleep(1.0)  # let peers' ShutdownTask RPCs land
                st.service.shutdown()
                st.service = None
            st.coordinator_address = None
            st.process_id = None
            st.num_processes = None
            _RAW_WORLD = False
        else:
            # Public-API world: best effort — the shutdown RPC can
            # LOG(FATAL) if the coordinator is unreachable.
            try:
                jax.distributed.shutdown()
            except Exception as e:  # noqa: BLE001 — half-dead world
                print(f"[hvd_tpu bootstrap] old jax world shutdown: {e}",
                      file=sys.stderr)
        try:
            from jax._src import xla_bridge as _xb
            _xb._clear_backends()
        except Exception as e:
            raise RuntimeError(
                "cannot rebuild the jax backend for the new elastic "
                f"round (jax internals changed?): {e}") from e
        jax.clear_caches()
        from ..ops import eager
        eager._cached_process_mesh.cache_clear()
        eager._jitted_global.cache_clear()
        eager._jitted_local.cache_clear()


def rebuild_jax_world(addr: str, num_processes: int,
                      process_id: int) -> None:
    """(Re)build this process's jax.distributed world for an elastic round
    — the SURVEY §7.3 hard part: the reference's cheap ``shutdown();
    init()`` reset becomes a backend re-initialization here.

    Fresh processes just initialize.  Survivors of a previous round run
    ``teardown_jax_world`` first (ordered client/service teardown; the
    device list and process count are baked into the old backend, and
    the eager plane's mesh/jit caches bake in the old mesh).  CPU/TPU
    both go through the same path; on TPU the backend rebuild is the
    expensive step the reference never pays (libtpu re-init)."""
    import jax
    jax.config.update("jax_enable_recoverability", True)
    teardown_jax_world()
    if not _raw_init_world(addr, num_processes, process_id):
        jax.distributed.initialize(
            coordinator_address=addr, num_processes=num_processes,
            process_id=process_id, initialization_timeout=60)


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "--":
        argv = argv[1:]
    apply_platform()
    apply_jax_distributed()
    if not argv:
        return 0
    if argv[0] == "-m":
        if len(argv) < 2:
            print("bootstrap: -m requires a module name", file=sys.stderr)
            return 2
        sys.argv = argv[1:]
        runpy.run_module(argv[1], run_name="__main__", alter_sys=True)
    elif argv[0] == "-c":
        if len(argv) < 2:
            print("bootstrap: -c requires a command", file=sys.stderr)
            return 2
        sys.argv = ["-c"] + argv[2:]
        exec(compile(argv[1], "<string>", "exec"),  # noqa: S102
             {"__name__": "__main__", "__builtins__": __builtins__})
    else:
        sys.argv = argv
        runpy.run_path(argv[0], run_name="__main__")
    return 0


if __name__ == "__main__":
    sys.exit(main())
