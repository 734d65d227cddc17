"""init / shutdown / topology queries.

Analog of the reference's ``HorovodBasics`` ctypes layer plus the C API it
wraps (horovod/common/basics.py:22-75 → operations.cc:703-915).  TPU-native
differences:

* There is no singleton background thread to spawn for the compiled path —
  XLA compiles collectives into the program. ``init()`` instead (a) resolves
  the chip/process topology, (b) builds the global device mesh, and (c)
  optionally attaches the native eager-path controller.
* Topology resolution honors the launcher env contract first
  (HOROVOD_RANK/SIZE/LOCAL_RANK/... — reference gloo_run.py:64-75) and falls
  back to JAX's own multi-controller topology.
"""

from __future__ import annotations

import os as _os
from typing import Optional, Sequence

from . import state as _state
from .config import Config, get_env as _cfg_get
from .exceptions import NotInitializedError
from .state import global_state, _env_int
from ..utils import logging as log

# <checkout>/.jax_cache: derived from the package's own location, never from
# tempfile, a pid or a time.
COMPILE_CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__)))), ".jax_cache")


def init(mesh=None,
         axes: Optional[Sequence[str]] = None,
         comm=None,
         use_controller: Optional[bool] = None) -> None:
    """Initialize the runtime.

    Args:
      mesh: optional pre-built ``jax.sharding.Mesh``. When None a 1-D mesh
        named ``("data",)`` over all global devices is created (ICI-ordered via
        ``mesh_utils.create_device_mesh``).
      axes: when ``mesh`` is None, optional axis names for a multi-dim mesh
        parsed from HVD_TPU_MESH_AXES (e.g. "data:8,model:4").
      comm: ignored; accepted for API compatibility with ``hvd.init(comm)``.
      use_controller: force-enable/disable the native eager-path controller.
        Default: enabled iff the launcher exported a rendezvous address.
    """
    del comm
    if global_state.initialized:
        return

    global_state.config = Config.from_env()

    # --- persistent compilation cache -------------------------------------
    # The directory is part of the cache key, so it must not move between
    # runs: JAX_COMPILATION_CACHE_DIR when the environment places it (JAX
    # reads that itself; nothing to do here), else one fixed directory in
    # the checkout.  Launched workers inherit the variable or resolve the
    # same path.  Setting the config does NOT initialize the accelerator
    # backend, so it is safe before the topology resolution below.
    if "JAX_COMPILATION_CACHE_DIR" not in _os.environ:
        import jax
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)

    # --- topology ---------------------------------------------------------
    # Launcher-spawned workers MUST NOT touch the JAX backend here: N
    # workers initializing the accelerator platform on one host contend for
    # the same chip(s) and block forever (the reference's init never touches
    # a device either — gloo_run workers get topology purely from env,
    # gloo_run.py:64-75).  JAX is consulted only in the single-process /
    # jax.distributed fallback, and the mesh is built lazily on first use.

    # Elastic workers fetch their (re-)assignment from the rendezvous KV
    # each init — the world may have changed since the last round.
    elastic_assignment = None
    if _os.environ.get("HVD_TPU_ELASTIC_SLOT"):
        from ..runner.worker import fetch_assignment
        elastic_assignment = fetch_assignment(
            min_round=global_state.elastic_round + 1)
        global_state.elastic_round = elastic_assignment["round"]
        global_state.rank = elastic_assignment["rank"]
        global_state.size = elastic_assignment["size"]
        global_state.local_rank = elastic_assignment["local_rank"]
        global_state.local_size = elastic_assignment["local_size"]
        global_state.cross_rank = elastic_assignment["cross_rank"]
        global_state.cross_size = elastic_assignment["cross_size"]
        # Elastic device plane: the driver publishes a fresh jax
        # coordinator per round; every worker (survivor or respawn)
        # rebuilds its jax.distributed world to the round's topology so
        # HBM-resident eager tensors keep riding the negotiated device
        # plane across failures (SURVEY §7.3 "Elastic on TPU").
        jax_addr = elastic_assignment.get("jax_coord_addr")
        if jax_addr:
            from ..runner.bootstrap import rebuild_jax_world
            rebuild_jax_world(jax_addr, global_state.size,
                              global_state.rank)
        else:
            # The round declares no jax world (e.g. the host set stopped
            # being all-local): a survivor must not keep a stale one —
            # its process count is wrong and its error poller dies with
            # old peers.  No-op when no world exists.
            from ..runner.bootstrap import teardown_jax_world
            teardown_jax_world()

    env_rank = _env_int("RANK")
    env_size = _env_int("SIZE")
    if elastic_assignment is not None:
        # One process per slot: process topology == slot topology.
        global_state.process_rank = global_state.rank
        global_state.process_count = global_state.size
    elif env_rank is not None and env_size is not None:
        # Launcher-provided chip topology (one launched process per slot).
        global_state.rank = env_rank
        global_state.size = env_size
        global_state.local_rank = _env_int("LOCAL_RANK") or 0
        global_state.local_size = _env_int("LOCAL_SIZE") or 1
        global_state.cross_rank = _env_int("CROSS_RANK") or 0
        global_state.cross_size = _env_int("CROSS_SIZE") or 1
        global_state.process_rank = env_rank
        global_state.process_count = env_size
        # If a spanning jax.distributed world already exists, its process
        # ids must match the env-provided ranks: eager device-plane
        # collectives place shards in JAX process-index order and read
        # them back in rank order (broadcast root, gather concatenation),
        # so a permuted world silently misroutes data.  Fail fast here —
        # every rank passes through init(), making this the one
        # synchronous point where the misconfiguration is visible before
        # any collective can hang aligned peers.  The distributed state is
        # read directly (NOT jax.process_index(), which initializes the
        # XLA backend — forbidden here per the note above).
        try:
            from jax._src import distributed as _jd
            _ds = _jd.global_state
            jax_pid = _ds.process_id if _ds.client is not None else None
            jax_np = _ds.num_processes
        except Exception:
            jax_pid = jax_np = None
        if jax_pid is not None and jax_np == env_size \
                and jax_pid != env_rank:
            raise RuntimeError(
                f"horovod_tpu.init(): jax.distributed process_id "
                f"{jax_pid} != rank {env_rank} from the environment. "
                "Initialize jax.distributed with process_id == rank "
                "(the launcher does this), or unset the rank env vars "
                "to derive ranks from JAX.")
    else:
        # Derive from JAX: rank = chip-rank of this process's first device.
        import jax
        global_state.process_rank = jax.process_index()
        global_state.process_count = jax.process_count()
        local_devices = jax.local_device_count()
        total_devices = jax.device_count()
        global_state.rank = global_state.process_rank * local_devices
        global_state.size = total_devices
        global_state.local_rank = 0
        global_state.local_size = local_devices
        global_state.cross_rank = global_state.process_rank
        global_state.cross_size = global_state.process_count

    # --- mesh (lazy: built on first mesh() access) ------------------------
    if mesh is not None:
        global_state.mesh = mesh
    else:
        global_state.mesh = None
        global_state.mesh_axes_hint = tuple(axes) if axes else None

    # --- eager-path controller -------------------------------------------
    if use_controller is None:
        use_controller = bool(_cfg_get("CONTROLLER_ADDR")) or \
            elastic_assignment is not None
    if use_controller:
        from ..native import runtime as native_runtime
        if elastic_assignment is not None:
            global_state.controller = native_runtime.attach(
                rank=elastic_assignment["rank"],
                size=elastic_assignment["size"],
                coord_addr=elastic_assignment["controller_addr"])
        else:
            global_state.controller = native_runtime.attach()

    # --- per-payload collective schedule dispatch -------------------------
    # Topology probe + dispatch-table install (ops/dispatch.py): a short
    # seeded probe (only on topologies where hierarchical schedules can
    # actually run — 1 < local_size < world dividing evenly) builds the
    # per-(op kind, payload bucket) table every subsequent collective is
    # stamped from.  Probe collectives ride the controller like any
    # other op, so a transport failure surfaces exactly like one
    # (elastic jobs: HorovodInternalError -> reset); the decision inputs
    # are env-derived and rank-consistent, so every rank enqueues the
    # identical probe sequence.
    if global_state.controller is not None:
        from ..ops import dispatch as _dispatch
        _dispatch.bootstrap(global_state.controller, global_state.config,
                            global_state.local_size)

    # --- metrics ----------------------------------------------------------
    # Topology gauges + (opt-in) the Prometheus scrape endpoint.  serve()
    # is idempotent, so elastic re-inits keep the one server alive across
    # rounds instead of rebinding the port; the daemon thread dies with
    # the process (shutdown() deliberately leaves it serving — a reset
    # mid-round must not blind the scraper).
    from ..metrics.registry import registry as _metrics_registry
    _mreg = _metrics_registry()
    _mreg.counter("hvd_init_total", "Runtime initializations").inc()
    _mreg.gauge("hvd_rank", "Chip-level rank of this process").set(
        global_state.rank)
    _mreg.gauge("hvd_size", "Total chips in the communicator").set(
        global_state.size)
    _mreg.gauge("hvd_elastic_round", "Current elastic rendezvous round "
                "(-1 outside elastic jobs)").set(
        global_state.elastic_round)
    if global_state.config.metrics_port:
        # Rank-gate the env-configured port: with several worker
        # processes per host (LOCAL_SIZE > 1) only local rank 0 can own
        # it.  Telemetry must never kill training — a bind failure
        # (port held by a dying predecessor after an elastic respawn,
        # another job, a stale server) degrades to a warning.
        if global_state.local_rank == 0:
            try:
                from ..metrics import serve as _metrics_serve
                _metrics_serve(port=global_state.config.metrics_port)
            except OSError as e:
                log.warning(
                    "metrics: cannot serve on port %d (%s); continuing "
                    "without a scrape endpoint",
                    global_state.config.metrics_port, e)

    # --- flight recorder / hang diagnosis ---------------------------------
    # The recorder itself is always armed; what init() adds is the
    # dump/triage plumbing: identity for dumps, the SIGUSR1 trigger, the
    # coordinator clock-offset estimate (piggybacked on the rendezvous
    # channel every worker already polls), the per-rank debug endpoint +
    # its KV-published address, and — on the coordinator rank of
    # launcher-run jobs — the stall→hang-report escalation watchdog.
    if not global_state.config.flight_disable:
        from .. import debug as _debug
        _debug.flight.set_identity(rank=global_state.rank,
                                   world=global_state.size)
        _debug.flight.record("init", None, rank=global_state.rank,
                             size=global_state.size,
                             round=global_state.elastic_round,
                             wire=global_state.config.compression)
        _debug.install_signal_handler()
        # The pause sentinel (debug/pause.py): collections and a heartbeat
        # on the profiler's clock, in the registry and, when the process
        # stops for long, in the recorder and on the log.
        _debug.pause.arm()
        _rdv = _os.environ.get("HVD_TPU_RENDEZVOUS_ADDR")
        if _rdv:
            try:
                _debug.estimate_clock_offset(_rdv, samples=3)
            except Exception as e:  # noqa: BLE001 — telemetry never kills
                log.debug("flight: clock-offset estimate failed: %r", e)
        if global_state.controller is not None:
            if _rdv:
                try:
                    _debug.serve_and_publish(
                        rank=global_state.controller.rank(), rdv_addr=_rdv,
                        port=global_state.config.flight_port)
                except OSError as e:
                    log.warning("flight: cannot serve debug endpoint "
                                "(%s); continuing without one", e)
            if global_state.config.flight_escalate and \
                    global_state.controller.rank() == 0:
                _debug.start_stall_watchdog(
                    global_state.controller,
                    report_dir=global_state.config.flight_dir,
                    rdv_addr=_rdv)

    # --- peer-to-peer hot recovery ----------------------------------------
    # Multi-process jobs with a rendezvous KV publish the replica
    # endpoint so buddies can push committed shards across processes
    # (horovod_tpu/recovery/transport.py).  Single-controller jobs need
    # none of this — every rank's store is this process's store.  Like
    # the debug endpoint, serving is idempotent across elastic rounds
    # and a bind failure degrades (the peer tier falls back to disk).
    if global_state.config.recovery and global_state.controller is not None:
        _rdv = _os.environ.get("HVD_TPU_RENDEZVOUS_ADDR")
        if _rdv:
            try:
                from .. import recovery as _recovery
                _recovery.transport.serve_and_publish(
                    rank=global_state.controller.rank(), rdv_addr=_rdv)
            except OSError as e:
                log.warning("recovery: cannot serve replica endpoint "
                            "(%s); peer tier degraded to disk", e)

    global_state.elastic_enabled = global_state.config.elastic
    global_state.initialized = True

    # --- host-sharded telemetry plane -------------------------------------
    # Tree mode: local rank 0 hosts the per-host observer (the host's
    # one serving slot, same gate as the metrics port above) that merges
    # its ranks' snapshots and runs the O(hosts) digest exchange.  Like
    # every telemetry server, a failure to start degrades to a warning —
    # the sync path then falls back to local-only digests, named.
    if global_state.config.metrics_tree and global_state.local_rank == 0:
        try:
            from ..metrics.observer import start_host_observer
            start_host_observer()
        except Exception as e:  # noqa: BLE001 — telemetry never kills
            log.warning("metrics tree: cannot start host observer (%r); "
                        "sync degrades to local-only digests", e)

    log.debug(
        "initialized: rank=%d size=%d local=%d/%d cross=%d/%d mesh=%s",
        global_state.rank, global_state.size, global_state.local_rank,
        global_state.local_size, global_state.cross_rank,
        global_state.cross_size, global_state.mesh or "<lazy>")


def _build_default_mesh(axes: Optional[Sequence[str]] = None):
    import jax
    from jax.experimental import mesh_utils

    spec = global_state.config.mesh_axes
    if axes is None and spec:
        # "data:8,model:4" → axes=("data","model"), shape=(8,4)
        names, dims = [], []
        for part in spec.split(","):
            name, _, dim = part.partition(":")
            names.append(name.strip())
            dims.append(int(dim))
        devices = mesh_utils.create_device_mesh(tuple(dims))
        return jax.sharding.Mesh(devices, tuple(names))
    from ..parallel.mesh import ici_device_array
    devices = ici_device_array((jax.device_count(),), jax.devices())
    return jax.sharding.Mesh(devices, (_state.DATA_AXIS,))


def shutdown() -> None:
    """Tear down the runtime (reference: horovod_shutdown, operations.cc)."""
    # Stop the hang watchdog BEFORE the controller it polls goes away
    # (its thread is named hvd-tpu-*, so a leak fails the test suite's
    # stray-thread check), and the pause sentinel's heartbeat with it.
    # The debug HTTP endpoint, like the metrics server, deliberately stays
    # up across elastic resets.
    try:
        from .. import debug as _debug
        _debug.stop_stall_watchdog()
        _debug.pause.disarm()
        _debug.flight.record("shutdown", None)
    except Exception:  # noqa: BLE001 - best-effort teardown
        pass
    # The host observer's exchange thread is also hvd-tpu-* named, and
    # unlike the metrics server its identity (cross_rank, local ranks)
    # is world-shaped: a re-init after an elastic renumber must build a
    # fresh one, not inherit a stale rank map that names departed ranks
    # "missing" forever.
    try:
        from ..metrics.observer import stop_host_observer
        stop_host_observer()
    except Exception:  # noqa: BLE001 - best-effort teardown
        pass
    try:
        # Drop the dispatch-table mirror: a fresh init() re-probes (the
        # topology may have changed), and annotation must not quote a
        # dead world's table in between.
        from ..ops import dispatch as _dispatch
        _dispatch.reset()
    except Exception:  # noqa: BLE001 - best-effort teardown
        pass
    if global_state.controller is not None:
        try:
            global_state.controller.shutdown()
        except Exception:  # noqa: BLE001 - best-effort teardown
            pass
    global_state.reset()


def is_initialized() -> bool:
    return global_state.initialized


def _check_init():
    if not global_state.initialized:
        raise NotInitializedError()


def rank() -> int:
    """Global (chip-level) rank of this process's first device."""
    _check_init()
    return global_state.rank


def size() -> int:
    """Total number of chips across all processes."""
    _check_init()
    return global_state.size


def local_rank() -> int:
    _check_init()
    return global_state.local_rank


def local_size() -> int:
    _check_init()
    return global_state.local_size


def cross_rank() -> int:
    """Rank among hosts (one per node) — reference common.h:119-123."""
    _check_init()
    return global_state.cross_rank


def cross_size() -> int:
    _check_init()
    return global_state.cross_size


def process_rank() -> int:
    _check_init()
    return global_state.process_rank


def process_count() -> int:
    _check_init()
    return global_state.process_count


def mesh():
    """The global device mesh.  Built lazily on first access so eager-only
    workers (launcher-spawned, native TCP data plane) never initialize the
    JAX backend at all."""
    _check_init()
    if global_state.mesh is None:
        global_state.mesh = _build_default_mesh(global_state.mesh_axes_hint)
    return global_state.mesh


def is_homogeneous() -> bool:
    """True when every node has the same number of chips."""
    _check_init()
    return global_state.size % max(global_state.cross_size, 1) == 0


def mpi_threads_supported() -> bool:
    """API-compat shim; there is no MPI in the TPU runtime."""
    return False


# Build-capability queries (reference common/util.py:137-220): scripts
# branch on these to pick a controller/ops stack.  On TPU the answers are
# static: the TCP controller is the gloo-analog control plane; there is no
# MPI/NCCL/CUDA/ROCm/oneCCL/DDL in the loop.

def mpi_built(verbose: bool = False) -> bool:
    return False


def gloo_built(verbose: bool = False) -> bool:
    return True  # the TCP controller + rendezvous fills the Gloo role


def nccl_built(verbose: bool = False) -> bool:
    return False


def ddl_built(verbose: bool = False) -> bool:
    return False


def ccl_built(verbose: bool = False) -> bool:
    return False


def cuda_built(verbose: bool = False) -> bool:
    return False


def rocm_built(verbose: bool = False) -> bool:
    return False


def start_timeline(filename: str, mark_cycles: bool = False) -> None:
    """Start Chrome-trace timeline recording at runtime (reference
    horovod_start_timeline, operations.cc:740-769).  Requires the native
    controller (launcher-run jobs); a warning is logged otherwise."""
    del mark_cycles  # cycle markers controlled by env knob at init
    _check_init()
    if global_state.controller is None:
        log.warning("start_timeline: no native runtime attached; timeline "
                    "is recorded only for launcher-run jobs")
        return
    global_state.controller.start_timeline(filename)


def stop_timeline() -> None:
    _check_init()
    if global_state.controller is not None:
        global_state.controller.stop_timeline()
