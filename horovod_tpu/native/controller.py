"""ctypes bindings to the native runtime (libhvdtpu_core.so).

The analog of the reference's ``HorovodBasics`` ctypes layer
(common/basics.py:22-75) plus the per-op enqueue wrappers the torch bridge
generates (torch/mpi_ops_v2.cc).  All eager ops are synchronous at this
level; async handles are layered above in ops/collective.py.
"""

from __future__ import annotations

import contextlib
import ctypes
import fcntl
import os
import subprocess
import threading
import time
from typing import Optional, Sequence, Tuple

import numpy as np

from ..core import config as _config
from ..utils import logging as log

_DTYPE_CODES = {
    np.dtype(np.uint8): 0,
    np.dtype(np.int8): 1,
    np.dtype(np.int32): 2,
    np.dtype(np.int64): 3,
    np.dtype(np.float16): 4,
    np.dtype(np.float32): 5,
    np.dtype(np.float64): 6,
    np.dtype(np.bool_): 7,
}
try:  # bfloat16 — the TPU-native wire format (C++ kernels: code 8)
    import ml_dtypes as _ml_dtypes
    _DTYPE_CODES[np.dtype(_ml_dtypes.bfloat16)] = 8
except ImportError:
    pass


_CODE_TO_DTYPE = {v: k for k, v in _DTYPE_CODES.items()}

# Device-executor callback signature (runtime.h DeviceExecutorFn): executes
# one negotiated, possibly-fused device-resident Response on the background
# thread, in coordinator response order.  Two-phase (runtime.h
# DeviceExecPhase): PREPARE(0) stages inputs + runs every locally-
# detectable check, EXECUTE(1) dispatches the SPMD collective, ABORT(2)
# drops staged state when a peer's prepare failed.
_PHASE_PREPARE, _PHASE_EXECUTE, _PHASE_ABORT = 0, 1, 2
_DEVICE_EXEC_FN = ctypes.CFUNCTYPE(
    ctypes.c_int,                        # return: 0 ok
    ctypes.c_int,                        # phase (DeviceExecPhase)
    ctypes.c_int, ctypes.c_int,          # request_type, n
    ctypes.POINTER(ctypes.c_char_p),     # names
    ctypes.POINTER(ctypes.c_int64),      # sizes (element counts)
    ctypes.c_int, ctypes.c_int,          # dtype code, reduce op
    ctypes.c_int,                        # root_rank
    ctypes.c_double, ctypes.c_double,    # prescale, postscale
    ctypes.POINTER(ctypes.c_char), ctypes.c_int)  # err buf, err cap


def _lib_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "libhvdtpu_core.so")


def _ensure_built() -> str:
    """Bring the library up to date with its sources and return its path.

    ``make`` always runs — a no-op when the library is current, a rebuild
    when a stale binary was left on disk — under an flock, so workers that
    start together on a clean tree build once instead of racing g++ onto
    one output.  An installed wheel ships the binary without ``src/``;
    there is nothing to rebuild from and the binary is used as shipped."""
    path = _lib_path()
    src = os.path.join(os.path.dirname(path), "src")
    if os.path.exists(os.path.join(src, "Makefile")):
        with open(path + ".lock", "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            before = os.path.getmtime(path) if os.path.exists(path) else None
            t0 = time.monotonic()
            subprocess.run(
                ["make", "-C", src, f"-j{os.cpu_count() or 1}"],
                check=True, capture_output=True)
            if os.path.getmtime(path) != before:
                log.info("native runtime built in %.1fs: %s",
                         time.monotonic() - t0, path)
    return path


_lib = None


def load_library():
    global _lib
    if _lib is not None:
        return _lib
    lib = ctypes.CDLL(_ensure_built())
    lib.hvd_native_init.restype = ctypes.c_int
    lib.hvd_native_init.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_char_p, ctypes.c_int64,
        ctypes.c_double, ctypes.c_double, ctypes.c_double, ctypes.c_char_p,
        ctypes.c_int64]
    lib.hvd_native_rank.restype = ctypes.c_int
    lib.hvd_native_size.restype = ctypes.c_int
    lib.hvd_native_initialized.restype = ctypes.c_int
    for fn in ("hvd_native_allreduce", "hvd_native_allgather",
               "hvd_native_broadcast", "hvd_native_alltoall"):
        getattr(lib, fn).restype = ctypes.c_int64
    lib.hvd_native_allreduce.argtypes = [
        ctypes.c_char_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_int,
        ctypes.c_double, ctypes.c_double]
    lib.hvd_native_allgather.argtypes = [
        ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int]
    lib.hvd_native_broadcast.argtypes = [
        ctypes.c_char_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_int]
    lib.hvd_native_alltoall.argtypes = [
        ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int]
    lib.hvd_native_poll.restype = ctypes.c_int
    lib.hvd_native_poll.argtypes = [ctypes.c_int64]
    lib.hvd_native_wait.restype = ctypes.c_int
    lib.hvd_native_wait.argtypes = [ctypes.c_int64]
    lib.hvd_native_result_bytes.restype = ctypes.c_int64
    lib.hvd_native_result_bytes.argtypes = [ctypes.c_int64]
    lib.hvd_native_result_dims.restype = ctypes.c_int
    lib.hvd_native_result_dims.argtypes = [
        ctypes.c_int64, ctypes.POINTER(ctypes.c_int64), ctypes.c_int]
    lib.hvd_native_result_copy.restype = ctypes.c_int
    lib.hvd_native_result_copy.argtypes = [
        ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64]
    lib.hvd_native_release.argtypes = [ctypes.c_int64]
    lib.hvd_native_join.restype = ctypes.c_int
    lib.hvd_native_barrier.restype = ctypes.c_int
    lib.hvd_native_last_error.restype = ctypes.c_char_p
    lib.hvd_native_stalled_json.restype = ctypes.c_int
    lib.hvd_native_stalled_json.argtypes = [
        ctypes.POINTER(ctypes.c_char), ctypes.c_int]
    lib.hvd_native_start_timeline.argtypes = [ctypes.c_char_p]
    lib.hvd_native_set_params.argtypes = [ctypes.c_int64, ctypes.c_double]
    lib.hvd_native_set_tuned_toggles.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_int]
    lib.hvd_native_set_schedule_table.argtypes = [
        ctypes.c_int, ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int]
    lib.hvd_native_set_cache_enabled.argtypes = [ctypes.c_int]
    lib.hvd_native_set_wire_compression.argtypes = [ctypes.c_int]
    lib.hvd_native_wire_compression.restype = ctypes.c_int
    lib.hvd_native_set_topology.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_int]
    lib.hvd_native_last_allgather_schedule.restype = ctypes.c_int
    lib.hvd_native_last_allreduce_schedule.restype = ctypes.c_int
    lib.hvd_native_last_allreduce_fanout.restype = ctypes.c_int
    lib.hvd_native_last_bcast_schedule.restype = ctypes.c_int
    lib.hvd_native_adasum_scratch_peak.restype = ctypes.c_int64
    lib.hvd_native_last_fused_names.restype = ctypes.c_int64
    lib.hvd_native_counters.argtypes = [
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_double)]
    lib.hvd_native_net_counters.restype = ctypes.c_int
    lib.hvd_native_net_counters.argtypes = [
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int]
    lib.hvd_native_allreduce_device.restype = ctypes.c_int64
    lib.hvd_native_allreduce_device.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int, ctypes.c_int, ctypes.c_double, ctypes.c_double]
    lib.hvd_native_broadcast_device.restype = ctypes.c_int64
    lib.hvd_native_broadcast_device.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int, ctypes.c_int]
    lib.hvd_native_allgather_device.restype = ctypes.c_int64
    lib.hvd_native_allgather_device.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int]
    lib.hvd_native_alltoall_device.restype = ctypes.c_int64
    lib.hvd_native_alltoall_device.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int, ctypes.POINTER(ctypes.c_int64), ctypes.c_int]
    lib.hvd_native_set_device_executor.argtypes = [_DEVICE_EXEC_FN]
    _lib = lib
    return lib


def _dtype_code(arr: np.ndarray) -> int:
    code = _DTYPE_CODES.get(arr.dtype)
    if code is None:
        raise TypeError(f"unsupported dtype {arr.dtype} for native path")
    return code


def _shape_arg(arr: np.ndarray):
    shape = (ctypes.c_int64 * max(arr.ndim, 1))(*(arr.shape or (1,)))
    return arr.ndim, shape


class NativeError(RuntimeError):
    pass


class NativeController:
    """Synchronous eager collectives through the native runtime."""

    def __init__(self, rank: int, size: int, coord_addr: str):
        self._lib = load_library()
        cfg = _config.Config.from_env()
        # Timeline/merge anchor: the native runtime's steady-clock t0 is
        # set inside hvd_native_init (Timeline::Start); bracketing the
        # call and taking the midpoint bounds the anchor to half the
        # init time (ms-scale — triage precision, not profiling).
        import time as _time
        _t0 = _time.time()
        rc = self._lib.hvd_native_init(
            rank, size, coord_addr.encode(),
            cfg.fusion_threshold_bytes, cfg.cycle_time_ms,
            1e9 if cfg.stall_check_disable else cfg.stall_warning_time_seconds,
            cfg.stall_shutdown_time_seconds,
            cfg.timeline_filename.encode(), cfg.cache_capacity)
        if rc != 0:
            raise NativeError(self._last_error())
        from ..debug import flight as _flight
        _flight.set_identity(rank=rank, world=size)
        _flight.set_meta("native_init_wall", (_t0 + _time.time()) / 2.0)
        _flight.record("native.attach", None, rank=rank, size=size,
                       coord_addr=coord_addr)
        # Metric children cached on the instance: _wait runs per eager
        # op, the registry lookup must not.
        from ..metrics.registry import registry as _metrics_registry
        _mreg = _metrics_registry()
        self._m_ops = _mreg.counter(
            "hvd_native_ops_total",
            "Completed native-runtime eager operations")
        self._m_fused = _mreg.gauge(
            "hvd_native_last_fused_names",
            "Names in the most recent fused allreduce Response")
        # Node topology for hierarchical collectives (from the launcher's
        # env contract; reference HOROVOD_HIERARCHICAL_ALLREDUCE knob).
        local_size = int(_config.get_env("LOCAL_SIZE", "1") or 1)
        self._lib.hvd_native_set_topology(
            local_size, 1 if cfg.hierarchical_allreduce else 0,
            1 if cfg.hierarchical_allgather else 0)
        # Seed the eager wire format from HVD_TPU_COMPRESSION.  Only the
        # coordinator's call takes effect (Runtime::SetWireCompression is
        # a no-op elsewhere); every rank adopts the choice from the
        # response stream, so a mixed-env fleet stays consistent.
        from ..ops.compression import WIRE_CODES
        self._lib.hvd_native_set_wire_compression(
            WIRE_CODES.get(cfg.compression, 0))
        self._counters = {}
        # Negotiated device plane: HBM-resident tensors enqueued with
        # *_device keep their payload on the accelerator; the registered
        # executor runs each fused Response through the jitted device plane
        # (reference: device-buffer fusion inside the negotiated runtime,
        # nccl_operations.cc:126-184).
        self._device_lock = threading.Lock()
        self._device_inputs = {}   # name -> jax.Array awaiting execution
        self._device_results = {}  # name -> executed result
        self._device_cb = None     # keep the CFUNCTYPE alive (GC hazard)
        self._device_exec_impl = None
        self._device_plan = None   # staged by PREPARE, consumed by EXECUTE
        # Register the executor NOW, not lazily on first device op: every
        # rank of the communicator must be able to participate in a device
        # Response (joined ranks contribute zero proxies) even if it never
        # submitted a device tensor itself — a rank without an executor
        # would strand its peers inside the SPMD collective.  Building the
        # impl touches no jax state; the spanning check happens at
        # enqueue/execution time.
        try:
            from ..ops.eager import _negotiated_executor
            self.set_device_executor(_negotiated_executor(self))
        except ImportError:
            pass
        # Autotune (reference ParameterManager): rank 0 owns fusion and
        # algorithm decisions, so the tuner runs there; numeric params
        # apply via SetParams, categorical toggles via SetTunedToggles
        # (the coordinator stamps each Response so every rank executes
        # the same schedule mid-flip).
        self._autotune = None
        self._autotune_pause = False
        # Per-payload dispatch table (ops/dispatch.py): installed by the
        # init()-time topology probe; once present, the tuner's two
        # hierarchical dims become bounded crossover shifts over it and
        # the coordinator stamps every response from the table.
        self._dispatch_table = None
        self._local_size = local_size
        self._autotune_kwargs = None
        if cfg.autotune and rank == 0:
            from ..autotune import ParameterManager
            self._autotune_kwargs = dict(
                apply_fn=self._apply_tuned,
                log_file=cfg.autotune_log or None,
                max_samples=cfg.autotune_bayes_opt_max_samples,
                warmup_samples=cfg.autotune_warmup_samples,
                steps_per_sample=cfg.autotune_steps_per_sample,
                gp_noise=cfg.autotune_gaussian_process_noise,
                initial_toggles=(cfg.hierarchical_allreduce,
                                 cfg.hierarchical_allgather,
                                 cfg.cache_capacity > 0),
                # Per-toggle: hierarchical variants are dead with a
                # single node; the cache cannot be enabled at capacity 0.
                tune_toggles=(local_size > 1, local_size > 1,
                              cfg.cache_capacity > 0),
                initial_compression=cfg.compression,
                # The wire-format categorical only changes anything on
                # the negotiated device plane: skip it when that plane
                # is switched off (same can't-take-effect gating as the
                # hierarchical/cache toggles), and respect — never
                # explore — an explicitly-pinned HVD_TPU_COMPRESSION.
                tune_compression=(
                    _config.get_env(_config.COMPRESSION) is None and
                    os.environ.get("HVD_TPU_EAGER_DEVICE_PLANE",
                                   "1") != "0"),
                initial_overlap=(cfg.overlap_bucket_bytes if cfg.overlap
                                 else 0),
                # The bucket-size dimension only takes effect for jobs
                # that opted into overlap (HVD_TPU_OVERLAP or an
                # optimizer overlap= argument reading the session
                # value); an explicit HVD_TPU_OVERLAP_BUCKET_BYTES pins
                # it — the operator chose, the tuner must not explore.
                tune_overlap=(
                    cfg.overlap and
                    _config.get_env(_config.OVERLAP_BUCKET_BYTES)
                    is None),
                # Multi-rank jobs explore bucket SIZES only: the tuned
                # session value is rank-0-local (not coordinated like
                # the response-stream wire stamp), and an on<->off flip
                # changes the eager collective NAME sequence (barrier
                # auto-names vs the queue's leaf-indexed names) —
                # rank 0 flipping alone would desync negotiation.
                # Size flips are name-invariant, hence safe; a
                # single-rank job may try off too.
                overlap_choices=(None if size == 1 else tuple(
                    c for c in ParameterManager.OVERLAP_CHOICES if c)))
            # Built NOW (worker scripts assert the tuner engaged right
            # after init); a probing job's bootstrap rebuilds it once in
            # shift mode before any window is scored (probe traffic is
            # excluded via autotune_paused, so no warmup is lost).
            self._autotune = ParameterManager(**self._autotune_kwargs)
            # Register with the closed loop (autotune.set_active_manager)
            # so the drift plane can open re-tune episodes and the
            # tuning memory can warm-start / write back.
            from .. import autotune as _autotune_mod
            _autotune_mod.set_active_manager(self._autotune)

    @contextlib.contextmanager
    def autotune_paused(self):
        """Suppress autotune ticks (and the lazy tuner build) for ops
        inside the scope — the dispatch probe's traffic is pinned-arm
        measurement, not a workload the tuner should score or warm up
        on."""
        prev = self._autotune_pause
        self._autotune_pause = True
        try:
            yield
        finally:
            self._autotune_pause = prev

    def adopt_dispatch_table(self, table) -> None:
        """Install a probe-built dispatch table (ops/dispatch.py
        DispatchTable): native coordinator tables on rank 0, and rebase
        the autotuner's two hierarchical booleans into bounded crossover
        SHIFTS over this table (the probe result is the warm start; the
        tuner may move each kind's crossover by one bucket per unit of
        shift, never flip the whole range blind)."""
        self._dispatch_table = table
        if self.rank() != 0:
            return
        from ..ops import dispatch as _dispatch
        for kind in _dispatch.KINDS:
            bounds, choices = table.to_native(kind)
            self.set_schedule_table(kind, bounds, choices)
        if self._autotune_kwargs is None:
            return
        if self._autotune is not None and (
                self._autotune.frozen or self._autotune._samples > 0):
            # A live mid-run tuner (elastic re-probe): keep its state —
            # its proposals now apply through the dispatch branch of
            # _apply_tuned, bounded by the fresh table.
            return
        # A kind the operator pinned (explicit HVD_TPU_HIERARCHICAL_*)
        # stays pinned at shift 0: the tuner must refine measurements,
        # not overrule an explicit operator decision.
        tunable = tuple(
            _config.get_env(knob) is None and self._local_size > 1
            for knob in (_config.HIERARCHICAL_ALLREDUCE,
                         _config.HIERARCHICAL_ALLGATHER))
        old_tune = self._autotune_kwargs.get("tune_toggles", True)
        cache_tunable = old_tune[2] if isinstance(old_tune, (tuple, list)) \
            else bool(old_tune)
        self._autotune_kwargs.update(
            dispatch_shifts=True,
            initial_toggles=(0, 0,
                             self._autotune_kwargs["initial_toggles"][2]),
            tune_toggles=tunable + (cache_tunable,))
        from .. import autotune as _autotune_mod
        from ..autotune import ParameterManager
        self._autotune = ParameterManager(**self._autotune_kwargs)
        _autotune_mod.set_active_manager(self._autotune)

    def _apply_tuned(self, fusion, cycle, hier_allreduce, hier_allgather,
                     cache_enabled, compression="none", overlap=None):
        from ..ops.compression import WIRE_CODES
        self._lib.hvd_native_set_params(int(fusion), float(cycle))
        if self._dispatch_table is not None:
            # Dispatch mode: the two hierarchical dims are crossover
            # SHIFTS over the probe-seeded table — applied as fresh
            # per-bucket tables so the cache flip below can never
            # clobber the dispatch plane the way the whole-range
            # set_tuned_toggles reinstall would.
            from ..ops import dispatch as _dispatch
            shifted = self._dispatch_table.shifted(
                {"allreduce": int(hier_allreduce),
                 "allgather": int(hier_allgather)})
            for kind in _dispatch.KINDS:
                bounds, choices = shifted.to_native(kind)
                self.set_schedule_table(kind, bounds, choices)
            _dispatch.set_active(shifted, reason="autotune")
            self._lib.hvd_native_set_cache_enabled(
                1 if cache_enabled else 0)
        else:
            self._lib.hvd_native_set_tuned_toggles(
                1 if hier_allreduce else 0, 1 if hier_allgather else 0,
                1 if cache_enabled else 0)
        # Coordinator-stamped per round (ResponseList::wire_compression):
        # workers adopt the flip at the round boundary, never mid-batch.
        self._lib.hvd_native_set_wire_compression(
            WIRE_CODES.get(compression, 0))
        if overlap is not None:
            # Overlap bucket size (0 = bucketing off): applied to the
            # overlap engine's session value — reaches EAGER dispatch at
            # the next step (value-invariant, so mid-run flips are
            # safe).  Compiled traces deliberately ignore it (a rank-
            # local tuned value must not shape a cross-rank SPMD
            # program; they read the env knobs), so this dimension's
            # measured effect — like fusion/cycle — is native-plane.
            from ..ops import overlap as _overlap_mod
            _overlap_mod.set_session_bucket_bytes(int(overlap))

    def wire_compression(self) -> str:
        """The response-stream-adopted eager wire format ("none" until
        the first round after the coordinator stamped one)."""
        from ..ops.compression import WIRE_NAMES
        return WIRE_NAMES.get(
            int(self._lib.hvd_native_wire_compression()), "none")

    @classmethod
    def from_env(cls) -> "NativeController":
        addr = _config.get_env("CONTROLLER_ADDR")
        if not addr:
            raise NativeError("HVD_TPU_CONTROLLER_ADDR not set")
        rank = int(_config.get_env("CONTROLLER_RANK",
                                   _config.get_env("RANK", "0")))
        size = int(_config.get_env("CONTROLLER_SIZE",
                                   _config.get_env("SIZE", "1")))
        return cls(rank, size, addr)

    def _last_error(self) -> str:
        return (self._lib.hvd_native_last_error() or b"").decode()

    def _auto_name(self, kind: str, name: Optional[str]) -> bytes:
        if name is not None:
            return name.encode()
        # Deterministic auto names: call order must match across ranks, the
        # same contract as the reference's handle-indexed auto names.
        n = self._counters.get(kind, 0)
        self._counters[kind] = n + 1
        return f"{kind}.noname.{n}".encode()

    def _wait(self, handle: int):
        if handle < 0:
            raise NativeError(self._last_error())
        if self._lib.hvd_native_wait(handle) != 0:
            err = self._last_error()
            self._lib.hvd_native_release(handle)
            from ..debug import flight as _flight
            _flight.record("collective.error", None, error=err[:256])
            raise NativeError(err)
        self._m_ops.inc()
        self._m_fused.set(self._lib.hvd_native_last_fused_names())
        self._autotune_tick()

    def _autotune_tick(self):
        if self._autotune is None or self._autotune_pause:
            return
        nbytes = ctypes.c_int64()
        secs = ctypes.c_double()
        self._lib.hvd_native_counters(ctypes.byref(nbytes),
                                      ctypes.byref(secs))
        self._autotune.record_bytes(nbytes.value)

    # -- negotiated device plane ------------------------------------------

    def set_device_executor(self, impl) -> None:
        """Register the device-plane executor.  ``impl(request_type, names,
        sizes, np_dtype, op, root_rank, prescale, postscale, inputs)`` runs
        one negotiated Response on device and returns {name: result} for the
        locally-submitted names (missing names are joined-rank zero
        proxies the impl synthesizes itself)."""
        self._device_exec_impl = impl
        if self._device_cb is not None:
            return
        controller = self

        def _cb(phase, rtype, n, names_p, sizes_p, dtype_code, op, root,
                prescale, postscale, err, err_cap):
            try:
                if phase == _PHASE_ABORT:
                    # A peer's prepare failed: drop the staged plan (the
                    # inputs stay in _device_inputs until device_finish
                    # pops them on the error path).
                    controller._device_plan = None
                    return 0
                if phase == _PHASE_PREPARE:
                    names = [names_p[i].decode() for i in range(n)]
                    # sizes length depends on the request type (matches
                    # the Response.sizes layout): allreduce/broadcast =
                    # element counts per name; allgather = per-rank dims
                    # + row_elems; alltoall = P x P matrix + row_elems.
                    P = controller.size()
                    if rtype == 1:
                        n_sizes = P + 1
                    elif rtype == 3:
                        n_sizes = P * P + 1
                    else:
                        n_sizes = n
                    sizes = [int(sizes_p[i]) for i in range(n_sizes)]
                    np_dtype = _CODE_TO_DTYPE[dtype_code]
                    with controller._device_lock:
                        inputs = {nm: controller._device_inputs[nm]
                                  for nm in names
                                  if nm in controller._device_inputs}
                    # Every check that can fail without touching the SPMD
                    # plane runs here, so a doomed rank is discovered
                    # BEFORE peers enter the unabortable collective.
                    validate = getattr(controller._device_exec_impl,
                                       "validate", None)
                    if validate is not None:
                        validate(rtype, names, sizes, np_dtype, op, root)
                    controller._device_plan = (
                        rtype, names, sizes, np_dtype, op, root,
                        prescale, postscale, inputs)
                    return 0
                # EXECUTE: unanimous OK was agreed across ranks.
                plan = controller._device_plan
                controller._device_plan = None
                if plan is None:
                    raise RuntimeError(
                        "device executor: EXECUTE without a prepared plan")
                results = controller._device_exec_impl(*plan)
                with controller._device_lock:
                    controller._device_results.update(results)
                return 0
            except BaseException as e:  # noqa: BLE001 — must not unwind into C
                msg = repr(e).encode()[: max(err_cap - 1, 0)]
                ctypes.memmove(err, msg + b"\x00", len(msg) + 1)
                return 1

        self._device_cb = _DEVICE_EXEC_FN(_cb)
        self._lib.hvd_native_set_device_executor(self._device_cb)

    def _device_dtype_code(self, arr) -> int:
        code = _DTYPE_CODES.get(np.dtype(arr.dtype))
        if code is None:
            raise TypeError(
                f"unsupported dtype {arr.dtype} for the device plane")
        return code

    def _device_shape_arg(self, arr):
        shape = (ctypes.c_int64 * max(arr.ndim, 1))(*(arr.shape or (1,)))
        return arr.ndim, shape

    def allreduce_device_submit(self, arr, op: int = 1,
                                prescale: float = 1.0,
                                postscale: float = 1.0,
                                name: Optional[str] = None
                                ) -> Tuple[int, str]:
        nm = self._auto_name("allreduce", name).decode()
        with self._device_lock:
            self._device_inputs[nm] = arr
        ndim, shape = self._device_shape_arg(arr)
        h = self._lib.hvd_native_allreduce_device(
            nm.encode(), ndim, shape, self._device_dtype_code(arr), op,
            prescale, postscale)
        if h < 0:
            with self._device_lock:
                self._device_inputs.pop(nm, None)
            raise NativeError(self._last_error())
        return h, nm

    def broadcast_device_submit(self, arr, root_rank: int = 0,
                                name: Optional[str] = None
                                ) -> Tuple[int, str]:
        nm = self._auto_name("broadcast", name).decode()
        with self._device_lock:
            self._device_inputs[nm] = arr
        ndim, shape = self._device_shape_arg(arr)
        h = self._lib.hvd_native_broadcast_device(
            nm.encode(), ndim, shape, self._device_dtype_code(arr),
            root_rank)
        if h < 0:
            with self._device_lock:
                self._device_inputs.pop(nm, None)
            raise NativeError(self._last_error())
        return h, nm

    def allgather_device_submit(self, arr, name: Optional[str] = None
                                ) -> Tuple[int, str]:
        nm = self._auto_name("allgather", name).decode()
        with self._device_lock:
            self._device_inputs[nm] = arr
        ndim, shape = self._device_shape_arg(arr)
        h = self._lib.hvd_native_allgather_device(
            nm.encode(), ndim, shape, self._device_dtype_code(arr))
        if h < 0:
            with self._device_lock:
                self._device_inputs.pop(nm, None)
            raise NativeError(self._last_error())
        return h, nm

    def alltoall_device_submit(self, arr,
                               splits: Optional[Sequence[int]] = None,
                               name: Optional[str] = None
                               ) -> Tuple[int, str]:
        size = self.size()
        if splits is None:
            if arr.shape[0] % size != 0:
                raise ValueError("alltoall dim0 not divisible by size")
            splits = [arr.shape[0] // size] * size
        nm = self._auto_name("alltoall", name).decode()
        with self._device_lock:
            self._device_inputs[nm] = arr
        sp = (ctypes.c_int64 * len(splits))(*splits)
        ndim, shape = self._device_shape_arg(arr)
        h = self._lib.hvd_native_alltoall_device(
            nm.encode(), ndim, shape, self._device_dtype_code(arr), sp,
            len(splits))
        if h < 0:
            with self._device_lock:
                self._device_inputs.pop(nm, None)
            raise NativeError(self._last_error())
        return h, nm

    def allgather_device(self, arr, name: Optional[str] = None):
        h, nm = self.allgather_device_submit(arr, name=name)
        return self.device_finish(h, nm)

    def alltoall_device(self, arr, splits: Optional[Sequence[int]] = None,
                        name: Optional[str] = None):
        """Returns (received, received_splits) like the host path."""
        h, nm = self.alltoall_device_submit(arr, splits=splits, name=name)
        return self.device_finish(h, nm)

    def device_finish(self, h: int, name: str):
        """Wait for a *_device_submit handle and collect the on-device
        result (the payload never visited host memory)."""
        try:
            self._wait(h)
        except NativeError:
            with self._device_lock:
                self._device_inputs.pop(name, None)
                self._device_results.pop(name, None)
            raise
        self._lib.hvd_native_release(h)
        with self._device_lock:
            self._device_inputs.pop(name, None)
            out = self._device_results.pop(name, None)
        return out

    def allreduce_device(self, arr, op: int = 1, prescale: float = 1.0,
                         postscale: float = 1.0,
                         name: Optional[str] = None):
        h, nm = self.allreduce_device_submit(
            arr, op=op, prescale=prescale, postscale=postscale, name=name)
        return self.device_finish(h, nm)

    def broadcast_device(self, arr, root_rank: int = 0,
                         name: Optional[str] = None):
        h, nm = self.broadcast_device_submit(arr, root_rank=root_rank,
                                             name=name)
        return self.device_finish(h, nm)

    # -- collectives -------------------------------------------------------

    def allreduce_async_(self, arr: np.ndarray, out: np.ndarray,
                         op: int = 1, prescale: float = 1.0,
                         postscale: float = 1.0,
                         name: Optional[str] = None) -> int:
        """In-place-capable async allreduce: arr/out may alias. Returns a
        native handle; pass to wait()/release(). Caller must keep arr/out
        alive until wait() returns (the reference's async handle contract,
        torch/mpi_ops.py:843-882)."""
        ndim, shape = _shape_arg(arr)
        h = self._lib.hvd_native_allreduce(
            self._auto_name("allreduce", name),
            arr.ctypes.data_as(ctypes.c_void_p),
            out.ctypes.data_as(ctypes.c_void_p),
            ndim, shape, _dtype_code(arr), op, prescale, postscale)
        if h < 0:
            raise NativeError(self._last_error())
        return h

    def wait(self, handle: int):
        self._wait(handle)
        self._lib.hvd_native_release(handle)

    def poll(self, handle: int) -> bool:
        return bool(self._lib.hvd_native_poll(handle))

    def allreduce_submit(self, arr: np.ndarray, op: int = 1,
                         prescale: float = 1.0, postscale: float = 1.0,
                         name: Optional[str] = None
                         ) -> Tuple[int, np.ndarray, np.ndarray]:
        """Enqueue an allreduce; returns (handle, in_buf, out_buf).  The
        caller must keep both buffers alive until the matching
        ``allreduce_finish`` (true-async contract: the background runtime
        streams from/to them while the op is in flight)."""
        arr = np.asarray(arr, order="C")  # keeps 0-d shape
        out = np.empty_like(arr)
        ndim, shape = _shape_arg(arr)
        h = self._lib.hvd_native_allreduce(
            self._auto_name("allreduce", name),
            arr.ctypes.data_as(ctypes.c_void_p),
            out.ctypes.data_as(ctypes.c_void_p),
            ndim, shape, _dtype_code(arr), op, prescale, postscale)
        if h < 0:
            raise NativeError(self._last_error())
        return h, arr, out

    def allreduce_finish(self, h: int, out: np.ndarray) -> np.ndarray:
        self._wait(h)
        self._lib.hvd_native_release(h)
        return out

    def allreduce(self, arr: np.ndarray, op: int = 1,
                  prescale: float = 1.0, postscale: float = 1.0,
                  name: Optional[str] = None) -> np.ndarray:
        h, _arr, out = self.allreduce_submit(arr, op=op, prescale=prescale,
                                             postscale=postscale, name=name)
        return self.allreduce_finish(h, out)

    def grouped_allreduce(self, arrs, op: int = 1, prescale: float = 1.0,
                          postscale: float = 1.0,
                          name: Optional[str] = None):
        """Enqueue a group atomically and wait on all (reference GroupTable
        semantics, group_table.h:30-59): all members are in flight together
        so the background runtime fuses them into shared ring launches."""
        base = (name or
                self._auto_name("grouped", None).decode())
        outs, handles = [], []
        for i, arr in enumerate(arrs):
            arr = np.asarray(arr, order="C")  # keeps 0-d shape
            out = np.empty_like(arr)
            outs.append(out)
            handles.append(self.allreduce_async_(
                arr, out, op=op, prescale=prescale, postscale=postscale,
                name=f"{base}.{i}"))
        for h in handles:
            self.wait(h)
        return outs

    def allgather_submit(self, arr: np.ndarray,
                         name: Optional[str] = None
                         ) -> Tuple[int, np.ndarray]:
        arr = np.asarray(arr, order="C")  # keeps 0-d shape
        ndim, shape = _shape_arg(arr)
        h = self._lib.hvd_native_allgather(
            self._auto_name("allgather", name),
            arr.ctypes.data_as(ctypes.c_void_p), ndim, shape,
            _dtype_code(arr))
        if h < 0:
            raise NativeError(self._last_error())
        return h, arr

    def allgather_finish(self, h: int, arr: np.ndarray) -> np.ndarray:
        self._wait(h)
        nbytes = self._lib.hvd_native_result_bytes(h)
        dims = (ctypes.c_int64 * self.size())()
        self._lib.hvd_native_result_dims(h, dims, self.size())
        total_rows = sum(dims)
        out = np.empty((total_rows,) + arr.shape[1:], dtype=arr.dtype)
        assert out.nbytes >= nbytes
        self._lib.hvd_native_result_copy(
            h, out.ctypes.data_as(ctypes.c_void_p), out.nbytes)
        self._lib.hvd_native_release(h)
        return out

    def allgather(self, arr: np.ndarray,
                  name: Optional[str] = None) -> np.ndarray:
        h, arr = self.allgather_submit(arr, name=name)
        return self.allgather_finish(h, arr)

    def broadcast_submit(self, arr: np.ndarray, root_rank: int = 0,
                         name: Optional[str] = None
                         ) -> Tuple[int, np.ndarray, np.ndarray]:
        arr = np.asarray(arr, order="C")  # keeps 0-d shape
        out = arr.copy()
        ndim, shape = _shape_arg(arr)
        h = self._lib.hvd_native_broadcast(
            self._auto_name("broadcast", name),
            arr.ctypes.data_as(ctypes.c_void_p),
            out.ctypes.data_as(ctypes.c_void_p),
            ndim, shape, _dtype_code(arr), root_rank)
        if h < 0:
            raise NativeError(self._last_error())
        return h, arr, out

    def broadcast_finish(self, h: int, out: np.ndarray) -> np.ndarray:
        self._wait(h)
        self._lib.hvd_native_release(h)
        return out

    def broadcast(self, arr: np.ndarray, root_rank: int = 0,
                  name: Optional[str] = None) -> np.ndarray:
        h, _arr, out = self.broadcast_submit(arr, root_rank=root_rank,
                                             name=name)
        return self.broadcast_finish(h, out)

    def alltoall_submit(self, arr: np.ndarray,
                        splits: Optional[Sequence[int]] = None,
                        name: Optional[str] = None
                        ) -> Tuple[int, np.ndarray]:
        arr = np.asarray(arr, order="C")  # keeps 0-d shape
        size = self.size()
        if splits is None:
            if arr.shape[0] % size != 0:
                raise ValueError("alltoall dim0 not divisible by size")
            splits = [arr.shape[0] // size] * size
        sp = (ctypes.c_int64 * len(splits))(*splits)
        ndim, shape = _shape_arg(arr)
        h = self._lib.hvd_native_alltoall(
            self._auto_name("alltoall", name),
            arr.ctypes.data_as(ctypes.c_void_p), ndim, shape,
            _dtype_code(arr), sp, len(splits))
        if h < 0:
            raise NativeError(self._last_error())
        return h, arr

    def alltoall_finish(self, h: int, arr: np.ndarray
                        ) -> Tuple[np.ndarray, np.ndarray]:
        self._wait(h)
        size = self.size()
        dims = (ctypes.c_int64 * size)()
        self._lib.hvd_native_result_dims(h, dims, size)
        recv_splits = np.array(list(dims), dtype=np.int32)
        out = np.empty((int(recv_splits.sum()),) + arr.shape[1:],
                       dtype=arr.dtype)
        self._lib.hvd_native_result_copy(
            h, out.ctypes.data_as(ctypes.c_void_p), max(out.nbytes, 1))
        self._lib.hvd_native_release(h)
        return out, recv_splits

    def alltoall(self, arr: np.ndarray,
                 splits: Optional[Sequence[int]] = None,
                 name: Optional[str] = None
                 ) -> Tuple[np.ndarray, np.ndarray]:
        h, arr = self.alltoall_submit(arr, splits=splits, name=name)
        return self.alltoall_finish(h, arr)

    def join(self) -> int:
        return self._lib.hvd_native_join()

    def barrier(self):
        if self._lib.hvd_native_barrier() != 0:
            raise NativeError(self._last_error())

    def last_fused_names(self) -> int:
        """Names in the most recent (possibly fused) allreduce Response —
        live evidence of the current fusion threshold (autotune)."""
        return self._lib.hvd_native_last_fused_names()

    def stalled(self) -> list:
        """Stall-inspector snapshot (coordinator only; [] elsewhere):
        tensors past the warning window, each with ``name``, request
        ``type``, ``age_s`` and the ``missing`` / ``submitted`` rank
        lists — the evidence the hang-report escalation consumes
        (debug/hang.py)."""
        import json
        n = self._lib.hvd_native_stalled_json(None, 0)
        buf = ctypes.create_string_buffer(max(n + 1, 3))
        self._lib.hvd_native_stalled_json(buf, len(buf))
        try:
            return json.loads(buf.value.decode() or "[]")
        except ValueError:
            # The table can change between the sizing and filling calls;
            # a truncated fill parses as garbage exactly once — treat as
            # "nothing stalled" and let the next poll see stable state.
            return []

    def set_schedule_table(self, kind, max_bytes, hierarchical) -> None:
        """Install one op kind's per-payload dispatch table on the
        coordinator (``hvd_native_set_schedule_table``): payloads up to
        ``max_bytes[i]`` use the hierarchical schedule iff
        ``hierarchical[i]``.  ``max_bytes`` must be ascending and end
        with INT64_MAX (ops/dispatch.py DispatchTable.to_native emits
        this shape).  Coordinator-only effect, like the wire stamp."""
        if isinstance(kind, int):
            code = kind
        else:
            # Single home of the name -> native ScheduleKind mapping.
            from ..ops.dispatch import KIND_CODES
            code = KIND_CODES[kind]
        n = len(max_bytes)
        mb = (ctypes.c_int64 * n)(*[int(b) for b in max_bytes])
        ch = (ctypes.c_int32 * n)(*[1 if c else 0 for c in hierarchical])
        self._lib.hvd_native_set_schedule_table(code, mb, ch, n)

    def set_cache_enabled(self, enabled: bool) -> None:
        """Response-cache toggle alone (does not touch the dispatch
        tables the way ``hvd_native_set_tuned_toggles`` would)."""
        self._lib.hvd_native_set_cache_enabled(1 if enabled else 0)

    def last_allgather_schedule(self) -> int:
        """0 = flat ring, 1 = hierarchical (chain fan-out),
        2 = hierarchical (CMA star fan-out) — most recent allgather."""
        return self._lib.hvd_native_last_allgather_schedule()

    def last_allreduce_schedule(self) -> int:
        """0 = flat ring / flat VHDD, 1 = hierarchical — schedule of
        this process's most recent allreduce/Adasum (the allreduce
        analog of ``last_allgather_schedule``)."""
        return self._lib.hvd_native_last_allreduce_schedule()

    def schedules(self) -> dict:
        """Most recent schedule per op kind, one dict for dashboards and
        drill assertions: allreduce/allgather report flat (0) vs
        hierarchical (1, or 2 for the allgather CMA-star fan-out);
        broadcast reports its fan-out (1 chain, 2 CMA star)."""
        return {"allreduce": self.last_allreduce_schedule(),
                "allgather": self.last_allgather_schedule(),
                "broadcast": self.last_bcast_schedule()}

    def last_allreduce_fanout(self) -> int:
        """0 = flat/none, 1 = chain, 2 = zero-copy CMA star — phase-3
        fan-out of the most recent hierarchical allreduce/Adasum."""
        return self._lib.hvd_native_last_allreduce_fanout()

    def last_bcast_schedule(self) -> int:
        """0 = none yet, 1 = pipelined chain, 2 = zero-copy CMA star —
        most recent broadcast."""
        return self._lib.hvd_native_last_bcast_schedule()

    def adasum_scratch_peak(self) -> int:
        """Peak scratch bytes of the Adasum VHDD path since last reset."""
        return self._lib.hvd_native_adasum_scratch_peak()

    NET_COUNTER_FIELDS = ("retries", "reconnects", "renegotiations",
                          "resets_avoided", "chaos_injected",
                          "recovering_now", "last_recovery_age_ms")

    def net_counters(self) -> dict:
        """Self-healing wire fabric counters (net.cc escalation ladder):
        recovery attempts / resumed reconnects / ring renegotiations /
        collectives completed after >= 1 recovery, plus the live
        ``recovering_now`` channel count and the age of the last
        recovery activity (-1 = never) — the hang-report evidence for
        "retrying, deadline not yet reached" vs "wedged"."""
        buf = (ctypes.c_int64 * len(self.NET_COUNTER_FIELDS))()
        n = self._lib.hvd_native_net_counters(buf, len(buf))
        return {k: int(buf[i]) for i, k in
                enumerate(self.NET_COUNTER_FIELDS[:n])}

    def adasum_scratch_reset(self) -> None:
        self._lib.hvd_native_adasum_scratch_reset()

    def rank(self) -> int:
        return self._lib.hvd_native_rank()

    def size(self) -> int:
        return self._lib.hvd_native_size()

    def start_timeline(self, filename: str):
        import time as _time
        t0 = _time.time()
        self._lib.hvd_native_start_timeline(filename.encode())
        # Merge anchor for runtime-started timelines (debug/merge.py).
        from ..debug import flight as _flight
        _flight.set_meta("timeline_start_wall", (t0 + _time.time()) / 2.0)

    def stop_timeline(self):
        self._lib.hvd_native_stop_timeline()

    def shutdown(self):
        if self._autotune is not None:
            # Deregister from the closed loop: a drift firing after
            # shutdown must not reach a tuner whose apply path is gone.
            from .. import autotune as _autotune_mod
            if _autotune_mod.active_manager() is self._autotune:
                _autotune_mod.set_active_manager(None)
        self._lib.hvd_native_shutdown()
