"""Per-process TPU chip partitioning for launched workers.

The reference's launcher gives each slot a pure-env contract
(gloo_run.py:64-75); on GPUs the analogous device split is
``CUDA_VISIBLE_DEVICES``.  The TPU analog is the libtpu multi-process env:
``TPU_VISIBLE_DEVICES`` + ``TPU_PROCESS_BOUNDS`` +
``TPU_CHIPS_PER_PROCESS_BOUNDS`` + ``TPU_PROCESS_ADDRESSES`` /
``TPU_PROCESS_PORT`` / ``CLOUD_TPU_TASK_ID``.  Without it, N spawned
workers each initialize the full backend and contend for the same chips —
which deadlocks inside the TPU client init.

Policy (``plan_host_platform``, ``--worker-platform auto``):
  * 1 worker on the host → worker inherits the platform (sole owner of the
    host's TPU, if it has one).
  * N workers, chips divisible by N and partitionable → per-slot chip
    partition env (each worker owns chips/N chips over ICI).
  * N workers on a host that has chips which cannot be split N ways →
    refused (``ChipPartitionError``): training on CPUs beside idle chips is
    never chosen silently; ``--worker-platform cpu`` asks for it.
  * N workers on a host with no chips (or an unknown remote inventory) →
    workers are pinned to the CPU platform; the eager TCP data plane still
    gives them working collectives.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

# Chip grid (x, y, z) per host by chip count — the common TPU VM configs
# (v2/v3/v4/v5p hosts: 4 chips in 2x2x1; v5e/v6e hosts: 8 chips in 2x4x1).
_HOST_TOPOLOGY = {1: (1, 1, 1), 2: (1, 2, 1), 4: (2, 2, 1), 8: (2, 4, 1)}

_BASE_TPU_PORT = 8476


def local_chip_inventory() -> Tuple[int, bool]:
    """(chip count, partitionable) for the local host, without touching any
    accelerator runtime (the launcher must never initialize a backend).

    Order: explicit env override → /dev/accel* and /dev/vfio/N device
    files (real TPU VMs) → none.
    """
    override = os.environ.get("HVD_TPU_CHIPS_PER_HOST")
    if override:
        try:
            return max(int(override), 0), True
        except ValueError:
            pass
    accels = glob.glob("/dev/accel*") + glob.glob("/dev/vfio/[0-9]*")
    if accels:
        return len(accels), True
    return 0, False


def host_chip_inventory(hostname: str, is_local: bool) -> Tuple[int, bool]:
    """(chip count, partitionable) for an arbitrary host.  Local hosts are
    probed directly; remote hosts use the env override or TPU slice
    discovery (tpu_discovery reports chips-per-host for slice members).
    Unknown remote inventory returns (-1, False): never partition or
    CPU-pin a remote host based on launcher-local evidence alone."""
    if is_local:
        return local_chip_inventory()
    override = os.environ.get("HVD_TPU_CHIPS_PER_HOST")
    if override:
        try:
            return max(int(override), 0), True
        except ValueError:
            pass
    from . import tpu_discovery
    try:
        slice_info = tpu_discovery.discover_tpu_slice()
    except Exception:
        slice_info = None
    if slice_info:
        hosts, cph = slice_info
        if any(h.hostname == hostname for h in hosts):
            return cph, True
    return -1, False


def _split_grid(grid: Tuple[int, int, int],
                nproc: int) -> Optional[Tuple[Tuple[int, int, int],
                                              Tuple[int, int, int]]]:
    """Factor nproc into per-axis process bounds dividing the chip grid.
    Returns (process_bounds, chips_per_process_bounds) or None."""
    x, y, z = grid
    best = None
    for px in range(1, x + 1):
        if x % px:
            continue
        for py in range(1, y + 1):
            if y % py:
                continue
            for pz in range(1, z + 1):
                if z % pz:
                    continue
                if px * py * pz == nproc:
                    cand = ((px, py, pz), (x // px, y // py, z // pz))
                    # Prefer splitting the longest axis first (keeps each
                    # process's chips ICI-contiguous on the host board).
                    if best is None or cand[0] > best[0]:
                        best = cand
    return best


def partition_env(local_rank: int, local_size: int, chips: int,
                  hostname: str = "localhost",
                  jax_coord_port: int = 0) -> Optional[Dict[str, str]]:
    """The per-slot libtpu env splitting ``chips`` among ``local_size``
    processes on one host.  None when no clean split exists.
    ``jax_coord_port``: per-launch port for the jax.distributed coordinator
    (0 falls back to a fixed default — collides across concurrent launches,
    so plans allocate a fresh one)."""
    if chips <= 0 or chips % local_size:
        return None
    grid = _HOST_TOPOLOGY.get(chips)
    if grid is None:
        return None
    split = _split_grid(grid, local_size)
    if split is None:
        return None
    pbounds, cbounds = split
    per_proc = chips // local_size
    first = local_rank * per_proc
    addresses = ",".join(
        f"{hostname}:{_BASE_TPU_PORT + i}" for i in range(local_size))
    return {
        "TPU_VISIBLE_DEVICES": ",".join(
            str(c) for c in range(first, first + per_proc)),
        "TPU_PROCESS_BOUNDS": ",".join(str(b) for b in pbounds),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": ",".join(str(b) for b in cbounds),
        "TPU_PROCESS_ADDRESSES": addresses,
        "TPU_PROCESS_PORT": str(_BASE_TPU_PORT + local_rank),
        "CLOUD_TPU_TASK_ID": str(local_rank),
        # jax.distributed bootstrap (applied by runner/bootstrap.py before
        # backend init): partitioned workers form one JAX world so compiled
        # multi-process programs AND the eager on-device ICI plane work.
        "HVD_TPU_JAX_COORD_ADDR":
            f"{hostname}:{jax_coord_port or (_BASE_TPU_PORT - 1)}",
        "HVD_TPU_JAX_NUM_PROCS": str(local_size),
        "HVD_TPU_JAX_PROC_ID": str(local_rank),
    }


def _free_port() -> int:
    """Probe a free port on the launcher.  Best effort for the worker-host
    coordinator bind: on localhost launches (the partition-mode norm) it is
    authoritative minus a close→bind race; for ssh-remote hosts an
    ephemeral port is merely unlikely to be taken there.  A losing worker
    fails fast in bootstrap.apply_jax_distributed rather than joining the
    wrong world."""
    import socket
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class ChipPartitionError(RuntimeError):
    """A host's chips cannot be split evenly among its workers."""


@dataclasses.dataclass
class HostPlatformPlan:
    """Resolved platform decision for one host's workers."""
    mode: str                      # "inherit" | "partition" | "cpu"
    chips: int = 0
    # Per-launch jax.distributed coordinator port (partition mode, or cpu
    # mode with cpu_jax_world): allocated fresh so concurrent launches on
    # a host don't join each other's worlds.
    jax_coord_port: int = 0
    # HVD_TPU_CPU_JAX_WORLD=1: CPU-pinned workers also form a spanning
    # jax.distributed world (one CPU device per process), so the eager
    # negotiated device plane and compiled multi-process programs run
    # without TPU hardware — the launcher-level analog of the test
    # suite's hand-spawned jax.distributed worlds.  Single-host launches
    # only (the world is sized to this host's local_size).
    cpu_jax_world: bool = False

    def __post_init__(self):
        if not self.jax_coord_port and \
                (self.mode == "partition" or self.cpu_jax_world):
            self.jax_coord_port = _free_port()

    def slot_env(self, local_rank: int, local_size: int,
                 hostname: str = "localhost") -> Dict[str, str]:
        if self.mode == "partition":
            env = partition_env(local_rank, local_size, self.chips, hostname,
                                jax_coord_port=self.jax_coord_port)
            if env is None:  # a plan made for another local size
                raise ChipPartitionError(_refusal(local_size, self.chips))
            return env
        if self.mode == "cpu":
            env = {"HVD_TPU_WORKER_PLATFORM": "cpu",
                   "HVD_TPU_WORKER_CPU_DEVICES": "1"}
            if self.cpu_jax_world:
                env.update({
                    "HVD_TPU_JAX_COORD_ADDR":
                        f"{hostname}:{self.jax_coord_port}",
                    "HVD_TPU_JAX_NUM_PROCS": str(local_size),
                    "HVD_TPU_JAX_PROC_ID": str(local_rank),
                })
            return env
        return {}


def _refusal(local_size: int, chips: int) -> str:
    return (f"{local_size} workers cannot split this host's {chips} TPU "
            f"chip(s) evenly; launch a worker count that divides them, or "
            f"pass --worker-platform cpu to run CPU workers and leave the "
            f"chips idle")


def plan_host_platform(local_size: int, policy: str = "auto",
                       chips: Optional[int] = None,
                       partitionable: Optional[bool] = None,
                       cpu_jax_world: Optional[bool] = None
                       ) -> HostPlatformPlan:
    """Decide how ``local_size`` workers on one host share its chips.

    policy: "auto" (described in the module docstring; raises
    ``ChipPartitionError`` when the host has chips that cannot be split),
    "cpu" (force CPU workers), "tpu" (force inherit — the user takes
    responsibility for contention, e.g. an externally partitioned
    environment).
    """
    cpu_world = (os.environ.get("HVD_TPU_CPU_JAX_WORLD") == "1"
                 if cpu_jax_world is None else cpu_jax_world)
    if policy == "cpu":
        return HostPlatformPlan("cpu", cpu_jax_world=cpu_world)
    if chips is None or partitionable is None:
        chips, partitionable = local_chip_inventory()
    if policy == "tpu":
        return HostPlatformPlan("inherit", chips)
    if local_size <= 1:
        # A sole worker on its host cannot contend — inherit whatever
        # platform the host offers (chips == -1 means unknown remote).
        return HostPlatformPlan("inherit", chips)
    if (partitionable and chips >= local_size and
            partition_env(0, local_size, chips) is not None):
        return HostPlatformPlan("partition", chips)
    if chips > 0:
        raise ChipPartitionError(_refusal(local_size, chips))
    return HostPlatformPlan("cpu", chips, cpu_jax_world=cpu_world)


def needs_bootstrap(env: Dict[str, str]) -> bool:
    """True when the slot env carries a platform override or a JAX world
    declaration that must be applied in-process before the user's
    ``import jax``."""
    return "HVD_TPU_WORKER_PLATFORM" in env or \
        "HVD_TPU_JAX_COORD_ADDR" in env


# Interpreter options that consume a following value and so must travel
# with the interpreter, not be mistaken for the worker script.
_PY_VALUE_FLAGS = {"-W", "-X", "--check-hash-based-pycs"}


def wrap_python_command(command: List[str]) -> List[str]:
    """Rewrite ``python [interp flags] script.py ...`` to run through the
    bootstrap module so the platform config lands before user imports.
    Interpreter flags (``-u``, ``-O``, ``-W x``, ...) stay on the
    interpreter; ``-m mod`` / ``-c cmd`` / script+args are handled by the
    bootstrap itself.  Non-python commands are returned unchanged (env-only
    best effort)."""
    if not command:
        return command
    base = os.path.basename(command[0])
    if not (base.startswith("python") or base == "pypy"):
        return command
    interp = [command[0]]
    rest = list(command[1:])
    while rest and rest[0].startswith("-") and rest[0] not in ("-m", "-c"):
        flag = rest.pop(0)
        interp.append(flag)
        if flag in _PY_VALUE_FLAGS and rest:
            interp.append(rest.pop(0))
    return interp + ["-m", "horovod_tpu.runner.bootstrap", "--"] + rest
