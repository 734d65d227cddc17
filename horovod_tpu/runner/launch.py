"""``hvdrun`` — the launcher CLI.

Capability parity with the reference's ``horovodrun``
(runner/launch.py:300-520 arg surface, gloo_run.py launch flow): parse
-np/-H/--hostfile (or discover the TPU slice), compute slot assignments,
start the rendezvous KV server, export the env contract per worker, exec
workers locally or over ssh with fail-fast, and (with --min-np/--max-np +
--host-discovery-script) run the elastic driver instead.

Config file (--config-file, JSON or YAML) keys mirror CLI flags
(reference runner/common/util/config_parser.py).
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
from typing import Dict, List, Optional

from . import exec as exec_mod
from . import tpu_discovery
from .chips import ChipPartitionError
from .hosts import HostInfo, get_host_assignments, parse_hostfile, parse_hosts
from .rendezvous import RendezvousServer


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        prog="hvdrun",
        description="Launch a data-parallel job across hosts / a TPU slice.")
    p.add_argument("-np", "--num-proc", type=int, default=None,
                   help="total number of worker processes")
    p.add_argument("-H", "--hosts", default=None,
                   help='host list "h1:slots,h2:slots"')
    p.add_argument("--hostfile", default=None,
                   help="hostfile path (mpirun-style slots=N supported)")
    p.add_argument("--controller-port", type=int, default=26000)
    p.add_argument("--ssh-port", type=int, default=None)
    p.add_argument("--ssh-identity-file", default=None,
                   help="ssh -i identity file for remote hosts")
    p.add_argument("--network-interface", default=None,
                   help="restrict the advertised driver/rendezvous address "
                        "to this interface (reference --network-interface)")
    p.add_argument("--output-filename", default=None,
                   help="directory for per-rank output capture "
                        "(<dir>/<rank>/stdout; streams still forwarded)")
    p.add_argument("--prefix-output-with-timestamp", action="store_true")
    p.add_argument("--start-timeout", type=float, default=None,
                   help="seconds workers may wait for the controller/"
                        "rendezvous to come up before giving up")
    p.add_argument("--elastic-timeout", type=float, default=None,
                   help="seconds an elastic rendezvous round may wait for "
                        "min-np workers")
    p.add_argument("--version", action="store_true",
                   help="print the version and exit")
    # Controller selection (reference --gloo/--mpi/--jsrun/--tcp): the TPU
    # control plane is always the TCP controller (the gloo analog; SURVEY
    # §5.8 — no MPI on TPU VMs), so --tcp/--gloo are accepted no-ops and
    # --mpi/--jsrun fail with an explanation instead of a silent fallback.
    p.add_argument("--tcp", action="store_true",
                   help="use the TCP controller (always on; compat flag)")
    p.add_argument("--gloo", action="store_true",
                   help="compat alias for the TCP controller (gloo analog)")
    p.add_argument("--mpi", action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--jsrun", action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--worker-platform", choices=("auto", "cpu", "tpu"),
                   default="auto",
                   help="how workers share each host's TPU chips: auto = "
                        "exclusive, or an even partition, or CPU workers "
                        "on a host with no chips (chips that cannot be "
                        "split evenly are refused), cpu = force CPU eager "
                        "workers, tpu = inherit (externally partitioned)")
    p.add_argument("--config-file", default=None)
    # Fleet service mode (docs/fleet.md): submit through a running job
    # gateway instead of owning the device fleet for the process
    # lifetime.
    p.add_argument("--submit", action="store_true",
                   help="submit this command to the fleet gateway "
                        "instead of launching directly (multi-tenant "
                        "fleet mode; see docs/fleet.md)")
    p.add_argument("--gateway", default=None,
                   help="fleet gateway address host:port for --submit "
                        "(default: HVD_TPU_FLEET_ADDR, then "
                        "127.0.0.1:<HVD_TPU_FLEET_PORT>)")
    p.add_argument("--priority", type=int, default=0,
                   help="job priority for --submit (higher preempts "
                        "lower)")
    p.add_argument("--tenant", default="default",
                   help="tenant name for --submit (quota/fair-share "
                        "accounting)")
    p.add_argument("--rendezvous-port", type=int, default=None,
                   help="bind the rendezvous KV server to this fixed "
                        "port (default: ephemeral)")
    # Elastic.
    p.add_argument("--min-np", type=int, default=None)
    p.add_argument("--max-np", type=int, default=None)
    p.add_argument("--host-discovery-script", default=None)
    p.add_argument("--slots", type=int, default=None,
                   help="slots per discovered host (elastic)")
    p.add_argument("--reset-limit", type=int, default=None)
    # Tunables → env knobs (reference config_parser mapping).
    p.add_argument("--fusion-threshold-mb", type=float, default=None)
    p.add_argument("--cycle-time-ms", type=float, default=None)
    p.add_argument("--cache-capacity", type=int, default=None)
    p.add_argument("--disable-cache", action="store_true")
    p.add_argument("--hierarchical-allreduce", action="store_true")
    p.add_argument("--hierarchical-allgather", action="store_true")
    p.add_argument("--timeline-filename", default=None)
    p.add_argument("--timeline-mark-cycles", action="store_true")
    p.add_argument("--autotune", action="store_true")
    p.add_argument("--autotune-log-file", default=None)
    p.add_argument("--autotune-warmup-samples", type=int, default=None)
    p.add_argument("--autotune-steps-per-sample", type=int, default=None)
    p.add_argument("--autotune-bayes-opt-max-samples", type=int,
                   default=None)
    p.add_argument("--autotune-gaussian-process-noise", type=float,
                   default=None)
    p.add_argument("--no-stall-check", action="store_true")
    p.add_argument("--stall-check-warning-time-seconds", type=float,
                   default=None)
    p.add_argument("--stall-check-shutdown-time-seconds", type=float,
                   default=None)
    p.add_argument("--log-level", default=None)
    p.add_argument("--log-hide-timestamp", action="store_true",
                   help="hide timestamps in runtime log lines")
    p.add_argument("--verbose", "-v", action="store_true")
    p.add_argument("--check-build", action="store_true",
                   help="print available frameworks/features and exit "
                        "(reference horovodrun --check-build)")
    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="worker command (e.g. python train.py)")
    args = p.parse_args(argv)
    if args.config_file:
        _apply_config_file(args, p, args.config_file)
    if args.check_build or args.version:
        return args
    if args.mpi or args.jsrun:
        p.error("MPI/jsrun control planes are not available on TPU VMs; "
                "the TCP controller (the gloo analog) is the only control "
                "plane — drop --mpi/--jsrun (or pass --tcp/--gloo, which "
                "are accepted aliases)")
    if not args.command:
        p.error("no worker command given")
    if args.command and args.command[0] == "--":
        args.command = args.command[1:]
    return args


def _apply_config_file(args, parser, path: str):
    with open(path) as f:
        text = f.read()
    try:
        cfg = json.loads(text)
    except json.JSONDecodeError:
        try:
            import yaml  # type: ignore
            cfg = yaml.safe_load(text)
        except ImportError as e:
            raise SystemExit(f"config file {path} is not JSON and PyYAML "
                             f"is unavailable: {e}")
    for key, value in (cfg or {}).items():
        attr = key.replace("-", "_")
        if hasattr(args, attr) and getattr(args, attr) in (None, False):
            setattr(args, attr, value)


def knob_env(args: argparse.Namespace) -> Dict[str, str]:
    env = {}
    if args.fusion_threshold_mb is not None:
        env["HVD_TPU_FUSION_THRESHOLD"] = str(
            int(args.fusion_threshold_mb * 1024 * 1024))
    if args.cycle_time_ms is not None:
        env["HVD_TPU_CYCLE_TIME"] = str(args.cycle_time_ms)
    if args.cache_capacity is not None:
        env["HVD_TPU_CACHE_CAPACITY"] = str(args.cache_capacity)
    if args.disable_cache:
        env["HVD_TPU_CACHE_CAPACITY"] = "0"
    if args.hierarchical_allreduce:
        env["HVD_TPU_HIERARCHICAL_ALLREDUCE"] = "1"
    if args.hierarchical_allgather:
        env["HVD_TPU_HIERARCHICAL_ALLGATHER"] = "1"
    if args.start_timeout is not None:
        env["HVD_TPU_START_TIMEOUT"] = str(args.start_timeout)
    if args.elastic_timeout is not None:
        env["HVD_TPU_ELASTIC_TIMEOUT"] = str(args.elastic_timeout)
    if args.network_interface:
        env["HVD_TPU_IFACE"] = args.network_interface
    if args.timeline_filename:
        env["HVD_TPU_TIMELINE"] = args.timeline_filename
    if args.timeline_mark_cycles:
        env["HVD_TPU_TIMELINE_MARK_CYCLES"] = "1"
    if args.autotune:
        env["HVD_TPU_AUTOTUNE"] = "1"
    if args.autotune_log_file:
        env["HVD_TPU_AUTOTUNE_LOG"] = args.autotune_log_file
    if args.autotune_warmup_samples is not None:
        env["HVD_TPU_AUTOTUNE_WARMUP_SAMPLES"] = str(
            args.autotune_warmup_samples)
    if args.autotune_steps_per_sample is not None:
        env["HVD_TPU_AUTOTUNE_STEPS_PER_SAMPLE"] = str(
            args.autotune_steps_per_sample)
    if args.autotune_bayes_opt_max_samples is not None:
        env["HVD_TPU_AUTOTUNE_BAYES_OPT_MAX_SAMPLES"] = str(
            args.autotune_bayes_opt_max_samples)
    if args.autotune_gaussian_process_noise is not None:
        env["HVD_TPU_AUTOTUNE_GAUSSIAN_PROCESS_NOISE"] = str(
            args.autotune_gaussian_process_noise)
    if args.no_stall_check:
        env["HVD_TPU_STALL_CHECK_DISABLE"] = "1"
    if args.stall_check_warning_time_seconds is not None:
        env["HVD_TPU_STALL_CHECK_TIME_SECONDS"] = str(
            args.stall_check_warning_time_seconds)
    if args.stall_check_shutdown_time_seconds is not None:
        env["HVD_TPU_STALL_SHUTDOWN_TIME_SECONDS"] = str(
            args.stall_check_shutdown_time_seconds)
    if args.log_level:
        env["HVD_TPU_LOG_LEVEL"] = args.log_level
    if args.log_hide_timestamp:
        env["HVD_TPU_LOG_HIDE_TIME"] = "1"
    return env


def resolve_hosts(args: argparse.Namespace) -> List[HostInfo]:
    if args.hosts:
        return parse_hosts(args.hosts)
    if args.hostfile:
        return parse_hostfile(args.hostfile)
    tpu = tpu_discovery.discover_tpu_slice()
    if tpu is not None:
        hosts, _ = tpu
        if args.verbose:
            print(f"discovered TPU slice: "
                  f"{','.join(h.hostname for h in hosts)}")
        return hosts
    np_ = args.num_proc or 1
    return [HostInfo("localhost", np_)]


def _controller_addr(hosts: List[HostInfo], port: int) -> str:
    first = hosts[0].hostname
    if first in ("localhost", "127.0.0.1"):
        first = "127.0.0.1"
    return f"{first}:{port}"


def bind_rendezvous(port: Optional[int],
                    secret: Optional[str] = None) -> RendezvousServer:
    """Construct the KV server on ``port`` (None/0 = ephemeral).  A bind
    failure on a fixed port used to surface as an opaque
    ``OSError: [Errno 98] Address already in use`` traceback; when the
    listener already there is a fleet gateway — the one service that
    legitimately parks on a well-known port — say exactly what to do
    instead."""
    try:
        return RendezvousServer(port=port or 0, secret=secret)
    except OSError as e:
        if port:
            from ..fleet.client import detect_gateway
            if detect_gateway(f"127.0.0.1:{port}") is not None:
                raise SystemExit(
                    f"port {port} is serving a fleet gateway: fleet mode "
                    "is active on this machine — the device fleet is "
                    "managed by the gateway, so submit the job instead "
                    "of launching it directly:\n"
                    f"    horovodrun --submit --gateway 127.0.0.1:{port} "
                    "... <command>\n"
                    "(or python -m horovod_tpu.fleet.submit; see "
                    "docs/fleet.md)") from None
            raise SystemExit(
                f"rendezvous port {port} is already bound ({e}); pick "
                "another --rendezvous-port or free the port") from None
        raise


def start_rendezvous(hosts: List[HostInfo],
                     ssh_port: Optional[int] = None,
                     iface: Optional[str] = None,
                     port: Optional[int] = None):
    """Per-launch rendezvous bring-up shared by every launch path: HMAC
    secret, KV server, and a driver address NIC-probed so every remote
    host can route to it (reference driver_service.py:49-218 —
    gethostname() may resolve to an unreachable interface on multi-NIC
    machines).  Returns (server, worker_env_fragment)."""
    from .probe import advertised_host
    from .rendezvous import generate_secret
    secret = generate_secret()
    rendezvous = bind_rendezvous(port, secret=secret)
    rdv_port = rendezvous.start()
    rdv_host = advertised_host(
        [h.hostname for h in hosts if not exec_mod._is_local(h.hostname)],
        ssh_port=ssh_port, iface=iface)
    return rendezvous, {
        "HVD_TPU_RENDEZVOUS_ADDR": f"{rdv_host}:{rdv_port}",
        "HVD_TPU_RENDEZVOUS_SECRET": secret,
    }


def run_static(args: argparse.Namespace) -> int:
    hosts = resolve_hosts(args)
    np_ = args.num_proc or sum(h.slots for h in hosts)
    slots = get_host_assignments(hosts, np_)
    controller_addr = _controller_addr(hosts, args.controller_port)

    rendezvous, rdv_env = start_rendezvous(
        hosts, ssh_port=args.ssh_port, iface=args.network_interface,
        port=getattr(args, "rendezvous_port", None))
    extra_env = knob_env(args)
    extra_env.update(rdv_env)
    rendezvous.put("global", "controller", controller_addr.encode())

    if args.verbose:
        for s in slots:
            print(f"rank {s.rank} -> {s.hostname} (local {s.local_rank}/"
                  f"{s.local_size}, cross {s.cross_rank}/{s.cross_size})")
    try:
        workers = exec_mod.launch_workers(
            slots, args.command, controller_addr,
            extra_env=extra_env,
            platform_policy=args.worker_platform,
            ssh_port=args.ssh_port,
            ssh_identity_file=args.ssh_identity_file,
            output_dir=args.output_filename,
            prefix_timestamp=args.prefix_output_with_timestamp)
        return exec_mod.wait_all(workers)
    except ChipPartitionError as e:
        # Raised while planning, before any worker started.
        raise SystemExit(f"hvdrun: {e}") from None
    finally:
        rendezvous.stop()


def run_elastic(args: argparse.Namespace) -> int:
    from .elastic_driver import run_elastic
    return run_elastic(args)


def run_submit(args: argparse.Namespace) -> int:
    """``horovodrun --submit``: hand the command to the fleet gateway
    (multi-tenant fleet mode) instead of owning the device fleet.  The
    launch knobs ride the job spec as worker env, so a submitted job
    tunes exactly like a directly-launched one."""
    from ..fleet import JobSpec, client
    min_np = args.min_np if args.min_np is not None else \
        (args.num_proc or 1)
    max_np = args.max_np if args.max_np is not None else args.num_proc
    spec = JobSpec(command=list(args.command), min_np=min_np,
                   max_np=max_np, priority=args.priority,
                   tenant=args.tenant, env=knob_env(args))
    addr = client.default_addr(args.gateway)
    if client.detect_gateway(addr) is None:
        raise SystemExit(
            f"no fleet gateway answering at {addr} — start one "
            "(horovod_tpu.fleet.FleetGateway.serve()) or drop --submit "
            "to launch directly (see docs/fleet.md)")
    rec = client.submit_job(spec, addr=addr)
    print(f"job {rec.id}: {rec.state}"
          + (f" ({rec.reason})" if rec.reason else ""))
    return 0 if rec.state == "queued" else 1


def check_build() -> int:
    """Available frameworks/features (reference horovodrun --check-build):
    each probed live, not baked at build time."""
    def probe(fn):
        try:
            return fn()
        except Exception:  # noqa: BLE001
            return False

    import importlib.util as iu

    def has(mod):
        return iu.find_spec(mod) is not None

    def native_ok():
        # Report built-ness only — a diagnostic must not trigger a build.
        from ..native.controller import _lib_path
        import os
        return os.path.exists(_lib_path())

    def tf_ops_ok():
        # Existence only — the loader would build on a miss, and a
        # diagnostic must not trigger a build.
        import horovod_tpu.tensorflow as _unused  # noqa: F401  has TF?
        import os
        import horovod_tpu
        return os.path.exists(os.path.join(
            os.path.dirname(os.path.abspath(horovod_tpu.__file__)),
            "tensorflow", "hvd_tf_ops.so"))

    from .. import version
    print(f"horovod_tpu v{version.__version__}\n")
    print("Available frameworks:")
    for label, mod in [("JAX", "jax"), ("TensorFlow", "tensorflow"),
                       ("Keras", "keras"), ("PyTorch", "torch"),
                       ("MXNet", "mxnet")]:
        mark = "X" if probe(lambda m=mod: has(m)) else " "
        print(f"    [{mark}] {label}")
    print("\nAvailable runtime features:")
    for label, fn in [
            ("native eager runtime (TCP controller)", native_ok),
            ("compiled TF op bridge (hvd_tf_ops.so)", tf_ops_ok),
            ("XLA/ICI compiled collectives", lambda: has("jax")),
            ("Pallas flash attention", lambda: has("jax")),
            ("elastic training", lambda: True),
            ("Adasum", lambda: True),
            ("Spark integration", lambda: has("pyspark")),
            ("Ray integration", lambda: has("ray"))]:
        mark = "X" if probe(fn) else " "
        print(f"    [{mark}] {label}")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    args = parse_args(argv)
    if args.version:
        from .. import version
        print(version.__version__)
        return 0
    if args.check_build:
        return check_build()
    if args.submit:
        return run_submit(args)
    if args.host_discovery_script or args.min_np or args.max_np:
        return run_elastic(args)
    return run_static(args)


if __name__ == "__main__":
    sys.exit(main())
