"""Launcher chip-partitioning policy (runner/chips.py): the TPU analog of
the reference's per-slot env contract (gloo_run.py:64-75)."""

import os

import pytest

from horovod_tpu.runner import chips


def test_partition_env_four_chips_four_procs():
    env = chips.partition_env(2, 4, 4)
    assert env["TPU_VISIBLE_DEVICES"] == "2"
    assert env["TPU_PROCESS_BOUNDS"] == "2,2,1"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["CLOUD_TPU_TASK_ID"] == "2"
    ports = env["TPU_PROCESS_ADDRESSES"].split(",")
    assert len(ports) == 4
    assert env["TPU_PROCESS_PORT"] == ports[2].split(":")[1]


def test_partition_env_eight_chips_two_procs():
    env = chips.partition_env(1, 2, 8)
    assert env["TPU_VISIBLE_DEVICES"] == "4,5,6,7"
    pb = [int(x) for x in env["TPU_PROCESS_BOUNDS"].split(",")]
    cb = [int(x) for x in env["TPU_CHIPS_PER_PROCESS_BOUNDS"].split(",")]
    assert pb[0] * pb[1] * pb[2] == 2
    assert cb[0] * cb[1] * cb[2] == 4
    # Process grid × chips-per-process grid must tile the 2x4x1 host board.
    assert [p * c for p, c in zip(pb, cb)] == [2, 4, 1]


def test_partition_env_indivisible_returns_none():
    assert chips.partition_env(0, 3, 4) is None
    assert chips.partition_env(0, 2, 0) is None


def test_plan_auto_single_worker_inherits():
    plan = chips.plan_host_platform(1, "auto", chips=1, partitionable=False)
    assert plan.mode == "inherit"
    assert plan.slot_env(0, 1) == {}


@pytest.mark.parametrize("local_size,chips_n,partitionable", [
    (3, 4, True),     # hvdrun -np 3 on a four-chip host
    (2, 1, False),    # one chip that cannot be shared
])
def test_plan_auto_refuses_chips_it_cannot_split(local_size, chips_n,
                                                 partitionable):
    # Training on CPUs beside idle chips is never chosen silently.
    with pytest.raises(chips.ChipPartitionError,
                       match="--worker-platform cpu"):
        chips.plan_host_platform(local_size, "auto", chips=chips_n,
                                 partitionable=partitionable)
    # Asked for explicitly, CPU workers are still available.
    assert chips.plan_host_platform(
        local_size, "cpu", chips=chips_n,
        partitionable=partitionable).mode == "cpu"


def test_plan_auto_without_chips_pins_cpu():
    # The test sandbox: no chips at all — CPU workers, as documented.
    plan = chips.plan_host_platform(2, "auto", chips=0, partitionable=False)
    assert plan.mode == "cpu"
    env = plan.slot_env(1, 2)
    assert env["HVD_TPU_WORKER_PLATFORM"] == "cpu"
    assert env["HVD_TPU_WORKER_CPU_DEVICES"] == "1"


def test_plan_auto_partitions_when_divisible():
    plan = chips.plan_host_platform(4, "auto", chips=4, partitionable=True)
    assert plan.mode == "partition"
    assert plan.slot_env(0, 4)["TPU_VISIBLE_DEVICES"] == "0"
    assert plan.slot_env(3, 4)["TPU_VISIBLE_DEVICES"] == "3"


def test_plan_forced_cpu_and_tpu():
    assert chips.plan_host_platform(4, "cpu").mode == "cpu"
    plan = chips.plan_host_platform(
        4, "tpu", chips=1, partitionable=False)
    assert plan.mode == "inherit"
    assert plan.slot_env(0, 4) == {}


def test_chip_inventory_env_override(monkeypatch):
    monkeypatch.setenv("HVD_TPU_CHIPS_PER_HOST", "4")
    count, partitionable = chips.local_chip_inventory()
    assert count == 4 and partitionable


def test_wrap_python_command():
    wrapped = chips.wrap_python_command(
        ["python", "train.py", "--epochs", "3"])
    assert wrapped[:4] == ["python", "-m", "horovod_tpu.runner.bootstrap",
                           "--"]
    assert wrapped[4:] == ["train.py", "--epochs", "3"]
    assert chips.wrap_python_command(["./a.out"]) == ["./a.out"]


def test_wrap_python_command_keeps_interpreter_flags():
    wrapped = chips.wrap_python_command(
        ["python3", "-u", "-W", "ignore", "train.py", "-m", "x"])
    assert wrapped == ["python3", "-u", "-W", "ignore", "-m",
                       "horovod_tpu.runner.bootstrap", "--",
                       "train.py", "-m", "x"]
    # -m/-c stay on the bootstrap side so runpy handles them.
    wrapped = chips.wrap_python_command(["python", "-m", "mymod", "--flag"])
    assert wrapped == ["python", "-m", "horovod_tpu.runner.bootstrap", "--",
                       "-m", "mymod", "--flag"]


def test_partition_plan_refuses_when_split_invalid():
    plan = chips.HostPlatformPlan("partition", chips=4)
    with pytest.raises(chips.ChipPartitionError):
        plan.slot_env(0, 3)  # 3 does not divide 4


def test_remote_unknown_inventory(monkeypatch):
    monkeypatch.delenv("HVD_TPU_CHIPS_PER_HOST", raising=False)
    monkeypatch.delenv("TPU_WORKER_HOSTNAMES", raising=False)
    count, part = chips.host_chip_inventory("far-away-host", is_local=False)
    assert (count, part) == (-1, False)
    # Unknown remote: sole worker inherits, multiple workers CPU-pin.
    assert chips.plan_host_platform(1, "auto", chips=-1,
                                    partitionable=False).mode == "inherit"
    assert chips.plan_host_platform(4, "auto", chips=-1,
                                    partitionable=False).mode == "cpu"


def test_needs_bootstrap():
    assert chips.needs_bootstrap({"HVD_TPU_WORKER_PLATFORM": "cpu"})
    assert not chips.needs_bootstrap({"TPU_VISIBLE_DEVICES": "0"})


def _bootstrap_probe(code, **env):
    import subprocess
    import sys
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    return subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, env=full,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.mark.timeout(300)
def test_apply_platform_failure_is_fatal():
    """A platform pin that can no longer take effect kills the worker: one
    meant for the CPU must not go on to take the host's chips."""
    late = ("import jax; jax.devices(); "
            "from horovod_tpu.runner.bootstrap import apply_platform; "
            "apply_platform(); print('survived')")
    r = _bootstrap_probe(late, HVD_TPU_WORKER_PLATFORM="tpu")
    assert r.returncode != 0 and "survived" not in r.stdout
    assert "cannot pin worker to 'tpu'" in r.stderr
    # Before backend init the pin applies; a backend that already is the
    # requested platform needs none.
    early = ("from horovod_tpu.runner.bootstrap import apply_platform; "
             "apply_platform(); import jax; jax.devices(); "
             "apply_platform(); "
             "print(jax.default_backend(), len(jax.devices()))")
    r = _bootstrap_probe(early, HVD_TPU_WORKER_PLATFORM="cpu",
                         HVD_TPU_WORKER_CPU_DEVICES="3", XLA_FLAGS="")
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == ["cpu", "3"]


def test_launcher_refuses_uneven_split(monkeypatch, capsys):
    """hvdrun -np 3 on a four-chip host: no worker starts, the exit is
    non-zero and the message names the way to ask for CPU workers."""
    from horovod_tpu.runner.launch import main
    monkeypatch.setenv("HVD_TPU_CHIPS_PER_HOST", "4")
    with pytest.raises(SystemExit) as exc:
        main(["-np", "3", "-H", "localhost:3", "--controller-port", "28779",
              "python", "-c", "print('worker ran')"])
    assert "--worker-platform cpu" in str(exc.value)
    assert "worker ran" not in capsys.readouterr().out


@pytest.mark.timeout(300)
def test_launcher_parent_never_initializes_a_jax_backend():
    """A chip belongs to one process: a launcher parent that touched the
    backend would hold the chips its workers need.  Covers a static launch
    (plan, native build, spawn, wait) and --check-build."""
    code = (
        "import sys\n"
        "from horovod_tpu.runner.launch import main\n"
        "assert main(['--check-build']) == 0\n"
        "assert main(['-np', '1', '-H', 'localhost:1', '--controller-port',"
        " '28781', sys.executable, '-c', 'pass']) == 0\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "print('parent stayed off jax')\n")
    r = _bootstrap_probe(code)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "parent stayed off jax" in r.stdout
    assert "[hvdrun] host localhost: inherit (0 chips, 1 workers)" \
        in r.stderr
