"""Smoke-run the runnable examples (tiny sizes, 1–2 processes) so they
cannot rot: the reference ships its examples as working artifacts and so
do we."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "examples")


def _run(args, timeout=240):
    # conftest.py already placed --xla_force_host_platform_device_count in
    # XLA_FLAGS, so subprocesses inherit the 8-device mesh.
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, timeout=timeout, env=env, cwd=REPO)


@pytest.mark.timeout(300)
def test_jax_mnist_single_proc():
    r = _run([os.path.join(EXAMPLES, "jax_mnist.py"), "--epochs", "1"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "loss" in r.stdout


@pytest.mark.timeout(300)
def test_jax_mnist_overlap_identical_losses():
    """--overlap switches the optimizer to the bucketed backward-overlap
    schedule (docs/overlap.md) — bit parity means the printed losses
    must be IDENTICAL, not merely close."""
    base = _run([os.path.join(EXAMPLES, "jax_mnist.py"), "--epochs", "2"])
    over = _run([os.path.join(EXAMPLES, "jax_mnist.py"), "--epochs", "2",
                 "--overlap"])
    assert base.returncode == 0, base.stderr[-2000:]
    assert over.returncode == 0, over.stderr[-2000:]
    base_losses = [ln for ln in base.stdout.splitlines() if "loss" in ln]
    over_losses = [ln for ln in over.stdout.splitlines() if "loss" in ln]
    assert base_losses and base_losses == over_losses, \
        (base_losses, over_losses)


@pytest.mark.timeout(300)
def test_jax_transformer_lm_overlap_identical_losses():
    """--overlap feeds the bucketed DistributedOptimizer path (explicit
    dp shard_map step) — same math as the AD-transpose baseline step, so
    losses at world 1 must match (tiny float tolerance only for the
    different step structure XLA compiles)."""
    args = ["--layers", "1", "--d-model", "64", "--seq", "32",
            "--batch", "4", "--steps", "3"]
    base = _run([os.path.join(EXAMPLES, "jax_transformer_lm.py")] + args)
    over = _run([os.path.join(EXAMPLES, "jax_transformer_lm.py")] + args +
                ["--overlap"])
    assert base.returncode == 0, base.stderr[-2000:]
    assert over.returncode == 0, over.stderr[-2000:]

    def losses(r):
        return [float(ln.split("loss")[-1]) for ln in r.stdout.splitlines()
                if "loss" in ln]

    lb, lo = losses(base), losses(over)
    assert len(lb) == 3 and len(lo) == 3, (base.stdout, over.stdout)
    # Printed at 4 decimals; allow one ulp of the print rounding.
    assert all(abs(a - b) <= 2e-4 for a, b in zip(lb, lo)), (lb, lo)


def test_jax_transformer_lm_zero_stages_identical_losses():
    """--zero-stage 1/2/3 end-to-end at world 1: ZeRO only changes the
    wire schedule and residency, never the math — the seeded run's
    printed losses must match the unsharded baseline at every stage."""
    args = ["--layers", "1", "--d-model", "64", "--seq", "32",
            "--batch", "4", "--steps", "3"]
    runs = {s: _run([os.path.join(EXAMPLES, "jax_transformer_lm.py")]
                    + args + ["--zero-stage", str(s)])
            for s in (0, 1, 2, 3)}
    for s, r in runs.items():
        assert r.returncode == 0, (s, r.stderr[-2000:])

    def losses(r):
        return [float(ln.split("loss")[-1]) for ln in r.stdout.splitlines()
                if "loss" in ln]

    base = losses(runs[0])
    assert len(base) == 3, runs[0].stdout
    for s in (1, 2, 3):
        ls = losses(runs[s])
        assert len(ls) == 3, (s, runs[s].stdout)
        # Printed at 4 decimals; one ulp of print rounding only.
        assert all(abs(a - b) <= 2e-4 for a, b in zip(base, ls)), \
            (s, base, ls)


@pytest.mark.timeout(300)
def test_pytorch_synthetic_benchmark_single_proc():
    pytest.importorskip("torch")
    r = _run([os.path.join(EXAMPLES, "pytorch_synthetic_benchmark.py"),
              "--num-iters", "1", "--num-batches-per-iter", "1",
              "--num-warmup-batches", "1", "--batch-size", "4",
              "--image-size", "32"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "img/sec" in r.stdout


@pytest.mark.timeout(300)
def test_tf2_synthetic_benchmark_single_proc():
    pytest.importorskip("tensorflow")
    r = _run([os.path.join(EXAMPLES, "tensorflow2_synthetic_benchmark.py"),
              "--num-iters", "1", "--num-batches-per-iter", "1",
              "--num-warmup-batches", "1", "--batch-size", "4",
              "--image-size", "32"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "img/sec" in r.stdout


@pytest.mark.timeout(300)
def test_elastic_pytorch_example_2proc(monkeypatch):
    pytest.importorskip("torch")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    from horovod_tpu.runner.launch import main
    rc = main(["-np", "2", "--controller-port", "28771", sys.executable,
               os.path.join(EXAMPLES, "elastic_pytorch_train.py")])
    assert rc == 0


@pytest.mark.timeout(300)
def test_zero_optimizer_example():
    r = _run([os.path.join(EXAMPLES, "zero_optimizer.py")])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "per-rank opt state" in r.stdout


@pytest.mark.timeout(120)
def test_chip_smoke_refuses_without_a_tpu():
    """chip_smoke.py has no CPU mode: off-TPU it exits non-zero in
    seconds, says what it found, trains nothing and prints no result."""
    r = _run([os.path.join(REPO, "chip_smoke.py")], timeout=100)
    assert r.returncode != 0
    assert "platform cpu" in r.stderr
    assert r.stdout.strip() == ""


@pytest.mark.timeout(300)
def test_token_sum_timing_refuses_without_a_tpu():
    """A timing comes from a TPU: off one the example says what it found,
    times nothing and prints no line."""
    r = _run([os.path.join(EXAMPLES, "token_sum_timing.py"), "sdar"],
             timeout=100)
    assert r.returncode != 0
    assert "this is cpu" in r.stderr
    assert r.stdout.strip() == ""


@pytest.mark.timeout(300)
def test_block_diffusion_lm_trains_on_the_virtual_mesh():
    """A tiny block-diffusion configuration (``diffusion_block``: the
    doubled sequence, the third batch array from ``synthetic_batch`` /
    ``noised_batch``) trains on two virtual devices."""
    r = _run([os.path.join(EXAMPLES, "block_diffusion_lm.py"), "--steps",
              "7"])              # prints steps 0, 5 and the last
    assert r.returncode == 0, r.stderr[-2000:]
    losses = [float(ln.split()[-1]) for ln in r.stdout.splitlines()
              if ln.startswith("step")]
    assert len(losses) == 3 and all(x == x and x < 20.0 for x in losses)
    assert "128 positions" in r.stdout
