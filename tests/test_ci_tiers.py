"""CI tier partition golden test (the reference's
test/single/test_buildkite.py spirit: the pipeline definition itself is
under test).  Every tests/test_*.py file must belong to exactly one tier
of ci/run_test_tiers.sh — a new test file that is not assigned to a tier
fails here instead of silently falling out of CI."""

import os
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "ci", "run_test_tiers.sh")


def _partition():
    out = subprocess.run(["bash", SCRIPT, "list"], capture_output=True,
                         text=True, timeout=30)
    assert out.returncode == 0, out.stderr
    tiers = {}
    for line in out.stdout.strip().splitlines():
        tier, fname = line.split()
        tiers.setdefault(tier, []).append(fname)
    return tiers


def test_script_is_valid_bash():
    out = subprocess.run(["bash", "-n", SCRIPT], capture_output=True,
                         text=True, timeout=30)
    assert out.returncode == 0, out.stderr


def test_every_test_file_in_exactly_one_tier():
    tiers = _partition()
    assigned = [f for files in tiers.values() for f in files]
    assert len(assigned) == len(set(assigned)), \
        sorted(f for f in assigned if assigned.count(f) > 1)
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    on_disk = sorted(f for f in os.listdir(tests_dir)
                     if f.startswith("test_") and f.endswith(".py"))
    missing = sorted(set(on_disk) - set(assigned))
    assert not missing, \
        f"test files not assigned to any CI tier: {missing}"
    # A tier may also name a file of a subdirectory (tests/benchmark_tests/
    # runs as a whole under `pytest tests/`; a tier takes one of its files
    # where it tests the program's other half): it has to exist.
    stale = sorted(f for f in assigned
                   if not os.path.isfile(os.path.join(tests_dir, f)))
    assert not stale, f"CI tiers reference deleted test files: {stale}"


def test_usage_error_on_unknown_tier():
    out = subprocess.run(["bash", SCRIPT, "bogus"], capture_output=True,
                         text=True, timeout=30)
    assert out.returncode == 2
    assert "usage:" in out.stderr


@pytest.mark.parametrize("tier", ["fast", "matrix", "slow"])
def test_tiers_are_nonempty(tier):
    assert _partition()[tier]
