"""A cell's chips, on four virtual CPU devices: weights and reference
split over the cell's mesh agree with one device, and a 4-chip set-up
that the program cannot serve or train yet fails fast, with no result.
Each case runs ``bench_four.py`` in a process of its own, since the
device count is fixed when JAX starts."""
from __future__ import annotations

import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent


def four(case: str, timeout: float = 300):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(HERE.parents[1] / "src"))
    t0 = time.perf_counter()
    p = subprocess.run([sys.executable, str(HERE / "bench_four.py"), case],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    return p, time.perf_counter() - t0


def test_weights_and_reference_over_four_devices():
    p, _ = four("weights")
    assert p.returncode == 0, p.stderr[-3000:]
    assert "weights: done" in p.stdout


@pytest.mark.parametrize("case,error", [
    ("serve", "NotImplementedError: Program.serve is single-host"),
    ("train", "NotImplementedError: a training cell runs on one chip")])
def test_a_four_chip_setup_fails_fast(case, error):
    p, took = four(case)
    assert p.returncode != 0
    assert error in p.stderr, p.stderr[-3000:]
    assert p.stdout.strip() == ""
    assert took < 60, took
