"""A cell's chips, on four virtual CPU devices: weights and reference
split over the cell's mesh agree with one device, and a 4-chip run
either completes with a correct result or stops fast with no result.
Each case runs ``bench_four.py`` in a process of its own, since the
device count is fixed when JAX starts."""
from __future__ import annotations

import json
import math
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
FAIL_FAST_S = 60     # a run that stops at the program's limit stops in this
RUN_S = 300          # a 4-chip run of the tiny chat cell completes in this


def four(case: str, timeout: float = RUN_S):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(HERE.parents[1] / "src"))
    t0 = time.perf_counter()
    p = subprocess.run([sys.executable, str(HERE / "bench_four.py"), case],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    return p, time.perf_counter() - t0


def raised_in_serve(stderr: str) -> bool:
    """The run ended in a ``NotImplementedError`` whose innermost frame
    is ``Program.serve`` (``repro/api.py``)."""
    lines = stderr.splitlines()
    err = [i for i, x in enumerate(lines)
           if x.startswith("NotImplementedError")]
    frames = [x for x in lines[:err[-1]] if x.lstrip().startswith("File ")] \
        if err else []
    return bool(frames) and re.search(
        r'repro[/\\]api\.py", line \d+, in serve$', frames[-1]) is not None


def serve_verdict(returncode: int, stdout: str, stderr: str,
                  took: float) -> str | None:
    """None where a 4-chip serving run keeps the harness's contract, else
    what broke it.  Two outcomes keep it: the program cannot serve over a
    mesh yet, and the run stops at ``Program.serve``'s
    ``NotImplementedError`` within ``FAIL_FAST_S``, printing no result; or
    it serves, exits 0, and its last line is a result that is correct with
    every check within its limit."""
    if returncode != 0:
        if not raised_in_serve(stderr):
            return f"exit {returncode} with another error"
        if stdout.strip():
            return "a run that failed printed on standard output"
        if took >= FAIL_FAST_S:
            return f"took {took:.0f} s to stop"
        return None
    lines = stdout.strip().splitlines()
    try:
        res = json.loads(lines[-1])
    except (IndexError, ValueError):
        return "no result line last on standard output"
    if not isinstance(res, dict) or res.get("correct") is not True:
        return "the result is not correct"
    checks = res.get("checks") or {}
    if not checks or not all(
            math.isfinite(c["value"]) and c["value"] <= c["limit"]
            for c in checks.values()):
        return f"a check is not within its limit: {checks}"
    return None


def test_weights_and_reference_over_four_devices():
    p, _ = four("weights")
    assert p.returncode == 0, p.stderr[-3000:]
    assert "weights: done" in p.stdout


@pytest.mark.parametrize("case", ["serve", "train"])
def test_a_four_chip_setup_fails_fast(case):
    """``train``: the harness has no 4-chip training cell, and says so
    fast, with no result.  ``serve``: the harness's contract, whatever
    the program can do: this passes while ``Program.serve`` raises over a
    mesh, and equally once the program serves over a mesh and the run
    completes correct (``serve_verdict``); a hang, another error or a
    result that is not correct fails it."""
    p, took = four(case)
    if case == "serve":
        why = serve_verdict(p.returncode, p.stdout, p.stderr, took)
        assert why is None, (why, p.stdout[-2000:], p.stderr[-3000:])
        return
    assert p.returncode != 0
    assert "NotImplementedError: a training cell runs on one chip" \
        in p.stderr, p.stderr[-3000:]
    assert p.stdout.strip() == ""
    assert took < FAIL_FAST_S, took


_SERVE_FRAME = ('Traceback (most recent call last):\n'
                '  File "/x/bench/serve.py", line 210, in setup\n'
                '    eng = program.serve(\n'
                '  File "/x/src/repro/api.py", line 579, in serve\n'
                '    raise NotImplementedError(\n')
_NIE = _SERVE_FRAME + "NotImplementedError: Program.serve is single-host\n"
_OTHER = _SERVE_FRAME.replace("api.py", "engine.py") + \
    "NotImplementedError: something else\n"
_ERR = 'Traceback:\n  File "/x/bench/serve.py", line 9, in run\n' \
    "ValueError: shapes differ\n"


def _line(correct: bool = True, value: float = 0.05) -> str:
    return "log\n" + json.dumps({
        "correct": correct, "attempted": 4, "failed": 0, "metrics": {},
        "device": {}, "checks": {"logit_gap": {"value": value,
                                               "limit": 0.25}}}) + "\n"


@pytest.mark.parametrize("rc,out,err,took,keeps", [
    (1, "", _NIE, 5.0, True),                  # the program's limit today
    (0, _line(), "", 120.0, True),             # a mesh served correctly
    (1, "", _NIE, 61.0, False),                # too slow to stop
    (1, "partial\n", _NIE, 5.0, False),        # printed though it failed
    (1, "", _OTHER, 5.0, False),               # raised elsewhere
    (1, "", _ERR, 5.0, False),                 # another error
    (0, _line(correct=False), "", 120.0, False),
    (0, _line(value=float("nan")), "", 120.0, False),
    (0, _line(value=0.3), "", 120.0, False),   # a check over its limit
    (0, "", "", 120.0, False)])                # exit 0 with no result
def test_the_four_chip_serve_verdict(rc, out, err, took, keeps):
    assert (serve_verdict(rc, out, err, took) is None) == keeps
