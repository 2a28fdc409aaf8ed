"""The one traffic generator.  A mix is a data file
(``bench/traffic/<mix>.json``); this module reads its parameters.

Every run gets the same work: the lengths of ``n`` requests are the
distribution's quantiles at ``(i + 0.5) / n``, and the gaps between
open-loop arrivals are the exponential's quantiles at the same points,
each list shuffled once in a fixed order (``SCHEDULE_SEED``).  The run's
``--seed`` draws the prompts' token ids (and, elsewhere, the weights):
runs with different seeds differ in content, not in the lengths, the
order or the arrival times, so the spread between runs is the system's
and not the draw's.

A serving mix has::

    {"kind": "open_loop", "rate_per_s": 0.9, ...}    Poisson arrivals
    {"kind": "closed_loop", "workers": 16, ...}      each worker waits
    "prompt": {"dist": "lognormal", "median": 512, "sigma": 0.8,
               "min": 32, "max": 1536}
    "output": {"dist": "uniform", "min": 16, "max": 48}
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

SCHEDULE_SEED = 0     # orders every mix's lengths and gaps
DRAIN_S = 30.0        # how long a run serves on after its window closes


@dataclasses.dataclass
class Planned:
    """One request as the generator plans it."""
    index: int
    prompt_len: int
    output_len: int
    due_s: float = 0.0        # open loop: seconds after the window opens


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2**32, seed >> 32, *stream])


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` whole lengths at the distribution's quantiles
    ``(i + 0.5) / n``, clipped to ``[min, max]``."""
    p = (np.arange(n) + 0.5) / n
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "uniform":
        x = lo + p * (hi - lo + 1) - 0.5
    elif spec["dist"] == "lognormal":
        nd = statistics.NormalDist()
        z = np.array([nd.inv_cdf(float(q)) for q in p])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def plan_requests(mix: dict, seconds: float,
                  drain_s: float = DRAIN_S) -> list:
    """The run's requests in the mix's order.

    Open loop: ``rate * seconds`` requests due inside the window (their
    gaps scaled so that the last falls inside it), then ``rate *
    drain_s`` more due after it, which keep the load on while the
    window's last requests finish.  Closed loop: a pool of ``pool``
    requests that the workers take in turn."""
    seed = SCHEDULE_SEED
    if mix["kind"] == "open_loop":
        rate = mix["rate_per_s"]
        n_w = max(1, int(round(rate * seconds)))
        n_d = int(math.ceil(rate * drain_s))
        window = _plan(mix, seed, n_w, 0)
        due = np.cumsum(_gaps(rate, seed, n_w, 3))
        due *= seconds / (due[-1] + 1.0 / rate)
        drain = _plan(mix, seed, n_d, n_w)
        due_d = seconds + np.cumsum(_gaps(rate, seed, n_d, 4))
        for r, t in zip(window + drain, np.concatenate([due, due_d])):
            r.due_s = float(t)
        return window + drain
    return _plan(mix, seed, mix["pool"], 0)


def _plan(mix: dict, seed: int, n: int, first: int) -> list:
    if n == 0:
        return []
    prompts = _rng(seed, 1, first).permutation(
        quantiles(mix["prompt"], n))
    outputs = _rng(seed, 2, first).permutation(
        quantiles(mix["output"], n))
    return [Planned(first + i, int(a), int(b))
            for i, (a, b) in enumerate(zip(prompts, outputs))]


def _gaps(rate: float, seed: int, n: int, stream: int) -> np.ndarray:
    """Exponential gaps at the quantiles ``(i + 0.5) / n``, shuffled."""
    p = (np.arange(n) + 0.5) / n
    return _rng(seed, stream).permutation(-np.log1p(-p) / rate)


def prompt_tokens(seed: int, index: int, length: int, vocab: int):
    """The prompt of request ``index``: ids drawn from the seed."""
    return _rng(seed, 5, index).integers(0, vocab, length,
                                             dtype=np.int32)
