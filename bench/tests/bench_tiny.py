"""Tiny versions of the benchmark's cells for CPU tests: the same files,
widths cut so that a run takes seconds.  Used by the CPU tests only; the
benchmark itself always runs the files as they are."""
from __future__ import annotations

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import common, peaks  # noqa: E402

TINY = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv": 2,
        "head_dim": 16, "d_ff": 128, "vocab": 256}


def cell(name: str):
    """(bench, cell, cfg, mix) with tiny widths and short lengths."""
    bench = common.load_benchmark()
    c = common.find_cell(bench, name)
    cfg = common.load_config(bench, c["config"])
    mix = common.load_traffic(c["traffic"])
    for k, v in TINY.items():
        if k in cfg["keys"]:
            cfg[cfg["keys"][k]] = v
    if mix["kind"] == "train":
        mix.update(batch=4, seq=64)
        return bench, c, cfg, mix
    cfg[cfg["keys"]["s_max"]] = 256
    cfg["serve"]["max_batch"] = 4
    cfg["serve"]["prefill_buckets"] = [32, 64, 128]
    mix["prompt"].update(min=8, max=min(200, mix["prompt"]["max"]))
    mix["output"].update(min=4, max=40)
    for part, med in (("prompt", 64), ("output", 16)):
        if "median" in mix[part]:
            mix[part]["median"] = med
    if mix["kind"] == "open_loop":
        mix["rate_per_s"] = 4.0
    else:
        mix["workers"] = 4
    return bench, c, cfg, mix


def run(monkeypatch, name: str, seconds: float = 3.0, seed: int = 2**33 + 5,
        trace: bool = False, control: bool = False):
    """``run_cell`` on the CPU: the device check skipped, the persistent
    compile cache left alone, and the CPU given the v5e's peaks so that
    the per-layer readers run.  ``control`` puts the fp8 reference in the
    program's place."""
    import jax

    from bench import run as bench_run
    monkeypatch.setattr(common, "use_compile_cache", lambda: "none")
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    bench, c, cfg, mix = cell(name)
    return bench_run.run_cell(bench, c, cfg, mix, seed, seconds, trace,
                              jax.devices()[:1], time.perf_counter(),
                              control)
