#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 bench/run.py --workload chatglm3-6b.chat --seed 7 \
        --seconds 45 --trace 0

Loads the cell's configuration and traffic mix by the names in
``BENCHMARK.json``, makes weights and inputs from ``--seed``, warms up
every shape the mix reaches (set-up), measures for ``--seconds``, checks
the outputs against the plain reference, and prints one JSON line last on
standard output.  ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` traces a stretch of the window and reports its per-layer
metrics.  Without a TPU, or with fewer chips than the cell asks for, it
exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import common  # noqa: E402
from bench.trace import Tracer  # noqa: E402

def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def run_cell(bench: dict, cell: dict, cfg: dict, mix: dict, seed: int,
             seconds: float, trace: bool, devices, t_start: float,
             control: bool = False) -> dict:
    """Everything after the device check.  Returns the result line.
    With ``control`` the fp8 reference stands in the program's place in
    the comparison that decides ``correct``."""
    res = measure(cfg, mix, seed, seconds, trace, devices, t_start, control)
    return result_line(bench, cell, res)


def measure(cfg: dict, mix: dict, seed: int, seconds: float, trace: bool,
            devices, t_start: float, control: bool = False) -> dict:
    """Set-up, the window and the reference check of one run."""
    from bench import serve, train

    cache = common.use_compile_cache()
    clock = common.CompileClock()
    tracer = Tracer(trace, seconds)
    runner = train if mix["kind"] == "train" else serve
    res = runner.run(cfg, mix, seed, seconds, tracer, clock, devices,
                     control)
    res["setup_s"] = res["w0"] - t_start
    res["traced"] = trace
    res["trace"] = tracer.reduce() if trace else None
    res["device"] = device_info(devices)
    res["cache_dir"] = cache
    return res


def result_line(bench: dict, cell: dict, res: dict) -> dict:
    """The metrics, ``correct`` and the numbers compared of one run."""
    name = cell["name"]
    kind = "per_layer" if res["traced"] else "end_to_end"
    metrics = {}
    for spec in common.cell_metrics(bench, name, kind):
        if kind == "end_to_end":
            v = res["setup_s"] if spec["name"] == "setup_s" \
                else res["numbers"][spec["name"]]
        else:
            v = common.load_metric_reader(spec["name"])(res)
        if v is not None:
            metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
    checks = {k: v for k, v in res["check"].items()
              if isinstance(v, dict) and "limit" in v}
    correct = bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    dev = dict(res["device"], memory_peak_bytes=res["peak_bytes"])
    out = {"correct": correct, "attempted": res["numbers"]["attempted"],
           "failed": res["numbers"]["failed"], "metrics": metrics,
           "device": dev}
    if res["trace"] is not None:
        dev["busy_s"] = res["trace"]["busy_s"]
        dev["window_s"] = res["trace"]["window_s"]
        out["breakdown"] = {"device_ops": res["trace"]["device_ops"],
                            "idle_gaps": res["trace"]["idle_gaps"]}
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in checks.items()}
    report(res)
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    return out


def report(res: dict):
    """Lines before the last: generator, requests, memory, compiles."""
    n = res["numbers"]
    count, hits, secs = res["setup_marks"]
    if res["kind"] == "serve":
        late = res["late"]
        log(f"generator late p50 {common.quantile(late, 0.5)!r} s "
            f"p99 {common.quantile(late, 0.99)!r} s ({len(late)} sends)")
        log(f"requests sent {len(res['client'].all)} in window "
            f"{n['attempted']} succeeded {n['finished']} failed "
            f"{n['failed']}; ttft samples {n['n_ttft']} tokens "
            f"{n['tokens']}")
        gaps = n["gaps_ms"]
        log(f"itl gaps {len(gaps)}: " + " ".join(
            f"p{q} {common.quantile(gaps, q / 100)!r} ms"
            for q in (50, 90, 95, 99)))
    else:
        log(f"steps {n['steps']} in {n['elapsed_s']!r} s; losses "
            f"{res['check']['losses']} reference "
            f"{res['check']['reference_losses']}")
    log(f"reference check {res['check'].get('reference_s', 0.0):.2f} s; "
        f"{ {k: v for k, v in res['check'].items() if k != 'losses'} }")
    peak = res["peak_bytes"]
    log(f"peak HBM {peak!r} bytes"
        + (f" ({peak / 2**30:.2f} GiB)" if peak else ""))
    tr = res["trace"]
    if tr:
        log(f"trace programs {tr['modules']}; collectives "
            f"{tr['collective_s']!r} s, exposed "
            f"{tr['exposed_collective_s']!r} s")
    log(f"set-up {res['setup_s']:.2f} s: {count} compiles "
        f"({secs:.1f} s compiling), {hits} persistent-cache hits; "
        f"window compiles {res['compiles_window']}; cache {res['cache_dir']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = common.load_benchmark()
    cell = common.find_cell(bench, args.workload)
    cfg = common.load_config(bench, cell["config"])
    mix = common.load_traffic(cell["traffic"])

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"bench: JAX found no TPU (platform {devices[0].platform!r})")
        return 2
    if len(devices) < cell["chips"]:
        log(f"bench: {cell['name']} needs {cell['chips']} chips, JAX "
            f"found {len(devices)}")
        return 2
    devices = devices[:cell["chips"]]
    out = run_cell(bench, cell, cfg, mix, args.seed, args.seconds,
                   bool(args.trace), devices, T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
