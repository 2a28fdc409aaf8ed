#!/usr/bin/env python3
"""Sweep an open-loop cell's offered rate once, to find the knee: the
highest rate served without a growing backlog.  Not part of a benchmark
run; the cell's mix file then fixes its rate as a number.

    python3 bench/sweep.py --workload chatglm3-6b.chat --seed 5 \
        --seconds 30 --rates 1.5,2,2.5,3,3.5

Set-up runs once; each rate gets its own window on the same engine, after
the engine has gone idle.  One JSON line per rate.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import common, serve, traffic  # noqa: E402
from bench.trace import Tracer  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    bench = common.load_benchmark()
    cell = common.find_cell(bench, args.workload)
    cfg = common.load_config(bench, cell["config"])
    mix = common.load_traffic(cell["traffic"])
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"sweep: {cell['name']} needs {cell['chips']} TPU chips, "
              f"JAX found {len(devices)} {devices[0].platform} devices",
              file=sys.stderr)
        return 2
    common.use_compile_cache()
    _, eng, _, m = serve.setup(cfg, mix, args.seed,
                               devices[:cell["chips"]])
    for rate in (float(r) for r in args.rates.split(",")):
        mx = dict(mix, rate_per_s=rate)
        plan = traffic.plan_requests(mx, args.seconds, drain_s=0)
        d, w0, late = serve.run_window(eng, mx, plan, args.seed,
                                       args.seconds, m["vocab"],
                                       Tracer(False), lambda: None,
                                       drain_s=0)
        left = len(d.live)
        nums = serve.window_numbers(d, w0, args.seconds, mx)
        d.run_until_idle()
        offered = sum(p.output_len for p in plan) / args.seconds
        print(json.dumps({
            "rate_per_s": rate, "offered_tokens_per_s": offered,
            "live_at_close": left,
            "late_p99_s": common.quantile(late, 0.99),
            **{k: v for k, v in nums.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
