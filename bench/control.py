#!/usr/bin/env python3
"""Readings from which the limits of ``correct`` are set; not part of a
benchmark run.

    python3 bench/control.py --workload chatglm3-6b.chat \
        --seeds 11,12,13 --seconds 20
    python3 bench/control.py --workload smollm-135m.train \
        --seeds 11,12,13 --seconds 2 --half-batch

For each seed it runs the cell as ``bench/run.py`` does (set-up, a window
of ``--seconds`` at the cell's load, the reference check) with the
control, the plain reference computed in fp8, put in the program's place
in the comparison that decides ``correct``, and prints one JSON line:
``correct`` and the numbers compared, as the result line has them, and
the program's own readings of the same run (``program``).
``--half-batch`` plants a fault in the train step instead: half of each
batch's rows are left out (their labels ignored), the mean taken over
the rest.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import common  # noqa: E402


def plant_half_batch():
    """Wrap ``Program.train_step`` so that its step ignores the labels of
    the second half of the rows."""
    import dataclasses

    from repro import api
    build = api.Program.train_step

    def train_step(self, *a, **kw):
        step = build(self, *a, **kw)
        fn = step.fn

        def half(params, opt, batch, i):
            lab = batch["labels"]
            n = lab.shape[0] // 2
            batch = dict(batch, labels=lab.at[n:].set(-100))
            return fn(params, opt, batch, i)

        return dataclasses.replace(step, fn=half)

    api.Program.train_step = train_step


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--half-batch", action="store_true")
    args = ap.parse_args(argv)
    bench = common.load_benchmark()
    cell = common.find_cell(bench, args.workload)
    cfg = common.load_config(bench, cell["config"])
    mix = common.load_traffic(cell["traffic"])
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"control: {cell['name']} needs {cell['chips']} TPU chips, "
              f"JAX found {len(devices)} {devices[0].platform} devices",
              file=sys.stderr)
        return 2
    if args.half_batch:
        plant_half_batch()
    from bench import run
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.measure(cfg, mix, seed, args.seconds, False,
                          devices[:cell["chips"]],
                          time.perf_counter(), control=not args.half_batch)
        line = run.result_line(bench, cell, res)
        chk = res["check"]
        print(json.dumps({
            "seed": seed, "half_batch": args.half_batch,
            "correct": line["correct"], "checks": line["checks"],
            "program": chk.get("program", {}),
            **{k: chk[k] for k in ("losses", "reference_losses",
                                   "requests_checked",
                                   "served_tokens_checked", "reference_s")
               if k in chk}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
