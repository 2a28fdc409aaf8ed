"""The plain reference over four chips against one chip, at
chatglm3-6b's published widths, on the requests a one-chip chat run
served.  It needs a host with four TPU chips and is skipped elsewhere.

    python3 bench/tests/test_chip_tp_reference.py sample --out S.npz
    python3 bench/tests/test_chip_tp_reference.py compare --sample S.npz

``sample`` (one chip) runs ``chatglm3-6b.chat`` as ``bench/run.py`` does
and keeps the requests its check samples.  ``compare`` (four chips, a
process of its own, so that no chip's peak holds the serving run) makes
the weights from the same seed over a (1, 4) mesh and runs the reference
over the mesh on those requests, reads each chip's peak, then makes them
on one chip and runs the reference there.  It prints the widest
difference of the two references' ``logit_gap`` per served token, each
chip's peak bytes and the share it is held to: a quarter of the weights,
plus one layer's matrices in float32 and one block of float32 logits (the
reference's temporaries).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "chatglm3-6b.chat"
GAP_TOL = 1e-3


def sample(seed: int, seconds: float, out: str) -> dict:
    import jax
    import numpy as np

    from bench import common, run, serve
    bench = common.load_benchmark()
    cell = common.find_cell(bench, CELL)
    cfg = common.load_config(bench, cell["config"])
    mix = common.load_traffic(cell["traffic"])
    res = run.measure(cfg, mix, seed, seconds, False, jax.devices()[:1],
                      time.perf_counter())
    picked = serve.pick_sample(res["client"], seed,
                               mix["check"]["requests"])
    np.savez(out, seed=seed, **{
        f"{k}{i}": np.asarray(getattr(t.req, k), np.int32)
        for i, t in enumerate(picked) for k in ("prompt", "output")})
    return {"seed": seed, "requests": len(picked),
            "served_tokens": sum(len(t.req.output) for t in picked),
            "logit_gap": res["check"]["logit_gap"]["value"]}


def peaks(devices) -> list:
    return [d.memory_stats()["peak_bytes_in_use"] for d in devices]


def compare(path: str, chips: int = 4) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import common, serve, weights
    bench = common.load_benchmark()
    cfg = common.load_config(bench, common.find_cell(bench, CELL)["config"])
    m = common.model_dims(cfg)
    got = np.load(path)
    seed = int(got["seed"])
    seqs = [(got[f"prompt{i}"], got[f"output{i}"])
            for i in range(len([k for k in got.files if k[:6] == "prompt"]))]
    devices = jax.devices()[:chips]
    key = common.seed_key(seed)
    t0 = time.perf_counter()
    w = weights.make(m, key, jnp.bfloat16, common.cell_mesh(devices))
    jax.block_until_ready(w)
    made_s = time.perf_counter() - t0
    after_make = peaks(devices)
    t0 = time.perf_counter()
    g_many = serve.logit_gaps(w, m, seqs)
    ref_s = time.perf_counter() - t0
    after_ref = peaks(devices)
    total = sum(a.nbytes for a in w.values())
    del w
    gc.collect()
    w = weights.make(m, key, jnp.bfloat16)
    g_one = serve.logit_gaps(w, m, seqs)
    fam = common.family(m)
    layer = sum(4 * int(np.prod(s[1:])) for n, (s, _) in
                fam.shapes(m).items() if n in fam.STACKED and len(s) > 2)
    block = 4 * 2048 * m["vocab"]
    held_to = total / chips + layer + block
    diff = max(float(np.abs(a - b).max()) for a, b in zip(g_many, g_one))
    return {"seed": seed, "chips": chips, "requests": len(seqs),
            "served_tokens": sum(len(s) for _, s in seqs),
            "logit_gap_diff": diff, "tolerance": GAP_TOL,
            "logit_gap_many": max(float(g.max()) for g in g_many),
            "logit_gap_one": max(float(g.max()) for g in g_one),
            "weights_bytes": total, "peak_after_weights": after_make,
            "peak_after_reference": after_ref, "held_to_bytes": held_to,
            "make_s": made_s, "reference_s": ref_s}


def _tpu_chips() -> int:
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        return 0
    p = subprocess.run(
        [sys.executable, "-c", "import jax; d = jax.devices(); "
         "print(len(d) if d[0].platform == 'tpu' else 0)"],
        capture_output=True, text=True, timeout=300)
    return int(p.stdout.strip() or 0) if p.returncode == 0 else 0


def test_four_chip_reference_agrees_with_one_chip(tmp_path):
    import pytest
    if _tpu_chips() < 4:
        pytest.skip("needs a host with four TPU chips")
    path = str(tmp_path / "sample.npz")
    me = [sys.executable, __file__]
    subprocess.run(me + ["sample", "--out", path], check=True, timeout=1500)
    p = subprocess.run(me + ["compare", "--sample", path],
                       capture_output=True, text=True, timeout=900)
    print(p.stdout, p.stderr[-3000:])
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["logit_gap_diff"] <= GAP_TOL
    assert max(got["peak_after_reference"]) <= got["held_to_bytes"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("phase", choices=("sample", "compare"))
    ap.add_argument("--seed", type=int, default=2**31 + 15)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out")
    ap.add_argument("--sample")
    args = ap.parse_args(argv)
    from bench import common
    common.use_compile_cache()
    if args.phase == "sample":
        seconds = args.seconds or common.load_benchmark()["run_seconds"]
        got = sample(args.seed, seconds, args.out)
    else:
        got = compare(args.sample)
    print(json.dumps(got), flush=True)
    if args.phase == "compare":
        ok = got["logit_gap_diff"] <= GAP_TOL and \
            max(got["peak_after_reference"]) <= got["held_to_bytes"]
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
