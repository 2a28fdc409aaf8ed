"""The plain reference over four chips against one chip, at
chatglm3-6b's published widths, on the requests a one-chip chat run
served.  It needs a host with four TPU chips and is skipped elsewhere.

    python3 bench/tests/test_chip_tp_reference.py sample --out S.npz
    python3 bench/tests/test_chip_tp_reference.py compare --sample S.npz
    python3 bench/tests/test_chip_tp_reference.py trace

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

``trace`` (four chips) runs the program's own 4-way tensor-parallel
prefill and decode steps, as the mesh-global builders give them
(``Program.prefill``, ``Program.decode_tiers``) and ``chip_smoke.py``
drives them, on the harness's weights, and reduces a profiler trace of
a run of decode steps, then of prefills, with ``bench/trace.py``: the
operations it counts as collectives, ``collective_s``,
``exposed_collective_s`` and the device programs.
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


def traced(fn) -> dict:
    """``fn()`` under the profiler, inside a ``window`` span, reduced by
    :func:`bench.trace.reduce`, with the device seconds of each operation
    that it counts as a collective (averaged over the chips)."""
    import glob
    import shutil
    import tempfile

    import jax
    from jax.profiler import ProfileData

    from bench import trace
    d = tempfile.mkdtemp(prefix="tp_trace_")
    try:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation("window"):
            jax.block_until_ready(fn())
        jax.profiler.stop_trace()
        pd = ProfileData.from_file(
            glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0])
        out = trace.reduce(pd)
        planes = [p for p in trace.device_planes(pd) if trace.op_events(p)]
        names = {}
        for p in planes:
            for n, a, b in trace.op_events(p):
                if trace.collective(n):
                    k = trace.short_name(n)
                    names[k] = names.get(k, 0.0) + (b - a) / 1e9 / len(planes)
        out["collective_ops"] = sorted(names.items(), key=lambda kv: -kv[1])
        return out
    finally:
        shutil.rmtree(d, ignore_errors=True)


def trace_steps(seed: int, chips: int = 4, batch: int = 4, seq: int = 1024,
                steps: int = 32, cfg: dict | None = None) -> dict:
    """The program's TP decode steps (``batch`` rows at position ``seq``)
    and prefills (``batch`` x ``seq``), traced and reduced."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import api

    from bench import common, weights
    if cfg is None:
        bench = common.load_benchmark()
        cfg = common.load_config(bench,
                                 common.find_cell(bench, CELL)["config"])
    m = common.model_dims(cfg)
    mesh = common.cell_mesh(jax.devices()[:chips])
    program = api.compile(common.family(m).arch_config(cfg, m), mesh=mesh)
    w = weights.make(m, common.seed_key(seed), jnp.bfloat16, mesh)
    params = weights.to_program(w, m, program)
    del w
    s_max = m["s_max"]
    pre = program.prefill(batch, seq, s_max=s_max)
    dec = program.decode_tiers(batch, s_max, tiers=(batch,))[batch]
    run_pre = jax.jit(lambda p, b: pre.fn(p, b),
                      in_shardings=pre.in_shardings)
    run_dec = jax.jit(lambda p, b: dec.fn(p, b),
                      in_shardings=dec.in_shardings)
    rng = np.random.default_rng(seed % 2**32)
    ids = jnp.asarray(rng.integers(0, m["vocab"], (batch, seq), np.int32))
    pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (batch, seq))
    pre_in = {"ids": ids, "positions": pos}
    out = run_pre(params, pre_in)
    pad = ((0, 0), (0, 0), (0, s_max - seq), (0, 0), (0, 0))
    clen = jnp.full((batch,), seq, jnp.int32)
    dec_in = {"ids": jnp.argmax(out["logits"][:, -1], -1)
              .astype(jnp.int32)[:, None],
              "cache_len": clen, "positions": clen[:, None],
              "k_cache": jnp.pad(out["layers.k"], pad),
              "v_cache": jnp.pad(out["layers.v"], pad)}
    del out
    jax.block_until_ready(run_dec(params, dec_in))

    def decode():
        return [run_dec(params, dec_in)["logits"] for _ in range(steps)]

    def prefill():
        return [run_pre(params, pre_in)["logits"] for _ in range(2)]

    return {"seed": seed, "chips": chips, "batch": batch, "seq": seq,
            "decode_steps": steps, "decode": traced(decode),
            "prefill": traced(prefill)}


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
    ap.add_argument("phase", choices=("sample", "compare", "trace"))
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
    elif args.phase == "trace":
        got = trace_steps(args.seed)
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
