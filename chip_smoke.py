#!/usr/bin/env python3
"""Smoke run of the main path on TPU, through ``repro.api``.

One chip (the default):

* serve chatglm3-6b at its published config (28 layers, d=4096, bf16)
  through ``api.compile(...).serve``: 8 requests, 4 prompts of 600-1000
  tokens and 4 of 16-200, 32 new tokens each.  The long prompts form one
  4x1024 prefill group, for which the dynamic policy picks TokenWeave and
  its Pallas ``fused_add_rmsnorm``, compiled by Mosaic;
* the repo's lowered-vs-interpreted differential: greedy tokens of two
  requests from ``ServeConfig(lowered=True)`` and ``lowered=False`` must
  match, and the largest gap of their first-step logits is printed;
* train smollm-135m (30 layers, d=576): 5 AdamW steps of
  ``Program.train_step(8, 2048)`` on one repeated batch; the loss must be
  finite and fall.

``--chips 4`` runs only chatglm3-6b prefill plus one decode step on a
(data=1, model=4) mesh, and the same two steps on one chip with the same
parameters; the logits must agree at bf16 tolerance.

Weights, prompts and batches come from ``--seed``.  Without a TPU, or
without the repo's ``src/`` next to this file, it exits non-zero and
prints no result.  It also exits non-zero when any phase fails.  The last
line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

  python chip_smoke.py [--seed 0]
  python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TOKENWEAVE_ROWS = 4 * 1024    # the long-prompt prefill group
TP_TOLERANCE = 5e-2           # max |logit gap| / max |logit|, bf16


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds XLA spent compiling, and persistent-cache hits, as JAX's
    monitoring events report them."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __str__(self):
        return (f"{self.count} compiles, {self.seconds:.1f} s compiling, "
                f"{self.cache_hits} persistent-cache hits")


def peak_bytes() -> str:
    import jax
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    peaks = [s.get("peak_bytes_in_use") for s in stats]
    if any(p is None for p in peaks):
        return "not reported by this backend"
    return ", ".join(f"{p / 2**30:.2f} GiB" for p in peaks)


def _ok(req) -> bool:
    return req.ok and len(req.output) > 0


# -- one chip: serve -------------------------------------------------------


def serve_phase(seed: int, arch: str = "chatglm3-6b", smoke: bool = False,
                scfg=None, long_lens=(600, 1000), short_lens=(16, 200),
                n_each: int = 4, max_new: int = 32,
                tw_rows: int = TOKENWEAVE_ROWS, diff_len: int = 256):
    """Serve ``2 * n_each`` requests, then the two-request differential.
    Returns a list of failure messages (empty: the phase passed)."""
    import jax
    import jax.numpy as jnp

    from repro import api
    from repro.kernels import ops as kops
    from repro.kernels.rmsnorm import row_block
    from repro.serve import Request, ServeConfig

    fails = []
    scfg = scfg or ServeConfig(max_batch=4, s_max=2048, prefill_batch=4,
                               prefill_buckets=(256, 1024))
    program = api.compile(arch, smoke=smoke)
    cfg = program.model.cfg
    t0 = time.perf_counter()
    params = jax.block_until_ready(program.init_params(seed))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    log(f"serve: {cfg.name} layers={cfg.n_layers} d={cfg.d_model} "
        f"params={n_params} init {time.perf_counter() - t0:.1f} s")

    rng = np.random.default_rng(seed)
    lens = np.concatenate([rng.integers(*long_lens, n_each, endpoint=True),
                           rng.integers(*short_lens, n_each, endpoint=True)])
    eng = program.serve(params, scfg)
    for rid, n in enumerate(lens):
        eng.submit(Request(rid=rid, max_new_tokens=max_new,
                           prompt=rng.integers(0, cfg.vocab, n, np.int32)))
    t0 = time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    st = eng.stats
    toks = sum(len(r.output) for r in done)
    ttft = sorted(r.first_token_s - r.submitted_s for r in done
                  if r.first_token_s)
    log(f"serve: prompt lengths {lens.tolist()}")
    log(f"serve: {len(done)} requests, {toks} tokens, "
        f"failed={st['failed']} shed={st['shed']} in {wall:.1f} s "
        "(compiles included)")
    if ttft:
        log(f"serve: TTFT min={ttft[0]:.2f} s median="
            f"{ttft[len(ttft) // 2]:.2f} s max={ttft[-1]:.2f} s "
            "(compiles included)")
    log(f"serve: dispatch {eng.dispatch_log} tier steps "
        f"{st['tier_steps']}")
    if len(done) != len(lens) or st["failed"] or st["shed"]:
        fails.append(f"serve: failed={st['failed']} shed={st['shed']} "
                     f"of {len(lens)}")
    for r in done:
        out = np.asarray(r.output)
        if not _ok(r) or len(out) != max_new \
                or ((out < 0) | (out >= cfg.vocab)).any():
            fails.append(f"serve: request {r.rid} {r.result} "
                         f"output {out.tolist()}")

    interp = kops.interpret_mode()
    tw = {k: n for k, n in kops.traced.items()
          if k[0] == "fused_add_rmsnorm" and k[2] == tw_rows}
    for (_, ip, rows, d, br), n in tw.items():
        log(f"serve: TokenWeave fused_add_rmsnorm traced rows={rows} d={d} "
            f"block_rows={br} (kernel block "
            f"{row_block(rows, d, 2, 4, br)[0]} rows) interpret={ip}")
    if not any(k[1] == interp for k in tw):
        fails.append(f"serve: no fused_add_rmsnorm over {tw_rows} rows "
                     f"with interpret={interp}; traced {dict(kops.traced)}")

    # lowered vs interpreted plan replay: same requests, same shapes
    prompts = [rng.integers(0, cfg.vocab, diff_len, np.int32)
               for _ in range(2)]
    outs = {}
    for lowered in (True, False):
        e = program.serve(params, scfg, lowered=lowered)
        for i, p in enumerate(prompts):
            e.submit(Request(rid=100 + i, prompt=p, max_new_tokens=max_new))
        got = e.run()
        e.shutdown()
        bad = [r for r in got if not _ok(r)]
        if bad:
            fails.append(f"differential lowered={lowered}: "
                         f"{[str(r.result) for r in bad]}")
        outs[lowered] = {r.rid: list(r.output) for r in got}
    pairs = [(a, b) for rid in outs[True]
             for a, b in zip(outs[True][rid], outs[False].get(rid, []))]
    match = sum(a == b for a, b in pairs)
    ids = jnp.asarray(np.stack(prompts))
    gap = float(jnp.max(jnp.abs(
        first_logits(program, params, ids, lowered=True)
        - first_logits(program, params, ids, lowered=False))))
    log(f"differential: lowered vs interpreted greedy tokens match "
        f"{match}/{2 * max_new}; first-step logit max |gap| = {gap}")
    if match != 2 * max_new or not np.isfinite(gap):
        fails.append(f"differential: {match}/{2 * max_new} tokens match, "
                     f"logit gap {gap}")
    program.close()
    return fails


def first_logits(program, params, ids, *, lowered: bool):
    """Prefill logits of ``ids`` through the program's model and policy,
    with the plan replayed lowered or interpreted."""
    import jax
    import jax.numpy as jnp

    from repro.core.scheduler import ScheduleContext
    from repro.models.base import build_forward
    model = program.model
    B, S = ids.shape
    segs, _ = model.build_segments("prefill", B, S, s_max=S)
    info = ScheduleContext(local_batch=B, seq_len=S, phase="prefill",
                           arch=model.cfg.name)
    fwd = build_forward(segs, program.policy, info, lowered=lowered,
                        op_config=model.op_closure_config())
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return jax.jit(lambda p, b: fwd(p, b))(
        params, {"ids": ids, "positions": pos})["logits"]


# -- one chip: train -------------------------------------------------------


def train_phase(seed: int, arch: str = "smollm-135m", smoke: bool = False,
                batch: int = 8, seq: int = 2048, steps: int = 5,
                lr: float = 1e-3):
    import jax
    import jax.numpy as jnp

    from repro import api
    from repro.optim import AdamWConfig
    from repro.train import TrainStepConfig

    program = api.compile(arch, smoke=smoke)
    cfg = program.model.cfg
    tcfg = TrainStepConfig(optimizer=AdamWConfig(lr=lr), warmup=1,
                           total_steps=steps)
    step = program.train_step(batch, seq, cfg=tcfg)
    params = program.init_params(seed, phase="train")
    opt = step.init_opt(params)
    fn = jax.jit(step.fn, donate_argnums=(0, 1))
    toks = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0,
                              cfg.vocab, jnp.int32)
    data = {"ids": toks[:, :-1], "labels": toks[:, 1:],
            "positions": jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32),
                                          (batch, seq))}
    losses, times = [], []
    for i in range(steps):
        t0 = time.perf_counter()
        params, opt, m = fn(params, opt, data, jnp.int32(i))
        losses.append(float(m["loss"]))
        times.append(time.perf_counter() - t0)
    log(f"train: {cfg.name} layers={cfg.n_layers} d={cfg.d_model} "
        f"batch={batch}x{seq} losses {losses}")
    log(f"train: step seconds {[round(t, 3) for t in times]} "
        "(first includes compile)")
    program.close()
    if not all(np.isfinite(losses)) or not losses[-1] < losses[0]:
        return [f"train: loss not finite and falling: {losses}"]
    return []


# -- four chips: tensor parallel against one chip --------------------------


def tp_phase(seed: int, arch: str = "chatglm3-6b", smoke: bool = False,
             tp: int = 4, batch: int = 4, seq: int = 1024,
             s_max: int = 2048):
    """Prefill + one greedy decode step on a (data=1, model=tp) mesh
    against the same steps on one chip with the same parameters."""
    import jax
    import jax.numpy as jnp

    from repro import api
    from repro.launch.sharding import dense_tp_params

    def steps(program, params, shard=None):
        """(prefill logits, decode logits) of the same prompt; the decode
        token is the one-chip reference's greedy pick."""
        pre = program.prefill(batch, seq, s_max=s_max)
        dec = program.decode_tiers(batch, s_max, tiers=(batch,))[batch]
        def jit(s):
            kw = {"in_shardings": s.in_shardings} if shard else {}
            return jax.jit(lambda p, b: s.fn(p, b), **kw)
        out = jit(pre)(params, {"ids": ids, "positions": pos})
        pad = ((0, 0), (0, 0), (0, s_max - seq), (0, 0), (0, 0))
        caches = {"k_cache": jnp.pad(out["layers.k"], pad),
                  "v_cache": jnp.pad(out["layers.v"], pad)}
        pre_logits = np.asarray(out["logits"][:, -1], np.float32)
        nonlocal tok
        if tok is None:
            tok = jnp.asarray(pre_logits.argmax(-1).astype(np.int32))
        clen = jnp.full((batch,), seq, jnp.int32)
        out = jit(dec)(params, {"ids": tok[:, None], "cache_len": clen,
                                "positions": clen[:, None], **caches})
        return pre_logits, np.asarray(out["logits"][:, -1], np.float32)

    rng = np.random.default_rng(seed)
    one = api.compile(arch, smoke=smoke)
    cfg = one.model.cfg
    ids = jnp.asarray(rng.integers(0, cfg.vocab, (batch, seq), np.int32))
    pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (batch, seq))
    tok = None
    t0 = time.perf_counter()
    params = one.init_params(seed)
    ref = steps(one, params)
    log(f"tp: one-chip reference {cfg.name} layers={cfg.n_layers} "
        f"prefill {batch}x{seq} + 1 decode step in "
        f"{time.perf_counter() - t0:.1f} s")
    host = jax.device_get(params)
    del params

    mesh = jax.make_mesh((1, tp), ("data", "model"),
                         devices=jax.devices()[:tp],
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    many = api.compile(arch, smoke=smoke, mesh=mesh)
    shd = many.prefill(batch, seq, s_max=s_max).in_shardings[0]
    t0 = time.perf_counter()
    params = jax.device_put(dense_tp_params(host, many.model), shd)
    del host
    got = steps(many, params, shard=True)
    log(f"tp: {tp}-way tensor parallel on mesh {dict(mesh.shape)} in "
        f"{time.perf_counter() - t0:.1f} s")
    fails = []
    for name, a, b in zip(("prefill", "decode"), got, ref):
        rel = float(np.abs(a - b).max() / np.abs(b).max())
        agree = float((a.argmax(-1) == b.argmax(-1)).mean())
        log(f"tp: {name} logits max|gap|/max|logit| = {rel} "
            f"(tolerance {TP_TOLERANCE}), argmax agreement {agree}")
        if not np.isfinite(a).all() or not rel <= TP_TOLERANCE:
            fails.append(f"tp: {name} logits differ by {rel}")
    return fails


# -- driver ----------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    import jax

    from repro import hw
    from repro.kernels import ops as kops
    from repro.launch.jax_cache import use_compile_cache

    devs = jax.devices()
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(devs)}")
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 2
    log(f"device: hardware entry {hw.chip(dev.device_kind)}")
    if kops.interpret_mode():
        print("chip_smoke: Pallas kernels would be interpreted",
              file=sys.stderr)
        return 2
    log(f"cache: {use_compile_cache()}")
    clock = CompileClock()

    phases = ([("tp", tp_phase)] if args.chips == 4
              else [("serve", serve_phase), ("train", train_phase)])
    fails = []
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            fails += phase(args.seed)
        except Exception:                           # noqa: BLE001
            fails.append(f"{name}: {traceback.format_exc()}")
        log(f"{name}: {time.perf_counter() - t0:.1f} s; {clock}; "
            f"peak_bytes_in_use {peak_bytes()}")
    for f in fails:
        print(f"chip_smoke FAILED {f}", file=sys.stderr)
    if fails:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
