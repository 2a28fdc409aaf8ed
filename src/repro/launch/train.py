"""Training launcher.

Single-host execution path (the multi-device production path is exercised
by dryrun.py; this entry point actually *runs* steps, so it sizes the
model to the local device set — CPU here, a real pod on TPU):

  python -m repro.launch.train --arch smollm-135m --steps 200 \
      --batch 8 --seq 256 --strategy dynamic --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import api
from ..data import DataConfig, SyntheticBackend, TokenPipeline
from ..ft.elastic import FailureSimulator
from ..optim import AdamWConfig
from ..train import TrainLoopConfig, TrainStepConfig, train_loop
from .jax_cache import use_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--strategy", default="dynamic")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--quantized-opt", action="store_true")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--crash-at", type=int, default=-1,
                    help="inject a simulated failure at this step")
    args = ap.parse_args(argv)
    use_compile_cache()

    program = api.compile(args.arch, policy=args.strategy,
                          smoke=args.smoke)
    cfg = program.model.cfg
    tcfg = TrainStepConfig(
        optimizer=AdamWConfig(lr=args.lr, quantized=args.quantized_opt),
        remat=args.remat, compress_grads=args.grad_compress,
        warmup=max(args.steps // 20, 1), total_steps=args.steps)
    step = program.train_step(args.batch, args.seq, cfg=tcfg)
    params = program.init_params(0, phase="train")
    opt = step.init_opt(params)
    jit_step = jax.jit(step.fn, donate_argnums=(0, 1))

    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"strategy={args.strategy}")

    pipe = TokenPipeline(SyntheticBackend(cfg.vocab),
                         DataConfig(seq_len=args.seq,
                                    global_batch=args.batch))

    def to_device(b):
        pos = np.broadcast_to(np.arange(args.seq, dtype=np.int32),
                              (args.batch, args.seq))
        if cfg.rope == "mrope":
            pos = np.broadcast_to(pos, (3, args.batch, args.seq))
        out = {"ids": jnp.asarray(b["ids"]), "labels": jnp.asarray(b["labels"]),
               "positions": jnp.asarray(pos)}
        if cfg.family == "vlm":
            out["vis"] = jnp.zeros((args.batch, args.seq, cfg.d_model),
                                   jnp.bfloat16)
        if cfg.family == "encdec":
            out["frames"] = jnp.zeros((args.batch, args.seq, cfg.d_model),
                                      jnp.bfloat16)
        return out

    sim = (FailureSimulator(crash_steps=(args.crash_at,))
           if args.crash_at >= 0 else None)
    t0 = time.perf_counter()
    params, opt, hist = train_loop(
        jit_step, params, opt, pipe,
        TrainLoopConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                        ckpt_every=args.ckpt_every, log_every=10),
        failure_sim=sim, to_device=to_device, log=print)
    dt = time.perf_counter() - t0
    toks = args.steps * args.batch * args.seq
    print(f"done: {args.steps} steps in {dt:.1f}s "
          f"({toks/dt:.0f} tok/s), final loss "
          f"{hist[-1]['loss']:.4f} (first {hist[0]['loss']:.4f})")
    return hist


if __name__ == "__main__":
    main()
