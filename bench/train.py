"""Training cells: ``Program.train_step(...).fn`` driven on seeded
batches.

Set-up builds one object, the jitted step with its parameters and AdamW
state, and drives it through its first three steps, each on a fresh batch
made on the device from the seed; the first of them compiles.  The window
then goes on with the same object.  Tokens per second count whole steps:
the window ends at the first step that completes after ``seconds``.

After the window the plain reference follows the same three steps from
the same weights in float32 and the harness compares the first step's
loss, the first gradient as AdamW received it (its first moment after
one step, over ``1 - b1``) by the norm of each leaf and by the norm of
each leaf's difference, and each weight's change over the three
steps.  With ``control`` the fp8 reference's three steps are put in the
program's place and go through the same comparison, which must fail.
"""
from __future__ import annotations

import functools
import gc
import time

import numpy as np

from . import common, weights

CHECK_STEPS = 3


def make_batch(key, i, batch: int, seq: int, vocab: int):
    import jax
    import jax.numpy as jnp
    toks = jax.random.randint(jax.random.fold_in(key, i), (batch, seq + 1),
                              0, vocab, jnp.int32)
    return {"ids": toks[:, :-1], "labels": toks[:, 1:],
            "positions": jnp.broadcast_to(
                jnp.arange(seq, dtype=jnp.int32), (batch, seq))}


def _f32_copy(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(lambda a: jnp.array(a, jnp.float32), tree)


def setup(cfg: dict, mix: dict, seed: int, devices):
    """The step object and the readings of its first three steps."""
    import jax
    import jax.numpy as jnp
    from repro import api
    from repro.optim import AdamWConfig
    from repro.train import TrainStepConfig

    if len(devices) > 1:
        raise NotImplementedError(
            "a training cell runs on one chip: the readings of the first "
            "steps are taken from the program's one-chip parameter tree")
    m = common.model_dims(cfg)
    fam = common.family(m)
    fam.check_model(m)
    o = mix["optimizer"]
    program = api.compile(fam.arch_config(cfg, m))
    tcfg = TrainStepConfig(
        optimizer=AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"],
                              eps=o["eps"], weight_decay=o["weight_decay"],
                              grad_clip=o["grad_clip"]),
        warmup=o["warmup"], total_steps=o["total_steps"])
    B, S = mix["batch"], mix["seq"]
    step = program.train_step(B, S, cfg=tcfg)
    key = common.seed_key(seed)
    w = weights.make(m, key, jnp.bfloat16)
    w0 = _f32_copy(w)
    params = weights.to_program(w, m)
    del w
    opt = step.init_opt(params)
    fn = jax.jit(step.fn, donate_argnums=(0, 1))
    data_key = jax.random.fold_in(key, 1)
    batch_fn = jax.jit(functools.partial(make_batch, batch=B, seq=S,
                                         vocab=m["vocab"]))
    losses, m1 = [], None
    for i in range(CHECK_STEPS):
        params, opt, met = fn(params, opt, batch_fn(data_key, i),
                              jnp.int32(i))
        losses.append(float(met["loss"]))
        if i == 0:
            m1 = {k: jnp.array(v["m"]) for k, v in
                  weights.from_program(opt["state"], m).items()}
    p3 = _f32_copy(weights.from_program(params, m))
    jax.block_until_ready((p3, m1))
    state = {"program": program, "fn": fn, "params": params, "opt": opt,
             "batch_fn": batch_fn, "data_key": data_key, "next": CHECK_STEPS}
    readings = {"losses": losses, "m1": m1, "p3": p3, "w0": w0}
    return state, readings, m


def run_window(st: dict, seconds: float, tracer):
    """Steps until the first completion after ``seconds``; returns
    (steps, elapsed seconds)."""
    import jax
    import jax.numpy as jnp
    fn, bf, dk = st["fn"], st["batch_fn"], st["data_key"]
    params, opt, i = st["params"], st["opt"], st["next"]
    tracer.window_opened()
    t0 = time.perf_counter()
    pending, steps, elapsed = None, 0, 0.0
    while True:
        with tracer.span("train.step"):
            params, opt, met = fn(params, opt, bf(dk, i), jnp.int32(i))
        i += 1
        if pending is not None:
            jax.block_until_ready(pending)
            steps += 1
            elapsed = time.perf_counter() - t0
            tracer.tick()
            if elapsed >= seconds:
                break
        pending = met["loss"]
    jax.block_until_ready((params, opt))
    st.update(params=params, opt=opt, next=i)
    return steps, elapsed


def leaf_gap(prog: dict, ref: dict, keep=None) -> tuple:
    """Worst leaf's |norm(prog) - norm(ref)| over max(norm(ref), the
    median leaf's norm(ref)); returns (gap, leaf)."""
    norms = {k: float(np.linalg.norm(np.asarray(ref[k], np.float64)))
             for k in ref}
    med = float(np.median(list(norms.values())))
    worst, which = 0.0, None
    for k in ref:
        if keep is not None and k not in keep:
            continue
        p = float(np.linalg.norm(np.asarray(prog[k], np.float64)))
        g = abs(p - norms[k]) / max(norms[k], med)
        if g >= worst:
            worst, which = g, k
    return worst, which


def reference(m: dict, mix: dict, readings: dict, data_key, batch_fn,
              mode: str = "f32") -> dict:
    """The reference's three steps from the same weights and batches."""
    import jax
    ref = common.reference(m)
    o = mix["optimizer"]
    mh = ref.hashable(m)
    w = readings["w0"]
    state = None
    losses, g1 = [], None
    for k in range(CHECK_STEPS):
        b = batch_fn(data_key, k)
        loss, g = ref.loss_and_grads(w, b["ids"], b["labels"], mh,
                                     mode=mode)
        w, state, gc_ = ref.adamw_step(w, g, state, k, o)
        losses.append(float(loss))
        if k == 0:
            g1 = jax.device_get(gc_)
    return {"losses": losses, "g1": g1, "w3": jax.device_get(w)}


def diff_gap(prog: dict, ref: dict) -> float:
    """Worst leaf's norm(prog - ref) over max(norm(ref), the median
    leaf's norm(ref))."""
    norms = {k: float(np.linalg.norm(np.asarray(ref[k], np.float64)))
             for k in ref}
    med = float(np.median(list(norms.values())))
    return max(float(np.linalg.norm(np.asarray(prog[k], np.float64)
                                    - np.asarray(ref[k], np.float64)))
               / max(norms[k], med) for k in ref)


def compare(readings: dict, ref: dict, b1: float, limits: dict) -> dict:
    """The four numbers compared, each beside its limit, and the leaves
    where the gaps of norms are widest."""
    lp, lr = readings["losses"], ref["losses"]
    g_prog = {k: np.asarray(v, np.float64) / (1 - b1)
              for k, v in readings["m1"].items()}
    grad_gap, grad_leaf = leaf_gap(g_prog, ref["g1"])
    gn = {k: float(np.linalg.norm(np.asarray(v, np.float64)))
          for k, v in ref["g1"].items()}
    med = float(np.median(list(gn.values())))
    moved = {k for k, v in gn.items() if v >= 1e-3 * med}
    w0 = {k: np.asarray(v, np.float64) for k, v in readings["w0"].items()}
    d_prog = {k: np.asarray(readings["p3"][k], np.float64) - w0[k]
              for k in w0}
    d_ref = {k: np.asarray(ref["w3"][k], np.float64) - w0[k] for k in w0}
    upd_gap, upd_leaf = leaf_gap(d_prog, d_ref, keep=moved)
    nums = {"loss_gap": abs(lp[0] - lr[0]) / abs(lr[0]),
            "grad_gap": grad_gap, "update_gap": upd_gap,
            "grad_diff": diff_gap(g_prog, ref["g1"])}
    out = {k: {"value": v, "limit": limits[k]} for k, v in nums.items()}
    out.update(grad_leaf=grad_leaf, update_leaf=upd_leaf, losses=lp,
               reference_losses=lr, leaves_left_out=sorted(set(w0) - moved))
    return out


def run(cfg: dict, mix: dict, seed: int, seconds: float, tracer, clock,
        devices, control: bool = False) -> dict:
    st, readings, m = setup(cfg, mix, seed, devices)
    c0 = clock.mark()
    tps = mix["batch"] * mix["seq"]
    w0_t = time.perf_counter()
    steps, elapsed = run_window(st, seconds, tracer)
    c1 = clock.mark()
    tracer.stop()
    peak = common.peak_bytes(devices)
    batch_fn, data_key = st["batch_fn"], st["data_key"]
    st.clear()
    gc.collect()
    t0 = time.perf_counter()
    ref = reference(m, mix, readings, data_key, batch_fn)
    b1 = mix["optimizer"]["b1"]
    chk = compare(readings, ref, b1, cfg["limits"])
    if control:
        ctl = reference(m, mix, readings, data_key, batch_fn, mode="fp8")
        as_prog = {"losses": ctl["losses"], "w0": readings["w0"],
                   "p3": ctl["w3"],
                   "m1": {k: (1 - b1) * np.asarray(v)
                          for k, v in ctl["g1"].items()}}
        program = chk
        chk = compare(as_prog, ref, b1, cfg["limits"])
        chk["program"] = {k: v["value"] for k, v in program.items()
                          if isinstance(v, dict)}
    chk["reference_s"] = time.perf_counter() - t0
    return {"kind": "train", "m": m, "cfg": cfg, "mix": mix,
            "seconds": seconds, "w0": w0_t,
            "numbers": {"train_tokens_per_s": steps * tps / elapsed,
                        "steps": steps, "elapsed_s": elapsed,
                        "attempted": steps, "failed": 0},
            "compiles_window": c1[0] - c0[0], "setup_marks": c0,
            "peak_bytes": peak, "check": chk}
