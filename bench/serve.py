"""Serving cells: ``Program.serve`` driven by a traffic mix.

Set-up makes the weights from the seed, builds the engine and warms every
shape the mix can reach.  The window then offers the mix's load (open
loop: requests due on a schedule; closed loop: each worker sends its next
request when the last one finished) through ``ServeEngine.submit`` and
``ServeEngine.step``, timestamping each output token after the step that
delivered it.  Open-loop latency counts from when a request was due.

After the window the program is shut down and the plain reference
recomputes the logits of a sample of finished requests over their prompts
and served tokens: the widest gap by which a served token's logit lies
below the reference's best is compared with the configuration's limit.
With ``control`` the tokens compared are the fp8 reference's instead,
put in the program's place: the same comparison must then fail.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from . import common, traffic, weights


class Track:
    """One request as the harness sees it."""

    __slots__ = ("plan", "req", "due", "tokens", "seen")

    def __init__(self, plan, req, due):
        self.plan, self.req, self.due = plan, req, due
        self.tokens: list = []        # host time each output token arrived
        self.seen = 0

    @property
    def done(self) -> bool:
        return bool(self.req.done_s)

    @property
    def ok(self) -> bool:
        return self.req.ok and len(self.req.output) == self.plan.output_len


class Client:
    """Submits requests, steps the engine and timestamps tokens."""

    def __init__(self, eng, vocab: int, seed: int, span=None):
        self.eng, self.vocab, self.seed = eng, vocab, seed
        self.live: dict = {}
        self.all: list = []
        self.next_rid = 0
        self.span = span or _no_span

    def submit(self, plan, due: float, prompt=None):
        from repro.serve import Request
        if prompt is None:
            prompt = traffic.prompt_tokens(self.seed, plan.index,
                                           plan.prompt_len, self.vocab)
        req = Request(rid=self.next_rid, prompt=prompt,
                      max_new_tokens=plan.output_len)
        self.next_rid += 1
        t = Track(plan, req, due)
        self.eng.submit(req)
        self.all.append(t)
        if not req.done_s:
            self.live[req.rid] = t
        return t

    def step(self) -> list:
        """One engine iteration; returns the requests that ended."""
        with self.span("engine.step"):
            self.eng.step()
        with self.span("stats"):
            now = time.perf_counter()
            ended = []
            for rid, t in list(self.live.items()):
                out = t.req.output
                n = len(out) - (1 if out and out[-1] == -100 else 0)
                if n > t.seen:
                    t.tokens.extend([now] * (n - t.seen))
                    t.seen = n
                if t.req.done_s:
                    ended.append(t)
                    del self.live[rid]
        return ended

    def run_until_idle(self):
        while self.live:
            self.step()


class _no_span:
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


# -- set-up ----------------------------------------------------------------


def warm_waves(eng, mix: dict, serve_cfg: dict) -> list:
    """Waves of (prompt_len, max_new) that together reach every shape the
    mix can: each prefill bucket at each group tier, each chunk length at
    each tier, and a wave whose rows end in halves, so the decode steps
    run at every tier and every compaction move happens once."""
    buckets = serve_cfg["prefill_buckets"]
    big = buckets[-1]
    tiers = eng.prefill_tiers
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    waves = []
    reach = sorted({next(b for b in buckets if n <= b)
                    for n in range(lo, min(hi, big) + 1)})
    for b in reach:
        for g in tiers:
            waves.append([(b, 1)] * g)
    chunk_len = {}
    for n in range(big + 1, hi + 1):
        for _, c in eng._chunk_plan(n):
            chunk_len.setdefault(c, n)
    for n in sorted(set(chunk_len.values())):
        for g in tiers:
            waves.append([(n, 1)] * g)
    first = reach[0] if reach else big
    waves.append([(first, k) for k in stagger(eng.cfg.max_batch)])
    return waves


def stagger(rows: int, gap: int = 4) -> list:
    """max_new_tokens of ``rows`` requests admitted together into rows
    0..rows-1 such that the lower half of the occupied rows ends first,
    again and again: the tier halves each time and every row from 1 up
    moves down once (the engine moves the highest row to the lowest
    free one)."""
    at = list(range(rows))                 # request at each row
    stage = [0] * rows
    s, t = 1, rows
    while t > 1:
        half = t // 2
        for r in range(half):
            stage[at[r]] = s
        moved = list(reversed(at[half:t]))
        at = moved + at[t:]
        t -= half
        s += 1
    stage[at[0]] = s
    return [2 + gap * k for k in stage]


def setup(cfg: dict, mix: dict, seed: int, devices):
    """The program, its engine warmed up, the weights and the model dict.
    Over several devices the weights and the program's tree are split
    over the cell's mesh (:func:`common.cell_mesh`)."""
    import jax
    import jax.numpy as jnp
    from repro import api
    from repro.serve import ServeConfig

    m = common.model_dims(cfg)
    fam = common.family(m)
    fam.check_model(m)
    mesh = common.cell_mesh(devices)
    program = api.compile(fam.arch_config(cfg, m), mesh=mesh)
    w = weights.make(m, common.seed_key(seed), jnp.bfloat16, mesh)
    jax.block_until_ready(w)
    sc = cfg["serve"]
    eng = program.serve(weights.to_program(w, m, program), ServeConfig(
        max_batch=sc["max_batch"], s_max=m["s_max"],
        prefill_buckets=tuple(sc["prefill_buckets"]),
        prefill_batch=sc["prefill_batch"], greedy=True))
    warm = Client(eng, m["vocab"], seed)
    for wave in warm_waves(eng, mix, sc):
        for n, k in wave:
            warm.submit(traffic.Planned(-1, n, k), 0.0,
                        prompt=np.zeros(n, np.int32) + 7)
        warm.run_until_idle()
    bad = [t.req.result for t in warm.all if not t.ok]
    if bad:
        raise RuntimeError(f"warm-up requests failed: {bad[:3]}")
    return program, eng, w, m


# -- the window --------------------------------------------------------------


def run_window(eng, mix, plan, seed, seconds, vocab, tracer, at_close,
               drain_s: float = traffic.DRAIN_S):
    """Offer the mix's load for ``seconds``, calling ``at_close()`` once
    when they have passed, then serve on for at most ``drain_s``; returns
    the client, the window's start and the generator's lateness
    (seconds)."""
    d = Client(eng, vocab, seed, span=tracer.span)
    late = []
    closed = mix["kind"] == "closed_loop"
    tracer.window_opened()
    w0 = time.perf_counter()
    if closed:
        nxt = 0
        for _ in range(mix["workers"]):
            d.submit(plan[nxt % len(plan)], 0.0)
            nxt += 1
        while True:
            tracer.tick()
            now = time.perf_counter() - w0
            if now >= seconds:
                at_close()
                break
            ended = d.step() if d.live else []
            with tracer.span("generator"):
                for _ in ended:
                    d.submit(plan[nxt % len(plan)],
                             time.perf_counter() - w0)
                    nxt += 1
        # after the window: long requests may not have finished yet;
        # serve on, with no new ones, until the check has its sample
        need = mix.get("check", {}).get("requests", 0)
        stop = time.perf_counter() + drain_s
        while (d.live and sum(t.ok for t in d.all) < need
               and time.perf_counter() < stop):
            d.step()
        return d, w0, late
    i, n = 0, len(plan)
    due_end = seconds
    while True:
        tracer.tick()
        now = time.perf_counter() - w0
        with tracer.span("generator"):
            while i < n and plan[i].due_s <= now:
                late.append(now - plan[i].due_s)
                d.submit(plan[i], plan[i].due_s)
                i += 1
        if now >= seconds and due_end == seconds:
            at_close()
        if now >= due_end:
            # after the window: serve on, at the same load, until every
            # request due in the window has its first token
            waiting = [t for t in d.all if t.due < seconds
                       and not t.tokens and not t.done]
            if not waiting or now >= seconds + drain_s \
                    or i >= n:
                break
            due_end = now
        if d.live:
            d.step()
        elif i < n:
            time.sleep(max(0.0, min(plan[i].due_s - now, 0.05)))
    return d, w0, late


def window_numbers(d: Client, w0: float, seconds: float, mix: dict) -> dict:
    """End-to-end numbers over the window [w0, w0 + seconds)."""
    w1 = w0 + seconds
    end = time.perf_counter()
    in_window = [t for t in d.all if t.due < seconds]
    ttft = []
    for t in in_window:
        if t.tokens and (t.req.ok or not t.done):
            ttft.append(t.tokens[0] - (w0 + t.due))
        else:      # failed, shed or still waiting: at least this long
            ttft.append(end - (w0 + t.due))
    gaps = []
    tokens = 0
    for t in d.all:
        ts = t.tokens
        tokens += sum(1 for x in ts if w0 <= x < w1)
        gaps.extend(b - a for a, b in zip(ts, ts[1:]) if w0 <= a and b < w1)
    failed = sum(1 for t in in_window if t.done and not t.req.ok)
    return {"ttft_p90_s": common.quantile(ttft, 0.9),
            "serve_output_tokens_per_s": tokens / seconds,
            "attempted": len(in_window), "failed": failed,
            "finished": sum(1 for t in in_window if t.done and t.req.ok),
            "n_ttft": len(ttft), "gaps_ms": [1e3 * g for g in gaps],
            "tokens": tokens}


# -- correctness -------------------------------------------------------------


def pick_sample(d: Client, seed: int, k: int) -> list:
    """``k`` finished requests drawn from the seed, the one with the most
    served tokens among them."""
    done = [t for t in d.all if t.ok]
    if not done:
        return []
    longest = max(done, key=lambda t: (len(t.req.output),
                                       len(t.req.prompt)))
    rest = [t for t in done if t is not longest]
    rng = np.random.default_rng([seed % 2**32, seed >> 32, 77])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def logit_gaps(w: dict, m: dict, seqs: list, mode: str = "f32",
               pad_to: int = 256, budget: int = 2048) -> list:
    """For each (prompt, served) pair, the gap by which each served
    token's reference logit lies below the reference's best.  With
    ``mode="fp8"`` (the control) the token is the one the fp8 reference
    puts first, and the gap is read on the float32 reference.  Sequences
    are padded at the end to a multiple of ``pad_to`` (causal attention:
    padding changes no earlier logit) and run together while the batch
    holds at most ``budget`` positions, which bounds the reference's
    memory beside the weights."""
    import jax.numpy as jnp

    ref = common.reference(m)
    full = [np.concatenate([p, s]).astype(np.int32) for p, s in seqs]
    groups, cur = [], []
    for i, f in enumerate(full):
        P = -(-max(len(full[j]) for j in cur + [i]) // pad_to) * pad_to
        if cur and P * (len(cur) + 1) > budget:
            groups.append(cur)
            cur = []
        cur.append(i)
    groups.append(cur)
    out = {}
    for group in groups:
        P = -(-max(len(full[j]) for j in group) // pad_to) * pad_to
        ids = np.zeros((len(group), P), np.int32)
        for r, j in enumerate(group):
            ids[r, :len(full[j])] = full[j]
        z_all = ref.logits(w, jnp.asarray(ids), m)
        zc_all = (ref.logits(w, jnp.asarray(ids), m, mode=mode)
                  if mode != "f32" else None)
        for r, j in enumerate(group):
            prompt, served = seqs[j]
            pos = np.arange(len(prompt) - 1, len(full[j]) - 1)
            z = z_all[r, pos]
            if zc_all is None:
                tok = jnp.asarray(np.asarray(served, np.int32))
            else:
                tok = jnp.argmax(zc_all[r, pos], -1)
            gap = jnp.max(z, -1) - jnp.take_along_axis(
                z, tok[:, None], -1)[:, 0]
            out[j] = np.asarray(gap, np.float64)
        del z_all, zc_all
    return [out[j] for j in range(len(seqs))]


def check(d: Client, w, m, seed: int, mix: dict, limit: float,
          control: bool = False) -> dict:
    """The widest logit gap of a sample of served requests.  With
    ``control`` the fp8 reference is put in the program's place: at each
    position of the same prompts and served tokens, the token it puts
    first is the one compared (``logit_gap``), and the program's own
    reading is kept beside it (``program``)."""
    sample = pick_sample(d, seed, mix["check"]["requests"])
    seqs = [(np.asarray(t.req.prompt), np.asarray(t.req.output))
            for t in sample]
    bad = [t.req.rid for t in sample
           if ((np.asarray(t.req.output) < 0)
               | (np.asarray(t.req.output) >= m["vocab"])).any()]
    gaps = logit_gaps(w, m, seqs) if seqs and not bad else []
    widest = max((float(g.max()) for g in gaps), default=math.inf)
    out = {"logit_gap": {"value": widest, "limit": limit},
           "served_tokens_checked": sum(len(s) for _, s in seqs),
           "requests_checked": len(seqs), "out_of_vocab": bad}
    if control:
        cg = logit_gaps(w, m, seqs, mode="fp8") if seqs else []
        out["program"] = {"logit_gap": widest}
        out["logit_gap"] = {"value": max((float(g.max()) for g in cg),
                                         default=math.inf), "limit": limit}
    return out


# -- the cell ----------------------------------------------------------------


def run(cfg: dict, mix: dict, seed: int, seconds: float, tracer, clock,
        devices, control: bool = False) -> dict:
    program, eng, w, m = setup(cfg, mix, seed, devices)
    plan = traffic.plan_requests(mix, seconds)
    st0 = eng.stats
    c0 = clock.mark()
    closed = {}
    tracer.on_start = lambda: closed.update(
        stats_trace=eng.stats, t_trace=time.perf_counter())

    def at_close():
        closed.update(stats=eng.stats, clock=clock.mark(),
                      t_close=time.perf_counter())
        tracer.stop()

    d, w0, late = run_window(eng, mix, plan, seed, seconds, m["vocab"],
                             tracer, at_close)
    st1, c1 = closed["stats"], closed["clock"]
    nums = window_numbers(d, w0, seconds, mix)
    peak = common.peak_bytes(devices)
    tracer.stop()
    d.eng = None
    eng.shutdown()
    program.close()
    del eng, program
    gc.collect()
    t0 = time.perf_counter()
    chk = check(d, w, m, seed, mix, cfg["limits"]["logit_gap"], control)
    chk["reference_s"] = time.perf_counter() - t0
    return {"kind": "serve", "m": m, "cfg": cfg, "mix": mix,
            "seconds": seconds,
            "numbers": nums, "late": late, "stats0": st0, "stats1": st1,
            "stats_trace": closed.get("stats_trace"),
            "t_trace": closed.get("t_trace"), "t_close": closed["t_close"],
            "compiles_window": c1[0] - c0[0], "setup_marks": c0,
            "peak_bytes": peak, "check": chk, "client": d, "w0": w0}
