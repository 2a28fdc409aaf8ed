"""Spans and counters inside the serve engine and the PlanStore: phase
counters, per-request stamps, named device programs, and the spans as a
profiler trace shows them."""
import glob
import re

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.strategies import get_strategy
from repro.models.layers import MeshInfo
from repro.models.registry import build_model
from repro.serve import Request, ServeConfig, ServeEngine, SpecConfig
from repro.serve import engine as engine_mod
from repro.spans import span

HARNESS = ("generator", "engine.step", "train.step", "stats", "window")
CHILDREN = ("engine.admit", "engine.decode", "engine.harvest_wait",
            "engine.harvest")


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("chatglm3-6b")
    model = build_model(cfg, MeshInfo(tp=1, dp=1))
    segs, _ = model.build_segments("prefill", 1, 32, s_max=64)
    params = model._init_from_segments(segs, jax.random.PRNGKey(0))
    return model, params


def make_engine(model, params, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("s_max", 64)
    kw.setdefault("prefill_buckets", (16, 32))
    return ServeEngine(model, params, get_strategy("sequential"),
                       ServeConfig(**kw))


def prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 100, n).astype(np.int32)


def test_span_counts_and_times_its_block():
    counters = {}
    for _ in range(3):
        with span(counters, "plan.lower", bucket=16):
            pass
    with pytest.raises(ValueError):
        with span(counters, "plan.lower"):
            raise ValueError("the span still closes")
    assert counters["plan.lower"]["count"] == 4
    assert 0 <= counters["plan.lower"]["seconds"] < 1.0
    # the counter is picked by name at exit: a rejected attempt moves
    with span(counters, "plan.restore") as sp:
        sp.name = "plan.restore_rejected"
    assert "plan.restore" not in counters
    assert counters["plan.restore_rejected"]["count"] == 1


def test_phase_counters_and_snapshots(setup):
    """Each iteration's direct children sum to no more than the
    iteration; a snapshot taken earlier does not move as the engine
    keeps stepping."""
    model, params = setup
    eng = make_engine(model, params)
    for i, n in enumerate((10, 16, 40, 7, 30)):
        eng.submit(Request(rid=i, prompt=prompt(n, i), max_new_tokens=5))
    eng.step()
    first = eng.stats
    frozen = {k: dict(v) for k, v in first["spans"].items()}
    tiers = dict(first["tier_steps"])
    it, busy = 1, True
    while busy:
        busy = eng.step()
        it += 1
        sp = eng.stats["spans"]
        children = sum(sp[k]["seconds"] for k in CHILDREN if k in sp)
        assert children <= sp["engine.iteration"]["seconds"]
    sp = eng.stats["spans"]
    assert sp["engine.iteration"]["count"] == it
    assert sp["engine.admit"]["count"] == it
    assert sp["engine.prefill"]["count"] >= 1
    assert sp["engine.chunk"]["count"] >= 2          # the 40-token prompt
    assert sp["engine.compact"]["count"] == sp["engine.decode"]["count"]
    assert sp["engine.harvest"]["count"] == sp["engine.harvest_wait"]["count"]
    assert {k: dict(v) for k, v in first["spans"].items()} == frozen
    assert first["tier_steps"] == tiers
    # the PlanStore's builds are spans too, and keep their stats keys
    ps = eng.stats["plan_store"]
    assert ps["spans"]["plan.lower"]["count"] == ps["misses"]
    assert ps["lower_s"] == ps["spans"]["plan.lower"]["seconds"] > 0
    assert ps["specialize_s"] == \
        ps["spans"].get("plan.specialize", {}).get("seconds", 0.0)


def _ordered(r):
    return r.submitted_s <= r.admitted_s <= r.first_token_s <= r.done_s


def test_request_stamps_in_order(setup):
    """Plain (a full bucket), bucket-padded and chunked requests."""
    model, params = setup
    eng = make_engine(model, params)
    reqs = [Request(rid=0, prompt=prompt(16, 1), max_new_tokens=4),
            Request(rid=1, prompt=prompt(9, 2), max_new_tokens=4),
            Request(rid=2, prompt=prompt(45, 3), max_new_tokens=4)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.ok and r.admitted_s > 0 and _ordered(r) for r in reqs)


def test_resumed_request_keeps_its_first_admission(setup):
    model, params = setup
    eng = make_engine(model, params, max_batch=1)
    low = Request(rid=0, prompt=prompt(12, 10), max_new_tokens=10)
    eng.submit(low)
    for _ in range(4):
        eng.step()
    admitted = low.admitted_s
    high = Request(rid=1, prompt=prompt(12, 11), max_new_tokens=3,
                   priority=5)
    eng.submit(high)
    eng.run()
    assert low.preemptions >= 1 and eng.stats["resumed"] >= 1
    assert low.admitted_s == admitted
    assert low.ok and high.ok and _ordered(low) and _ordered(high)
    # the resumed request waited for its row after the first admission
    assert high.admitted_s > admitted


def _expected_name(key) -> str:
    kind = key[0]
    if kind == "prefill":
        return f"prefill_b{key[3]}_s{key[4]}"
    if kind == "chunk":
        return f"chunk_b{key[2]}_s{key[3]}"
    if kind == "decode":
        return f"decode_t{key[3]}"
    if kind == "spec_verify":
        return f"spec_verify_t{key[3]}_k{key[4]}"
    assert kind == "spec_draft", key
    return f"spec_draft_t{key[4]}_k{key[5]}"


@pytest.mark.parametrize("spec", [None, SpecConfig(proposer="self", k=2)])
def test_every_engine_jit_lowers_under_its_name(setup, monkeypatch, spec):
    model, params = setup
    lowered = {}
    real = engine_mod._jit

    def spy(fn, name, donate=()):
        jitted = real(fn, name, donate)

        def call(*args):
            if name not in lowered:
                text = jitted.lower(*args).as_text()
                lowered[name] = text.split(" ", 2)[1]
            return jitted(*args)
        return call

    monkeypatch.setattr(engine_mod, "_jit", spy)
    eng = make_engine(model, params, spec=spec)
    for i, n in enumerate((16, 9, 40, 12, 20)):
        eng.submit(Request(rid=i, prompt=prompt(n, i), max_new_tokens=6))
    eng.run()
    want = {_expected_name(k) for k in eng.store._execs}
    assert set(lowered) == want
    assert all(mod == f"@jit_{name}" for name, mod in lowered.items())
    kinds = {re.sub(r"_[bt]\d.*", "", name) for name in lowered}
    assert kinds == ({"prefill", "chunk", "decode"} if spec is None
                     else {"prefill", "chunk", "spec_verify", "spec_draft"})


def test_spans_in_a_profiler_trace(setup, tmp_path):
    """The engine's spans land on a host line of a real trace, nested in
    the caller's annotation, and none takes a harness's name."""
    from jax.profiler import ProfileData
    model, params = setup
    eng = make_engine(model, params)
    eng.submit(Request(rid=0, prompt=prompt(9, 4), max_new_tokens=3))
    eng.run()                                     # compile outside
    eng.submit(Request(rid=1, prompt=prompt(9, 5), max_new_tokens=3))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("caller"):
            eng.run()
    finally:
        jax.profiler.stop_trace()
    pb = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
               dict(e.stats))
              for p in ProfileData.from_file(pb[0]).planes
              if p.name.startswith("/host:")
              for line in p.lines for e in line.events]
    caller = [(a, b) for n, a, b, _ in events if n == "caller"]
    assert len(caller) == 1
    lo, hi = caller[0]
    ours = [(n, a, b, st) for n, a, b, st in events
            if n.startswith(("engine.", "plan."))]
    names = {n for n, *_ in ours}
    assert {"engine.iteration", "engine.admit", "engine.prefill",
            "engine.decode", "engine.compact", "engine.harvest_wait",
            "engine.harvest"} <= names
    assert not names & set(HARNESS)
    assert all(lo <= a and b <= hi for _, a, b, _ in ours)
    dec = [st for n, _, _, st in ours if n == "engine.decode"]
    assert all(st["tier"] >= 1 and "rids" in st for st in dec)
    its = {st["iter"] for n, _, _, st in ours if n == "engine.iteration"}
    assert {st["iter"] for n, _, _, st in ours
            if n == "engine.harvest"} <= its
