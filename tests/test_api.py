"""PR-5 frontend tests: StrategyPolicy combinators, policy-salted
PlanStore keys, the repro.api.Program facade, and the deprecation shims
over the pre-facade entry points."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro._deprecation import reset as reset_deprecations
from repro.core import (LoweringError, PlanStore, Realizer, ScheduleContext,
                        by_phase, by_token_threshold, first_viable, has_ops,
                        local_batch_below, record_plan, resolve_strategy,
                        strategy_salt, trace, when)
from repro.core.module import Module, Op, Param
from repro.core.strategies import get_strategy
from repro.core.strategies.dynamic import dynamic_policy


# -- fixtures ----------------------------------------------------------------


class _Linear(Op):
    resource = "compute"

    def __init__(self, d, name):
        super().__init__()
        self.w = Param((d, d), jnp.float32)
        self.named(name)

    def kernel(self, p, x):
        return jnp.tanh(x @ p["w"])


class _Net(Module):
    def __init__(self, d=8):
        super().__init__()
        self.lin0 = _Linear(d, "lin0")
        self.lin1 = _Linear(d, "lin1")
        self.lin2 = _Linear(d, "lin2")

    def forward(self, x):
        return self.lin2(self.lin1(self.lin0(x)))


def _ctx(phase="prefill", b=8, s=256):
    return ScheduleContext(local_batch=b, global_batch=b, seq_len=s,
                           phase=phase, arch="t")


# -- policy combinators ------------------------------------------------------


def test_by_phase_routes_and_defaults():
    p = by_phase(decode="sequential", default="sbo")
    assert type(p(_ctx("decode"))).__name__ == "Sequential"
    assert type(p(_ctx("prefill"))).__name__ == "SingleBatchOverlap"
    with pytest.raises(KeyError, match="no branch"):
        by_phase(decode="sequential")(_ctx("train"))


def test_by_token_threshold_orders():
    p = by_token_threshold([(64, "sequential"), (2048, "sbo")],
                           above="nanoflow")
    assert type(p(_ctx(b=1, s=8))).__name__ == "Sequential"
    assert type(p(_ctx(b=2, s=128))).__name__ == "SingleBatchOverlap"
    assert type(p(_ctx(b=8, s=1024))).__name__ == "NanoFlow"
    with pytest.raises(ValueError, match="ascend"):
        by_token_threshold([(2048, "sbo"), (64, "sequential")],
                           above="nanoflow")


def test_first_viable_and_when():
    p = first_viable(when(local_batch_below(2), "sequential"),
                     default="nanoflow")
    assert type(p(_ctx(b=1))).__name__ == "Sequential"
    assert type(p(_ctx(b=8))).__name__ == "NanoFlow"
    # a top-level decline is a loud error, not a silent None
    undecided = first_viable(when(local_batch_below(2), "sequential"))
    with pytest.raises(ValueError, match="declined"):
        resolve_strategy(undecided, _ctx(b=8))


def test_has_ops_reads_graph_from_context():
    net = _Net()
    g = trace(net, {"x": jax.ShapeDtypeStruct((4, 8), jnp.float32)})
    pred = has_ops(r"lin1")
    assert not pred(_ctx())                       # no graph rode along
    assert resolve_strategy(
        first_viable(when(pred, "sbo"), default="sequential"),
        _ctx(), graph=g).name == "sbo"
    assert resolve_strategy(
        first_viable(when(has_ops(r"nope"), "sbo"), default="sequential"),
        _ctx(), graph=g).name == "sequential"


def test_dynamic_policy_matches_legacy_pick():
    """The combinator reimplementation preserves the PR-0 pick table."""
    p = dynamic_policy()
    assert type(p(_ctx(b=1, s=8))).__name__ == "Sequential"
    assert type(p(_ctx(b=4, s=100))).__name__ == "SingleBatchOverlap"
    assert type(p(_ctx(b=1, s=4096))).__name__ == "SingleBatchOverlap"
    assert type(p(_ctx(b=8, s=1024))).__name__ == "NanoFlow"
    assert type(p(_ctx("decode", b=4, s=1))).__name__ == "Sequential"
    # DynamicScheduler defers to the same policy at schedule time
    dyn = get_strategy("dynamic")
    assert dyn.identity()[0] == "dynamic"
    assert dyn.partition_rules() == p.partition_rules()


def test_strategy_salt_stability_and_separation():
    assert strategy_salt(get_strategy("dynamic")) == \
        strategy_salt(get_strategy("dynamic"))
    assert strategy_salt(get_strategy("dynamic")) != \
        strategy_salt(get_strategy("dynamic", split_tokens=512))
    assert strategy_salt(get_strategy("sequential")) != \
        strategy_salt(get_strategy("sbo"))
    assert strategy_salt(dynamic_policy()) == strategy_salt(dynamic_policy())
    # combinator structure enters the identity
    assert strategy_salt(by_phase(default="sequential")) != \
        strategy_salt(by_phase(decode="sequential", default="sequential"))


# -- policy-salted PlanStore keys (satellite) --------------------------------


def _lowered_via(store, policy, graph, info):
    from repro.core.plan import strategy_salt as salt_of
    sched = resolve_strategy(policy, info, graph=graph)
    plan = record_plan(graph, sched, info)
    return store.get_or_lower(graph, plan,
                              salt=f"t|{info.phase}|{salt_of(policy)}")


def test_two_policies_two_outer_keys_zero_cross_hits(tmp_path):
    """Same graph, same resolved scheduler, two policies: distinct outer
    keys, no cross-policy cache hits — and a restart redeems both."""
    net = _Net()
    g = trace(net, {"x": jax.ShapeDtypeStruct((4, 8), jnp.float32)})
    info = _ctx(b=4, s=1)
    pol_a = repro.core.as_policy("sequential")
    pol_b = by_phase(default="sequential")     # resolves identically
    path = str(tmp_path / "pol.dfps")
    store = PlanStore(path=path)
    _lowered_via(store, pol_a, g, info)
    _lowered_via(store, pol_b, g, info)
    st = store.stats
    assert st["misses"] == 2, st               # B never hit A's entry
    assert st["hits"] == 0 and st["shares"] == 0, st
    assert len({outer for outer, _ in store._plans}) == 2
    # same policy again: a clean hit
    _lowered_via(store, pol_a, g, info)
    assert store.stats["hits"] == 1
    assert store.save() == 2

    store2 = PlanStore.open(path)
    _lowered_via(store2, pol_a, g, info)
    _lowered_via(store2, pol_b, g, info)
    st2 = store2.stats
    assert st2["restore_hits"] == 2, st2       # both policies redeemed
    assert st2["misses"] == 0, st2


def test_program_policy_swap_never_replays(tmp_path):
    """Facade-level version of the same contract: one store, two
    programs with different policies — zero cross hits."""
    net = _Net()
    ex = {"x": jax.ShapeDtypeStruct((4, 8), jnp.float32)}
    store = PlanStore()
    params = net.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8))
    prog_a = repro.api.compile(net, policy="sequential",
                               example_inputs=ex, plan_store=store)
    prog_b = repro.api.compile(net, policy=by_phase(default="sequential"),
                               example_inputs=ex, plan_store=store)
    out_a = prog_a(params, {"x": x})
    out_b = prog_b(params, {"x": x})
    np.testing.assert_allclose(np.asarray(out_a["out"]),
                               np.asarray(out_b["out"]), atol=1e-6)
    st = store.stats
    assert st["misses"] == 2 and st["hits"] == 0, st


def test_policy_branch_rules_use_union_partition():
    """Two buckets resolving to different branches (one with partition
    rules, one without) must see the SAME partitioned graph — branch-
    dependent partitioning would diverge the structural keys and kill
    cross-bucket PlanStore sharing."""
    from repro.core import OpSchedulerBase, SplitFunc

    class RuledSeq(OpSchedulerBase):
        name = "ruledseq"

        def partition_rules(self):
            return [SplitFunc(r"lin1")]

    net = _Net()
    ex = {"x": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
    policy = by_token_threshold([(6, "sequential")], above=RuledSeq())
    prog = repro.api.compile(net, policy=policy, example_inputs=ex)
    assert type(policy(_ctx(b=4, s=0))).__name__ == "Sequential"
    assert isinstance(policy(_ctx(b=8, s=0)), RuledSeq)
    prog.plan(local_batch=4)             # Sequential branch
    prog.plan(local_batch=8)             # RuledSeq branch
    st = prog.stats
    # identical partitioned structure: the second bucket is a pure hit
    assert st["misses"] == 1 and st["hits"] == 1, st


# -- specialize_rejects fallback coverage (satellite) ------------------------


def _graph_plan_bucket(net, b):
    g = trace(net, {"x": jax.ShapeDtypeStruct((b, 8), jnp.float32)})
    info = ScheduleContext(local_batch=b)
    plan = record_plan(g, get_strategy("sequential"), info)
    return g, plan


def test_specialize_reject_on_restored_skeleton(tmp_path, monkeypatch):
    """Restart path: when the rehydrated canonical skeleton cannot
    specialize an unseen bucket, the store counts the reject and falls
    back to a cold lower that still computes correctly."""
    from repro.core import plan_store as plan_store_mod
    net = _Net()
    path = str(tmp_path / "skel.dfps")
    store = PlanStore(path=path)
    g4, p4 = _graph_plan_bucket(net, 4)
    store.get_or_lower(g4, p4, salt="s")
    assert store.save() == 1

    store2 = PlanStore.open(path)

    def always_reject(*a, **k):
        raise LoweringError("forced drift")
    monkeypatch.setattr(plan_store_mod, "specialize", always_reject)
    g8, p8 = _graph_plan_bucket(net, 8)
    lowered = store2.get_or_lower(g8, p8, salt="s")
    st = store2.stats
    assert st["restore_canonicals"] == 1, st   # skeleton was rehydrated
    assert st["specialize_rejects"] == 1, st
    assert st["misses"] == 1, st               # cold-lower fallback
    params = net.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 8))
    want = Realizer(g8, p8, lowered=False)(params, {"x": x})
    got = _realizer_with(g8, p8, lowered)(params, {"x": x})
    np.testing.assert_allclose(np.asarray(got["out"]),
                               np.asarray(want["out"]), atol=1e-6)


def _realizer_with(graph, plan, lowered):
    rz = Realizer.__new__(Realizer)
    rz.graph = graph
    rz.plan = plan
    rz._nodes = graph.nodes
    rz.lowered = lowered
    rz.analysis = lowered.analysis
    return rz


def test_specialize_reject_live_canonical_still_correct(monkeypatch):
    """Live-store reject (no restart): fallback result is bit-identical
    to the interpreter reference."""
    from repro.core import plan_store as plan_store_mod
    net = _Net()
    store = PlanStore()
    g4, p4 = _graph_plan_bucket(net, 4)
    store.get_or_lower(g4, p4, salt="s")

    def always_reject(*a, **k):
        raise LoweringError("forced drift")
    monkeypatch.setattr(plan_store_mod, "specialize", always_reject)
    g8, p8 = _graph_plan_bucket(net, 8)
    lowered = store.get_or_lower(g8, p8, salt="s")
    assert store.stats["specialize_rejects"] == 1
    assert store.stats["misses"] == 2
    params = net.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 8))
    want = Realizer(g8, p8, lowered=False)(params, {"x": x})
    got = _realizer_with(g8, p8, lowered)(params, {"x": x})
    np.testing.assert_allclose(np.asarray(got["out"]),
                               np.asarray(want["out"]), atol=1e-6)


# -- the facade --------------------------------------------------------------


def test_program_graph_path_matches_sequential():
    net = _Net()
    ex = {"x": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
    params = net.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 8))
    want = repro.api.compile(net, policy="sequential",
                             example_inputs=ex)(params, {"x": x})
    prog = repro.api.compile(net, policy="sbo", example_inputs=ex)
    plan = prog.plan(local_batch=8)
    assert plan.steps
    got = prog(params, {"x": x})
    np.testing.assert_allclose(np.asarray(got["out"]),
                               np.asarray(want["out"]), atol=1e-6)
    # second call is a pure cache hit (one realizer per shape bucket)
    prog(params, {"x": x})
    assert prog.stats["misses"] == 1


def test_program_train_step_smoke():
    prog = repro.api.compile("chatglm3-6b", smoke=True)
    step = prog.train_step(2, 16)
    assert step.init_opt is not None and step.segments
    params = prog.init_params(0, phase="train")
    opt = step.init_opt(params)
    B, S = 2, 16
    batch = {"ids": jnp.zeros((B, S), jnp.int32) + 3,
             "labels": jnp.zeros((B, S), jnp.int32) + 4,
             "positions": jnp.broadcast_to(
                 jnp.arange(S, dtype=jnp.int32), (B, S))}
    _, _, metrics = step(params, opt, batch, 0)
    assert np.isfinite(float(metrics["loss"]))


def test_program_requires_right_path():
    net = _Net()
    ex = {"x": jax.ShapeDtypeStruct((4, 8), jnp.float32)}
    prog = repro.api.compile(net, example_inputs=ex)
    with pytest.raises(TypeError, match="raw Module"):
        prog.train_step(2, 16)
    lm = repro.api.compile("chatglm3-6b", smoke=True)
    with pytest.raises(TypeError, match="wraps an LM"):
        lm({}, {})
    with pytest.raises(ValueError, match="example_inputs"):
        repro.api.compile(net)


# -- deprecation shims -------------------------------------------------------


def test_old_builders_warn_once(monkeypatch):
    import repro.launch.steps as steps_mod
    import repro.train.step as train_mod
    sentinel = object()
    monkeypatch.setattr(train_mod, "_build_train_step",
                        lambda *a, **k: sentinel)
    monkeypatch.setattr(steps_mod, "_build_global_train_step",
                        lambda *a, **k: sentinel)
    reset_deprecations()
    with pytest.warns(DeprecationWarning, match="repro.api.compile"):
        assert train_mod.build_train_step(None, None, 2, 4, None) is sentinel
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # second call: silent
        assert train_mod.build_train_step(None, None, 2, 4, None) is sentinel
    with pytest.warns(DeprecationWarning, match="mesh"):
        assert steps_mod.build_global_train_step(None, None, None, None) \
            is sentinel


def test_compile_cache_shims_warn_and_behave():
    from repro.core import compile_cache as legacy_mod
    from repro.core.plan_store import (GLOBAL_CACHE, GLOBAL_PLAN_CACHE,
                                       GLOBAL_STORE, CompileCache,
                                       LoweredPlanCache)
    assert GLOBAL_CACHE is GLOBAL_STORE
    assert GLOBAL_PLAN_CACHE is GLOBAL_STORE
    assert legacy_mod.CompileCache is CompileCache
    assert legacy_mod.GLOBAL_CACHE is GLOBAL_STORE
    reset_deprecations()
    with pytest.warns(DeprecationWarning, match="PlanStore"):
        cc = CompileCache(capacity=4)
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # warn-once
        cc2 = CompileCache(capacity=2)
    fn = cc.get_or_build(("k", 1), lambda: (lambda x: x + 1))
    assert fn(1) == 2
    assert cc.get_or_build(("k", 1), lambda: (lambda x: x + 9))(1) == 2
    # legacy stats contract: exec counters mirrored onto the old keys
    assert cc.stats["hits"] == 1 and cc.stats["misses"] == 1
    assert len(cc) == cc.n_execs == 1
    del cc2
    reset_deprecations()
    with pytest.warns(DeprecationWarning, match="PlanStore"):
        lp = LoweredPlanCache(capacity=8)
    assert len(lp) == lp.n_plans == 0
    assert lp.plan_capacity == 8


# -- Program bundles (save/load one-file deployment) -------------------------

def test_program_bundle_round_trip(tmp_path):
    """save() packs arch + policy spec + cache backend + plans into one
    file; load() rebuilds the Program and replays without re-lowering."""
    from repro.serve import PagedCache
    path = str(tmp_path / "prog.dfpb")
    p1 = repro.api.compile("chatglm3-6b", policy="sequential", smoke=True,
                           cache="paged")
    p1.prefill(global_batch=1, seq_len=16)
    n = p1.save(path)
    assert n > 0
    misses1 = p1.stats["misses"]
    assert misses1 > 0

    p2 = repro.api.Program.load(path)
    assert isinstance(p2.cache_backend, PagedCache)
    assert p2.policy_spec == "sequential"
    assert p2.model.cfg.name == p1.model.cfg.name
    p2.prefill(global_batch=1, seq_len=16)
    assert p2.stats["misses"] == 0, \
        f"loaded program re-lowered: {p2.stats}"


def test_program_bundle_rejects_bad_header(tmp_path):
    import json

    from repro.api import ProgramBundleError
    path = str(tmp_path / "prog.dfpb")
    p1 = repro.api.compile("chatglm3-6b", policy="sequential", smoke=True)
    p1.prefill(global_batch=1, seq_len=16)
    p1.save(path)

    with open(path) as f:
        lines = f.read().splitlines(True)
    hdr = json.loads(lines[0])
    hdr["format_version"] += 1
    bad = str(tmp_path / "bad.dfpb")
    with open(bad, "w") as f:
        f.writelines([json.dumps(hdr) + "\n"] + lines[1:])
    with pytest.raises(ProgramBundleError, match="format"):
        repro.api.Program.load(bad)

    junk = str(tmp_path / "junk.dfpb")
    with open(junk, "w") as f:
        f.write("not a bundle\n")
    with pytest.raises(ProgramBundleError):
        repro.api.Program.load(junk)


def test_program_bundle_opaque_policy(tmp_path):
    """An opaque policy object can't ride in the bundle: load() demands
    an explicit policy= and trusts it (no salt check); a named policy
    needs nothing."""
    from repro.api import ProgramBundleError
    path = str(tmp_path / "prog.dfpb")
    p1 = repro.api.compile("chatglm3-6b",
                           policy=get_strategy("sequential"), smoke=True)
    p1.prefill(global_batch=1, seq_len=16)
    p1.save(path)
    with pytest.raises(ProgramBundleError, match="policy"):
        repro.api.Program.load(path)
    p2 = repro.api.Program.load(path, policy="sequential")
    p2.prefill(global_batch=1, seq_len=16)
    assert p2.stats["misses"] == 0


def test_stacked_init_matches_per_layer_init():
    """Layer stacks are initialized by one jitted map into the stacked
    buffer (no list-plus-stack double copy); each layer's slice equals
    initializing that layer alone."""
    import zlib
    program = repro.api.compile("chatglm3-6b", smoke=True)
    params = program.init_params(0)
    segs, _ = program.model.build_segments("prefill", 2, 2, s_max=4)
    seg = next(s for s in segs if s.count > 1)
    k = jax.random.fold_in(jax.random.PRNGKey(0),
                           zlib.crc32(seg.name.encode()))
    for i in range(seg.count):
        one = seg.module.init(jax.random.fold_in(k, i))
        got = jax.tree_util.tree_map(lambda x: x[i], params[seg.name])
        assert jax.tree_util.tree_all(jax.tree_util.tree_map(
            lambda a, b: bool((np.asarray(a) == np.asarray(b)).all()),
            one, got))
