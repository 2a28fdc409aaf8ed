"""PlanStore tests: the unified fingerprint-v2 plan/capture cache.

  * cross-bucket sharing — structurally identical (graph, plan) pairs at
    different shapes hit one canonical lowering; buckets 2..N are counted
    as shares and never re-run analysis + lowering,
  * differential — a specialized lowering agrees bitwise with the
    reference interpreter (``Realizer(lowered=False)``) on every bucket,
    including split/merge plans that exercise slice + pad rewriting,
  * fingerprint-v2 rejection — structural mismatches refuse to
    specialize (``LoweringError``) and the store falls back to a full
    lower; op-config / salt changes scope to distinct outer entries,
  * LRU — entry-count and byte-budget eviction with counters, canonical
    promotion after the canonical bucket is evicted.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FULL, LoweringError, OpSchedulerBase, PlanStore,
                        Realizer, ScheduleContext, fingerprint_v2, lower,
                        record_plan, specialize, trace)
from repro.core.module import Module, Op, Param
from repro.core.plan import OpHandle, structural_key
from repro.core.plan_store import plan_nbytes

D = 8


class Lin(Op):
    def __init__(self, d_in, d_out, name):
        super().__init__()
        self.w = Param((d_in, d_out), jnp.float32)
        self.named(name)

    def kernel(self, p, x):
        return jnp.tanh(x @ p["w"])


class Chain(Module):
    def __init__(self, n=4):
        super().__init__()
        self.n = n
        for i in range(n):
            setattr(self, f"l{i}", Lin(D, D, f"l{i}"))

    def forward(self, x):
        for i in range(self.n):
            x = getattr(self, f"l{i}")(x)
        return x


class SplitThenMerge(OpSchedulerBase):
    """Per-part chain ending in a merged step: exercises slice reads and
    the pad-created merge buffer, the shape-dependent halves of an
    instruction stream."""

    def __init__(self, sizes):
        self.sizes = sizes

    def schedule(self, ctx):
        ctx.split(self.sizes)
        oids = ctx.graph.topo_order()
        for oid in oids[:-1]:
            for p in range(len(self.sizes)):
                ctx.execute(OpHandle(oid, p, ""))
        ctx.execute(tuple(OpHandle(oids[-1], p, "")
                          for p in range(len(self.sizes))))


def _bucket(net, B, sizes, seed=0):
    g = trace(net, {"x": jax.ShapeDtypeStruct((B, D), jnp.float32)})
    plan = record_plan(g, SplitThenMerge(sizes),
                       ScheduleContext(local_batch=B))
    params = net.init(jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, D))
    return g, plan, params, x


def _assert_same(a: dict, b: dict):
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=f"output {k!r} diverged")


# ---------------------------------------------------------------------------
# cross-bucket sharing + differential agreement
# ---------------------------------------------------------------------------


def test_cross_bucket_share_counters_and_differential():
    net = Chain()
    store = PlanStore()
    for i, (B, sizes) in enumerate([(8, (4, 4)), (16, (8, 8)),
                                    (12, (4, 8))]):
        g, plan, params, x = _bucket(net, B, sizes)
        lowered = store.get_or_lower(g, plan, salt="t")
        _assert_same(Realizer(g, plan, lowered=False)(params, {"x": x}),
                     lowered(params, {"x": x}))
    assert store.stats["misses"] == 1          # first bucket pays lowering
    assert store.stats["shares"] == 2          # buckets 2..3 specialize
    assert store.stats["hits"] == 0
    assert store.share_rate == pytest.approx(2 / 3)
    # re-requesting a known bucket is a hit, not a share
    g, plan, *_ = _bucket(net, 8, (4, 4))
    store.get_or_lower(g, plan, salt="t")
    assert store.stats["hits"] == 1


def test_specialized_plan_matches_fresh_lower():
    """Specialization must produce the same instruction semantics as a
    from-scratch lowering of the new bucket."""
    net = Chain()
    g1, p1, *_ = _bucket(net, 8, (4, 4))
    g2, p2, params, x = _bucket(net, 16, (6, 10))
    canon = lower(g1, p1)
    spec = specialize(canon, g2, p2)
    fresh = lower(g2, p2)
    assert spec.fingerprint == fresh.fingerprint
    assert spec.n_slots == fresh.n_slots
    assert spec.input_slots == fresh.input_slots
    assert spec.output_slots == fresh.output_slots
    for a, b in zip(spec.instrs, fresh.instrs):
        assert a.reads == b.reads
        assert a.frees == b.frees
        # writes carry a numpy pad seed; compare structure
        assert len(a.writes) == len(b.writes)
        for (sa, ba), (sb, bb) in zip(a.writes, b.writes):
            assert sa == sb
            assert (ba is None) == (bb is None)
            if ba is not None:
                assert ba[:3] == bb[:3]
    _assert_same(fresh(params, {"x": x}), spec(params, {"x": x}))


def test_unsplit_plans_share_across_buckets():
    net = Chain()
    store = PlanStore()

    class Seq(OpSchedulerBase):
        pass

    for B in (4, 8, 32):
        g = trace(net, {"x": jax.ShapeDtypeStruct((B, D), jnp.float32)})
        plan = record_plan(g, Seq(), ScheduleContext(local_batch=B))
        store.get_or_lower(g, plan)
    assert store.stats["misses"] == 1
    assert store.stats["shares"] == 2


# ---------------------------------------------------------------------------
# fingerprint v2: rejection + scoping
# ---------------------------------------------------------------------------


def test_specialize_rejects_structural_mismatch():
    net4, net5 = Chain(4), Chain(5)
    g1, p1, *_ = _bucket(net4, 8, (4, 4))
    g2, p2, *_ = _bucket(net5, 8, (4, 4))
    assert structural_key(g1, p1) != structural_key(g2, p2)
    canon = lower(g1, p1)
    with pytest.raises(LoweringError, match="cannot specialize"):
        specialize(canon, g2, p2)


def test_split_count_is_structural():
    """Same graph, different micro-batch *count*: never shared.  (The
    decode-tier analogue: a batch tier whose scheduler changes the split
    count becomes its own canonical instead of specializing.)"""
    net = Chain()
    g1, p1, *_ = _bucket(net, 8, (4, 4))
    g2, p2, *_ = _bucket(net, 9, (3, 3, 3))
    assert structural_key(g1, p1) != structural_key(g2, p2)
    store = PlanStore()
    store.get_or_lower(g1, p1)
    store.get_or_lower(g2, p2)
    assert store.stats["misses"] == 2
    assert store.stats["shares"] == 0
    # distinct outer keys never reach the specialize attempt
    assert store.stats["specialize_rejects"] == 0


def test_specialize_fallback_is_counted(monkeypatch):
    """When a canonical exists but specialize rejects (structure drift),
    the store falls back to a cold lower and counts the reject."""
    from repro.core import plan_store as plan_store_mod
    net = Chain()
    store = PlanStore()
    g1, p1, *_ = _bucket(net, 8, (4, 4))
    store.get_or_lower(g1, p1)

    def always_reject(*a, **k):
        raise LoweringError("forced drift")
    monkeypatch.setattr(plan_store_mod, "specialize", always_reject)
    g2, p2, params, x = _bucket(net, 16, (8, 8))
    lowered = store.get_or_lower(g2, p2)
    assert store.stats["specialize_rejects"] == 1
    assert store.stats["misses"] == 2           # fell back to a cold lower
    # the rejected attempt is timed apart: specialize_s keeps its meaning
    assert store.spans["plan.specialize_rejected"]["count"] == 1
    assert "plan.specialize" not in store.spans
    assert store.snapshot()["specialize_s"] == 0.0
    _assert_same(Realizer(g2, p2, lowered=False)(params, {"x": x}),
                 lowered(params, {"x": x}))


def test_fused_closure_config_scopes_outer_key():
    """Two same-class schedulers whose fused kernels close over different
    config must not alias: partial kwargs enter the structural key."""
    import functools

    def scaled(info, x, factor=1.0):
        p = info.params_of(0)
        return jnp.tanh(x @ p["w"]) * factor

    class FuseFirst(OpSchedulerBase):
        def __init__(self, factor):
            self.fn = functools.partial(scaled, factor=factor)

        def schedule(self, ctx):
            oids = ctx.graph.topo_order()
            ctx.execute((OpHandle(oids[0], FULL, ""),),
                        replace_func=self.fn, replace_name="scaled")
            ctx.run_rest_sequential()

    net = Chain(3)
    store = PlanStore()
    outs = {}
    for factor in (2.0, 100.0):
        g = trace(net, {"x": jax.ShapeDtypeStruct((8, D), jnp.float32)})
        plan = record_plan(g, FuseFirst(factor),
                           ScheduleContext(local_batch=8))
        params = net.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (8, D))
        lowered = store.get_or_lower(g, plan, salt="FuseFirst")
        outs[factor] = np.asarray(lowered(params, {"x": x})["out"])
    assert store.stats["misses"] == 2       # different closures: no alias
    assert store.stats["shares"] == 0 and store.stats["hits"] == 0
    assert not np.allclose(outs[2.0], outs[100.0])
    # same closure config at a new bucket still shares
    g = trace(net, {"x": jax.ShapeDtypeStruct((16, D), jnp.float32)})
    plan = record_plan(g, FuseFirst(2.0), ScheduleContext(local_batch=16))
    store.get_or_lower(g, plan, salt="FuseFirst")
    assert store.stats["shares"] == 1


def test_op_config_and_salt_scope_outer_key():
    net = Chain()
    g1, p1, *_ = _bucket(net, 8, (4, 4))
    g2, p2, *_ = _bucket(net, 16, (8, 8))
    cfg_a = (("attn_impl", "xla"), ("tp", 1))
    cfg_b = (("attn_impl", "pallas"), ("tp", 1))
    assert fingerprint_v2(g1, p1, op_config=cfg_a) != \
        fingerprint_v2(g1, p1, op_config=cfg_b)
    assert fingerprint_v2(g1, p1, salt="a") != fingerprint_v2(g1, p1,
                                                              salt="b")
    store = PlanStore()
    store.get_or_lower(g1, p1, op_config=cfg_a)
    store.get_or_lower(g2, p2, op_config=cfg_b)   # same structure, new cfg
    assert store.stats["misses"] == 2             # must NOT share
    store.get_or_lower(g2, p2, op_config=cfg_a)   # matching cfg: shares
    assert store.stats["shares"] == 1


# ---------------------------------------------------------------------------
# LRU: byte budget, canonical promotion
# ---------------------------------------------------------------------------


def test_lru_eviction_under_byte_budget():
    net = Chain()
    one = plan_nbytes(lower(*_bucket(net, 8, (4, 4))[:2]))
    store = PlanStore(plan_budget_bytes=int(one * 2.5))
    buckets = [(8, (4, 4)), (16, (8, 8)), (12, (4, 8)), (20, (10, 10)),
               (24, (12, 12))]
    for B, sizes in buckets:
        g, plan, params, x = _bucket(net, B, sizes)
        lowered = store.get_or_lower(g, plan)
        _assert_same(Realizer(g, plan, lowered=False)(params, {"x": x}),
                     lowered(params, {"x": x}))
    assert store.stats["evictions"] >= len(buckets) - 2
    assert store.n_plans <= 2
    assert store.stats["plan_bytes"] <= int(one * 2.5)
    # byte accounting survives eviction churn
    assert store.stats["plan_bytes"] == sum(
        e[1] for e in store._plans.values())


def test_canonical_promotion_after_eviction():
    """Evicting the canonical bucket must not kill sharing: a surviving
    bucket of the same outer entry is promoted to canonical."""
    net = Chain()
    store = PlanStore(plan_capacity=1)
    g1, p1, *_ = _bucket(net, 8, (4, 4))
    g2, p2, *_ = _bucket(net, 16, (8, 8))
    g3, p3, params, x = _bucket(net, 12, (6, 6))
    store.get_or_lower(g1, p1)            # canonical (miss)
    store.get_or_lower(g2, p2)            # share; evicts bucket 1
    assert store.stats["evictions"] == 1
    lowered = store.get_or_lower(g3, p3)  # must still share, off bucket 2
    assert store.stats["shares"] == 2
    assert store.stats["misses"] == 1
    _assert_same(Realizer(g3, p3, lowered=False)(params, {"x": x}),
                 lowered(params, {"x": x}))


def test_full_eviction_of_outer_entry_recovers():
    net = Chain()
    store = PlanStore(plan_capacity=1)

    class Seq(OpSchedulerBase):
        pass

    g1 = trace(Chain(2), {"x": jax.ShapeDtypeStruct((8, D), jnp.float32)})
    p1 = record_plan(g1, Seq(), ScheduleContext(local_batch=8))
    store.get_or_lower(g1, p1)
    g2, p2, *_ = _bucket(net, 8, (4, 4))
    store.get_or_lower(g2, p2)            # different structure: evicts g1
    # g1's outer entry is gone entirely; asking again is a clean miss
    store.get_or_lower(g1, p1)
    assert store.stats["misses"] == 3
    assert store.stats["shares"] == 0


# ---------------------------------------------------------------------------
# capture/replay survives specialization
# ---------------------------------------------------------------------------


def test_specialized_plans_capture_independently():
    net = Chain()
    store = PlanStore()
    g1, p1, params1, x1 = _bucket(net, 8, (4, 4))
    g2, p2, params2, x2 = _bucket(net, 16, (8, 8))
    l1 = store.get_or_lower(g1, p1)
    l2 = store.get_or_lower(g2, p2)
    assert store.stats["shares"] == 1
    jax.make_jaxpr(lambda p, v: l1(p, {"x": v}))(params1, x1)
    jax.make_jaxpr(lambda p, v: l2(p, {"x": v}))(params2, x2)
    assert l1.stats.get("captures") == 1
    assert l2.stats.get("captures") == 1   # own replay cache, own captures
    jax.make_jaxpr(lambda p, v: l2(p, {"x": v}))(params2, x2)
    assert l2.stats.get("replays", 0) >= 1
