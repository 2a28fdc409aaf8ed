"""Persistent PlanStore tests: the cross-process half of capture/replay.

  * round-trip — a store saved in one process and loaded in another
    serves every previously-seen bucket with zero ``lower`` calls
    (restore hits + shares only) and agrees bitwise with the reference
    interpreter,
  * rejection — corrupt entries, corrupt/garbage headers, and
    format/fingerprint version mismatches all degrade to cold lowering
    (counted in the ``restore_*`` stats family), never crash or serve
    a wrong plan,
  * admission policy — a bucket evicted before its second touch is
    recorded one-shot and never re-admitted to the artifact, even
    after being re-lowered,
  * format — atomic writes, deterministic bytes, unpersistable
    (process-local closure) entries excluded.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FINGERPRINT_VERSION, PlanStore, Realizer,
                        ScheduleContext, record_plan, trace)
from repro.core import plan_store as plan_store_mod
from repro.core.plan_serde import (FORMAT_VERSION, key_digest,
                                   persistable_key)
from test_plan_store import Chain, D, SplitThenMerge, _assert_same, _bucket


def _bomb_lower(monkeypatch):
    """Make any further ``lower`` call inside the store an immediate
    failure — the acceptance contract for a warm-started store."""
    def bomb(*a, **k):
        raise AssertionError("lower() called on a warm-started store")
    monkeypatch.setattr(plan_store_mod, "lower", bomb)


def _populate(net, buckets, salt="t"):
    store = PlanStore()
    pairs = [_bucket(net, B, sizes) for B, sizes in buckets]
    for g, plan, _, _ in pairs:
        store.get_or_lower(g, plan, salt=salt)
    return store, pairs


# ---------------------------------------------------------------------------
# round-trip
# ---------------------------------------------------------------------------


def test_round_trip_serves_all_buckets_without_lowering(tmp_path,
                                                        monkeypatch):
    net = Chain()
    store, pairs = _populate(net, [(8, (4, 4)), (16, (8, 8)), (12, (4, 8))])
    path = str(tmp_path / "store.dfps")
    assert store.save(path) == 1          # one outer entry (canonical only)

    _bomb_lower(monkeypatch)
    warm = PlanStore.open(path)
    for g, plan, params, x in pairs:
        lowered = warm.get_or_lower(g, plan, salt="t")
        _assert_same(Realizer(g, plan, lowered=False)(params, {"x": x}),
                     lowered(params, {"x": x}))
    s = warm.snapshot()
    assert s["misses"] == 0
    assert s["restore_hits"] + s["shares"] == len(pairs)
    assert s["restore_entries"] == 1


def test_unseen_bucket_specializes_restored_canonical(tmp_path,
                                                      monkeypatch):
    """A bucket never seen before the restart must still avoid lowering:
    the restored canonical is rehydrated as a skeleton and specialized."""
    net = Chain()
    store, _ = _populate(net, [(8, (4, 4))])
    path = str(tmp_path / "store.dfps")
    store.save(path)

    _bomb_lower(monkeypatch)
    warm = PlanStore.open(path)
    g, plan, params, x = _bucket(net, 20, (10, 10))     # unseen shape
    lowered = warm.get_or_lower(g, plan, salt="t")
    _assert_same(Realizer(g, plan, lowered=False)(params, {"x": x}),
                 lowered(params, {"x": x}))
    assert warm.stats["restore_canonicals"] == 1
    assert warm.stats["shares"] == 1 and warm.stats["misses"] == 0


def test_restored_plans_capture_and_replay(tmp_path):
    """Jaxpr captures are rebuilt on load, not deserialized: a redeemed
    plan captures on first traced call and replays afterwards."""
    net = Chain()
    store, pairs = _populate(net, [(8, (4, 4))])
    path = str(tmp_path / "store.dfps")
    store.save(path)
    warm = PlanStore.open(path)
    g, plan, params, x = pairs[0]
    lowered = warm.get_or_lower(g, plan, salt="t")
    assert lowered.stats.get("captures") is None
    jax.make_jaxpr(lambda p, v: lowered(p, {"x": v}))(params, x)
    jax.make_jaxpr(lambda p, v: lowered(p, {"x": v}))(params, x)
    assert lowered.stats["captures"] == 1
    assert lowered.stats["replays"] >= 1


def test_redeemed_then_evicted_entry_survives_checkpoint(tmp_path):
    """LRU churn after a redeem must not shrink the artifact: the
    restored record backs the entry even when the live plan is gone,
    and it can be redeemed again instead of cold-lowering."""
    net = Chain()
    store, pairs = _populate(net, [(8, (4, 4))])
    path = str(tmp_path / "store.dfps")
    store.save(path)

    warm = PlanStore.open(path, plan_capacity=1)
    g, plan, *_ = pairs[0]
    warm.get_or_lower(g, plan, salt="t")            # redeem
    g2, p2, *_ = _bucket(Chain(2), 8, (4, 4))       # different structure:
    warm.get_or_lower(g2, p2, salt="t")             # evicts the redeem
    assert warm.stats["evictions"] == 1
    warm.get_or_lower(g, plan, salt="t")            # redeems again, no miss
    assert warm.stats["restore_hits"] == 2
    assert warm.spans["plan.restore"]["count"] == 2
    assert warm.stats["misses"] == 1                # only the g2 structure
    path2 = str(tmp_path / "store2.dfps")
    warm.get_or_lower(g2, p2, salt="t")             # evict the redeem again
    assert warm.save(path2) >= 1
    warm2 = PlanStore.open(path2)
    warm2.get_or_lower(g, plan, salt="t")
    assert warm2.stats["restore_hits"] == 1 and warm2.stats["misses"] == 0


def test_checkpoint_skips_clean_store(tmp_path):
    net = Chain()
    store, pairs = _populate(net, [(8, (4, 4))])
    path = str(tmp_path / "store.dfps")
    store.path = path
    assert store.dirty
    store.save()
    assert not store.dirty                          # bound-path save cleans
    g, plan, *_ = pairs[0]
    store.get_or_lower(g, plan, salt="t")           # pure hit: still clean
    assert not store.dirty
    g2, p2, *_ = _bucket(net, 24, (12, 12))
    store.get_or_lower(g2, p2, salt="t")            # new bucket: dirty
    assert store.dirty


def test_save_load_passthrough_preserves_unredeemed_entries(tmp_path):
    """A short-lived process that never touches a restored entry must not
    shrink the artifact when it checkpoints."""
    net = Chain()
    store, pairs = _populate(net, [(8, (4, 4))])
    path = str(tmp_path / "store.dfps")
    store.save(path)

    relay = PlanStore.open(path)          # loads, redeems nothing
    path2 = str(tmp_path / "store2.dfps")
    assert relay.save(path2) == 1
    warm = PlanStore.open(path2)
    g, plan, *_ = pairs[0]
    warm.get_or_lower(g, plan, salt="t")
    assert warm.stats["restore_hits"] == 1 and warm.stats["misses"] == 0


# ---------------------------------------------------------------------------
# rejection: corruption + versioning
# ---------------------------------------------------------------------------


def _saved_lines(tmp_path, net=None):
    net = net or Chain()
    store, pairs = _populate(net, [(8, (4, 4))])
    path = str(tmp_path / "store.dfps")
    store.save(path)
    with open(path, encoding="utf-8") as f:
        return path, f.read().splitlines(), pairs


def test_corrupt_entry_rejected_then_cold_lower(tmp_path):
    path, lines, pairs = _saved_lines(tmp_path)
    bad = str(tmp_path / "bad.dfps")
    with open(bad, "w", encoding="utf-8") as f:
        f.write(lines[0] + "\n" + lines[1].replace("reads", "rEAds", 1)
                + "\n")
    store = PlanStore.open(bad)
    assert store.stats["restore_rejected"] == 1   # checksum catches it
    g, plan, params, x = pairs[0]
    lowered = store.get_or_lower(g, plan, salt="t")
    assert store.stats["misses"] == 1             # graceful cold fallback
    _assert_same(Realizer(g, plan, lowered=False)(params, {"x": x}),
                 lowered(params, {"x": x}))


def test_header_version_mismatch_rejects_file(tmp_path):
    path, lines, pairs = _saved_lines(tmp_path)
    for mutation in ({"format_version": FORMAT_VERSION + 1},
                     {"fingerprint_version": FINGERPRINT_VERSION + 1},
                     {"magic": "not-a-planstore"}):
        hdr = json.loads(lines[0])
        hdr.update(mutation)
        bad = str(tmp_path / "bad.dfps")
        with open(bad, "w", encoding="utf-8") as f:
            f.write(json.dumps(hdr) + "\n" + lines[1] + "\n")
        store = PlanStore.open(bad)
        assert store.stats["restore_errors"] == 1, mutation
        assert store.n_restorable == 0


def test_garbage_and_empty_files_rejected(tmp_path):
    for body in ("", "complete garbage\n", "{}\n", '{"magic": 3}\n'):
        bad = str(tmp_path / "bad.dfps")
        with open(bad, "w", encoding="utf-8") as f:
            f.write(body)
        store = PlanStore.open(bad)
        assert store.stats["restore_errors"] == 1
        g, plan, *_ = _bucket(Chain(), 8, (4, 4))
        store.get_or_lower(g, plan, salt="t")
        assert store.stats["misses"] == 1


def test_schema_malformed_entry_degrades_to_cold_lower(tmp_path):
    """A checksum-valid payload missing a record field must reject at
    redeem time (RestoreError net), not crash the serving request."""
    import hashlib

    path, lines, pairs = _saved_lines(tmp_path)
    parts = lines[1].split(" ", 4)
    obj = json.loads(parts[4])
    del obj["buckets"][0]["instrs"]
    payload = json.dumps(obj, sort_keys=True, separators=(",", ":"))
    check = hashlib.sha256(payload.encode()).hexdigest()[:16]
    bad = str(tmp_path / "bad.dfps")
    with open(bad, "w", encoding="utf-8") as f:
        f.write(lines[0] + "\n")
        f.write(f"{parts[0]} {parts[1]} {parts[2]} {check} {payload}\n")
    store = PlanStore.open(bad)
    assert store.stats["restore_rejected"] == 0    # checksum passes
    g, plan, params, x = pairs[0]
    lowered = store.get_or_lower(g, plan, salt="t")
    assert store.stats["restore_rejected"] >= 1
    assert store.stats["misses"] == 1
    # a rejected restore is timed apart from the successful ones
    assert store.spans["plan.restore_rejected"]["count"] >= 1
    assert "plan.restore" not in store.spans
    _assert_same(Realizer(g, plan, lowered=False)(params, {"x": x}),
                 lowered(params, {"x": x}))


def test_entry_version_mismatch_rejects_entry(tmp_path):
    path, lines, _ = _saved_lines(tmp_path)
    parts = lines[1].split(" ", 2)
    tampered = f"{parts[0]} {FORMAT_VERSION + 1} {parts[2]}"
    bad = str(tmp_path / "bad.dfps")
    with open(bad, "w", encoding="utf-8") as f:
        f.write(lines[0] + "\n" + tampered + "\n")
    store = PlanStore.open(bad)
    assert store.stats["restore_rejected"] == 1
    assert store.n_restorable == 0


def test_missing_file_is_empty_store_not_error(tmp_path):
    store = PlanStore.open(str(tmp_path / "never-written.dfps"))
    assert store.stats["restore_errors"] == 0
    assert store.n_restorable == 0


# ---------------------------------------------------------------------------
# format: determinism, atomicity, unpersistable keys
# ---------------------------------------------------------------------------


def test_save_is_deterministic_and_atomic(tmp_path):
    net = Chain()
    store, _ = _populate(net, [(8, (4, 4)), (16, (8, 8))])
    a, b = str(tmp_path / "a.dfps"), str(tmp_path / "b.dfps")
    store.save(a)
    store.save(b)
    with open(a, encoding="utf-8") as fa, open(b, encoding="utf-8") as fb:
        assert fa.read() == fb.read()
    # atomic replace: no tempfile litter next to the artifact
    assert [f for f in os.listdir(tmp_path) if f.startswith(".planstore")] \
        == []
    # saving over an existing file keeps it loadable
    store.save(a)
    assert PlanStore.open(a).n_restorable == 1


def test_opaque_closure_entries_not_persisted(tmp_path):
    """Fused kernels closing over non-primitives key as ("id", id(fn)) —
    meaningless in another process, so save() must skip them."""
    from repro.core import FULL, OpSchedulerBase
    from repro.core.plan import OpHandle

    box = {"factor": 2.0}                  # non-primitive closure cell

    def scaled(info, x):
        p = info.params_of(0)
        return jnp.tanh(x @ p["w"]) * box["factor"]

    class FuseFirst(OpSchedulerBase):
        def schedule(self, ctx):
            oids = ctx.graph.topo_order()
            ctx.execute((OpHandle(oids[0], FULL, ""),),
                        replace_func=scaled, replace_name="scaled")
            ctx.run_rest_sequential()

    net = Chain(3)
    g = trace(net, {"x": jax.ShapeDtypeStruct((8, D), jnp.float32)})
    plan = record_plan(g, FuseFirst(), ScheduleContext(local_batch=8))
    store = PlanStore()
    store.get_or_lower(g, plan, salt="fuse")
    path = str(tmp_path / "store.dfps")
    assert store.save(path) == 0
    assert store.stats["restore_skipped"] == 1


def test_persistable_key_marks_id_fallbacks():
    assert persistable_key(("fn", "mod", "qual"))
    assert persistable_key((("closure", "m", "q", (1, b"x")), "s", ()))
    assert not persistable_key(("id", 140234))
    assert not persistable_key((("deep", ("id", 7)), "s"))


# ---------------------------------------------------------------------------
# admission policy: one-shot buckets stay out of the artifact
# ---------------------------------------------------------------------------


def test_one_shot_eviction_not_readmitted(tmp_path):
    from repro.core import OpSchedulerBase

    class Seq(OpSchedulerBase):
        pass

    def pair(n):
        g = trace(Chain(n), {"x": jax.ShapeDtypeStruct((8, D),
                                                       jnp.float32)})
        return g, record_plan(g, Seq(), ScheduleContext(local_batch=8))

    store = PlanStore(plan_capacity=2)
    p1, p2, p3 = pair(2), pair(3), pair(4)
    store.get_or_lower(*p1)
    store.get_or_lower(*p2)
    store.get_or_lower(*p3)               # evicts p1 before a 2nd touch
    assert store.stats["one_shot_evictions"] >= 1
    store.get_or_lower(*p1)               # re-lowered, live again
    path = str(tmp_path / "store.dfps")
    store.save(path)
    # the one-shot record is part of the artifact's header...
    hdr = json.loads(open(path, encoding="utf-8").readline())
    assert len(hdr["one_shot"]) >= 1
    # ...and p1, despite being live at save time, was not re-admitted
    warm = PlanStore.open(path)
    warm.get_or_lower(*pair(2))
    assert warm.stats["restore_hits"] == 0 and warm.stats["misses"] == 1


def test_touched_entries_are_persisted_under_churn():
    """A hit or a share marks the entry as reused — not one-shot."""
    net = Chain()
    store = PlanStore(plan_capacity=1)
    g1, p1, *_ = _bucket(net, 8, (4, 4))
    g2, p2, *_ = _bucket(net, 16, (8, 8))
    store.get_or_lower(g1, p1)
    store.get_or_lower(g2, p2)            # share touches the canonical,
    assert store.stats["evictions"] == 1  # then evicts it
    assert store.stats["one_shot_evictions"] == 0


# ---------------------------------------------------------------------------
# exec level: tightened key_for + byte budget
# ---------------------------------------------------------------------------


def test_key_for_accepts_arrays_and_scalars_only():
    store = PlanStore()
    key = store.key_for("fp", {"x": np.zeros((2, 3), np.float32),
                               "n": 7, "flag": True, "name": "bucket"})
    assert key == ("fp", (("flag", "py", "bool", True),
                          ("n", "py", "int", 7),
                          ("name", "py", "str", "bucket"),
                          ("x", (2, 3), "float32")))
    with pytest.raises(TypeError, match="neither an array"):
        store.key_for("fp", {"bad": [1, 2, 3]})
    with pytest.raises(TypeError, match="neither an array"):
        store.key_for("fp", {"bad": object()})


def test_exec_byte_budget_evicts_lru():
    store = PlanStore(exec_capacity=100, exec_budget_bytes=3 * 4096)
    for i in range(5):
        store.get_or_build(("k", i), lambda i=i: (lambda: i))
    assert store.n_execs <= 3
    assert store.stats["exec_evictions"] >= 2
    assert store.stats["exec_bytes"] <= 3 * 4096
    # byte accounting survives eviction churn
    assert store.stats["exec_bytes"] == sum(
        nb for _, nb in store._execs.values())
    # LRU: the newest keys survive
    assert ("k", 4) in store._execs and ("k", 0) not in store._execs


def test_snapshot_exec_symmetry():
    store = PlanStore()
    store.get_or_build(("a",), lambda: (lambda: 1))
    store.get_or_build(("a",), lambda: (lambda: 1))
    snap = store.snapshot()
    for k in ("exec_hits", "exec_misses", "exec_evictions", "exec_bytes",
              "exec_hit_rate", "n_execs", "share_rate", "n_plans",
              "n_restorable"):
        assert k in snap, k
    assert snap["exec_hit_rate"] == 0.5


# ---------------------------------------------------------------------------
# stack threading: train-step builder persistence
# ---------------------------------------------------------------------------


def test_digest_is_stable_across_key_copies():
    k = (("a", (1, 2)), "s", ())
    assert key_digest(k) == key_digest((("a", (1, 2)), "s", ()))
