"""Serving engine tests: continuous batching, determinism, cache reuse."""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.strategies import get_strategy
from repro.models.layers import MeshInfo
from repro.models.registry import build_model
from repro.serve import Request, ServeConfig, ServeEngine


@pytest.fixture(scope="module")
def engine_setup():
    cfg = get_smoke_config("chatglm3-6b")
    model = build_model(cfg, MeshInfo(tp=1, dp=1))
    segs, _ = model.build_segments("prefill", 1, 32, s_max=64)
    params = model._init_from_segments(segs, jax.random.PRNGKey(0))
    return cfg, model, params


def make_engine(model, params, **kw):
    cfg = ServeConfig(max_batch=4, s_max=64, prefill_buckets=(16, 32), **kw)
    return ServeEngine(model, params, get_strategy("sequential"), cfg)


def test_serves_more_requests_than_slots(engine_setup):
    cfg, model, params = engine_setup
    eng = make_engine(model, params)
    rng = np.random.default_rng(0)
    for i in range(9):                      # > max_batch: rows recycle
        eng.submit(Request(rid=i, prompt=rng.integers(
            0, 100, int(rng.integers(4, 14))).astype(np.int32),
            max_new_tokens=6))
    done = eng.run()
    assert len(done) == 9
    assert all(len(r.output) == 6 for r in done)
    assert len(eng.cache.free_rows) == 4    # all rows released


def test_same_prompt_same_output(engine_setup):
    cfg, model, params = engine_setup
    eng = make_engine(model, params)
    pr = np.arange(7, dtype=np.int32)
    eng.submit(Request(rid=0, prompt=pr, max_new_tokens=6))
    eng.submit(Request(rid=1, prompt=pr.copy(), max_new_tokens=6))
    done = eng.run()
    assert done[0].output == done[1].output


def test_engine_matches_offline_greedy(engine_setup):
    """Engine output == running prefill(n+i) argmax step by step."""
    import jax.numpy as jnp
    from repro.core.scheduler import OpSchedulerBase, ScheduleContext
    from repro.models.base import build_forward
    cfg, model, params = engine_setup
    pr = np.arange(5, dtype=np.int32) + 3
    eng = make_engine(model, params)
    eng.submit(Request(rid=0, prompt=pr, max_new_tokens=3))
    got = eng.run()[0].output

    ids = list(pr)
    want = []
    for _ in range(3):
        n = len(ids)
        segs, _ = model.build_segments("prefill", 1, n, s_max=64)
        fwd = build_forward(segs, OpSchedulerBase(),
                            ScheduleContext(local_batch=1, seq_len=n,
                                            phase="prefill",
                                            arch=cfg.name))
        out = fwd(params, {
            "ids": jnp.asarray(ids, jnp.int32)[None],
            "positions": jnp.arange(n, dtype=jnp.int32)[None]})
        nxt = int(jnp.argmax(out["logits"][0, -1]))
        want.append(nxt)
        ids.append(nxt)
    assert got == want


def test_executable_cache_reuse(engine_setup):
    cfg, model, params = engine_setup
    eng = make_engine(model, params)
    rng = np.random.default_rng(1)
    for i in range(6):
        eng.submit(Request(rid=i, prompt=rng.integers(
            0, 100, 10).astype(np.int32), max_new_tokens=4))
    eng.run()
    st = eng.store.stats
    # every executable build is one (phase, tier/bucket) capture; the
    # steady state replays them: a run of 6 requests over 2 admission
    # waves must hit far more often than it builds
    assert st["exec_misses"] <= 1 + len(eng.prefill_tiers) + len(eng.tiers)
    assert st["exec_hits"] >= st["exec_misses"]
    # and every non-canonical plan bucket came from specialize, not lower
    assert st["misses"] <= 3 * 2, st    # 3 segments x (prefill, decode)


def test_cross_bucket_plan_share(engine_setup):
    """Later prefill buckets and smaller decode tiers must not re-lower:
    their segment plans are structurally identical to the first bucket's
    / the first tier's, so the PlanStore serves them via fingerprint-v2
    specialization (counted as shares)."""
    cfg, model, params = engine_setup
    eng = make_engine(model, params, prefill_batch=1)
    rng = np.random.default_rng(2)
    eng.submit(Request(rid=0, prompt=rng.integers(0, 100, 10)
                       .astype(np.int32), max_new_tokens=3))   # bucket 16
    eng.submit(Request(rid=1, prompt=rng.integers(0, 100, 20)
                       .astype(np.int32), max_new_tokens=3))   # bucket 32
    done = eng.run()
    assert len(done) == 2
    st = eng.store.stats
    # the second prefill bucket and every decode tier after the first
    # share their segment plans off the canonical lowerings
    assert st["shares"] >= 3, st
    assert eng.store.share_rate > 0
    # eviction stats surface through engine metrics
    assert "evictions" in eng.stats["plan_store"]


def test_engine_warm_starts_from_persisted_store(engine_setup, tmp_path,
                                                 monkeypatch):
    """A restarted engine bound to the same plan_store_path serves its
    requests with zero lower() calls (restore hits + shares only) and
    produces identical tokens."""
    cfg, model, params = engine_setup
    path = str(tmp_path / "plans.dfps")
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 100, n).astype(np.int32) for n in (10, 20)]

    eng = make_engine(model, params, plan_store_path=path)
    for i, pr in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=pr.copy(), max_new_tokens=3))
    want = [r.output for r in sorted(eng.run(), key=lambda r: r.rid)]
    eng.shutdown()
    assert path and eng.store.stats["restore_saved"] >= 1

    # "restart": fresh engine, same path; any lower() call is a failure
    from repro.core import plan_store as plan_store_mod

    def bomb(*a, **k):
        raise AssertionError("warm-started engine re-lowered a plan")
    monkeypatch.setattr(plan_store_mod, "lower", bomb)
    eng2 = make_engine(model, params, plan_store_path=path)
    for i, pr in enumerate(prompts):
        eng2.submit(Request(rid=i, prompt=pr.copy(), max_new_tokens=3))
    got = [r.output for r in sorted(eng2.run(), key=lambda r: r.rid)]
    assert got == want
    st = eng2.store.snapshot()
    assert st["misses"] == 0, st
    assert st["restore_hits"] + st["shares"] > 0, st


def test_train_step_builder_warm_starts(engine_setup, tmp_path,
                                        monkeypatch):
    """build_train_step(plan_store_path=...) persists the lowerings and a
    relaunch restores them without re-lowering (trainer preemption)."""
    from repro.core.strategies import get_strategy
    from repro.train.step import TrainStepConfig, build_train_step
    cfg, model, params = engine_setup
    path = str(tmp_path / "train-plans.dfps")
    tcfg = TrainStepConfig(remat=False)
    build_train_step(model, get_strategy("sequential"), 2, 16, tcfg,
                     plan_store_path=path)
    assert (tmp_path / "train-plans.dfps").exists()

    from repro.core import plan_store as plan_store_mod

    def bomb(*a, **k):
        raise AssertionError("relaunched trainer re-lowered a plan")
    monkeypatch.setattr(plan_store_mod, "lower", bomb)
    build_train_step(model, get_strategy("sequential"), 2, 16, tcfg,
                     plan_store_path=path)


def _serve_argv():
    return ["--arch", "chatglm3-6b", "--smoke", "--requests", "3",
            "--max-new", "4"]


def test_serve_launcher_serves_every_request(tmp_path):
    """The smoke launcher run finishes every request with tokens: a clean
    exit must not hide requests the engine failed."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=os.path.join(repo, "src"))
    r = subprocess.run([sys.executable, "-m", "repro.launch.serve",
                        *_serve_argv()], capture_output=True, text=True,
                       timeout=300, env=env)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "served 3 requests, 12 tokens" in r.stdout
    assert "'failed': 0" in r.stdout and "'shed': 0" in r.stdout
    assert "TTFT p50=" in r.stdout and "TTFT p50=-" not in r.stdout


def test_serve_launcher_exits_nonzero_on_failed_requests(monkeypatch,
                                                         capsys):
    from repro.launch import serve as launcher

    def broken(self, bp, bucket):
        raise RuntimeError("injected prefill build failure")

    monkeypatch.setattr(launcher, "use_compile_cache", lambda: "")
    monkeypatch.setattr(ServeEngine, "_prefill_fn", broken)
    assert launcher.main(_serve_argv()) == 1
    out = capsys.readouterr()
    assert "TTFT" not in out.out          # no request produced a token
    assert "injected prefill build failure" in out.err


def test_compile_cache_dir(monkeypatch, tmp_path):
    from repro.launch import jax_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(jax_cache.ENV, str(tmp_path))
        assert jax_cache.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv(jax_cache.ENV)
        want = str(jax_cache.CHECKOUT / ".jax_cache")
        assert jax_cache.use_compile_cache() == want
        assert (jax_cache.CHECKOUT / "src" / "repro").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
