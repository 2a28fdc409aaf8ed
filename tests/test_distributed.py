"""Multi-device SPMD tests (subprocess isolation: each case forces its
own host-device count before importing jax, keeping the main test
session single-device as required)."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_devices(n, body, timeout=420):
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        assert jax.device_count() == {n}
    """) + textwrap.dedent(body)
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src") + os.pathsep + REPO)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    return r.stdout


def test_tp_sharded_matches_single_device():
    """TP=4 forward under shard_map == tp=1 forward (same global math)."""
    run_devices(4, """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.configs import get_smoke_config
        from repro.models.registry import build_model
        from repro.models.layers import MeshInfo
        from repro.models.base import build_forward
        from repro.core.strategies import get_strategy
        from repro.core.scheduler import ScheduleContext
        from repro.launch.sharding import (global_param_specs,
                                           global_batch_specs,
                                           shard_specs_of)
        import dataclasses

        cfg = dataclasses.replace(get_smoke_config("chatglm3-6b"),
                                  n_heads=4, n_kv=2, d_model=32, d_ff=64)
        mesh = jax.make_mesh((1, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        B, S = 2, 16

        # single-device reference
        m1 = build_model(cfg, MeshInfo(tp=1, dp=1))
        segs1, binputs1 = m1.build_segments("train", B, S)
        fwd1 = build_forward(segs1, get_strategy("sequential"),
                             ScheduleContext(local_batch=B, seq_len=S,
                                             phase="train"))
        p1 = m1._init_from_segments(segs1, jax.random.PRNGKey(0),
                                    global_=True)
        batch = {"ids": jax.random.randint(jax.random.PRNGKey(2),
                                           (B, S), 0, 100),
                 "labels": jax.random.randint(jax.random.PRNGKey(3),
                                              (B, S), 0, 100),
                 "positions": jnp.broadcast_to(
                     jnp.arange(S, dtype=jnp.int32), (B, S))}
        out1 = fwd1(p1, batch)
        want = float(jnp.sum(out1["loss_sum"]) / jnp.sum(out1["token_count"]))

        # TP=4 under shard_map, global params initialized identically
        m4 = build_model(cfg, MeshInfo(tp=4, dp=1))
        segs4, _ = m4.build_segments("train", B, S)
        fwd4 = build_forward(segs4, get_strategy("sequential"),
                             ScheduleContext(local_batch=B, seq_len=S,
                                             phase="train"))
        pg = m4._init_from_segments(segs4, jax.random.PRNGKey(0),
                                    global_=True)
        _, pshd = global_param_specs(m4, segs4, mesh)
        p_specs = shard_specs_of(pshd)

        def step(params, batch):
            out = fwd4(params, batch)
            return (jnp.sum(out["loss_sum"]),
                    jnp.sum(out["token_count"]))

        fm = jax.shard_map(step, mesh=mesh,
                           in_specs=(p_specs,
                                     {"ids": P(), "labels": P(),
                                      "positions": P()}),
                           out_specs=(P(), P()), check_vma=False)
        pg_dev = jax.device_put(pg, pshd)
        ls, cnt = jax.jit(fm)(pg_dev, batch)
        got = float(ls / cnt)
        # NOTE: tp=1 vs tp=4 differ in param INIT layout for sharded dims,
        # so exact equality needs identical global init: both used
        # global_=True from the same fold_in keys => identical tables.
        assert abs(got - want) < 5e-2 * max(abs(want), 1.0), (got, want)
        print("TP4 OK", got, want)
    """)


def test_moe_token_sharded_vs_replicated():
    """EP token-sharded (a2a) MoE == replicated (slice+psum) MoE."""
    run_devices(4, """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs.base import MoEConfig, ArchConfig
        from repro.models.moe import MoEBlock
        from repro.models.layers import MeshInfo
        cfg = ArchConfig(name="t", family="moe", n_layers=1, d_model=16,
                         n_heads=2, n_kv=2, d_ff=32, vocab=64,
                         moe=MoEConfig(n_experts=4, top_k=2,
                                       d_ff_expert=8, n_shared=1,
                                       capacity_factor=4.0))
        mesh = jax.make_mesh((4,), ("model",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        minfo = MeshInfo(tp=4, dp=1)
        blk_ts = MoEBlock(cfg, minfo, token_sharded=True)
        blk_rp = MoEBlock(cfg, minfo, token_sharded=False)
        params = blk_ts.init(jax.random.PRNGKey(0), global_=True)
        params_rp = blk_rp.init(jax.random.PRNGKey(0), global_=True)
        # expert weights: global (V=4 experts total); token_sharded blocks
        # see the same expert set
        B, S, d = 2, 8, 16
        x = jax.random.normal(jax.random.PRNGKey(1), (B, S, d),
                              jnp.bfloat16)

        def ts(params, x):
            # x arrives seq-sharded (B, S/4, d)
            return blk_ts.apply(params, x)

        def rp(params, x):
            return blk_rp.apply(params, x)

        from repro.launch.sharding import spec_to_p
        import jax.tree_util as jtu
        pspec_ts = jtu.tree_map(spec_to_p, blk_ts.param_pspecs(),
                                is_leaf=lambda v: isinstance(v, tuple))
        pspec_rp = jtu.tree_map(spec_to_p, blk_rp.param_pspecs(),
                                is_leaf=lambda v: isinstance(v, tuple))
        f_ts = jax.shard_map(ts, mesh=mesh,
                             in_specs=(pspec_ts, P(None, "model", None)),
                             out_specs=P(None, "model", None),
                             check_vma=False)
        f_rp = jax.shard_map(rp, mesh=mesh,
                             in_specs=(pspec_rp, P()), out_specs=P(),
                             check_vma=False)
        from jax.sharding import NamedSharding
        put = lambda t, s: jax.device_put(t, jtu.tree_map(
            lambda sp: NamedSharding(mesh, sp), s,
            is_leaf=lambda v: isinstance(v, P)))
        y_ts = jax.jit(f_ts)(put(params, pspec_ts),
                             jax.device_put(x, NamedSharding(
                                 mesh, P(None, "model", None))))
        y_rp = jax.jit(f_rp)(put(params_rp, pspec_rp), x)
        np.testing.assert_allclose(np.asarray(y_ts, np.float32),
                                   np.asarray(y_rp, np.float32),
                                   atol=3e-2, rtol=3e-2)
        print("MoE modes agree")
    """)


def test_tokenweave_fused_collective_4dev():
    run_devices(4, """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.kernels import ops, ref
        mesh = jax.make_mesh((4,), ("model",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        B, S, d = 2, 16, 32
        y_parts = jax.random.normal(jax.random.PRNGKey(0), (4, B, S, d))
        x = jax.random.normal(jax.random.PRNGKey(1), (B, S, d))
        g = jax.random.normal(jax.random.PRNGKey(2), (d,))

        def f(yp, x, g):
            return ops.fused_ar_add_rmsnorm(yp[0], x, g, axis="model")

        fm = jax.shard_map(f, mesh=mesh, in_specs=(P("model"), P(), P()),
                           out_specs=(P(), P()), check_vma=False)
        s, h = jax.jit(fm)(y_parts, x, g)
        s2, h2 = ref.fused_add_rmsnorm(x, y_parts.sum(0), g)
        np.testing.assert_allclose(s, s2, atol=1e-4)
        np.testing.assert_allclose(h, h2, atol=1e-4)
        print("tokenweave 4dev OK")
    """)


def test_pipeline_driver_4stages():
    run_devices(4, """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.dist.pipeline import pipeline_apply
        mesh = jax.make_mesh((4,), ("pod",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        Ws = jnp.stack([jnp.eye(8) * (i + 1) for i in range(4)])
        mbs = jax.random.normal(jax.random.PRNGKey(0), (6, 3, 8))

        def f(ws, mb):
            return pipeline_apply(lambda w, x: x @ w, ws[0], mb, axis="pod")

        fm = jax.shard_map(f, mesh=mesh, in_specs=(P("pod"), P()),
                           out_specs=P("pod"), check_vma=False)
        out = jax.jit(fm)(Ws, mbs)
        np.testing.assert_allclose(out[18:24], mbs @ (jnp.eye(8) * 24.0),
                                   atol=1e-4)
        print("pipeline OK")
    """)


def test_grad_reduction_rules_dp():
    """DP=2: per-replica grads psum; loss normalized by global tokens."""
    run_devices(2, """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_smoke_config
        from repro.models.registry import build_model
        from repro.models.layers import MeshInfo
        from repro.core.strategies import get_strategy
        from repro.train import TrainStepConfig, build_train_step
        from repro.optim import AdamWConfig
        mesh = jax.make_mesh((2, 1), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = get_smoke_config("smollm-135m")
        model = build_model(cfg, MeshInfo(tp=1, dp=2))
        B_loc, S = 2, 16
        step, segs, binputs, init_opt = build_train_step(
            model, get_strategy("sequential"), B_loc, S,
            TrainStepConfig(optimizer=AdamWConfig(lr=1e-3), remat=False,
                            warmup=1, total_steps=5))
        params = model._init_from_segments(segs, jax.random.PRNGKey(0))
        opt = init_opt(params)
        batch = {"ids": jax.random.randint(jax.random.PRNGKey(1),
                                           (2 * B_loc, S), 0, 100),
                 "labels": jax.random.randint(jax.random.PRNGKey(2),
                                              (2 * B_loc, S), 0, 100),
                 "positions": jnp.broadcast_to(
                     jnp.arange(S, dtype=jnp.int32), (2 * B_loc, S))}
        bspec = {"ids": P("data"), "labels": P("data"),
                 "positions": P("data")}
        fm = jax.shard_map(step, mesh=mesh,
                           in_specs=(P(), P(), bspec, P()),
                           out_specs=(P(), P(),
                                      {"loss": P(), "grad_norm": P(),
                                       "lr": P(), "tokens": P()}),
                           check_vma=False)
        p2, o2, m = jax.jit(fm)(params, opt, batch, jnp.int32(0))
        assert float(m["tokens"]) == 2 * B_loc * S
        # reference: single-device over the full batch
        step1, segs1, _, init_opt1 = build_train_step(
            build_model(cfg, MeshInfo(tp=1, dp=1)),
            get_strategy("sequential"), 2 * B_loc, S,
            TrainStepConfig(optimizer=AdamWConfig(lr=1e-3), remat=False,
                            warmup=1, total_steps=5))
        p1 = build_model(cfg, MeshInfo(tp=1, dp=1))._init_from_segments(
            segs1, jax.random.PRNGKey(0))
        o1 = init_opt1(p1)
        p1n, _, m1 = jax.jit(step1)(p1, o1, batch, jnp.int32(0))
        assert abs(float(m["loss"]) - float(m1["loss"])) < 1e-3
        # updated params agree (grad psum == full-batch grad)
        for a, b in zip(jax.tree_util.tree_leaves(p2),
                        jax.tree_util.tree_leaves(p1n)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=5e-3)
        print("DP grad reduction OK")
    """)


def test_fsdp_resident_decode_linear_matches_gathered():
    """DataShardedLinearOp (resident ZeRO decode path) == gather path."""
    run_devices(4, """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        import jax.tree_util as jtu
        from repro.models.layers import (MeshInfo, ShardedLinear)
        from repro.launch.sharding import spec_to_p
        mesh = jax.make_mesh((4, 1), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        d_in, d_out, B = 32, 16, 4
        x = jax.random.normal(jax.random.PRNGKey(0), (B, 1, d_in))

        w = jax.random.normal(jax.random.PRNGKey(1), (d_in, d_out))
        outs = {}
        for resident in (False, True):
            minfo = MeshInfo(tp=1, dp=4, fsdp=True, fsdp_resident=resident)
            lin = ShardedLinear(d_in, d_out, "proj", minfo,
                                dtype=jnp.float32)
            params = lin.init(jax.random.PRNGKey(1), global_=True)
            # identical weight in both storage layouts
            child = "lin" if resident else "gather"
            params = {child: {"w": w}}
            pspec = jtu.tree_map(spec_to_p, lin.param_pspecs(),
                                 is_leaf=lambda v: isinstance(v, tuple))
            f = jax.shard_map(lambda p, x: lin.apply(p, x), mesh=mesh,
                              in_specs=(pspec, P()), out_specs=P(),
                              check_vma=False)
            pd = jax.device_put(params, jtu.tree_map(
                lambda sp: NamedSharding(mesh, sp), pspec,
                is_leaf=lambda v: isinstance(v, P)))
            outs[resident] = np.asarray(jax.jit(f)(pd, x))
        np.testing.assert_allclose(outs[False], outs[True],
                                   atol=1e-5, rtol=1e-5)
        print("resident decode linear OK")
    """)


def test_ff_sharded_experts_match_dense_experts():
    """FFShardedExpertGEMM partials + psum == full expert FFN."""
    run_devices(4, """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        import jax.tree_util as jtu
        from repro.configs.base import MoEConfig
        from repro.models.moe import ExpertGEMMOp, FFShardedExpertGEMM
        from repro.models.layers import MeshInfo
        from repro.launch.sharding import spec_to_p
        mesh = jax.make_mesh((4, 1), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        m = MoEConfig(n_experts=2, top_k=1, d_ff_expert=16)
        d = 8
        buf = jax.random.normal(jax.random.PRNGKey(0), (2, 4, d))

        dense = ExpertGEMMOp(d, m, MeshInfo(tp=1, dp=4), dtype=jnp.float32)
        pd = dense.init(jax.random.PRNGKey(1), global_=True)
        want = dense.apply(pd, buf)

        ff = FFShardedExpertGEMM(d, m, MeshInfo(tp=1, dp=4, fsdp=True),
                                 dtype=jnp.float32)
        pf = ff.init(jax.random.PRNGKey(1), global_=True)
        pspec = jtu.tree_map(spec_to_p, ff.param_pspecs(),
                             is_leaf=lambda v: isinstance(v, tuple))

        def f(p, x):
            return jax.lax.psum(ff.apply(p, x), "data")

        fm = jax.shard_map(f, mesh=mesh, in_specs=(pspec, P()),
                           out_specs=P(), check_vma=False)
        pdev = jax.device_put(pf, jtu.tree_map(
            lambda sp: NamedSharding(mesh, sp), pspec,
            is_leaf=lambda v: isinstance(v, P)))
        got = jax.jit(fm)(pdev, buf)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)
        print("ff-sharded experts OK")
    """)


def test_decode_tier_steps_share_one_lowering_tp2():
    """build_global_decode_tiers under a tp=2 mesh: one canonical decode
    lowering, every further batch tier a PlanStore share — the launch
    layer's half of the tiered-serve story."""
    run_devices(2, """
        import jax
        from repro.configs import get_smoke_config
        from repro.configs.base import ShapeConfig
        from repro.core import PlanStore
        from repro.core.strategies import get_strategy
        from repro.launch.steps import build_global_decode_tiers
        from repro.models.layers import MeshInfo
        from repro.models.registry import build_model

        mesh = jax.make_mesh((1, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = get_smoke_config("chatglm3-6b")
        model = build_model(cfg, MeshInfo(tp=2, dp=1))
        store = PlanStore()
        shape = ShapeConfig("decode_smoke", seq_len=32, global_batch=4,
                            kind="decode")
        tiers = build_global_decode_tiers(model, get_strategy("sequential"),
                                          shape, mesh, plan_store=store)
        assert set(tiers) == {1, 2, 4}, sorted(tiers)
        st = store.stats
        # first tier lowers each segment once; tiers 2 and 4 specialize
        assert st["misses"] == 3, st
        assert st["shares"] == 6, st
        # the derived-tier step must actually compile and keep its
        # tier-sized global batch
        fn, in_sdss, _, donate, _ = tiers[2]
        assert in_sdss[1]["ids"].shape == (2, 1), in_sdss[1]["ids"].shape
        jax.jit(fn).lower(*in_sdss).compile()
        print("decode tiers OK")
    """)


def test_chip_smoke_tp_phase_matches_one_device():
    """``chip_smoke.py --chips 4``'s comparison at smoke size: chatglm3
    prefill + one decode step on a (1, 4) mesh, with the one-device params
    relaid out by ``dense_tp_params`` (replicated-KV branch: n_kv=2 < tp),
    against one device."""
    out = run_devices(4, """
        import chip_smoke
        fails = chip_smoke.tp_phase(0, smoke=True, batch=4, seq=64,
                                    s_max=128)
        assert not fails, fails
    """)
    assert "tp: decode logits" in out
