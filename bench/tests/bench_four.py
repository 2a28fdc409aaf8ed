"""Checks that need four devices, run by ``test_bench_chips_cpu.py`` in a
process of their own on four virtual CPU devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python3 bench/tests/bench_four.py weights|serve|train

``weights``: the weights drawn over a (1, 4) mesh are bitwise those of
one device; the program's tree laid out over the mesh is bitwise what the
program's own host relayout (``dense_tp_params``) gives, with the
program's shardings; the reference over the mesh gives the one-device
reference's logits within float32 rounding.  ``serve`` and ``train``: a
4-chip set-up, driven as a run drives it, past the device check.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
for p in (str(HERE), str(HERE.parents[1]), str(HERE.parents[1] / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# float32 logits of a sum regrouped over four shards: they move by some
# units of float32's 1.2e-7 times the largest logit (1e-6 read on the
# CPU); a shard out of place or bfloat16 arithmetic moves them by 1e-3
# or more
REL_TOL = 1e-5


def weights_case():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench_tiny import cell
    from repro import api
    from repro.launch.sharding import dense_tp_params

    from bench import common, serve, weights
    _, _, cfg, _ = cell("chatglm3-6b.chat")
    m = common.model_dims(cfg)
    mesh = common.cell_mesh(jax.devices()[:4])
    key = common.seed_key(2**40 + 3)
    w1 = weights.make(m, key, jnp.bfloat16)
    w4 = weights.make(m, key, jnp.bfloat16, mesh)
    for k in w1:
        assert w4[k].sharding.mesh.devices.size == 4, k
        assert np.array_equal(np.asarray(w1[k]), np.asarray(w4[k])), k
    held = {k: max(s.data.size for s in w4[k].addressable_shards)
            for k in w4}
    for k in common.family(m).SPLIT:
        if k in w4:
            assert held[k] * 4 == w4[k].size, k

    program = api.compile(common.family(m).arch_config(cfg, m), mesh=mesh)
    got = weights.to_program(w4, m, program)
    want = dense_tp_params(jax.device_get(weights.to_program(w1, m)),
                           program.model)
    shd = weights.program_shardings(program)
    for (path, a), b, s in zip(jax.tree_util.tree_leaves_with_path(got),
                               jax.tree_util.tree_leaves(want),
                               jax.tree_util.tree_leaves(shd)):
        assert np.array_equal(np.asarray(a), b), path
        assert a.sharding.is_equivalent_to(s, a.ndim), path

    ref = common.reference(m)
    ids = jnp.asarray(np.random.default_rng(1).integers(
        0, m["vocab"], (2, 64), np.int32))
    z1, z4 = (np.asarray(ref.logits(w, ids, m)) for w in (w1, w4))
    rel = float(np.abs(z1 - z4).max() / np.abs(z1).max())
    seqs = [(np.arange(40) % m["vocab"], np.arange(9) + 3),
            (np.arange(100) % m["vocab"], np.arange(20) + 1)]
    g1, g4 = (serve.logit_gaps(w, m, seqs, pad_to=64) for w in (w1, w4))
    gap = max(float(np.abs(a - b).max()) for a, b in zip(g1, g4))
    print(f"logits rel {rel!r} logit_gap {gap!r}")
    assert rel <= REL_TOL and gap <= REL_TOL * float(np.abs(z1).max())


def setup_case(name: str):
    import jax
    from bench_tiny import cell

    from bench import common
    from bench import run as bench_run
    common.use_compile_cache = lambda: "none"
    bench, c, cfg, mix = cell(name)
    bench_run.run_cell(bench, c, cfg, mix, 5, 1.0, False,
                       jax.devices()[:4], time.perf_counter())


if __name__ == "__main__":
    case = sys.argv[1]
    if case == "weights":
        weights_case()
    else:
        setup_case({"serve": "chatglm3-6b.chat",
                    "train": "smollm-135m.train"}[case])
    print(f"{case}: done")
