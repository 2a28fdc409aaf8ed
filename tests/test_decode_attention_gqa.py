"""Grouped-query decode attention (``DecodeAttentionOp.grouped``).

When every shard's local q head ``i`` reads KV slot ``i // g``, the op
reads the cache once per KV head; other layouts copy K and V to every q
head with ``jnp.take`` and run ``_sdpa``.  Both paths must give the same
result, and the grouped one must never build the copies.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.layers import DecodeAttentionOp, HeadLayout

LAYOUTS = {                       # (n_q, n_kv, tp)
    "32-2-tp1": (32, 2, 1),       # chatglm3-6b on one chip
    "32-2-tp4": (32, 2, 4),       # chatglm3-6b, 4-way TP: one slot a shard
    "9-3-tp1": (9, 3, 1),         # smollm-135m
    "48-8-tp4": (48, 8, 4),
    "mha": (4, 4, 1),             # g = 1
    "9-3-tp2": (9, 3, 2),         # padded to 10: 5 q heads over 2 slots
}
HD, S_MAX, B = 16, 24, 3


def _inputs(lay, Sq, dtype, seed=0):
    """q, k_new, v_new, k_cache, v_cache, each with a leading shard axis."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    tp, ql, kl = lay.tp, lay.q_local, lay.kv_local
    shapes = [(tp, B, Sq, ql, HD)] + [(tp, B, Sq, kl, HD)] * 2 \
        + [(tp, B, S_MAX, kl, HD)] * 2
    return [jax.random.normal(k, s).astype(dtype) for k, s in zip(ks, shapes)]


def _run(lay, args, cache_len, grouped=None):
    """The op's kernel on every shard of ``lay`` (a vmap binds the
    'model' axis); ``grouped`` forces a path."""
    op = DecodeAttentionOp(lay)
    if grouped is not None:
        op = type("Forced", (DecodeAttentionOp,),
                  {"grouped": grouped})(lay)
    fn = jax.vmap(lambda *a: op.kernel({}, *a), axis_name="model",
                  in_axes=(0, 0, 0, 0, 0, None))
    return jax.jit(fn)(*args, cache_len)


def _reference(lay, args, cache_len):
    """Plain float64 attention of each true q head over its KV head."""
    q, k_new, v_new, kc, vc = (np.asarray(a, np.float64) for a in args)
    Sq, out = q.shape[2], np.zeros(q.shape)
    slot, valid = lay.q_slot_map(), lay.q_valid_map()
    for s in range(lay.tp):
        for b, t in enumerate(np.asarray(cache_len)):
            k, v = kc[s, b].copy(), vc[s, b].copy()
            k[t:t + Sq], v[t:t + Sq] = k_new[s, b], v_new[s, b]
            for j in range(Sq):
                for i in range(lay.q_local):
                    kk, vv = k[:t + j + 1, slot[s, i]], v[:t + j + 1, slot[s, i]]
                    w = np.exp(kk @ q[s, b, j, i] / np.sqrt(HD))
                    out[s, b, j, i] = valid[s, i] * (w / w.sum()) @ vv
    return out


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("Sq", [1, 4], ids=["decode", "chunk"])
@pytest.mark.parametrize("name", list(LAYOUTS))
def test_grouped_matches_expanded(name, Sq, dtype):
    """Ragged cache lengths, 0 among them; ``Sq > 1`` (chunked prefill,
    speculative verify) masks with per-position lengths ``(B, Sq)``."""
    lay = HeadLayout(*LAYOUTS[name], HD)
    cache_len = jnp.array([0, 7, S_MAX - Sq], jnp.int32)
    args = _inputs(lay, Sq, dtype)
    got = _run(lay, args, cache_len)
    want = _run(lay, args, cache_len, grouped=False)
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   atol=tol, rtol=tol)
    if dtype == jnp.float32:      # padding heads read zero
        np.testing.assert_allclose(got[0], _reference(lay, args, cache_len),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("arch,tp,grouped", [
    ("chatglm3-6b", 1, True), ("chatglm3-6b", 4, True),
    ("smollm-135m", 1, True), ("smollm-135m", 4, True),
    ("minitron-8b", 4, True),
    ("smollm-135m", 2, False),    # 10 padded q heads, 5 a shard, 2 slots
])
def test_path_selection(arch, tp, grouped):
    cfg = get_config(arch)
    lay = HeadLayout(cfg.n_heads, cfg.n_kv, tp, cfg.hd)
    assert DecodeAttentionOp(lay).grouped is grouped


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("grouped", [True, False])
def test_grouped_never_expands_the_cache(grouped):
    """No gather, and no value as large as K or V copied per q head;
    the expanded path is traced too, to show the check would see it."""
    lay = HeadLayout(8, 2, 1, HD)
    op = type("Forced", (DecodeAttentionOp,), {"grouped": grouped})(lay)
    args = [a[0] for a in _inputs(lay, 1, jnp.bfloat16)]
    jaxpr = jax.make_jaxpr(lambda *a: op.kernel({}, *a))(
        *args, jnp.zeros((B,), jnp.int32))
    expanded = B * S_MAX * lay.q_local * HD
    big = [e for e in _eqns(jaxpr.jaxpr)
           if e.primitive.name == "gather"
           or any(np.prod(v.aval.shape) >= expanded for v in e.outvars)]
    assert (not big) is grouped, big
