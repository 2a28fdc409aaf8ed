"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes and dtypes, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

DTYPES = [jnp.float32, jnp.bfloat16]


def tol(dtype):
    return dict(atol=3e-2, rtol=3e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,d", [(4, 32), (64, 96), (128, 256), (7, 40)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_rmsnorm_sweep(n, d, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d)).astype(dtype)
    g = jax.random.normal(jax.random.PRNGKey(1), (d,)).astype(dtype)
    np.testing.assert_allclose(
        np.asarray(ops.rmsnorm(x, g), np.float32),
        np.asarray(ref.rmsnorm(x, g), np.float32), **tol(dtype))


@pytest.mark.parametrize("n,d", [(8, 16), (33, 64), (256, 128)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_fused_add_rmsnorm_sweep(n, d, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d)).astype(dtype)
    y = jax.random.normal(jax.random.PRNGKey(1), (n, d)).astype(dtype)
    g = jax.random.normal(jax.random.PRNGKey(2), (d,)).astype(dtype)
    s1, h1 = ops.fused_add_rmsnorm(x, y, g)
    s2, h2 = ref.fused_add_rmsnorm(x, y, g)
    np.testing.assert_allclose(np.asarray(s1, np.float32),
                               np.asarray(s2, np.float32), **tol(dtype))
    np.testing.assert_allclose(np.asarray(h1, np.float32),
                               np.asarray(h2, np.float32), **tol(dtype))


@pytest.mark.parametrize("n,d,block_rows", [(300, 64, 128), (4095, 576, 256),
                                            (41, 32, 16), (264, 32, 256)])
def test_fused_add_rmsnorm_padded_rows(n, d, block_rows):
    """Row counts no aligned block divides are padded, not split into
    blocks that break the (8, 128) tiling."""
    from repro.kernels import rmsnorm as rn
    br, n_pad = rn.row_block(n, d, 4, 4, block_rows)
    assert br % 8 == 0 and n_pad % br == 0 and n <= n_pad < n + 8
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    y = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    g = jax.random.normal(jax.random.PRNGKey(2), (d,))
    s1, h1 = rn.fused_add_rmsnorm(x, y, g, block_rows=block_rows)
    s2, h2 = ref.fused_add_rmsnorm(x, y, g)
    np.testing.assert_allclose(s1, s2, **tol(jnp.float32))
    np.testing.assert_allclose(h1, h2, **tol(jnp.float32))


def test_row_block_fits_scoped_vmem():
    from repro import hw
    from repro.kernels.rmsnorm import row_block
    # chatglm3-6b width: 256 requested rows would overflow 16 MiB
    assert row_block(4096, 4096, 2, 4, 256) == (128, 4096)
    assert row_block(4096, 576, 2, 4, 256) == (256, 4096)
    assert row_block(264, 576, 2, 4, 256) == (8, 264)   # divides: no pad
    br, _ = row_block(4096, 8192, 2, 4, 256)
    assert br * 8192 * (2 * 4 * 2 + 12) <= hw.VMEM_BYTES


def test_interpret_mode_follows_backend():
    assert jax.default_backend() == "cpu" and ops.interpret_mode()
    before = sum(n for k, n in ops.traced.items() if k[2] == 24)
    ops.fused_add_rmsnorm(jnp.ones((2, 12, 32)), jnp.ones((2, 12, 32)),
                          jnp.ones((32,)), 128)
    assert ops.traced[("fused_add_rmsnorm", True, 24, 32, 128)] >= 1
    assert sum(n for k, n in ops.traced.items() if k[2] == 24) > before


def test_fused_add_rmsnorm_grad_matches_autodiff():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 32))
    y = jax.random.normal(jax.random.PRNGKey(1), (8, 32))
    g = jax.random.normal(jax.random.PRNGKey(2), (32,))

    def lk(x, y, g):
        s, h = ops.fused_add_rmsnorm(x, y, g)
        return jnp.sum(jnp.sin(s) + h * h)

    def lr(x, y, g):
        s, h = ref.fused_add_rmsnorm(x, y, g)
        return jnp.sum(jnp.sin(s) + h * h)

    gk = jax.grad(lk, argnums=(0, 1, 2))(x, y, g)
    gr = jax.grad(lr, argnums=(0, 1, 2))(x, y, g)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(a, b, atol=1e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,H,hd", [(1, 32, 2, 16), (2, 64, 4, 32),
                                      (2, 128, 1, 64), (1, 96, 3, 32)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, S, H, hd, causal):
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, hd))
    np.testing.assert_allclose(
        ops.flash_attention(q, k, v, causal=causal),
        ref.flash_attention(q, k, v, causal=causal), atol=2e-5, rtol=1e-4)


def test_flash_attention_bf16():
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 2, 32)).astype(jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 2, 32)).astype(jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 2, 32)).astype(jnp.bfloat16)
    np.testing.assert_allclose(
        np.asarray(ops.flash_attention(q, k, v), np.float32),
        np.asarray(ref.flash_attention(q, k, v), np.float32),
        atol=3e-2, rtol=3e-2)


@settings(max_examples=10, deadline=None)
@given(sq=st.sampled_from([16, 48, 64]), sk=st.sampled_from([16, 64, 96]))
def test_flash_cross_attention_rectangular(sq, sk):
    """Non-square q/k lengths (cross-attention shapes)."""
    q = jax.random.normal(jax.random.PRNGKey(0), (1, sq, 2, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, sk, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, sk, 2, 16))
    np.testing.assert_allclose(
        ops.flash_attention(q, k, v, causal=False),
        ref.flash_attention(q, k, v, causal=False), atol=2e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,H,hd", [(2, 128, 4, 32), (4, 64, 2, 16)])
def test_decode_attention_sweep(B, S, H, hd):
    kc = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, hd))
    vc = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, hd))
    q = jax.random.normal(jax.random.PRNGKey(2), (B, 1, H, hd))
    for clen in (jnp.int32(1), jnp.int32(S // 2), jnp.int32(S)):
        np.testing.assert_allclose(
            ops.decode_attention(q, kc, vc, clen),
            ref.decode_attention(q, kc, vc, clen), atol=2e-5, rtol=1e-4)


def test_decode_attention_ragged_lengths():
    """Per-request cache lengths (continuous batching)."""
    B, S, H, hd = 4, 64, 2, 16
    kc = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, hd))
    vc = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, hd))
    q = jax.random.normal(jax.random.PRNGKey(2), (B, 1, H, hd))
    clen = jnp.asarray([3, 17, 64, 1], jnp.int32)
    np.testing.assert_allclose(
        ops.decode_attention(q, kc, vc, clen),
        ref.decode_attention(q, kc, vc, clen), atol=2e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# grouped expert FFN
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("E,N,D,F", [(2, 16, 24, 32), (4, 64, 48, 96),
                                     (1, 128, 64, 256)])
def test_grouped_ffn_sweep(E, N, D, F):
    k = jax.random.PRNGKey
    x = jax.random.normal(k(0), (E, N, D)) * 0.5
    w1 = jax.random.normal(k(1), (E, D, F)) * 0.1
    w3 = jax.random.normal(k(2), (E, D, F)) * 0.1
    w2 = jax.random.normal(k(3), (E, F, D)) * 0.1
    np.testing.assert_allclose(ops.grouped_ffn(x, w1, w3, w2),
                               ref.grouped_ffn(x, w1, w3, w2),
                               atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# Mamba2 SSD
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("L,chunk", [(32, 8), (64, 16), (64, 64), (48, 16)])
def test_ssd_scan_sweep(L, chunk):
    b, H, P, G, N = 2, 4, 8, 1, 16
    k = jax.random.PRNGKey
    x = jax.random.normal(k(0), (b, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(k(1), (b, L, H)))
    A = -jnp.exp(jax.random.normal(k(2), (H,)))
    B = jax.random.normal(k(3), (b, L, G, N)) * 0.5
    C = jax.random.normal(k(4), (b, L, G, N)) * 0.5
    D = jnp.ones((H,))
    np.testing.assert_allclose(
        ops.ssd_scan(x, dt, A, B, C, D, chunk=chunk),
        ref.ssd_scan(x, dt, A, B, C, D), atol=2e-3, rtol=1e-2)


def test_ssd_scan_multi_group():
    b, L, H, P, G, N = 1, 32, 4, 8, 2, 8
    k = jax.random.PRNGKey
    x = jax.random.normal(k(0), (b, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(k(1), (b, L, H)))
    A = -jnp.exp(jax.random.normal(k(2), (H,)))
    B = jax.random.normal(k(3), (b, L, G, N)) * 0.5
    C = jax.random.normal(k(4), (b, L, G, N)) * 0.5
    D = jnp.zeros((H,))
    np.testing.assert_allclose(
        ops.ssd_scan(x, dt, A, B, C, D, chunk=8),
        ref.ssd_scan(x, dt, A, B, C, D), atol=2e-3, rtol=1e-2)


def test_ssd_matches_model_reference():
    """The Pallas SSD must agree with SSDScanOp's chunked jnp ref."""
    from repro.configs import get_smoke_config
    from repro.models.mamba2 import SSDScanOp, ssm_dims
    from repro.models.layers import MeshInfo
    cfg = get_smoke_config("mamba2-2.7b")
    mesh = MeshInfo(tp=1)
    op_x = SSDScanOp(cfg, mesh, impl="xla")
    op_p = SSDScanOp(cfg, mesh, impl="pallas")
    _, d_in_loc, _, H_loc, ch_loc = ssm_dims(cfg, 1)
    p = {n: pp.initializer()(jax.random.PRNGKey(i), pp.shape, pp.dtype)
         for i, (n, pp) in enumerate(op_x._params.items())}
    B, L = 2, 16
    xbc = jax.random.normal(jax.random.PRNGKey(9), (B, L, ch_loc))
    dt = jax.random.normal(jax.random.PRNGKey(10), (B, L, H_loc))
    np.testing.assert_allclose(
        np.asarray(op_p.kernel(p, xbc, dt), np.float32),
        np.asarray(op_x.kernel(p, xbc, dt), np.float32),
        atol=2e-2, rtol=2e-2)


# ---------------------------------------------------------------------------
# tokenweave fused collective (single shard: collectives = identity)
# ---------------------------------------------------------------------------


def test_tokenweave_fused_unsharded():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32))
    y = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    g = jax.random.normal(jax.random.PRNGKey(2), (32,))
    s, h = ops.fused_ar_add_rmsnorm(y, x, g)
    s2, h2 = ref.fused_add_rmsnorm(x, y, g)
    np.testing.assert_allclose(s, s2, atol=1e-5)
    np.testing.assert_allclose(h, h2, atol=1e-5)


# ---------------------------------------------------------------------------
# kernel names: a profile finds each kernel by the name its call carries
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,fn,shapes", [
    ("add_rmsnorm", lambda x, y, g: ops.fused_add_rmsnorm(x, y, g)[0],
     [(8, 16), (8, 16), (16,)]),
    ("rmsnorm", ops.rmsnorm, [(8, 16), (16,)]),
    ("flash_attention", ops.flash_attention, [(1, 32, 2, 16)] * 3),
    ("decode_attention", lambda q, k, v: ops.decode_attention(q, k, v, 4),
     [(1, 1, 2, 16), (1, 32, 2, 16), (1, 32, 2, 16)]),
    ("grouped_matmul", ops.grouped_ffn,
     [(2, 16, 24), (2, 24, 32), (2, 24, 32), (2, 32, 24)]),
    ("ssd_scan", lambda x, dt, b, c: ops.ssd_scan(
        x, dt, jnp.ones((2,)), b, c, jnp.ones((2,)), chunk=8),
     [(1, 16, 2, 8), (1, 16, 2), (1, 16, 1, 4), (1, 16, 1, 4)]),
])
def test_every_pallas_call_is_named(name, fn, shapes):
    args = [jnp.ones(s, jnp.float32) for s in shapes]
    eqns = jax.make_jaxpr(fn)(*args).jaxpr.eqns
    found = []
    while eqns:                       # the call may sit inside a custom_vjp
        e = eqns.pop()
        if e.primitive.name == "pallas_call":
            found.append(e.params["name"])
        for sub in jax.core.jaxprs_in_params(e.params):
            eqns.extend(sub.eqns)
    assert found == [name]
