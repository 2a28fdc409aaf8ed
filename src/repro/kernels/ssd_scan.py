"""Pallas TPU kernel: Mamba2 chunked SSD scan (state-space duality).

Layout: the wrapper flattens (batch, head) into the first grid axis; the
second grid axis walks chunks *sequentially* (TPU grid iterations run in
order on a core), carrying the running SSM state in a VMEM scratch buffer
— the inter-chunk recurrence needs no HBM round-trip.

Per program (one head, one chunk of Q timesteps):
  intra-chunk:  M[i,j] = (C_i · B_j) * exp(cum_i - cum_j) * dt_j   (j <= i)
                y_intra = M @ x
  inter-chunk:  y_inter = (C * exp(cum)) @ state
  state update: state' = state * exp(cum_Q) + B^T diag(w) x,
                w_j = exp(cum_Q - cum_j) * dt_j

VMEM per program (Q=256, N=128, P=64, f32): x 64 KiB, B/C 128 KiB each,
M 256 KiB, state 32 KiB — comfortably inside the ~128 MiB v5e VMEM budget
with double buffering.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


def _ssd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref, state_ref):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)       # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)     # (Q,)
    B = b_ref[0, 0].astype(jnp.float32)       # (Q, N)
    C = c_ref[0, 0].astype(jnp.float32)       # (Q, N)
    A = a_ref[0].astype(jnp.float32)          # ()
    D = d_ref[0].astype(jnp.float32)          # ()
    Q = x.shape[0]

    dA = dt * A
    cum = jnp.cumsum(dA)                      # (Q,) inclusive
    # intra-chunk
    CB = C @ B.T                              # (Q, Q)
    i = lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    j = lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    expo = jnp.where(j <= i, cum[:, None] - cum[None, :], -jnp.inf)
    M = CB * jnp.exp(expo) * dt[None, :]
    y = M @ x
    # inter-chunk
    state = state_ref[...].astype(jnp.float32)          # (N, P)
    y = y + (C * jnp.exp(cum)[:, None]) @ state
    # state update
    last = cum[Q - 1]
    w = jnp.exp(last - cum) * dt                        # (Q,)
    state_new = state * jnp.exp(last) + (B * w[:, None]).T @ x
    state_ref[...] = state_new
    y_ref[0, 0] = (y + D * x).astype(y_ref.dtype)


def ssd_scan(x, dt, A, B, C, D, *, chunk: int = 128, interpret: bool = True):
    """Chunked SSD.  x (b, L, H, P); dt (b, L, H); A/D (H,);
    B/C (b, L, G, N) with H % G == 0.  Returns y (b, L, H, P)."""
    b, L, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    Q = min(chunk, L)
    while L % Q:
        Q //= 2
    Q = max(Q, 1)
    nc = L // Q
    rep = H // G

    BH = b * H
    xt = x.transpose(0, 2, 1, 3).reshape(BH, nc, Q, P)
    dtt = dt.transpose(0, 2, 1).reshape(BH, nc, Q)
    Bt = jnp.repeat(B, rep, axis=2).transpose(0, 2, 1, 3).reshape(BH, nc, Q, N)
    Ct = jnp.repeat(C, rep, axis=2).transpose(0, 2, 1, 3).reshape(BH, nc, Q, N)
    At = jnp.tile(A.astype(jnp.float32), b)
    Dt = jnp.tile(D.astype(jnp.float32), b)

    y = pl.pallas_call(
        _ssd_kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda h, c: (h, c, 0, 0)),
            pl.BlockSpec((1, 1, Q), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda h, c: (h, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda h, c: (h, c, 0, 0)),
            pl.BlockSpec((1,), lambda h, c: (h,)),
            pl.BlockSpec((1,), lambda h, c: (h,)),
        ],
        out_specs=pl.BlockSpec((1, 1, Q, P), lambda h, c: (h, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, nc, Q, P), x.dtype),
        scratch_shapes=[_vmem_scratch((N, P))],
        interpret=interpret,
        name="ssd_scan",
    )(xt, dtt, Bt, Ct, At, Dt)
    return y.reshape(b, H, L, P).transpose(0, 2, 1, 3)


def _vmem_scratch(shape):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, jnp.float32)
