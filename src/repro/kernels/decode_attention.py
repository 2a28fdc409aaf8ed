"""Pallas TPU kernel: single-token decode attention (flash-decode).

Decode attention is memory-bound: the entire KV cache is streamed once per
step.  The kernel tiles the KV sequence into VMEM blocks and keeps the
online-softmax state in registers; invalid cache positions (>= cache_len)
are masked.  Grid: (B*H, Sk_blocks) with the KV-block axis innermost so
the running (acc, m, l) scratch carries across blocks.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, block_k, sm_scale):
    kb = pl.program_id(1)
    n_kb = pl.num_programs(1)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * sm_scale       # (1, hd)
    k = k_ref[0].astype(jnp.float32)                  # (block_k, hd)
    v = v_ref[0].astype(jnp.float32)
    valid_len = len_ref[0]

    s = (q @ k.T)[0]                                  # (block_k,)
    pos = kb * block_k + lax.iota(jnp.int32, block_k)
    s = jnp.where(pos < valid_len, s, NEG_INF)

    m_prev, l_prev = m_ref[0], l_ref[0]
    m_new = jnp.maximum(m_prev, jnp.max(s))
    p = jnp.exp(s - m_new)                            # (block_k,)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p)
    acc_ref[...] = acc_ref[...] * alpha + (p[None, :] @ v)
    m_ref[0], l_ref[0] = m_new, l_new

    @pl.when(kb == n_kb - 1)
    def _fin():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[0], 1e-20)
                    ).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *, block_k: int = 512,
                     sm_scale=None, interpret: bool = True):
    """q (B, 1, H, hd); k/v_cache (B, S, H, hd); cache_len () or (B,) int32.

    Attends to positions [0, cache_len[b]); returns (B, 1, H, hd)."""
    B, _, H, hd = q.shape
    S = k_cache.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    bk = min(block_k, S)
    while S % bk:
        bk //= 2
    bk = max(bk, 1)

    qt = q.transpose(0, 2, 1, 3).reshape(B * H, 1, hd)
    kt = k_cache.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vt = v_cache.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    clen = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (B,))

    from jax.experimental.pallas import tpu as pltpu
    kernel = functools.partial(_decode_kernel, block_k=bk, sm_scale=sm_scale)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, S // bk),
        in_specs=[
            pl.BlockSpec((1,), lambda b, i, H=H: (b // H,)),
            pl.BlockSpec((1, 1, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, hd), lambda b, i: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, hd), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
        ],
        interpret=interpret,
        name="decode_attention",
    )(clen, qt, kt, vt)
    return out.reshape(B, H, 1, hd).transpose(0, 2, 1, 3)
