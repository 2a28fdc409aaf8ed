"""Pallas TPU kernel: blockwise flash attention (online softmax).

Grid: (batch*heads, q_blocks); the kernel body loops over K/V blocks with
``lax.fori_loop``, keeping the running max / sum / accumulator in VMEM
scratch.  Block shapes are MXU-aligned (q/k blocks multiples of 128 when
the sequence allows; head_dim padded to 128 by the wrapper in ops.py when
needed).  Causal masking skips fully-masked K blocks by bounding the loop
trip count per q block — the standard TPU flash schedule.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, causal, sm_scale,
                  block_k, seq_k):
    # q_ref: (1, block_q, hd); k_ref/v_ref: (1, seq_k, hd)
    _, block_q, hd = q_ref.shape
    qi = pl.program_id(1)
    # full-block loads + array indexing (older pallas interpret mode does
    # not discharge raw-int ref indices)
    q = q_ref[...][0].astype(jnp.float32) * sm_scale

    n_kb = seq_k // block_k
    if causal:
        # last K block that intersects [0, (qi+1)*block_q)
        hi = lax.min(((qi + 1) * block_q + block_k - 1) // block_k, n_kb)
    else:
        hi = n_kb

    def body(kb, carry):
        acc, m, lsum = carry
        k = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = q @ k.astype(jnp.float32).T                     # (bq, bk)
        if causal:
            qpos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = kb * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        lsum_new = lsum * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + p @ v.astype(jnp.float32)
        return acc, m_new, lsum_new

    acc0 = jnp.zeros((block_q, hd), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    lsum0 = jnp.zeros((block_q,), jnp.float32)
    acc, m, lsum = lax.fori_loop(0, hi, body, (acc0, m0, lsum0))
    o_ref[...] = (acc / jnp.maximum(lsum, 1e-20)[:, None]).astype(
        o_ref.dtype)[None]


def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: int = 128, block_k: int = 128,
                    sm_scale: float | None = None, interpret: bool = True):
    """q (B, Sq, H, hd), k/v (B, Sk, H, hd) -> (B, Sq, H, hd).

    H is the per-q-head layout (GQA already expanded by the caller).
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    bq = min(block_q, Sq)
    while Sq % bq:
        bq //= 2
    bq = max(bq, 1)
    bk = min(block_k, Sk)
    while Sk % bk:
        bk //= 2
    bk = max(bk, 1)

    # (B, S, H, hd) -> (B*H, S, hd)
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kt = k.transpose(0, 2, 1, 3).reshape(B * H, Sk, hd)
    vt = v.transpose(0, 2, 1, 3).reshape(B * H, Sk, hd)

    kernel = functools.partial(_flash_kernel, causal=causal,
                               sm_scale=sm_scale, block_k=bk, seq_k=Sk)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, Sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Sk, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Sk, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(qt, kt, vt)
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
