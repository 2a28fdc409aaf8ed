"""Pallas TPU kernel: fused (residual-add +) RMSNorm.

The TokenWeave-style fusion target: after a reduce-scatter, each chip
holds a (tokens/tp, d) shard; the residual add + RMSNorm run on that shard
in one VMEM pass (one HBM read of x and y, one write of s and h) instead
of three separate memory-bound ops over the full token set.

Tiling: grid over row blocks; each program loads a (block_rows, d) tile of
x and y into VMEM, computes s = x + y, h = s * rsqrt(mean(s^2) + eps) * g,
and writes both.  The requested ``block_rows`` is capped so that the
double-buffered tiles plus the f32 temporaries fit the scoped VMEM limit
(``hw.VMEM_BYTES``, 16 MiB on v5e): at d=4096 in bf16 that is 128 rows.
Row blocks are multiples of 8 (the sublane tiling); a row count that no
such block divides is zero-padded to a whole number of blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import hw

_SUBLANE = 8
_F32_TEMPS = 3          # s, s*s and h live in f32 inside the kernel


def row_block(n: int, d: int, itemsize: int, n_tensors: int,
              block_rows: int) -> tuple[int, int]:
    """(rows per block, padded row count) for ``n`` rows of width ``d``.

    ``n_tensors`` row-tiled operands (inputs + outputs) are double
    buffered; the block is the largest power of two <= ``block_rows``
    that divides ``n`` and whose tiles and f32 temporaries fit
    ``hw.VMEM_BYTES``, but never below 8 rows: a row count no such block
    divides is padded.  A single block spanning all rows is always legal,
    so small ``n`` is not padded."""
    per_row = d * (2 * n_tensors * itemsize + _F32_TEMPS * 4)
    cap = max(_SUBLANE, hw.VMEM_BYTES // per_row)
    br = min(block_rows, cap)
    if n <= br:
        return n, n
    p = _SUBLANE
    while p * 2 <= br:
        p *= 2
    while p > _SUBLANE and n % p:
        p //= 2
    return p, -(-n // p) * p


def _pad_rows(a, n_pad):
    n = a.shape[0]
    return a if n == n_pad else jnp.pad(a, ((0, n_pad - n), (0, 0)))


def _fused_add_rmsnorm_kernel(x_ref, y_ref, g_ref, s_ref, h_ref, *, eps):
    x = x_ref[...]
    y = y_ref[...]
    s = (x.astype(jnp.float32) + y.astype(jnp.float32))
    var = jnp.mean(s * s, axis=-1, keepdims=True)
    h = s * jax.lax.rsqrt(var + eps)
    s_ref[...] = s.astype(s_ref.dtype)
    h_ref[...] = h.astype(h_ref.dtype) * g_ref[...].astype(h_ref.dtype)


def fused_add_rmsnorm(x, y, g, *, eps: float = 1e-5, block_rows: int = 256,
                      interpret: bool = True):
    """(x + y, rmsnorm(x + y) * g) over rows; x,y (n, d), g (d,).

    Returns (s, h).  ``interpret=True`` executes on CPU for validation;
    on TPU pass interpret=False.
    """
    n, d = x.shape
    br, n_pad = row_block(n, d, x.dtype.itemsize, 4, block_rows)
    kernel = functools.partial(_fused_add_rmsnorm_kernel, eps=eps)
    s, h = pl.pallas_call(
        kernel,
        grid=(n_pad // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, d), x.dtype),
            jax.ShapeDtypeStruct((n_pad, d), x.dtype),
        ],
        interpret=interpret,
        name="add_rmsnorm",
    )(_pad_rows(x, n_pad), _pad_rows(y, n_pad), g.reshape(1, d))
    return s[:n], h[:n]


def _rmsnorm_kernel(x_ref, g_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = ((x * jax.lax.rsqrt(var + eps)).astype(o_ref.dtype)
                  * g_ref[...].astype(o_ref.dtype))


def rmsnorm(x, g, *, eps: float = 1e-5, block_rows: int = 256,
            interpret: bool = True):
    """Plain RMSNorm over rows; x (n, d), g (d,)."""
    n, d = x.shape
    br, n_pad = row_block(n, d, x.dtype.itemsize, 2, block_rows)
    kernel = functools.partial(_rmsnorm_kernel, eps=eps)
    out = pl.pallas_call(
        kernel,
        grid=(n_pad // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, d), x.dtype),
        interpret=interpret,
        name="rmsnorm",
    )(_pad_rows(x, n_pad), g.reshape(1, d))
    return out[:n]
