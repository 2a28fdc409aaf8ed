"""Jit'd public wrappers for the Pallas kernels.

Whether a kernel is interpreted is decided when it is traced, from the
default backend (:func:`interpret_mode`): interpreted on CPU (kernel
bodies execute in Python for validation), compiled by Mosaic on TPU, and
refused anywhere else.  Model code calls these; strategy
``replace_func``s call the fused variants.

``traced`` counts, per kernel and interpret flag, the shapes the wrappers
were traced at, so a caller can show which kernels a jitted step holds.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp

from . import decode_attention as _dec
from . import flash_attention as _fa
from . import grouped_matmul as _gm
from . import rmsnorm as _rn
from . import ssd_scan as _ssd

traced: collections.Counter = collections.Counter()


def interpret_mode() -> bool:
    """True on CPU, False on TPU; any other backend is an error."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run compiled on TPU or interpreted on CPU; the "
        f"default backend is {backend!r}")


@functools.partial(jax.jit, static_argnames=("causal",))
def flash_attention(q, k, v, *, causal: bool = True):
    return _fa.flash_attention(q, k, v, causal=causal,
                               interpret=interpret_mode())


@jax.jit
def decode_attention(q, k_cache, v_cache, cache_len):
    return _dec.decode_attention(q, k_cache, v_cache, cache_len,
                                 interpret=interpret_mode())


@jax.jit
def rmsnorm(x, g):
    shape = x.shape
    out = _rn.rmsnorm(x.reshape(-1, shape[-1]), g,
                      interpret=interpret_mode())
    return out.reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_add_rmsnorm(x, y, g, block_rows):
    """Differentiable fused add+RMSNorm: Pallas forward, analytic VJP
    (the backward is memory-bound elementwise math XLA fuses well; a
    Pallas backward kernel is a further perf iteration)."""
    shape = x.shape
    interpret = interpret_mode()
    traced[("fused_add_rmsnorm", interpret, x.size // shape[-1], shape[-1],
            block_rows)] += 1
    s, h = _rn.fused_add_rmsnorm(x.reshape(-1, shape[-1]),
                                 y.reshape(-1, shape[-1]), g,
                                 block_rows=block_rows,
                                 interpret=interpret)
    return s.reshape(shape), h.reshape(shape)


def fused_add_rmsnorm(x, y, g, block_rows: int = 256):
    return _fused_add_rmsnorm(x, y, g, block_rows)


def _farn_fwd(x, y, g, block_rows):
    s, h = _fused_add_rmsnorm(x, y, g, block_rows)
    return (s, h), (s, g)


def _farn_bwd(block_rows, res, cts):
    s, g = res
    ds_out, dh = cts
    eps = 1e-5
    sf = s.astype(jnp.float32)
    dhf = dh.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    n = s.shape[-1]
    var = jnp.mean(sf * sf, axis=-1, keepdims=True)
    r = jax.lax.rsqrt(var + eps)
    dg = jnp.sum((dhf * sf * r).reshape(-1, n), axis=0).astype(g.dtype)
    dhg = dhf * gf
    ds_h = r * dhg - (r ** 3 / n) * sf * jnp.sum(dhg * sf, -1, keepdims=True)
    ds = (ds_out.astype(jnp.float32) + ds_h).astype(s.dtype)
    return ds, ds, dg


_fused_add_rmsnorm.defvjp(_farn_fwd, _farn_bwd)


@jax.jit
def grouped_ffn(x, w1, w3, w2):
    return _gm.grouped_ffn(x, w1, w3, w2,
                           interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, B, C, D, *, chunk: int = 128):
    return _ssd.ssd_scan(x, dt, A, B, C, D, chunk=chunk,
                         interpret=interpret_mode())


def fused_ar_add_rmsnorm(y_partial, x, g, *, axis="model", block_rows=256):
    """TokenWeave fused collective+norm — must run inside shard_map (or
    unsharded, where the collective halves degrade to identity)."""
    from . import tokenweave as _tw
    return _tw.fused_ar_add_rmsnorm(y_partial, x, g, axis=axis,
                                    block_rows=block_rows)
