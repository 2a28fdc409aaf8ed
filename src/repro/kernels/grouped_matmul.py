"""Pallas TPU kernel: grouped expert FFN (Comet's compute hot-spot).

Computes, per expert e:  y_e = (silu(x_e @ w1_e) * (x_e @ w3_e)) @ w2_e
with x (E, N, D), w1/w3 (E, D, F), w2 (E, F, D).

Grid: (E, N/block_n, F/block_f).  Each program computes a
(block_n, block_f) tile of the hidden activation for one expert, applies
the gate, and accumulates its contribution to the (block_n, D) output tile
— accumulation over the F grid axis happens in-place in the output block
(revisited across the innermost grid dim, the standard Pallas reduction
pattern).  Block shapes are MXU-aligned multiples of 128 where shapes
allow.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _grouped_ffn_kernel(x_ref, w1_ref, w3_ref, w2_ref, o_ref):
    # x (1, bn, D), w1/w3 (1, D, bf), w2 (1, bf, D), o (1, bn, D)
    fi = pl.program_id(2)
    x = x_ref[0].astype(jnp.float32)
    h1 = x @ w1_ref[0].astype(jnp.float32)
    h3 = x @ w3_ref[0].astype(jnp.float32)
    h = jax.nn.silu(h1) * h3
    part = h @ w2_ref[0].astype(jnp.float32)

    @pl.when(fi == 0)
    def _init():
        o_ref[0] = part.astype(o_ref.dtype)

    @pl.when(fi != 0)
    def _acc():
        o_ref[0] = (o_ref[0].astype(jnp.float32) + part).astype(o_ref.dtype)


def grouped_ffn(x, w1, w3, w2, *, block_n: int = 128, block_f: int = 512,
                interpret: bool = True):
    """x (E, N, D) -> (E, N, D); SwiGLU expert FFN, grouped over E."""
    E, N, D = x.shape
    F = w1.shape[-1]
    bn = min(block_n, N)
    while N % bn:
        bn //= 2
    bn = max(bn, 1)
    bf = min(block_f, F)
    while F % bf:
        bf //= 2
    bf = max(bf, 1)

    kernel = _grouped_ffn_kernel
    return pl.pallas_call(
        kernel,
        grid=(E, N // bn, F // bf),
        in_specs=[
            pl.BlockSpec((1, bn, D), lambda e, n, f: (e, n, 0)),
            pl.BlockSpec((1, D, bf), lambda e, n, f: (e, 0, f)),
            pl.BlockSpec((1, D, bf), lambda e, n, f: (e, 0, f)),
            pl.BlockSpec((1, bf, D), lambda e, n, f: (e, f, 0)),
        ],
        out_specs=pl.BlockSpec((1, bn, D), lambda e, n, f: (e, n, 0)),
        out_shape=jax.ShapeDtypeStruct((E, N, D), x.dtype),
        interpret=interpret,
        name="grouped_matmul",
    )(x, w1, w3, w2)
