"""TokenWeave-style fused AllReduce + residual-add + RMSNorm for TPU.

GPU TokenWeave fuses a multimem AllReduce with RMSNorm inside one kernel,
reserving a few CTAs for communication.  The TPU-native adaptation splits
the AllReduce into its ring halves and fuses the *memory-bound* middle:

    all_reduce(y); s = x + y; h = rmsnorm(s)          (sequential: 3 full
                                                       HBM passes over B·S·d)
    ==>
    y_s = reduce_scatter(y)         # network, 1/tp payload per hop
    s_s, h_s = pallas fused add+norm on the (B·S/tp, d) shard   # 1 pass,
                                                                # 1/tp tokens
    s, h = all_gather([s_s, h_s])   # network

The elementwise work drops by tp× and fuses into one VMEM pass (the Pallas
kernel in rmsnorm.py); RS+AG moves the same bytes as the AllReduce it
replaces.  The residual stream ``s`` and the normed ``h`` are both
returned because both are consumed downstream (s by the next residual
add, h by the next projection).

The CTA-count runtime knob from the paper maps to ``block_rows`` of the
Pallas kernel — selected per batch bucket by the TokenWeave strategy
(§5.3.4's 12% adaptive win).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..dist import collectives as col


def fused_ar_add_rmsnorm(y_partial, x, g, *, axis: str = "model",
                         eps: float = 1e-5, block_rows: int = 256):
    """Fused psum(y) + (x + .) + rmsnorm over mesh axis ``axis``.

    y_partial, x: (B, S, d) with S divisible by the axis size.
    Returns (s, h) both (B, S, d), s = x + psum(y), h = rmsnorm(s) * g.
    Outside shard_map (tests, tp=1) the collective halves are identity.
    """
    from . import ops as kops
    B, S, d = x.shape
    tp = col.axis_size(axis)
    y_s = col.reduce_scatter(y_partial, axis, dim=1)      # (B, S/tp, d)
    idx = col.axis_index(axis)
    x_s = jax.lax.dynamic_slice_in_dim(x, idx * (S // tp), S // tp, axis=1)
    # differentiable Pallas core (ops.py carries the custom VJP)
    s_s, h_s = kops.fused_add_rmsnorm(x_s, y_s, g, block_rows=block_rows)
    sh = jnp.stack([s_s, h_s])                            # (2, B, S/tp, d)
    sh = col.all_gather(sh, axis, dim=2)                  # (2, B, S, d)
    return sh[0], sh[1]
