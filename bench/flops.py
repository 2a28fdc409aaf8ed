"""Operations and bytes of a decoder, computed from its shapes, for the
whole model (over all the chips that hold it).

``m`` is the model dict :func:`common.model_dims` gives: ``n_layers``,
``d_model``, ``n_heads``, ``n_kv``, ``head_dim``, ``d_ff``, ``vocab``.
The weights a token multiplies are its family's (``matmul_params`` of
``bench/families/<family>.py``); attention is GQA's.  A multiply-add
counts two operations.  Padding to shape buckets never counts: every function takes
the true lengths.
"""
from __future__ import annotations

from . import common


def matmul_params(m: dict) -> int:
    """Weights every token multiplies once per forward pass."""
    return common.family(m).matmul_params(m)


def attn_flops(m: dict, context: float) -> float:
    """Forward attention operations of one query token that attends to
    ``context`` keys (itself included): scores and weighted sum."""
    return 4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * context


def prefill_flops(m: dict, n: int, start: int = 0) -> float:
    """Forward operations of prompt positions ``start .. n-1`` (a chunk
    that follows ``start`` cached tokens), causal attention included."""
    tokens = n - start
    ctx = (start + 1 + n) * tokens / 2.0       # sum of (i + 1), i in range
    return 2.0 * matmul_params(m) * tokens + attn_flops(m, ctx)


def decode_flops(m: dict, context: int) -> float:
    """Forward operations of one decoded token that attends to
    ``context`` keys (its own included)."""
    return 2.0 * matmul_params(m) + attn_flops(m, context)


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward and backward operations per trained token at sequence
    length ``seq`` (causal): three times the forward.  Recomputation
    does not count."""
    return 3.0 * (2.0 * matmul_params(m) + attn_flops(m, (seq + 1) / 2.0))


def weight_bytes(m: dict, itemsize: int = 2) -> int:
    """Bytes of the weights one decode step reads: every matmul weight
    once (the embedding rows it gathers are negligible)."""
    return matmul_params(m) * itemsize


def kv_bytes(m: dict, context: int, itemsize: int = 2) -> int:
    """Bytes of K and V that one row's attention must read at
    ``context`` cached positions."""
    return 2 * m["n_layers"] * m["n_kv"] * m["head_dim"] * context * itemsize
