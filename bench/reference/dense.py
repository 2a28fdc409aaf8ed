"""Plain float32 reference of the dense decoder the configurations state:
pre-norm RMSNorm layers, GQA attention with rotary embeddings on the
first ``rope_fraction`` of each head (the two halves of that part rotated
against each other), a SwiGLU MLP, and an output head that may be tied to
the embedding.  It follows ``bench/families/dense.py`` for names and
imports nothing of the program.  Weights split over several chips stay
split: each jitted step runs over their mesh.

Every matrix product runs at ``Precision.HIGHEST``.  ``mode="fp8"`` is
the control: the operands of every projection are rounded to
float8_e4m3fn (activations scaled per token, weights per output column)
before an exact product, as an fp8 path would compute them; in training
the gradients pass the rounding straight through.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
FP8_MAX = 448.0


def _fp8(x, axis):
    """x rounded to float8_e4m3fn, scaled by its absolute maximum along
    ``axis``; gradients pass straight through, as in fp8 training."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = lax.stop_gradient(jnp.where(s > 0, s, 1.0))
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + lax.stop_gradient(q - x)


def mm(x, w, mode: str):
    """x (..., k) @ w (k, n) in float32."""
    if mode == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.einsum("...k,kn->...n", x, w, precision=HIGHEST)


def rmsnorm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, positions, m):
    """x (B, S, heads, hd); rotates the first ``rope_fraction`` of hd."""
    hd = x.shape[-1]
    rot = int(hd * m["rope_fraction"])
    inv = 1.0 / (m["rope_theta"] ** (jnp.arange(0, rot, 2,
                                                 dtype=jnp.float32) / rot))
    ang = positions.astype(jnp.float32)[..., None] * inv     # (B, S, rot/2)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def layer(x, lw: dict, positions, m: dict, mode: str):
    """One decoder layer over x (B, S, d) in float32, causal."""
    B, S, _ = x.shape
    H, K, hd = m["n_heads"], m["n_kv"], m["head_dim"]
    eps = m["norm_eps"]
    h = rmsnorm(x, lw["ln1"], eps)
    qkv = mm(h, lw["wqkv"], mode)
    q = qkv[..., :H * hd].reshape(B, S, H, hd)
    k = qkv[..., H * hd:(H + K) * hd].reshape(B, S, K, hd)
    v = qkv[..., (H + K) * hd:].reshape(B, S, K, hd)
    q, k = rope(q, positions, m), rope(k, positions, m)
    k = jnp.repeat(k, H // K, axis=2)
    v = jnp.repeat(v, H // K, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / hd ** 0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                   precision=HIGHEST).reshape(B, S, H * hd)
    x = x + mm(a, lw["wo"], mode)
    h = rmsnorm(x, lw["ln2"], eps)
    gate, up = jnp.split(mm(h, lw["w_in"], mode), 2, axis=-1)
    return x + mm(jax.nn.silu(gate) * up, lw["w_out"], mode)


LAYER_KEYS = ("ln1", "wqkv", "wo", "ln2", "w_in", "w_out")


def head_matrix(w: dict):
    return w["head"] if "head" in w else w["embed"].T


# -- serving: logits of whole sequences, one layer at a time --------------


@functools.partial(jax.jit, static_argnames=("m", "mode"))
def _embed(embed, ids, m, mode):
    return jnp.take(embed, ids, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("m", "mode"))
def _layer_at(x, stacked, i, positions, m, mode):
    lw = {k: lax.dynamic_index_in_dim(stacked[k], i, keepdims=False)
          .astype(jnp.float32) for k in LAYER_KEYS}
    return layer(x, lw, positions, m, mode)


@functools.partial(jax.jit, static_argnames=("m", "mode"))
def _head(x, ln_f, head, m, mode):
    h = rmsnorm(x, ln_f.astype(jnp.float32), m["norm_eps"])
    return mm(h, head.astype(jnp.float32), mode)


def logits(w: dict, ids, m: dict, mode: str = "f32"):
    """Logits (B, S, vocab) of token ids (B, S) at positions 0..S-1.
    The weights stay in their stored type; each layer is upcast when it
    runs, so the reference holds one float32 layer at a time."""
    mh = _hashable(m)
    positions = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
    x = _embed(w["embed"], ids, mh, mode)
    stacked = {k: w[k] for k in LAYER_KEYS}
    for i in range(m["n_layers"]):
        x = _layer_at(x, stacked, jnp.int32(i), positions, mh, mode)
    return _head(x, w["ln_f"], head_matrix(w), mh, mode)


class _hashable(dict):
    """A model dict usable as a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def hashable(m: dict) -> dict:
    return _hashable(m)


# -- training: loss, gradients and AdamW ----------------------------------


def loss_sum(w: dict, ids, labels, m: dict, mode: str):
    """Summed cross-entropy of one block of rows (B, S), float32
    weights, layers rematerialised so that the block's activations fit."""
    positions = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
    x = jnp.take(w["embed"], ids, axis=0)

    @jax.checkpoint
    def body(x, lw):
        return layer(x, lw, positions, m, mode), None

    x, _ = lax.scan(body, x, {k: w[k] for k in LAYER_KEYS})
    h = rmsnorm(x, w["ln_f"], m["norm_eps"])
    z = mm(h, head_matrix(w), mode)
    lse = jax.nn.logsumexp(z, -1)
    tgt = jnp.take_along_axis(z, labels[..., None], -1)[..., 0]
    return jnp.sum(lse - tgt)


@functools.partial(jax.jit, static_argnames=("m", "mode"))
def loss_and_grads(w: dict, ids, labels, m, mode: str = "f32"):
    """Mean loss over all tokens of (B, S) and its gradients, summed over
    the rows one at a time, so that one row's activations fit."""
    B, S = ids.shape
    blocks = (ids.reshape(B, 1, S), labels.reshape(B, 1, S))
    grad_fn = jax.value_and_grad(loss_sum)

    def body(acc, blk):
        l, g = grad_fn(w, blk[0], blk[1], m, mode)
        return (acc[0] + l, jax.tree_util.tree_map(jnp.add, acc[1], g)), None

    zero = (jnp.zeros((), jnp.float32),
            jax.tree_util.tree_map(jnp.zeros_like, w))
    (total, grads), _ = lax.scan(body, zero, blocks)
    n = B * S
    return total / n, jax.tree_util.tree_map(lambda g: g / n, grads)


def lr_at(step: int, opt: dict) -> float:
    """Linear warm-up to ``lr`` over ``warmup`` steps, then a cosine to a
    tenth of it at ``total_steps`` (step counts from 0)."""
    import math
    peak, warm, total = opt["lr"], opt["warmup"], opt["total_steps"]
    if step < warm:
        return peak * min(1.0, (step + 1.0) / max(warm, 1))
    frac = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return peak * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * frac)))


@jax.jit
def _adamw(w, grads, state, lr, count, b1, b2, eps, wd, clip):
    gnorm = jnp.sqrt(sum(jnp.sum(g * g)
                         for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-12))
    grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                               state["m"], grads)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                               state["v"], grads)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count
    new = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                  + wd * p), w, m, v)
    return new, {"m": m, "v": v}, grads


def adamw_step(w, grads, state, step: int, opt: dict):
    """One AdamW step with global-norm clipping.  Returns the new
    weights, the new state and the clipped gradients."""
    if state is None:
        state = {"m": jax.tree_util.tree_map(jnp.zeros_like, w),
                 "v": jax.tree_util.tree_map(jnp.zeros_like, w)}
    return _adamw(w, grads, state, jnp.float32(lr_at(step, opt)),
                  jnp.float32(step + 1), opt["b1"], opt["b2"], opt["eps"],
                  opt["weight_decay"], opt["grad_clip"])
