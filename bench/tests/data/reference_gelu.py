"""Plain float32 reference of the test family ``gelu``: the dense
reference's attention and norms, with an ungated tanh-GELU MLP."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bench.reference.dense import (HIGHEST, adamw_step, hashable,  # noqa: F401
                                   head_matrix, mm, rmsnorm, rope)

LAYER_KEYS = ("ln1", "wqkv", "wo", "ln2", "w_up", "w_down")


def layer(x, lw, positions, m, mode):
    B, S, _ = x.shape
    H, K, hd = m["n_heads"], m["n_kv"], m["head_dim"]
    h = rmsnorm(x, lw["ln1"], m["norm_eps"])
    qkv = mm(h, lw["wqkv"], mode)
    q = rope(qkv[..., :H * hd].reshape(B, S, H, hd), positions, m)
    k = rope(qkv[..., H * hd:(H + K) * hd].reshape(B, S, K, hd),
             positions, m)
    v = qkv[..., (H + K) * hd:].reshape(B, S, K, hd)
    k, v = jnp.repeat(k, H // K, axis=2), jnp.repeat(v, H // K, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / hd ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                   precision=HIGHEST).reshape(B, S, H * hd)
    x = x + mm(a, lw["wo"], mode)
    h = rmsnorm(x, lw["ln2"], m["norm_eps"])
    return x + mm(jax.nn.gelu(mm(h, lw["w_up"], mode), approximate=True),
                  lw["w_down"], mode)


def forward(w, ids, m, mode):
    positions = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
    x = jnp.take(w["embed"], ids, axis=0).astype(jnp.float32)

    def body(x, lw):
        lw = {k: v.astype(jnp.float32) for k, v in lw.items()}
        return layer(x, lw, positions, m, mode), None

    x, _ = lax.scan(body, x, {k: w[k] for k in LAYER_KEYS})
    h = rmsnorm(x, w["ln_f"].astype(jnp.float32), m["norm_eps"])
    return mm(h, head_matrix(w).astype(jnp.float32), mode)


_forward = jax.jit(forward, static_argnames=("m", "mode"))


def logits(w, ids, m, mode="f32"):
    return _forward(w, ids, hashable(m), mode)


def loss_sum(w, ids, labels, m, mode):
    z = forward(w, ids, m, mode)
    lse = jax.nn.logsumexp(z, -1)
    return jnp.sum(lse - jnp.take_along_axis(z, labels[..., None], -1)[..., 0])


@functools.partial(jax.jit, static_argnames=("m", "mode"))
def loss_and_grads(w, ids, labels, m, mode="f32"):
    n = ids.size
    loss, g = jax.value_and_grad(loss_sum)(w, ids, labels, m, mode)
    return loss / n, jax.tree_util.tree_map(lambda x: x / n, g)
