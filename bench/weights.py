"""Weights of a dense decoder, made on the device from the seed, and
where the program under test keeps each of them.

The benchmark makes the weights, not the program: the reference reads
the same arrays under its own names, and so takes nothing the program
made.  Names (``m`` is :func:`common.model_dims`)::

    embed   (vocab, d)                 normal, std 0.02
    ln1     (L, d), ln2 (L, d)          ones
    wqkv    (L, d, (H + 2 K) hd)        q heads, then K keys, then K values
    wo      (L, H hd, d)
    w_in    (L, d, 2 F)                 gate, then up (SwiGLU)
    w_out   (L, F, d)
    ln_f    (d,)                        ones
    head    (d, vocab)                  absent where the embedding is tied

Matrices are normal with std ``1 / sqrt(fan_in)``, drawn in float32 and
stored in the served type, one layer at a time inside one jitted call.
"""
from __future__ import annotations

import functools
import zlib

# canonical name -> path in the program's parameter tree
PROGRAM_PATHS = {
    "embed": ("embed", "emb", "w"),
    "ln1": ("layers", "ln1", "g"),
    "wqkv": ("layers", "qkv", "proj", "lin", "w"),
    "wo": ("layers", "oproj", "proj", "lin", "w"),
    "ln2": ("layers", "ln2", "g"),
    "w_in": ("layers", "mlp", "wi", "lin", "w"),
    "w_out": ("layers", "mlp", "wo", "lin", "w"),
    "ln_f": ("head", "ln", "g"),
    "head": ("head", "out", "w"),
}
STACKED = ("ln1", "wqkv", "wo", "ln2", "w_in", "w_out")


def shapes(m: dict) -> dict:
    """Canonical name -> (shape, init) where init is ``"ones"`` or the
    normal's standard deviation."""
    d, hd, L = m["d_model"], m["head_dim"], m["n_layers"]
    H, K, F, V = m["n_heads"], m["n_kv"], m["d_ff"], m["vocab"]
    out = {
        "embed": ((V, d), 0.02),
        "ln1": ((L, d), "ones"),
        "wqkv": ((L, d, (H + 2 * K) * hd), d ** -0.5),
        "wo": ((L, H * hd, d), (H * hd) ** -0.5),
        "ln2": ((L, d), "ones"),
        "w_in": ((L, d, 2 * F), d ** -0.5),
        "w_out": ((L, F, d), F ** -0.5),
        "ln_f": ((d,), "ones"),
    }
    if not m["tie_embeddings"]:
        out["head"] = ((d, V), d ** -0.5)
    return out


def make(m: dict, key, dtype):
    """All weights, on the default device, from one key, in ``dtype``."""
    import jax
    return jax.jit(functools.partial(_make, m, dtype=dtype))(key)


def _make(m, key, dtype):
    import jax
    import jax.numpy as jnp
    from jax import lax
    out = {}
    for name, (shape, init) in shapes(m).items():
        if init == "ones":
            out[name] = jnp.ones(shape, dtype)
            continue
        k = jax.random.fold_in(key, zlib.crc32(name.encode()))

        def one(k, shape=shape, init=init):
            return (jax.random.normal(k, shape, jnp.float32)
                    * init).astype(dtype)

        if name in STACKED:
            ks = jax.vmap(lambda i, k=k: jax.random.fold_in(k, i))(
                jnp.arange(shape[0]))
            out[name] = lax.map(functools.partial(one, shape=shape[1:]), ks)
        else:
            out[name] = one(k)
    return out


def to_program(w: dict) -> dict:
    """The program's nested tree over the same arrays (no copy)."""
    tree: dict = {}
    for name, arr in w.items():
        node = tree
        path = PROGRAM_PATHS[name]
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = arr
    return tree


def from_program(tree: dict) -> dict:
    """Canonical names over a tree shaped like the program's (its
    parameters, or optimizer moments that mirror them)."""
    out = {}
    for name, path in PROGRAM_PATHS.items():
        node = tree
        for p in path:
            if not isinstance(node, dict) or p not in node:
                break
            node = node[p]
        else:
            out[name] = node
    return out
