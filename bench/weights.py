"""Weights of a model, made on the device from the seed, and where the
program under test keeps each of them.

The benchmark makes the weights, not the program: the reference reads
the same arrays under its own names, and so takes nothing the program
made.  The family module (:func:`common.family`) gives each canonical
name's shape and init, the names stacked by layer, the axis each matrix
is split on over several chips, and the path of each in the program's
tree.

Matrices are drawn in float32 and stored in the served type, one layer at
a time inside one jitted call.  Over several chips they are drawn
straight into their shards: with the threefry generator partitionable
(JAX's default), each shard draws its own part of the same numbers, so
the weights are bitwise those of one chip and no chip holds more than its
share and one layer's temporaries.
"""
from __future__ import annotations

import functools
import zlib

from . import common


def shardings(m: dict, mesh) -> dict:
    """Canonical name -> ``NamedSharding`` over ``mesh``: each matrix in
    the family's ``SPLIT`` split on that axis over ``model``, the rest
    replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    fam = common.family(m)
    out = {}
    for name, (shape, _) in fam.shapes(m).items():
        dims = [None] * len(shape)
        if name in fam.SPLIT:
            dims[fam.SPLIT[name]] = "model"
        out[name] = NamedSharding(mesh, P(*dims))
    return out


def make(m: dict, key, dtype, mesh=None):
    """All weights from one key, in ``dtype``: on the default device, or
    split over ``mesh`` (:func:`shardings`)."""
    import jax
    kw = {} if mesh is None else {"out_shardings": shardings(m, mesh)}
    return jax.jit(functools.partial(_make, m, dtype=dtype), **kw)(key)


def _make(m, key, dtype):
    import jax
    import jax.numpy as jnp
    from jax import lax
    fam = common.family(m)
    out = {}
    for name, (shape, init) in fam.shapes(m).items():
        if init in ("ones", "zeros"):
            out[name] = (jnp.ones if init == "ones" else jnp.zeros)(
                shape, dtype)
            continue
        k = jax.random.fold_in(key, zlib.crc32(name.encode()))

        def one(k, shape=shape, init=init):
            return (jax.random.normal(k, shape, jnp.float32)
                    * init).astype(dtype)

        if name in fam.STACKED:
            ks = jax.vmap(lambda i, k=k: jax.random.fold_in(k, i))(
                jnp.arange(shape[0]))
            out[name] = lax.map(functools.partial(one, shape=shape[1:]), ks)
        else:
            out[name] = one(k)
    return out


def to_program(w: dict, m: dict, program=None) -> dict:
    """The program's nested tree over the same arrays.  On one chip no
    array is copied.  Where ``program`` runs over a mesh, the weights
    are laid out as its tensor-parallel tree (the family's
    ``program_columns``) and placed with the program's own shardings,
    one layer at a time."""
    fam = common.family(m)
    if program is not None and program.mesh is not None:
        w = _relayout(w, m, program)
    tree: dict = {}
    for name, arr in w.items():
        path = fam.PROGRAM_PATHS[name]
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = arr
    return tree


def program_shardings(program) -> dict:
    """The program's parameter shardings, as its prefill step takes
    them."""
    tp = dict(zip(program.mesh.axis_names,
                  program.mesh.devices.shape))["model"]
    return program.prefill(1, 8 * tp).in_shardings[0]


def _relayout(w: dict, m: dict, program) -> dict:
    import jax
    import jax.numpy as jnp
    from jax import lax
    fam = common.family(m)
    cols = fam.program_columns(program.model, m)
    shd = program_shardings(program)
    out_shd = {}
    for name in w:
        node = shd
        for p in fam.PROGRAM_PATHS[name]:
            node = node[p]
        out_shd[name] = node

    def move(w):
        out = dict(w)
        for name, idx in cols.items():       # layer weights, one at a time
            idx = jnp.asarray(idx)
            out[name] = lax.map(lambda x, idx=idx: jnp.take(x, idx, axis=-1),
                                w[name])
        return out

    return jax.jit(move, out_shardings=out_shd)(w)


def from_program(tree: dict, m: dict) -> dict:
    """Canonical names over a tree shaped like the program's (its
    parameters, or optimizer moments that mirror them)."""
    out = {}
    for name, path in common.family(m).PROGRAM_PATHS.items():
        node = tree
        for p in path:
            if not isinstance(node, dict) or p not in node:
                break
            node = node[p]
        else:
            out[name] = node
    return out
