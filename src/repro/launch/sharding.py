"""Global input/param sharding construction for the production mesh.

Everything the model knows locally (per-shard shapes from MeshInfo) is
lifted to global ShapeDtypeStructs + PartitionSpecs here:

  * params: ``model.param_pspecs(segs)`` tuples -> PartitionSpec
  * batch inputs: batch dim sharded over ('pod','data'); sequence dim of
    SP-sharded inputs ('vis') over 'model'
  * decode caches: batch dim over data axes, head/channel dim over
    'model' per ``model.decode_cache_layout()``
  * when global_batch < dp_total the batch is replicated over the data
    axes (the long_500k single-request case) — each data row redundantly
    computes the same step.
"""
from __future__ import annotations


import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def _entry(e):
    if e is None or e == ():
        return None
    if isinstance(e, str):
        return e
    return e[0] if len(e) == 1 else tuple(e)


def spec_to_p(spec) -> P:
    if spec is None:
        return P()
    return P(*[_entry(e) for e in spec])


def param_pspec_tree(model, segs):
    """Tree of PartitionSpec matching the (stacked) param tree."""
    return jax.tree_util.tree_map(
        spec_to_p, model.param_pspecs(segs),
        is_leaf=lambda x: isinstance(x, tuple))


def global_param_specs(model, segs, mesh):
    """(ShapeDtypeStruct tree, NamedSharding tree) for the global params.
    ``Param.global_shape`` (declared at construction from the MeshInfo) is
    the global view; the pspec tree gives the matching PartitionSpecs."""
    shapes = model.param_shapes(segs, global_=True)
    pspecs = param_pspec_tree(model, segs)
    shds = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), pspecs,
        is_leaf=lambda x: isinstance(x, P))
    return shapes, shds


# special per-input extra sharding: name -> (dim, axis)
EXTRA_INPUT_SHARD = {"vis": (1, "model")}


def global_batch_specs(model, phase: str, seq_len: int, global_batch: int,
                       mesh, s_max: int = 0):
    """Global (sds, NamedSharding) dicts for the step's batch inputs
    (+ decode caches).  Returns (sds, shardings, B_loc, replicated)."""
    axis = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp_total = axis.get("data", 1) * axis.get("pod", 1)
    tp = axis.get("model", 1)
    dp_axes = tuple(a for a in ("pod", "data") if a in axis)
    replicated = global_batch < dp_total
    B_loc = max(1, global_batch // dp_total)

    # decode steps are single-token here (``seq_len`` is the cache depth
    # s_max, not the step width — chunked decode is a serve-engine path)
    step_len = 1 if phase == "decode" else seq_len
    binputs = model.batch_inputs(phase, B_loc, step_len, s_max=s_max)
    sdss, shds = {}, {}
    for name, (sds, bd) in binputs.items():
        gshape = list(sds.shape)
        dims = [None] * len(gshape)
        if bd is not None and not replicated:
            gshape[bd] *= dp_total
            dims[bd] = dp_axes if len(dp_axes) > 1 else dp_axes[0]
        if name in EXTRA_INPUT_SHARD:
            d, ax = EXTRA_INPUT_SHARD[name]
            gshape[d] *= axis.get(ax, 1)
            dims[d] = ax
        sdss[name] = jax.ShapeDtypeStruct(tuple(gshape), sds.dtype)
        shds[name] = NamedSharding(mesh, P(*dims))
    if phase == "decode":
        layout = model.decode_cache_layout()
        for name, sds in model.decode_cache_env(B_loc, s_max).items():
            bd, md = layout[name]
            gshape = list(sds.shape)
            dims = [None] * len(gshape)
            if not replicated:
                gshape[bd] *= dp_total
                dims[bd] = dp_axes if len(dp_axes) > 1 else dp_axes[0]
            gshape[md] = gshape[md] * tp
            dims[md] = "model"
            sdss[name] = jax.ShapeDtypeStruct(tuple(gshape), sds.dtype)
            shds[name] = NamedSharding(mesh, P(*dims))
    return sdss, shds, B_loc, replicated


def shard_specs_of(shardings):
    """NamedSharding tree -> PartitionSpec tree (for shard_map specs)."""
    return jax.tree_util.tree_map(
        lambda s: s.spec, shardings,
        is_leaf=lambda x: isinstance(x, NamedSharding))


def dense_tp_params(params, model):
    """Single-chip (tp=1) params of a dense LM, laid out as the global
    params of ``model``, the same architecture built for ``model.mesh.tp``.

    Two leaves change layout between the two: the fused QKV projection,
    whose columns regroup per shard (the shard's q heads, then the k and
    v heads it stores, copied onto several shards when tp > n_kv), and
    the fused SwiGLU input ``[w1 | w3]``, which interleaves per shard.
    Every other leaf is shared as is.  Works on numpy arrays, so the
    relayout can run on the host."""
    import numpy as np
    lay, cfg = model.layout, model.cfg
    if lay.q_pad != lay.n_q:
        raise ValueError(f"{cfg.name}: {lay.n_q} q heads do not split "
                         f"evenly over tp={lay.tp}")
    hd = lay.head_dim

    def heads(base, ids):
        return [base + h * hd + j for h in ids for j in range(hd)]

    qkv_cols, mlp_cols = [], []
    ff = cfg.d_ff // lay.tp
    for s, kv_ids in enumerate(lay.kv_store_map()):
        q_ids = range(s * lay.q_local, (s + 1) * lay.q_local)
        qkv_cols += heads(0, q_ids)
        qkv_cols += heads(lay.n_q * hd, kv_ids)
        qkv_cols += heads((lay.n_q + lay.n_kv) * hd, kv_ids)
        mlp_cols += list(range(s * ff, (s + 1) * ff))
        if cfg.act == "swiglu":                   # w3 follows w1
            mlp_cols += [cfg.d_ff + c for c in range(s * ff, (s + 1) * ff)]
    layers = dict(params["layers"])
    qkv = layers["qkv"]["proj"]["lin"]["w"]
    wi = layers["mlp"]["wi"]["lin"]["w"]
    layers["qkv"] = {"proj": {"lin": {"w": np.take(qkv, qkv_cols, -1)}}}
    layers["mlp"] = dict(layers["mlp"],
                         wi={"lin": {"w": np.take(wi, mlp_cols, -1)}})
    return dict(params, layers=layers)
