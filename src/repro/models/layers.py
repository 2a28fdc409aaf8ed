"""Schedulable model layers (manual SPMD) shared by all architectures.

Every layer is a DynaFlow ``Op``/``Module``: the traced graph exposes
logical operators (norm / projections / attention / collectives / MoE
stages) so the programmable scheduler can split, reorder, overlap and fuse
them.  Kernels are written against the *local shard*; mesh axis names
('data', 'model', optionally 'pod') are bound by the launch layer's
``shard_map``.

Sharding scheme
  * activations: batch over ('pod','data'); sequence over 'model' when
    sequence-parallel (SP) sections are active
  * attention: Q heads over 'model' (padded to a multiple of TP when
    needed), KV heads via a static per-shard slot map (GQA kv < TP is
    stored replicated per group — standard practice)
  * MLP: column-parallel in / row-parallel out + reduce-scatter (SP) or
    all-reduce
  * vocab: embedding + LM head sharded over 'model'
  * MoE: experts over 'model' (virtual-expert construction shards a single
    expert across multiple chips when n_experts < TP)
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.module import Module, Op, Param
from ..dist import collectives as col

# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class MeshInfo:
    """Static mesh-shape info modules need at construction time."""

    tp: int = 1        # 'model' axis size
    dp: int = 1        # 'data' axis size
    pods: int = 1      # 'pod' axis size (1 = single pod)
    fsdp: bool = False  # ZeRO-3: shard params over 'data' too
    fsdp_resident: bool = False  # decode: keep data-sharded weights
                                 # resident (partial matmul + tiny psum)
                                 # instead of per-step all-gathers
    attn_impl: str = "xla"   # xla | chunked | pallas (execution hint)

    @property
    def dp_axes(self):
        return ("pod", "data") if self.pods > 1 else ("data",)


def P(*names):
    return tuple(names)


def make_param(local_shape, dtype, pspec, mesh: MeshInfo, init=None,
               axis_sizes: Optional[dict] = None) -> Param:
    """Declare a param by LOCAL shape + partition spec; derive global.

    Axes of size 1 are dropped from the stored pspec: a module built with
    tp=1 (e.g. a replicated shared expert inside a TP mesh) must not claim
    'model' sharding the launch layer would then wrongly apply."""
    sizes = {"model": mesh.tp, "data": mesh.dp, "pod": mesh.pods}
    if axis_sizes:
        sizes.update(axis_sizes)
    gshape, eff_spec = [], []
    for d, names in zip(local_shape, tuple(pspec) + ((),) * (len(local_shape) - len(pspec))):
        if names is None or names == ():
            gshape.append(d)
            eff_spec.append(())
            continue
        if isinstance(names, str):
            names = (names,)
        names = tuple(n for n in names if sizes.get(n, 1) > 1)
        mult = 1
        for n in names:
            mult *= sizes[n]
        gshape.append(d * mult)
        eff_spec.append(names)
    return Param(tuple(local_shape), dtype, init=init, pspec=tuple(eff_spec),
                 global_shape=tuple(gshape))


# ---------------------------------------------------------------------------
# elementary ops
# ---------------------------------------------------------------------------


class LinearOp(Op):
    """Local matmul over the last dim.  Sharding is encoded in shapes.

    With ``owns_weight=False`` the weight arrives as a second *input*
    tensor (produced by a ``WeightGatherOp`` under FSDP) instead of a
    parameter — which is exactly what makes the weight gather schedulable.
    """

    resource = "compute"

    def __init__(self, d_in, d_out, name, mesh: MeshInfo,
                 pspec=((), ("model",)), dtype=jnp.bfloat16, owns_weight=True):
        super().__init__()
        self._shape = (d_in, d_out)
        if owns_weight:
            self.w = make_param((d_in, d_out), dtype, pspec, mesh)
        self.named(name)

    def kernel(self, p, x, *maybe_w):
        w = maybe_w[0] if maybe_w else p["w"]
        return jnp.einsum("...d,df->...f", x, w,
                          preferred_element_type=x.dtype)

    def flops_estimate(self, in_shapes):
        b = int(np.prod(in_shapes[0].shape[:-1]))
        return 2.0 * b * int(np.prod(self._shape))


class WeightGatherOp(Op):
    """FSDP: all-gather a data-axis-sharded weight before use (network).

    This is the paper's §2.1 'prefetch the next layer's weight shards in
    parallel with computation' made a first-class schedulable op.  The
    gather dim adapts to divisibility (row-parallel weights whose input
    dim is not a dp multiple shard the output dim instead).
    """

    resource = "network"
    out_batch_dim = None

    def __init__(self, local_shape, name, mesh: MeshInfo, pspec=((), ("model",)),
                 dtype=jnp.bfloat16):
        super().__init__()
        self.mesh = mesh
        self._full = tuple(local_shape)
        gdim = next(i for i in range(len(local_shape))
                    if local_shape[i] % mesh.dp == 0)
        self.gdim = gdim
        shape = list(local_shape)
        shape[gdim] //= mesh.dp
        spec = [tuple(e) for e in pspec]
        spec[gdim] = tuple(spec[gdim]) + ("data",)
        self.w = make_param(tuple(shape), dtype, tuple(spec), mesh)
        self.named(name)

    def kernel(self, p):
        return col.all_gather(p["w"], "data", dim=self.gdim)

    def infer_out(self, in_shapes):
        return jax.ShapeDtypeStruct(self._full, self.w.dtype)


class RMSNormOp(Op):
    resource = "memory"

    def __init__(self, d, name="rmsnorm", mesh: MeshInfo = None,
                 dtype=jnp.bfloat16, eps=1e-5):
        super().__init__()
        self.eps = eps
        self.g = Param((d,), dtype, init=lambda k, s, dt: jnp.ones(s, dt),
                       pspec=((),), global_shape=(d,))
        self.named(name)

    def kernel(self, p, x):
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        return (xf * lax.rsqrt(var + self.eps)).astype(x.dtype) * p["g"]


class AddOp(Op):
    resource = "memory"

    def __init__(self, name="residual_add"):
        super().__init__()
        self.named(name)

    def kernel(self, p, a, b):
        return a + b


class SwiGLUOp(Op):
    """Fused gate activation: silu(gate) * up  (memory-bound)."""

    resource = "memory"

    def __init__(self, name="swiglu"):
        super().__init__()
        self.named(name)

    def kernel(self, p, gate_up):
        gate, up = jnp.split(gate_up, 2, axis=-1)
        return jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype) * up


class GELUOp(Op):
    resource = "memory"

    def __init__(self, name="gelu"):
        super().__init__()
        self.named(name)

    def kernel(self, p, x):
        return jax.nn.gelu(x.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# collectives as schedulable network ops
# ---------------------------------------------------------------------------


class PsumOp(Op):
    resource = "network"

    def __init__(self, axis="model", name="allreduce"):
        super().__init__()
        self.axis = axis
        self.named(name)

    def kernel(self, p, x):
        return col.psum(x, self.axis)

    def infer_out(self, in_shapes):
        return in_shapes[0]


class ReduceScatterOp(Op):
    """psum_scatter over ``dim`` (SP entry: partial sums -> seq shards)."""

    resource = "network"

    def __init__(self, mesh: MeshInfo, axis="model", dim=1, name="reduce_scatter"):
        super().__init__()
        self.axis, self.dim, self.mesh = axis, dim, mesh
        self.named(name)

    def kernel(self, p, x):
        return col.reduce_scatter(x, self.axis, dim=self.dim)

    def infer_out(self, in_shapes):
        s = list(in_shapes[0].shape)
        n = self.mesh.tp if self.axis == "model" else self.mesh.dp
        assert s[self.dim] % n == 0, (s, self.dim, n)
        s[self.dim] //= n
        return jax.ShapeDtypeStruct(tuple(s), in_shapes[0].dtype)


class AllGatherOp(Op):
    """all-gather over ``dim`` (SP exit: seq shards -> full sequence)."""

    resource = "network"

    def __init__(self, mesh: MeshInfo, axis="model", dim=1, name="all_gather"):
        super().__init__()
        self.axis, self.dim, self.mesh = axis, dim, mesh
        self.named(name)

    def kernel(self, p, x):
        return col.all_gather(x, self.axis, dim=self.dim)

    def infer_out(self, in_shapes):
        s = list(in_shapes[0].shape)
        n = self.mesh.tp if self.axis == "model" else self.mesh.dp
        s[self.dim] *= n
        return jax.ShapeDtypeStruct(tuple(s), in_shapes[0].dtype)


class AllToAllOp(Op):
    resource = "network"

    def __init__(self, mesh: MeshInfo, axis="model", split_dim=0, concat_dim=0,
                 name="all_to_all"):
        super().__init__()
        self.axis, self.split_dim, self.concat_dim = axis, split_dim, concat_dim
        self.mesh = mesh
        self.named(name)

    def kernel(self, p, x):
        return col.all_to_all(x, self.axis, split_dim=self.split_dim,
                              concat_dim=self.concat_dim)

    def infer_out(self, in_shapes):
        s = list(in_shapes[0].shape)
        n = self.mesh.tp if self.axis == "model" else self.mesh.dp
        s[self.split_dim] //= n
        s[self.concat_dim] *= n
        return jax.ShapeDtypeStruct(tuple(s), in_shapes[0].dtype)


class DataShardedLinearOp(Op):
    """Decode-path ZeRO alternative: the weight's input dim stays sharded
    over 'data' (resident, never gathered); each chip multiplies its x
    slice and a psum over 'data' completes the contraction.  Trades
    d_in·d_out weight-gather bytes for d_out activation bytes — a huge
    win whenever tokens << d_in (single-token decode)."""

    resource = "compute"

    def __init__(self, d_in, d_out, name, mesh: MeshInfo,
                 pspec=((), ("model",)), dtype=jnp.bfloat16):
        super().__init__()
        assert d_in % mesh.dp == 0, (name, d_in, mesh.dp)
        self.d_loc = d_in // mesh.dp
        self._shape = (d_in, d_out)
        self.w = make_param((self.d_loc, d_out), dtype,
                            (tuple(pspec[0]) + ("data",), pspec[1]), mesh)
        self.named(name)

    def kernel(self, p, x):
        off = col.axis_index("data") * self.d_loc
        xs = lax.dynamic_slice_in_dim(x, off, self.d_loc, axis=x.ndim - 1)
        part = jnp.einsum("...d,df->...f", xs, p["w"],
                          preferred_element_type=x.dtype)
        return col.psum(part, "data")

    def infer_out(self, in_shapes):
        s = list(in_shapes[0].shape)
        s[-1] = self._shape[1]
        return jax.ShapeDtypeStruct(tuple(s), self.w.dtype)

    def flops_estimate(self, in_shapes):
        b = int(np.prod(in_shapes[0].shape[:-1]))
        return 2.0 * b * self.d_loc * self._shape[1]


class ShardedLinear(Module):
    """Linear with optional FSDP: when ``mesh.fsdp`` the weight is stored
    data-sharded and re-assembled by a schedulable WeightGather (network)
    op — the ZeRO-3 prefetch-overlap target.  ``mesh.fsdp_resident``
    (decode) keeps the shard resident and psums the partial output
    instead (see DataShardedLinearOp)."""

    def __init__(self, d_in, d_out, name, mesh: MeshInfo,
                 pspec=((), ("model",)), dtype=jnp.bfloat16, fsdp=None):
        super().__init__()
        self._fsdp = mesh.fsdp if fsdp is None else fsdp
        self._resident = self._fsdp and mesh.fsdp_resident             and d_in % mesh.dp == 0
        if self._resident:
            self.lin = DataShardedLinearOp(d_in, d_out, name, mesh,
                                           pspec=pspec, dtype=dtype)
        elif self._fsdp:
            self.gather = WeightGatherOp((d_in, d_out), f"{name}_wgather",
                                         mesh, pspec=pspec, dtype=dtype)
            self.lin = LinearOp(d_in, d_out, name, mesh, pspec=pspec,
                                dtype=dtype, owns_weight=False)
        else:
            self.lin = LinearOp(d_in, d_out, name, mesh, pspec=pspec,
                                dtype=dtype)
        self.named(name)

    def forward(self, x):
        if self._fsdp and not self._resident:
            return self.lin(x, self.gather())
        return self.lin(x)


# ---------------------------------------------------------------------------
# rotary position embeddings (3 variants)
# ---------------------------------------------------------------------------


def _rope_angles(positions, dim, base=10000.0, dtype=jnp.float32):
    """positions (...,) -> cos/sin (..., dim/2)."""
    inv = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def apply_rope(x, cos, sin):
    """x (..., hd_rot) with hd_rot even; NeoX-style half rotation."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def rope_full(q, k, positions, base=10000.0):
    """Standard llama RoPE over the whole head dim.
    q (B,S,H,hd), positions (B,S)."""
    hd = q.shape[-1]
    cos, sin = _rope_angles(positions, hd, base, q.dtype)
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    return apply_rope(q, cos, sin), apply_rope(k, cos, sin)


def rope_partial(q, k, positions, fraction=0.5, base=10000.0):
    """ChatGLM-style 2d RoPE: rotate only the first ``fraction`` of hd."""
    hd = q.shape[-1]
    rot = int(hd * fraction)
    cos, sin = _rope_angles(positions, rot, base, q.dtype)
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]

    def app(x):
        xr, xp = x[..., :rot], x[..., rot:]
        return jnp.concatenate([apply_rope(xr, cos, sin), xp], -1)

    return app(q), app(k)


def rope_mrope(q, k, positions3, sections=(16, 24, 24), base=10000.0):
    """Qwen2-VL M-RoPE: head dim halves partitioned into (t,h,w) sections,
    each rotated by its own position stream.  positions3: (3, B, S)."""
    hd = q.shape[-1]
    half = hd // 2
    assert sum(sections) == half, (sections, hd)
    cos_parts, sin_parts = [], []
    offset = 0
    inv = 1.0 / (base ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    for sec, pos in zip(sections, positions3):
        ang = pos.astype(jnp.float32)[..., None] * inv[offset:offset + sec]
        cos_parts.append(jnp.cos(ang))
        sin_parts.append(jnp.sin(ang))
        offset += sec
    cos = jnp.concatenate(cos_parts, -1).astype(q.dtype)[:, :, None, :]
    sin = jnp.concatenate(sin_parts, -1).astype(q.dtype)[:, :, None, :]
    return apply_rope(q, cos, sin), apply_rope(k, cos, sin)


ROPE_FNS = {
    "full": rope_full,
    "partial2d": rope_partial,
    "mrope": rope_mrope,
    "none": lambda q, k, pos, **kw: (q, k),
}


# ---------------------------------------------------------------------------
# GQA head layout under TP
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class HeadLayout:
    """Static mapping of (padded) Q heads / replicated KV heads to shards."""

    n_q: int                 # true q heads
    n_kv: int                # true kv heads
    tp: int
    head_dim: int

    @property
    def q_pad(self) -> int:  # padded q heads (multiple of tp)
        return ((self.n_q + self.tp - 1) // self.tp) * self.tp

    @property
    def q_local(self) -> int:
        return self.q_pad // self.tp

    def kv_ids_for_shard(self, s: int) -> list[int]:
        """Distinct true-KV head ids shard ``s`` needs (>=1)."""
        group = max(1, self.n_q // self.n_kv)
        ids = []
        for i in range(self.q_local):
            h = s * self.q_local + i
            kv = min(h // group, self.n_kv - 1)
            if kv not in ids:
                ids.append(kv)
        return ids

    @property
    def kv_local(self) -> int:
        return max(len(self.kv_ids_for_shard(s)) for s in range(self.tp))

    def kv_store_map(self) -> np.ndarray:
        """(tp, kv_local): true kv-head id stored in each local slot."""
        m = np.zeros((self.tp, self.kv_local), np.int32)
        for s in range(self.tp):
            ids = self.kv_ids_for_shard(s)
            ids = ids + [ids[-1]] * (self.kv_local - len(ids))
            m[s] = ids
        return m

    def q_slot_map(self) -> np.ndarray:
        """(tp, q_local): local KV slot each local q head attends to."""
        m = np.zeros((self.tp, self.q_local), np.int32)
        group = max(1, self.n_q // self.n_kv)
        for s in range(self.tp):
            ids = self.kv_ids_for_shard(s)
            for i in range(self.q_local):
                h = s * self.q_local + i
                kv = min(h // group, self.n_kv - 1)
                m[s, i] = ids.index(kv)
        return m

    def q_valid_map(self) -> np.ndarray:
        """(tp, q_local) 1.0 for true heads, 0.0 for padding heads."""
        m = np.zeros((self.tp, self.q_local), np.float32)
        for s in range(self.tp):
            for i in range(self.q_local):
                m[s, i] = 1.0 if s * self.q_local + i < self.n_q else 0.0
        return m


# ---------------------------------------------------------------------------
# attention ops
# ---------------------------------------------------------------------------


def _sdpa(q, k, v, causal: bool, q_offset=0, valid_len=None):
    """Reference attention.  q (B,Sq,H,hd), k/v (B,Sk,H,hd).
    ``valid_len``: scalar, (B,) per-request cache lengths, or (B,Sq)
    per-query-position lengths (chunked decode: position j of the chunk
    sees ``cache_len + j + 1`` keys)."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        qi = jnp.arange(Sq)[:, None] + q_offset
        ki = jnp.arange(Sk)[None, :]
        logits = jnp.where(ki <= qi, logits, -1e30)
    if valid_len is not None:
        vl = jnp.asarray(valid_len)
        if vl.ndim == 2:                    # (B,Sq) -> (B,1,Sq,1)
            vl = vl[:, None, :, None]
        elif vl.ndim:                       # (B,)   -> (B,1,1,1)
            vl = vl.reshape(-1, 1, 1, 1)
        ki = jnp.arange(Sk)[None, None, None, :]
        logits = jnp.where(ki < vl, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def _sdpa_grouped(q, k, v, valid_len):
    """``_sdpa`` without causal masking for grouped-query attention: local
    q head ``i`` reads KV slot ``i // g``.  q (B,Sq,H,hd), k/v
    (B,Sk,kv,hd) with ``H = kv * g``; ``valid_len`` (B,) or (B,Sq).

    K and V are read once per KV head: the scores are one dot per (row,
    KV head) with the group's ``g * Sq`` query rows as M, not a
    multiply-reduce over K and V copied to every query head."""
    B, Sq, H, hd = q.shape
    Sk, kv = k.shape[1], k.shape[2]
    qg = q.reshape(B, Sq, kv, H // kv, hd)
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    vl = jnp.asarray(valid_len)
    vl = (vl[:, None, None, :, None] if vl.ndim == 2     # (B,Sq)
          else vl.reshape(-1, 1, 1, 1, 1))              # (B,)
    logits = jnp.where(jnp.arange(Sk) < vl, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v)
    return out.reshape(B, Sq, H, hd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _sdpa_chunked(q, k, v, causal: bool, chunk_q: int):
    """Exact attention, scanned over q blocks (bounded peak memory).

    custom_vjp with an explicit flash-style backward so BOTH directions
    sit inside named_scopes ("flashable_attention[_bwd]") — on TPU each
    scope is one Pallas kernel whose HBM traffic is q/k/v(/o/do) at the
    boundary; the roofline analyzer substitutes that cost (--attn-sub)."""
    B, Sq, H, hd = q.shape
    cq = _chunk_of(Sq, chunk_q)
    n = Sq // cq
    with jax.named_scope("flashable_attention"):
        qb = q.reshape(B, n, cq, H, hd).transpose(1, 0, 2, 3, 4)

        def body(_, inp):
            qi, off = inp
            o = _sdpa(qi, k, v, causal, q_offset=off)
            return None, o

        offs = jnp.arange(n, dtype=jnp.int32) * cq
        _, ob = lax.scan(body, None, (qb, offs))
        return ob.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd)


def _chunk_of(Sq, chunk_q):
    cq = min(chunk_q, Sq)
    while Sq % cq:
        cq //= 2
    return max(cq, 1)


def _sdpa_chunked_fwd(q, k, v, causal, chunk_q):
    return _sdpa_chunked(q, k, v, causal, chunk_q), (q, k, v)


def _sdpa_chunked_bwd(causal, chunk_q, res, do):
    """Flash-style backward: recompute per-chunk probabilities, accumulate
    dk/dv across q chunks, all inside the substitutable scope."""
    q, k, v = res
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    cq = _chunk_of(Sq, chunk_q)
    n = Sq // cq
    scale = 1.0 / math.sqrt(hd)
    with jax.named_scope("flashable_attention_bwd"):
        qb = q.reshape(B, n, cq, H, hd).transpose(1, 0, 2, 3, 4)
        dob = do.reshape(B, n, cq, H, hd).transpose(1, 0, 2, 3, 4)
        offs = jnp.arange(n, dtype=jnp.int32) * cq

        def body(carry, inp):
            dk, dv = carry
            qi, doi, off = inp
            sl = jnp.einsum("bqhd,bkhd->bhqk", qi, k,
                            preferred_element_type=jnp.float32) * scale
            if causal:
                qpos = jnp.arange(cq)[:, None] + off
                kpos = jnp.arange(Sk)[None, :]
                sl = jnp.where(kpos <= qpos, sl, -1e30)
            p = jax.nn.softmax(sl, axis=-1)                     # (B,H,cq,Sk)
            dof = doi.astype(jnp.float32)
            dvi = jnp.einsum("bhqk,bqhd->bkhd", p, dof)
            dp = jnp.einsum("bqhd,bkhd->bhqk", dof,
                            v.astype(jnp.float32))
            ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
            dqi = jnp.einsum("bhqk,bkhd->bqhd", ds,
                             k.astype(jnp.float32)) * scale
            dki = jnp.einsum("bhqk,bqhd->bkhd", ds,
                             qi.astype(jnp.float32)) * scale
            return (dk + dki, dv + dvi), dqi.astype(q.dtype)

        zk = jnp.zeros(k.shape, jnp.float32)
        zv = jnp.zeros(v.shape, jnp.float32)
        (dk, dv), dqb = lax.scan(body, (zk, zv), (qb, dob, offs))
        dq = dqb.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd)
        return dq, dk.astype(k.dtype), dv.astype(v.dtype)


_sdpa_chunked.defvjp(_sdpa_chunked_fwd, _sdpa_chunked_bwd)


class RopeOp(Op):
    """Apply rotary embeddings to q and k (its own schedulable memory op)."""

    resource = "memory"

    def __init__(self, rope: str = "full", rope_kw: Optional[dict] = None,
                 name="rope"):
        super().__init__()
        self.rope = rope
        self.rope_kw = rope_kw or {}
        self.named(name)

    def kernel(self, p, q, k, positions):
        return ROPE_FNS[self.rope](q, k, positions, **self.rope_kw)


class AttentionOp(Op):
    """Full (train/prefill) attention over roped q/k with GQA slot mapping.

    Inputs: q (B,S,q_local,hd), k,v (B,S,kv_local,hd).
    impl: 'pallas' (flash TPU kernel), 'chunked' (exact q-block scan, the
    XLA-level flash used for the large dry-run shapes — peak memory is one
    (B,H,cq,Sk) block instead of the full (B,H,S,S) score matrix), or
    'xla' (reference _sdpa).
    """

    resource = "compute"

    def __init__(self, layout: HeadLayout, causal=True,
                 name="attention", impl="xla", chunk_q=512):
        super().__init__()
        self.layout = layout
        self.causal = causal
        self.impl = impl
        self.chunk_q = chunk_q
        self.named(name)

    def kernel(self, p, q, k, v):
        lay = self.layout
        slot = jnp.asarray(lay.q_slot_map())[col.axis_index("model")]
        valid = jnp.asarray(lay.q_valid_map())[col.axis_index("model")]
        k_per_q = jnp.take(k, slot, axis=2)   # (B,S,q_local,hd)
        v_per_q = jnp.take(v, slot, axis=2)
        if self.impl == "pallas":
            from ..kernels import ops as kops
            out = kops.flash_attention(q, k_per_q, v_per_q, causal=self.causal)
        elif self.impl == "chunked" and q.shape[1] > self.chunk_q:
            out = _sdpa_chunked(q, k_per_q, v_per_q, self.causal,
                                self.chunk_q)
        else:
            out = _sdpa(q, k_per_q, v_per_q, self.causal)
        return out * valid[None, None, :, None].astype(out.dtype)

    def flops_estimate(self, in_shapes):
        B, S, H, hd = in_shapes[0].shape
        return 4.0 * B * S * S * H * hd * (0.5 if self.causal else 1.0)


class DecodeAttentionOp(Op):
    """Single-token decode attention against a KV cache (memory-bound).

    Inputs: q/k_new (roped) (B,1,·,hd), v_new,
            k_cache/v_cache (B,S_max,kv_local,hd),
            cache_len (B,) int32 per-request lengths (ragged batch).
    Outputs: attn (B,1,q_local,hd), updated k_cache, v_cache.
    ``impl='pallas'`` uses the flash-decode kernel for the cache read.
    Otherwise a ``grouped`` layout reads the cache once per KV head
    (``_sdpa_grouped``); other layouts copy K and V to every q head.
    """

    resource = "memory"

    def __init__(self, layout: HeadLayout, name="decode_attention",
                 window: Optional[int] = None, impl: str = "xla"):
        super().__init__()
        self.layout = layout
        self.window = window
        self.impl = impl
        self.named(name)

    @property
    def grouped(self) -> bool:
        """True when, on every shard, local q head ``i`` reads KV slot
        ``i // g`` with ``g = q_local // kv_local``: each slot serves one
        contiguous group of q heads, padding heads included."""
        lay = self.layout
        g, rem = divmod(lay.q_local, lay.kv_local)
        return rem == 0 and bool(
            (lay.q_slot_map() == np.arange(lay.q_local) // g).all())

    def kernel(self, p, q, k_new, v_new, k_cache, v_cache, cache_len):
        lay = self.layout
        clen = (jnp.broadcast_to(cache_len, (q.shape[0],))
                if jnp.ndim(cache_len) == 0 else cache_len)
        k_cache = _dus_time(k_cache, k_new, clen)
        v_cache = _dus_time(v_cache, v_new, clen)
        slot = jnp.asarray(lay.q_slot_map())[col.axis_index("model")]
        valid = jnp.asarray(lay.q_valid_map())[col.axis_index("model")]
        per_q = functools.partial(jnp.take, indices=slot, axis=2)
        Sq = q.shape[1]
        if self.impl == "pallas" and Sq == 1:
            from ..kernels import ops as kops
            out = kops.decode_attention(q, per_q(k_cache), per_q(v_cache),
                                        clen + 1)
        else:
            # chunked decode (Sq > 1): query position j attends the cache
            # prefix plus the chunk up to and including itself —
            # ``cache_len + j + 1`` keys (per-row, per-position lengths).
            vl = (clen + 1 if Sq == 1
                  else clen[:, None] + 1 + jnp.arange(Sq, dtype=clen.dtype))
            with jax.named_scope("flashable_decode"):
                if self.grouped:
                    out = _sdpa_grouped(q, k_cache, v_cache, vl)
                else:
                    out = _sdpa(q, per_q(k_cache), per_q(v_cache),
                                causal=False, valid_len=vl)
        out = out * valid[None, None, :, None].astype(out.dtype)
        return out, k_cache, v_cache

    def infer_out(self, in_shapes):
        q, k_new, v_new, kc, vc, clen = in_shapes
        return (jax.ShapeDtypeStruct(q.shape, q.dtype),
                jax.ShapeDtypeStruct(kc.shape, kc.dtype),
                jax.ShapeDtypeStruct(vc.shape, vc.dtype))

    def bytes_estimate(self, in_shapes, out_shapes):
        kc = in_shapes[3]
        return 2.0 * 2 * int(np.prod(kc.shape))  # read K+V cache


def _dus_time(cache, new, t):
    """dynamic_update_slice at per-row time indices ``t`` (B,) along dim 1.
    ``new`` may carry one token (decode) or a whole chunk (chunked
    prefill); callers must keep ``t + new.shape[1] <= S_max`` or the
    clamped start would silently shift the write window."""
    t = jnp.asarray(t, jnp.int32)
    if t.ndim == 0:
        idx = (jnp.int32(0), t.reshape(()), jnp.int32(0), jnp.int32(0))
        return lax.dynamic_update_slice(cache, new.astype(cache.dtype), idx)

    def one(c, n, ti):   # c (S,kv,hd), n (Sq,kv,hd)
        return lax.dynamic_update_slice(
            c, n.astype(c.dtype), (ti, jnp.int32(0), jnp.int32(0)))

    return jax.vmap(one)(cache, new, t)


# ---------------------------------------------------------------------------
# embedding / head / loss (vocab-sharded)
# ---------------------------------------------------------------------------


class EmbedOp(Op):
    """Vocab-sharded embedding lookup; emits a *partial* value that a
    following Psum/ReduceScatter network op completes."""

    resource = "memory"

    def __init__(self, vocab, d, mesh: MeshInfo, name="embed",
                 dtype=jnp.bfloat16):
        super().__init__()
        vpad = -(-vocab // mesh.tp) * mesh.tp   # pad to a tp multiple
        self.vshard = vpad // mesh.tp
        self.mesh = mesh
        self.w = make_param((self.vshard, d), dtype, (("model",), ()), mesh,
                            init=lambda k, s, dt: jax.random.normal(k, s, jnp.float32).astype(dt) * 0.02)
        self.named(name)

    def kernel(self, p, ids):
        off = col.axis_index("model") * self.vshard
        local = ids - off
        ok = (local >= 0) & (local < self.vshard)
        local = jnp.clip(local, 0, self.vshard - 1)
        out = jnp.take(p["w"], local, axis=0)
        return out * ok[..., None].astype(out.dtype)


class LmHeadOp(Op):
    """x (B,S,d) -> logits (B,S,Vshard) vocab-sharded."""

    resource = "compute"

    def __init__(self, d, vocab, mesh: MeshInfo, name="lm_head",
                 dtype=jnp.bfloat16, tie_path: Optional[tuple] = None):
        super().__init__()
        self.vocab = vocab
        self.vshard = -(-vocab // mesh.tp)
        self.tied = tie_path is not None
        if tie_path is None:
            self.w = make_param((d, self.vshard), dtype, ((), ("model",)), mesh)
        else:
            self.share_params(tie_path)
        self.named(name)

    def kernel(self, p, x):
        w = p["w"]
        if self.tied:
            w = w.T  # embed table (Vshard, d) -> (d, Vshard)
        out = jnp.einsum("bsd,dv->bsv", x, w, preferred_element_type=x.dtype)
        # mask vocab-padding logits so sampling can never pick them
        gid = col.axis_index("model") * self.vshard + jnp.arange(self.vshard)
        return jnp.where(gid < self.vocab, out, -1e30)

    def infer_out(self, in_shapes):
        B, S, d = in_shapes[0].shape
        return jax.ShapeDtypeStruct((B, S, self.vshard), in_shapes[0].dtype)

    def flops_estimate(self, in_shapes):
        B, S, d = in_shapes[0].shape
        return 2.0 * B * S * d * self.vshard


class ShardedXentOp(Op):
    """Cross-entropy over vocab-sharded logits (psum'd logsumexp),
    seq-chunked to bound the live logits buffer.  Emits per-device mean
    loss; the train step psum-means it over the data axis."""

    resource = "compute"

    def __init__(self, mesh: MeshInfo, vshard: int, vocab: int = 0,
                 name="xent"):
        super().__init__()
        self.mesh = mesh
        self.vshard = vshard
        self.vocab = vocab or vshard * mesh.tp
        self.named(name)
        self.out_batch_dim = None  # scalar loss

    def kernel(self, p, logits, labels):
        # logits (B,S,Vs) local shard; labels (B,S) global ids
        lf = logits.astype(jnp.float32)
        gid = col.axis_index("model") * self.vshard + jnp.arange(self.vshard)
        lf = jnp.where(gid < self.vocab, lf, -1e30)
        m_local = jnp.max(lf, axis=-1)
        # stability max carries no gradient (cancels in lse - tgt)
        m = col.pmax(lax.stop_gradient(m_local), "model")
        se = jnp.sum(jnp.exp(lf - m[..., None]), axis=-1)
        se = col.psum(se, "model")
        lse = jnp.log(se) + m
        off = col.axis_index("model") * self.vshard
        loc = labels - off
        ok = (loc >= 0) & (loc < self.vshard)
        loc = jnp.clip(loc, 0, self.vshard - 1)
        tgt = jnp.take_along_axis(lf, loc[..., None], axis=-1)[..., 0]
        tgt = col.psum(tgt * ok.astype(jnp.float32), "model")
        return jnp.mean(lse - tgt)

    def infer_out(self, in_shapes):
        return jax.ShapeDtypeStruct((), jnp.float32)


class HeadLossOp(Op):
    """Fused LM head + cross entropy, seq-chunked so the (B,S,V/tp) logits
    never fully materialize (memory-term optimization for 256k vocabs).

    Inputs x (B,S,d), labels (B,S) int32 (-100 = ignore).
    Outputs per-sample (loss_sum (B,), token_count (B,)) f32 — merged and
    normalized by the step function with a data-axis psum.
    """

    resource = "compute"

    def __init__(self, d, vocab, mesh: MeshInfo, name="head_loss",
                 dtype=jnp.bfloat16, tie_path: Optional[tuple] = None,
                 chunk=512):
        super().__init__()
        self.vocab = vocab
        self.vshard = -(-vocab // mesh.tp)
        self.chunk = chunk
        self.tied = tie_path is not None
        self._d = d
        if tie_path is None:
            self.w = make_param((d, self.vshard), dtype, ((), ("model",)), mesh)
        else:
            self.share_params(tie_path)
        self.named(name)

    def kernel(self, p, x, labels):
        w = p["w"].T if self.tied else p["w"]
        B, S, d = x.shape
        c = min(self.chunk, S)
        n = -(-S // c)
        pad = n * c - S
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
            labels = jnp.pad(labels, ((0, 0), (0, pad)),
                             constant_values=-100)
        xc = x.reshape(B, n, c, d).swapaxes(0, 1)        # (n,B,c,d)
        lc = labels.reshape(B, n, c).swapaxes(0, 1)      # (n,B,c)
        off = col.axis_index("model") * self.vshard

        def body(carry, inp):
            ls, cnt = carry
            xi, li = inp
            logits = jnp.einsum("bcd,dv->bcv", xi, w,
                                preferred_element_type=jnp.float32)
            gid = (col.axis_index("model") * self.vshard
                   + jnp.arange(self.vshard))
            logits = jnp.where(gid < self.vocab, logits, -1e30)
            m = col.pmax(lax.stop_gradient(jnp.max(logits, -1)), "model")
            se = col.psum(jnp.sum(jnp.exp(logits - m[..., None]), -1), "model")
            lse = jnp.log(se) + m
            loc = li - off
            ok = (loc >= 0) & (loc < self.vshard)
            locc = jnp.clip(loc, 0, self.vshard - 1)
            tgt = jnp.take_along_axis(logits, locc[..., None], -1)[..., 0]
            tgt = col.psum(jnp.where(ok, tgt, 0.0), "model")
            valid = (li != -100)
            tok = jnp.where(valid, lse - tgt, 0.0)
            return (ls + jnp.sum(tok, -1),
                    cnt + jnp.sum(valid, -1).astype(jnp.float32)), None

        (ls, cnt), _ = lax.scan(body, (jnp.zeros((B,), jnp.float32),
                                       jnp.zeros((B,), jnp.float32)),
                                (xc, lc))
        return ls, cnt

    def infer_out(self, in_shapes):
        B = in_shapes[0].shape[0]
        return (jax.ShapeDtypeStruct((B,), jnp.float32),
                jax.ShapeDtypeStruct((B,), jnp.float32))

    def flops_estimate(self, in_shapes):
        B, S, d = in_shapes[0].shape
        return 2.0 * B * S * d * self.vshard


class TakeLastOp(Op):
    """Keep only the final sequence position (prefill -> next-token logits)."""

    resource = "memory"

    def __init__(self, name="take_last"):
        super().__init__()
        self.named(name)

    def kernel(self, p, x):
        return x[:, -1:, :]


# ---------------------------------------------------------------------------
# composite blocks
# ---------------------------------------------------------------------------


class MLPBlock(Module):
    """SwiGLU MLP, column/row parallel (+SP reduce-scatter outside)."""

    def __init__(self, d, d_ff, mesh: MeshInfo, name="mlp",
                 dtype=jnp.bfloat16, act="swiglu"):
        super().__init__()
        assert d_ff % mesh.tp == 0, (d_ff, mesh.tp)
        ff_loc = d_ff // mesh.tp
        mult = 2 if act == "swiglu" else 1
        self.wi = ShardedLinear(d, mult * ff_loc, "mlp_in", mesh, dtype=dtype)
        self.act = SwiGLUOp() if act == "swiglu" else GELUOp()
        self.wo = ShardedLinear(ff_loc, d, "mlp_out", mesh,
                                pspec=(("model",), ()), dtype=dtype)
        self.named(name)

    def forward(self, x):
        return self.wo(self.act(self.wi(x)))


class QKVProj(Module):
    """Fused QKV projection, head-sharded; emits q/k/v split ops."""

    def __init__(self, d, layout: HeadLayout, mesh: MeshInfo, name="qkv",
                 dtype=jnp.bfloat16):
        super().__init__()
        lay = layout
        hd = lay.head_dim
        self.lay = lay
        out_dim = (lay.q_local + 2 * lay.kv_local) * hd
        self.proj = ShardedLinear(d, out_dim, "qkv_proj", mesh, dtype=dtype)
        self.splitter = _QKVSplit(lay).named("qkv_split")
        self.named(name)

    def forward(self, x):
        return self.splitter(self.proj(x))


class _QKVSplit(Op):
    resource = "memory"

    def __init__(self, lay: HeadLayout):
        super().__init__()
        self.lay = lay

    def kernel(self, p, qkv):
        lay = self.lay
        hd = lay.head_dim
        B, S, _ = qkv.shape
        nq, nk = lay.q_local * hd, lay.kv_local * hd
        q = qkv[..., :nq].reshape(B, S, lay.q_local, hd)
        k = qkv[..., nq:nq + nk].reshape(B, S, lay.kv_local, hd)
        v = qkv[..., nq + nk:].reshape(B, S, lay.kv_local, hd)
        return q, k, v


class OProj(Module):
    """Row-parallel attention output projection (emits partial sums)."""

    def __init__(self, d, layout: HeadLayout, mesh: MeshInfo, name="o_proj",
                 dtype=jnp.bfloat16):
        super().__init__()
        self.flat = _FlattenHeads().named("flatten_heads")
        self.proj = ShardedLinear(layout.q_local * layout.head_dim, d, "o_proj",
                                  mesh, pspec=(("model",), ()), dtype=dtype)
        self.named(name)

    def forward(self, attn):
        return self.proj(self.flat(attn))


class _FlattenHeads(Op):
    resource = "memory"

    def kernel(self, p, x):
        B, S, H, hd = x.shape
        return x.reshape(B, S, H * hd)
