"""Model assembly: segments, scan-over-layers realization, LM base.

A model is a list of *segments* (embed → layer-stack(s) → head).  Each
segment is one traced OpGraph; layer stacks are realized with ``lax.scan``
over stacked params (compact HLO ⇒ tractable 512-device compiles) and the
DynaFlow plan programs the scan *body* — per-layer schedules are periodic,
which is exactly the paper's per-subgraph CUDA-graph reuse, transplanted.

Conventions
  * layer graphs:  inputs {x, positions, ...}, outputs {x, ...}
  * decode graphs: extra inputs  {cache_len, <name>_cache...} scanned per
    layer; matching outputs are collected as the updated cache stack.
  * prefill:       extra outputs (k, v) collected into a new cache stack.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ArchConfig
from ..core import (OpGraph, Realizer, ScheduleContext, partition,
                    record_plan, trace)
from ..core.module import Module
from .layers import (AddOp, AllGatherOp, AttentionOp, DecodeAttentionOp,
                     EmbedOp, HeadLayout, HeadLossOp, LmHeadOp, MeshInfo,
                     MLPBlock, OProj, PsumOp, QKVProj, ReduceScatterOp,
                     RMSNormOp, RopeOp, TakeLastOp)


# ---------------------------------------------------------------------------
# segments
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Segment:
    name: str                      # key into the params tree
    module: Module
    graph: OpGraph
    count: int = 1                 # scan length (stacked params when > 1)
    scan_inputs: tuple = ()        # graph inputs stacked per layer (caches)
    scan_outputs: tuple = ()       # graph outputs collected per layer
    carry: tuple = ("x",)          # outputs fed to the next segment
    input_map: dict = dataclasses.field(default_factory=dict)   # graph->env
    output_map: dict = dataclasses.field(default_factory=dict)  # graph->env
    uid: str = ""                  # unique id when name repeats (shared wts)

    @property
    def key(self):
        return self.uid or self.name

    def collect_key(self, k: str) -> str:
        """env key a collected scan output lands on.  Outputs that are
        also scan *inputs* (decode caches) round-trip onto the same env
        key so the updated cache replaces the stale one."""
        if k in self.output_map:
            return self.output_map[k]
        if self.count > 1 and k in self.scan_inputs:
            return self.input_map.get(k, k)
        return f"{self.key}.{k}" if self.count > 1 else k


@dataclasses.dataclass
class Forward:
    """A realized forward pass over segments with per-segment plans."""

    segments: list
    realizers: dict                # name -> Realizer
    remat: bool = False
    remat_policy: str = "full"     # full | dots | none

    def __call__(self, params, batch: dict) -> dict:
        env = dict(batch)
        collected = {}
        for seg in self.segments:
            rz = self.realizers[seg.key]
            g = seg.graph
            imap = seg.input_map

            def _env(k):
                return env[imap.get(k, k)]

            if seg.count == 1:
                ins = {k: _env(k) for k in g.inputs}
                # merge the global tree under the segment's own subtree so
                # cross-segment share paths (tied embeddings) resolve
                seg_params = dict(params.get(seg.name) or {})
                merged = {**{k: v for k, v in params.items()
                             if k not in seg_params}, **seg_params}
                out = rz(merged, ins)
                env.update({seg.output_map.get(k, k): v
                            for k, v in out.items()})
                continue
            # scan over stacked layer params (+ scanned cache inputs)
            static_ins = {k: _env(k) for k in g.inputs
                          if k not in seg.carry and k not in seg.scan_inputs}
            xs = (params.get(seg.name),
                  {k: _env(k) for k in seg.scan_inputs})

            def body(carry, x, _rz=rz, _g=g, _seg=seg, _static=static_ins):
                layer_params, scanned = x
                ins = dict(_static)
                ins.update(carry)
                ins.update(scanned)
                out = _rz(layer_params, ins)
                new_carry = {k: out[k] for k in _seg.carry}
                ys = {k: out[k] for k in _seg.scan_outputs}
                return new_carry, ys

            if self.remat:
                if self.remat_policy == "dots":
                    pol = jax.checkpoint_policies.checkpoint_dots
                    body = jax.checkpoint(body, policy=pol)
                else:
                    body = jax.checkpoint(body)
            carry0 = {k: env[imap.get(k, k)] for k in seg.carry}
            carry, ys = lax.scan(body, carry0, xs)
            env.update({seg.output_map.get(k, k): v for k, v in carry.items()})
            for k, v in ys.items():
                collected[seg.collect_key(k)] = v
        env.update(collected)
        return env


def build_forward(segments: Sequence[Segment],
                  scheduler,
                  info: ScheduleContext,
                  remat: bool = False,
                  remat_policy: str = "full",
                  lowered: bool = True,
                  plan_cache=None,
                  op_config=(),
                  verify: str = "off",
                  verify_sink: Optional[list] = None) -> Forward:
    """Partition + schedule every segment graph, returning the Forward.

    ``scheduler`` may be an ``OpSchedulerBase``, a ``StrategyPolicy``, or
    a strategy name: a policy is resolved per segment against the
    ScheduleContext (enriched with the segment's traced graph under
    ``extra['graph']`` so graph-conditional predicates can see op names).
    The *policy's* identity — not merely the resolved scheduler's class —
    enters the PlanStore salt, so two policies never alias cached plans.

    ``lowered=True`` (default) compiles each segment plan to the slot-based
    instruction stream.  Pass a ``PlanStore`` as ``plan_cache`` to share
    lowered plans across builds: the store's outer key is fingerprint v2
    (shape-free graph/plan structure + an (arch, phase, strategy-salt,
    segment) key + ``op_config``), the inner key is the shape bucket —
    so rebuilding a known bucket is a hit, and a *new* bucket of a known
    structure specializes the canonical lowering instead of re-running
    static analysis and lowering (the cross-prefill-bucket share path).

    ``op_config`` is the op-closure config (attention impl, shard layout,
    dtype policy — ``LMBase.op_closure_config()``): everything the op
    callables close over that neither the graph structure nor the shapes
    can see.  Pass it whenever one store serves more than one (model,
    mesh) so structurally identical graphs with different kernel or
    sharding choices cannot alias.

    ``verify`` runs the static verifier (``core.verify``) on every
    segment's recorded plan *and* its lowered IR (including plans
    redeemed from a persisted store): ``"off"`` skips, ``"warn"`` emits
    a Python warning on error-severity diagnostics, ``"strict"`` raises
    ``PlanVerificationError``.  ``verify_sink`` (a list) collects every
    ``(segment_key, VerifyReport)`` pair regardless of mode — the feed
    behind ``api.Program.verify()``.
    """
    from ..core.plan import strategy_salt
    from ..core.policy import as_policy, resolve_strategy
    policy = as_policy(scheduler)
    salt = f"{info.arch}|{info.phase}|{strategy_salt(policy)}"
    # partition with the policy's rule UNION, never the resolved branch's
    # rules: two shape buckets of one program must see the same graph, or
    # their structural keys diverge and cross-bucket PlanStore sharing
    # silently dies (the StrategyPolicy.partition_rules invariant)
    rules = policy.partition_rules()
    realizers = {}
    segs = []
    for seg in segments:
        g = seg.graph
        sched = resolve_strategy(policy, info, graph=g)
        if rules:
            g = partition(g, rules, default_depth=2)
        plan = record_plan(g, sched, info)
        seg = dataclasses.replace(seg, graph=g)
        rz = Realizer(g, plan, lowered=lowered,
                      plan_cache=plan_cache,
                      plan_salt=f"{salt}|{seg.key}",
                      op_config=op_config)
        if verify != "off" or verify_sink is not None:
            from ..core.verify import enforce, verify as run_verify
            report = run_verify(
                g, plan, lowered=getattr(rz, "lowered", None), lint=True)
            if verify_sink is not None:
                verify_sink.append((f"{info.phase}/{seg.key}", report))
            enforce(report, verify if verify != "off" else "report",
                    what=f"segment {seg.key!r} plan")
        realizers[seg.key] = rz
        segs.append(seg)
    return Forward(segs, realizers, remat=remat, remat_policy=remat_policy)


# ---------------------------------------------------------------------------
# dense-LM building blocks
# ---------------------------------------------------------------------------


class EmbedSegment(Module):
    def __init__(self, cfg: ArchConfig, mesh: MeshInfo, sp: bool):
        super().__init__()
        self.emb = EmbedOp(cfg.vocab, cfg.d_model, mesh)
        self.finish = (ReduceScatterOp(mesh, dim=1, name="embed_rs") if sp
                       else PsumOp(name="embed_ar"))
        self.named("embed")

    def forward(self, *, ids):
        return {"x": self.finish(self.emb(ids))}


class DenseDecoderLayer(Module):
    """Pre-norm decoder layer; SP collectives when ``sp`` else all-reduce."""

    def __init__(self, cfg: ArchConfig, mesh: MeshInfo, sp: bool,
                 collect_kv: bool = False, attn_impl: str = None):
        super().__init__()
        d = cfg.d_model
        lay = HeadLayout(cfg.n_heads, cfg.n_kv, mesh.tp, cfg.hd)
        self.lay = lay
        self.sp = sp
        self.collect_kv = collect_kv
        self.ln1 = RMSNormOp(d, "ln_attn")
        if sp:
            self.ag1 = AllGatherOp(mesh, dim=1, name="ag_attn")
            self.ag2 = AllGatherOp(mesh, dim=1, name="ag_mlp")
            self.fin1 = ReduceScatterOp(mesh, dim=1, name="rs_attn")
            self.fin2 = ReduceScatterOp(mesh, dim=1, name="rs_mlp")
        else:
            self.fin1 = PsumOp(name="ar_attn")
            self.fin2 = PsumOp(name="ar_mlp")
        self.qkv = QKVProj(d, lay, mesh)
        self.rope = RopeOp(cfg.rope, cfg.rope_kwargs())
        self.attn = AttentionOp(lay, impl=attn_impl or mesh.attn_impl)
        self.oproj = OProj(d, lay, mesh)
        self.add1 = AddOp("add_attn")
        self.ln2 = RMSNormOp(d, "ln_mlp")
        self.mlp = MLPBlock(d, cfg.d_ff, mesh, act=cfg.act)
        self.add2 = AddOp("add_mlp")
        self.named("layer")

    def forward(self, *, x, positions):
        h = self.ln1(x)
        if self.sp:
            h = self.ag1(h)
        q, k, v = self.qkv(h)
        q, k = self.rope(q, k, positions)
        a = self.attn(q, k, v)
        a = self.oproj(a)
        a = self.fin1(a)
        x = self.add1(x, a)
        h = self.ln2(x)
        if self.sp:
            h = self.ag2(h)
        m = self.mlp(h)
        m = self.fin2(m)
        x = self.add2(x, m)
        out = {"x": x}
        if self.collect_kv:
            out["k"], out["v"] = k, v
        return out


class DenseDecodeLayer(Module):
    """Decode layer: replicated activations, KV-cache update, all-reduce."""

    def __init__(self, cfg: ArchConfig, mesh: MeshInfo):
        super().__init__()
        d = cfg.d_model
        lay = HeadLayout(cfg.n_heads, cfg.n_kv, mesh.tp, cfg.hd)
        self.lay = lay
        self.ln1 = RMSNormOp(d, "ln_attn")
        self.qkv = QKVProj(d, lay, mesh)
        self.rope = RopeOp(cfg.rope, cfg.rope_kwargs())
        self.attn = DecodeAttentionOp(lay)
        self.oproj = OProj(d, lay, mesh)
        self.fin1 = PsumOp(name="ar_attn")
        self.add1 = AddOp("add_attn")
        self.ln2 = RMSNormOp(d, "ln_mlp")
        self.mlp = MLPBlock(d, cfg.d_ff, mesh, act=cfg.act)
        self.fin2 = PsumOp(name="ar_mlp")
        self.add2 = AddOp("add_mlp")
        self.named("layer")

    def forward(self, *, x, positions, cache_len, k_cache, v_cache):
        h = self.ln1(x)
        q, k, v = self.qkv(h)
        q, k = self.rope(q, k, positions)
        a, kc, vc = self.attn(q, k, v, k_cache, v_cache, cache_len)
        a = self.oproj(a)
        a = self.fin1(a)
        x = self.add1(x, a)
        h = self.ln2(x)
        m = self.mlp(h)
        m = self.fin2(m)
        x = self.add2(x, m)
        return {"x": x, "k_cache": kc, "v_cache": vc}


class TrainHead(Module):
    def __init__(self, cfg: ArchConfig, mesh: MeshInfo, sp: bool):
        super().__init__()
        d = cfg.d_model
        self.sp = sp
        self.ln = RMSNormOp(d, "ln_f")
        if sp:
            self.ag = AllGatherOp(mesh, dim=1, name="ag_head")
        tie = ("embed", "emb") if cfg.tie_embeddings else None
        self.out = HeadLossOp(d, cfg.vocab, mesh, tie_path=tie)
        self.named("head")

    def forward(self, *, x, labels):
        h = self.ln(x)
        if self.sp:
            h = self.ag(h)
        ls, cnt = self.out(h, labels)
        return {"loss_sum": ls, "token_count": cnt}


class LogitsHead(Module):
    """Prefill/decode head: vocab-sharded logits.

    ``keep_last=True`` (prefill) slices to the final position before the
    head matmul; ``keep_last=False`` (decode) keeps every position so a
    width-k verify step (speculative decode) sees all k+1 logits.  For
    the plain decode bucket (S=1) the two are the same computation —
    the slice is the identity — so decode tokens are bitwise unchanged.
    """

    def __init__(self, cfg: ArchConfig, mesh: MeshInfo, sp: bool,
                 keep_last: bool = True):
        super().__init__()
        d = cfg.d_model
        self.sp = sp
        self.ln = RMSNormOp(d, "ln_f")
        if sp:
            self.ag = AllGatherOp(mesh, dim=1, name="ag_head")
        self.last = TakeLastOp() if keep_last else None
        tie = ("embed", "emb") if cfg.tie_embeddings else None
        self.out = LmHeadOp(d, cfg.vocab, mesh, tie_path=tie)
        self.named("head")

    def forward(self, *, x):
        h = self.ln(x)
        if self.sp:
            h = self.ag(h)
        if self.last is not None:
            h = self.last(h)
        return {"logits": self.out(h)}


# ---------------------------------------------------------------------------
# LM base class
# ---------------------------------------------------------------------------


class LMBase:
    """Shared machinery: build segments per phase, init params, shardings."""

    def __init__(self, cfg: ArchConfig, mesh: MeshInfo):
        self.cfg = cfg
        self.mesh = mesh

    def op_closure_config(self) -> tuple:
        """Canonical (name, value) pairs for the PlanStore fingerprint-v2
        outer key: everything this model's op callables close over that
        graph structure and shapes cannot see — attention impl, shard
        layout, dtype policy.  Two models whose graphs trace to the same
        structure but differ in any of these must not share lowerings."""
        m, c = self.mesh, self.cfg
        return (("arch", c.name),
                ("attn_impl", m.attn_impl),
                ("tp", m.tp), ("dp", m.dp), ("pods", m.pods),
                ("fsdp", m.fsdp), ("fsdp_resident", m.fsdp_resident),
                ("seq_parallel", bool(getattr(c, "seq_parallel", False))),
                ("act_dtype", "bfloat16"),
                ("rope", c.rope), ("act", c.act),
                ("tie_embeddings", bool(getattr(c, "tie_embeddings",
                                                False))))

    # subclasses define these ------------------------------------------------
    def make_embed(self, phase: str) -> Module:
        raise NotImplementedError

    def layer_stacks(self, phase: str) -> list[tuple[str, Module, int, tuple, tuple]]:
        """[(name, module, count, scan_inputs, scan_outputs)]"""
        raise NotImplementedError

    def make_head(self, phase: str) -> Module:
        raise NotImplementedError

    def batch_inputs(self, phase: str, B_loc: int, S: int,
                     s_max: int = 0) -> dict:
        """name -> (ShapeDtypeStruct, batch_dim) for non-cache inputs."""
        i32 = jnp.int32
        pos_shape = ((3, B_loc, S) if self.cfg.rope == "mrope"
                     else (B_loc, S))
        pos_bd = 1 if self.cfg.rope == "mrope" else 0
        if phase == "train":
            return {
                "ids": (jax.ShapeDtypeStruct((B_loc, S), i32), 0),
                "labels": (jax.ShapeDtypeStruct((B_loc, S), i32), 0),
                "positions": (jax.ShapeDtypeStruct(pos_shape, i32), pos_bd),
            }
        if phase == "prefill":
            return {
                "ids": (jax.ShapeDtypeStruct((B_loc, S), i32), 0),
                "positions": (jax.ShapeDtypeStruct(pos_shape, i32), pos_bd),
            }
        # decode: S tokens per step.  S == 1 is the classic single-token
        # decode; S > 1 runs the same cached-attention graph over a chunk
        # of S query positions (chunked prefill through the decode path).
        pos_shape = ((3, B_loc, S) if self.cfg.rope == "mrope"
                     else (B_loc, S))
        return {
            "ids": (jax.ShapeDtypeStruct((B_loc, S), i32), 0),
            "positions": (jax.ShapeDtypeStruct(pos_shape, i32), pos_bd),
            "cache_len": (jax.ShapeDtypeStruct((B_loc,), i32), 0),
        }

    def cache_specs(self, stack_name: str, B_loc: int, s_max: int) -> dict:
        """Per-layer cache ShapeDtypeStructs for decode (unstacked)."""
        return {}

    # shared ------------------------------------------------------------------
    def uses_sp(self, phase: str) -> bool:
        """Sequence parallelism for this phase's train/prefill sections.

        Off for decode, and off at tp=1: there the reduce-scatter /
        all-gather pair is an identity, so the layer keeps its all-reduce
        form, whose [all-reduce -> add -> RMSNorm] chains TokenWeave fuses."""
        return (bool(self.cfg.seq_parallel) and self.mesh.tp > 1
                and phase != "decode")

    def seq_local(self, phase: str, S: int) -> int:
        return S // self.mesh.tp if self.uses_sp(phase) else S

    def build_segments(self, phase: str, B_loc: int, S: int,
                       s_max: int = 0) -> tuple[list[Segment], dict]:
        """Trace all segment graphs.  Returns (segments, batch_input_specs)."""
        cfg = self.cfg
        binputs = self.batch_inputs(phase, B_loc, S, s_max)
        segs = []
        emb = self.make_embed(phase)
        import inspect
        esig = inspect.signature(emb.forward)
        emb_in = {k: v[0] for k, v in binputs.items()
                  if k in esig.parameters}
        g = trace(emb, emb_in, batch_dims={k: binputs[k][1] for k in emb_in})
        segs.append(Segment("embed", emb, g))
        # decode is never sequence-parallel, so its x keeps the full chunk
        # length S (1 for single-token decode, the chunk size for chunked
        # prefill through the decode graph)
        d_loc = self.seq_local(phase, S)
        x_sds = jax.ShapeDtypeStruct((B_loc, d_loc, cfg.d_model),
                                     jnp.bfloat16)
        for stack in self.layer_stacks(phase):
            name, mod, count, sc_in, sc_out = stack[:5]
            opts = stack[5] if len(stack) > 5 else {}
            lay_in = {"x": x_sds, "x0": x_sds}
            bd = {"x": 0, "x0": 0}
            for k, (sds, b) in binputs.items():
                if k in ("ids", "labels"):
                    continue
                lay_in[k] = sds
                bd[k] = b
            if phase == "decode":
                for cname, csds in self.cache_specs(name, B_loc, s_max).items():
                    lay_in[cname] = csds
                    bd[cname] = 0
            # drop inputs the module doesn't take
            sig = inspect.signature(mod.forward)
            lay_in = {k: v for k, v in lay_in.items() if k in sig.parameters}
            bd = {k: v for k, v in bd.items() if k in lay_in}
            g = trace(mod, lay_in, batch_dims=bd)
            segs.append(Segment(name, mod, g, count=count,
                                scan_inputs=sc_in, scan_outputs=sc_out,
                                **opts))
        head = self.make_head(phase)
        head_in = {"x": x_sds}
        hbd = {"x": 0}
        if phase == "train":
            head_in["labels"] = binputs["labels"][0]
            hbd["labels"] = 0
        g = trace(head, head_in, batch_dims=hbd)
        segs.append(Segment("head", head, g))
        return segs, binputs

    def decode_cache_env(self, B_loc: int, s_max: int) -> dict:
        """env-key -> ShapeDtypeStruct for all decode caches (launch layer).

        Generic: walks ``layer_stacks('decode')``; stacked (count,)+shape for
        scan segments.  Hybrid models override (aperiodic cache layout)."""
        out = {}
        for stack in self.layer_stacks("decode"):
            name, mod, count, sc_in = stack[0], stack[1], stack[2], stack[3]
            opts = stack[5] if len(stack) > 5 else {}
            imap = opts.get("input_map", {})
            for cn, sds in self.cache_specs(name, B_loc, s_max).items():
                if cn not in sc_in:
                    continue
                key = imap.get(cn, cn)
                shape = (count,) + sds.shape if count > 1 else sds.shape
                out[key] = jax.ShapeDtypeStruct(shape, sds.dtype)
        return out

    CACHE_MODEL_DIMS = {"k_cache": -2, "v_cache": -2,
                        "conv_state": -1, "ssm_state": -3}

    def decode_cache_layout(self) -> dict:
        """env-key -> (batch_dim, model_dim) for every decode cache: which
        dim is the request batch (sharded over data axes) and which dim is
        model-sharded (kv heads / SSM channels) — the launch layer derives
        global shapes + PartitionSpecs from this."""
        out = {}
        for stack in self.layer_stacks("decode"):
            name, _, count, sc_in = stack[0], stack[1], stack[2], stack[3]
            opts = stack[5] if len(stack) > 5 else {}
            imap = opts.get("input_map", {})
            for cn in self.cache_specs(name, 1, 2):
                if cn not in sc_in:
                    continue
                key = imap.get(cn, cn)
                base = next(k for k in self.CACHE_MODEL_DIMS if cn.endswith(k))
                out[key] = (1 if count > 1 else 0, self.CACHE_MODEL_DIMS[base])
        return out

    def decode_cache_page_env(self, num_pages: int, page_size: int) -> dict:
        """Paged decode-cache pool shapes: ``decode_cache_env`` with the
        request-batch dim reinterpreted as a physical-page dim and the
        sequence dim shrunk to one page — ``(P, page, kv, hd)`` per-layer,
        ``(L, P, page, kv, hd)`` stacked.  The serve layer gathers pages
        back into the contiguous ``(B, s_max, ...)`` view per step, so
        the decode graph itself never sees the paging.

        Raises for decode state with no sequence axis to page over (SSM
        conv/ssm states are constant-size per request): probe whether
        every cache's ``batch_dim + 1`` axis scales with ``s_max``."""
        a = self.decode_cache_env(1, page_size)
        b = self.decode_cache_env(1, 2 * page_size)
        layout = self.decode_cache_layout()
        for key, sa in a.items():
            bd = layout[key][0]
            want = list(sa.shape)
            want[bd + 1] *= 2
            if sa.shape[bd + 1] != page_size \
                    or tuple(want) != b[key].shape:
                from ..serve.kv_cache import UnpageableCache
                raise UnpageableCache(
                    f"decode cache {key!r} has no s_max-proportional "
                    f"sequence axis at dim {bd + 1} "
                    f"(shape {sa.shape} at s_max={page_size} vs "
                    f"{b[key].shape} at s_max={2 * page_size}); "
                    "serve this model with DenseCache")
        return self.decode_cache_env(num_pages, page_size)

    # params -------------------------------------------------------------------
    def init_params(self, key, phase="train", global_=False) -> dict:
        segs, _ = self.build_segments(phase, 2, 2 * self.mesh.tp
                                      if self.cfg.seq_parallel else 2,
                                      s_max=4)
        return self._init_from_segments(segs, key, global_)

    def _init_from_segments(self, segs, key, global_=False):
        import zlib
        out = {}
        for seg in segs:
            k = jax.random.fold_in(key, zlib.crc32(seg.name.encode()))
            if seg.name in out:  # shared-weight segment (same params reused)
                continue
            if seg.count == 1:
                p = seg.module.init(k, global_=global_)
                if p:
                    out[seg.name] = p
            else:
                # one jitted map writes each layer into the stacked
                # buffer: peak memory is the stack plus one layer, never
                # a list of layers and its stacked copy at once
                ks = jnp.stack([jax.random.fold_in(k, i)
                                for i in range(seg.count)])
                init = functools.partial(seg.module.init, global_=global_)
                out[seg.name] = jax.jit(
                    functools.partial(lax.map, init))(ks)
        return out

    def param_shapes(self, segs, global_=True) -> dict:
        """ShapeDtypeStruct tree (stacked for layer segments) — dry-run."""
        out = {}
        for seg in segs:
            if seg.name in out:
                continue
            shapes = (seg.module.global_param_shapes() if global_
                      else seg.module.param_shapes())
            if not shapes:
                continue
            if seg.count > 1:
                shapes = jax.tree_util.tree_map(
                    lambda s: jax.ShapeDtypeStruct((seg.count,) + s.shape,
                                                   s.dtype), shapes)
            out[seg.name] = shapes
        return out

    def param_pspecs(self, segs) -> dict:
        out = {}
        for seg in segs:
            if seg.name in out:
                continue
            ps = seg.module.param_pspecs()
            if not ps:
                continue
            if seg.count > 1:
                ps = jax.tree_util.tree_map(
                    lambda spec: (None,) + tuple(spec),
                    ps, is_leaf=lambda x: isinstance(x, tuple))
            out[seg.name] = ps
        return out
