"""Dense decoder-only LM (chatglm3 / deepseek-coder / smollm / minitron /
qwen2-vl backbone) over the DynaFlow segment machinery."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .base import (DenseDecodeLayer, DenseDecoderLayer, EmbedSegment, LMBase,
                   LogitsHead, TrainHead)
from .layers import HeadLayout, MeshInfo


class DenseLM(LMBase):
    family = "dense"

    def __init__(self, cfg: ArchConfig, mesh: MeshInfo):
        super().__init__(cfg, mesh)
        self.layout = HeadLayout(cfg.n_heads, cfg.n_kv, mesh.tp, cfg.hd)

    def make_embed(self, phase):
        sp = self.uses_sp(phase)
        return EmbedSegment(self.cfg, self.mesh, sp)

    def layer_stacks(self, phase):
        cfg, mesh = self.cfg, self.mesh
        if phase == "decode":
            mod = DenseDecodeLayer(cfg, mesh)
            return [("layers", mod, cfg.n_layers,
                     ("k_cache", "v_cache"), ("k_cache", "v_cache"))]
        mod = DenseDecoderLayer(cfg, mesh, self.uses_sp(phase),
                                collect_kv=(phase == "prefill"))
        sc_out = ("k", "v") if phase == "prefill" else ()
        return [("layers", mod, cfg.n_layers, (), sc_out)]

    def make_head(self, phase):
        sp = self.uses_sp(phase)
        if phase == "train":
            return TrainHead(self.cfg, self.mesh, sp)
        return LogitsHead(self.cfg, self.mesh, sp,
                          keep_last=(phase != "decode"))

    def cache_specs(self, stack_name, B_loc, s_max):
        lay = self.layout
        sds = jax.ShapeDtypeStruct((B_loc, s_max, lay.kv_local, lay.head_dim),
                                   jnp.bfloat16)
        return {"k_cache": sds, "v_cache": sds}
