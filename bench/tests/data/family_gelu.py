"""A model family for tests: the dense block with an ungated GELU MLP
(one matrix in, one out, as ``MLPBlock`` runs it for ``act="gelu"``).
Its weights have names and shapes of their own (``w_up``, ``w_down``),
and its plain reference is ``reference/gelu.py``; the rest is the dense
family's."""
from __future__ import annotations

from functools import partial

from bench.families import dense

REFERENCE = "gelu"
MLP = ("w_up", "w_down")
PROGRAM_PATHS = dense.program_paths(MLP)
STACKED = dense.stacked(MLP)
SPLIT = dense.split(MLP)
arch_config = partial(dense.arch_config, act="gelu")
check_model = partial(dense.check_model, act="gelu_pytorch_tanh")
shapes = partial(dense.shapes, mlp=MLP, gated=False)
program_columns = partial(dense.program_columns, mlp=MLP, gated=False)
matmul_params = partial(dense.matmul_params, gated=False)
