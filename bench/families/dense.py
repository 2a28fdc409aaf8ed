"""The dense decoder family: pre-norm RMSNorm layers, GQA attention with
rotary embeddings on the first ``rope_fraction`` of each head, a SwiGLU
MLP, and an output head that may be tied to the embedding.

A family module tells the harness what it needs to know about a block,
and is found by the ``family`` key of a configuration file
(``bench/families/<family>.py``; ``dense`` where the key is absent):

- ``arch_config(cfg, m)``: the program's ``ArchConfig`` for the file;
- ``check_model(m)``: raises where the program cannot run what the file
  states;
- ``shapes(m)``: canonical weight name -> (shape, init), where init is
  ``"ones"``, ``"zeros"`` or the normal's standard deviation;
- ``STACKED``: the names that hold one slice per layer (axis 0);
- ``SPLIT``: the axis each matrix is split on over a cell's chips
  (names left out are replicated);
- ``PROGRAM_PATHS``: canonical name -> path in the program's parameter
  tree;
- ``program_columns(model, m)``: where the program's tensor-parallel
  layout reorders the columns of a layer weight (one of ``STACKED``),
  the canonical column of each of its columns;
- ``matmul_params(m)``: weights every token multiplies once per forward
  pass;
- ``REFERENCE``: the name of its plain reference, ``bench/reference/
  <REFERENCE>.py``.

Another family of this block can take these pieces from here
(``from bench.families import dense``) with its own MLP names, gating
and activation, and define only what differs.

``m`` is :func:`common.model_dims` of the file.
"""
from __future__ import annotations

REFERENCE = "dense"
# the MLP's matrices in and out; a gated MLP's first holds gate, then up
MLP = ("w_in", "w_out")


def program_paths(mlp: tuple = MLP) -> dict:
    """Canonical name -> path in the program's tree, the MLP's matrices
    named ``mlp``."""
    return {
        "embed": ("embed", "emb", "w"),
        "ln1": ("layers", "ln1", "g"),
        "wqkv": ("layers", "qkv", "proj", "lin", "w"),
        "wo": ("layers", "oproj", "proj", "lin", "w"),
        "ln2": ("layers", "ln2", "g"),
        mlp[0]: ("layers", "mlp", "wi", "lin", "w"),
        mlp[1]: ("layers", "mlp", "wo", "lin", "w"),
        "ln_f": ("head", "ln", "g"),
        "head": ("head", "out", "w"),
    }


def stacked(mlp: tuple = MLP) -> tuple:
    return ("ln1", "wqkv", "wo", "ln2") + tuple(mlp)


def split(mlp: tuple = MLP) -> dict:
    """Column-parallel in, row-parallel out, the vocabulary split."""
    return {"embed": 0, "wqkv": -1, "wo": -2, mlp[0]: -1, mlp[1]: -2,
            "head": -1}


PROGRAM_PATHS = program_paths()
STACKED = stacked()
SPLIT = split()


def arch_config(cfg: dict, m: dict, act: str = "swiglu"):
    """The program's ``ArchConfig`` for a configuration file, its MLP
    run with ``act``."""
    from repro.configs.base import ArchConfig
    frac = m["rope_fraction"]
    return ArchConfig(
        name=cfg["name"], family="dense", n_layers=m["n_layers"],
        d_model=m["d_model"], n_heads=m["n_heads"], n_kv=m["n_kv"],
        d_ff=m["d_ff"], vocab=m["vocab"], head_dim=m["head_dim"],
        rope="full" if frac == 1 else "partial2d",
        rope_kw=() if frac == 1 else (("fraction", frac),),
        act=act, tie_embeddings=bool(m["tie_embeddings"]),
        source=cfg["source"])


def check_model(m: dict, act: str = "silu"):
    """The program computes RMSNorm with eps 1e-5, rotary base 10000 and
    an MLP of ``act`` (SwiGLU where it is ``silu``) in bfloat16; a
    configuration that states otherwise cannot run on it."""
    want = {"norm_eps": 1e-5, "rope_theta": 10000.0, "act": act,
            "dtype": "bfloat16"}
    bad = {k: m[k] for k, v in want.items() if m[k] != v}
    if bad:
        raise ValueError(f"the program cannot run {bad}; it runs {want}")


def shapes(m: dict, mlp: tuple = MLP, gated: bool = True) -> dict:
    """Canonical name -> (shape, init)::

        embed   (vocab, d)                 normal, std 0.02
        ln1     (L, d), ln2 (L, d)          ones
        wqkv    (L, d, (H + 2 K) hd)        q heads, then K keys, then K values
        wo      (L, H hd, d)
        w_in    (L, d, 2 F)                 gate, then up (SwiGLU); F ungated
        w_out   (L, F, d)
        ln_f    (d,)                        ones
        head    (d, vocab)                  absent where the embedding is tied

    Matrices are normal with std ``1 / sqrt(fan_in)``."""
    d, hd, L = m["d_model"], m["head_dim"], m["n_layers"]
    H, K, F, V = m["n_heads"], m["n_kv"], m["d_ff"], m["vocab"]
    out = {
        "embed": ((V, d), 0.02),
        "ln1": ((L, d), "ones"),
        "wqkv": ((L, d, (H + 2 * K) * hd), d ** -0.5),
        "wo": ((L, H * hd, d), (H * hd) ** -0.5),
        "ln2": ((L, d), "ones"),
        mlp[0]: ((L, d, (2 if gated else 1) * F), d ** -0.5),
        mlp[1]: ((L, F, d), F ** -0.5),
        "ln_f": ((d,), "ones"),
    }
    if not m["tie_embeddings"]:
        out["head"] = ((d, V), d ** -0.5)
    return out


def program_columns(model, m: dict, mlp: tuple = MLP,
                    gated: bool = True) -> dict:
    """The canonical column of each column of the fused QKV and MLP-in
    weights in the layout the program gives them over its ``model`` axis
    (``launch.sharding.dense_tp_params``, run on column numbers)."""
    import numpy as np
    from repro.launch.sharding import dense_tp_params
    s = shapes(m, mlp, gated)
    probe = {"layers": {
        "qkv": {"proj": {"lin": {"w": np.arange(s["wqkv"][0][-1])}}},
        "mlp": {"wi": {"lin": {"w": np.arange(s[mlp[0]][0][-1])}}}}}
    got = dense_tp_params(probe, model)["layers"]
    return {"wqkv": got["qkv"]["proj"]["lin"]["w"],
            mlp[0]: got["mlp"]["wi"]["lin"]["w"]}


def matmul_params(m: dict, gated: bool = True) -> int:
    """Weights every token multiplies once per forward pass: attention
    projections and MLP of every layer, and the output head."""
    d, hd = m["d_model"], m["head_dim"]
    attn = d * (m["n_heads"] + 2 * m["n_kv"]) * hd + m["n_heads"] * hd * d
    mlp = (3 if gated else 2) * d * m["d_ff"]
    return m["n_layers"] * (attn + mlp) + d * m["vocab"]
