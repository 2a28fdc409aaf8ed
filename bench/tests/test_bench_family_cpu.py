"""A model family added as files alone: a family module and its plain
reference, in a directory of their own and found by the same loader as
the benchmark's families, run a tiny serving and a tiny training cell
end to end with ``correct`` true, and their fp8 control reads ``correct``
false.  No file of the benchmark is edited."""
from __future__ import annotations

import shutil
import time
from pathlib import Path

import jax
import pytest
from bench_tiny import cell

from bench import common, flops
from bench import run as bench_run

DATA = Path(__file__).parent / "data"


@pytest.fixture
def gelu_dir(tmp_path, monkeypatch):
    """A benchmark directory that holds only the ``gelu`` family and its
    reference; the benchmark's own files are the same after the test."""
    root = tmp_path / "bench"
    (root / "families").mkdir(parents=True)
    (root / "reference").mkdir()
    shutil.copy(DATA / "family_gelu.py", root / "families" / "gelu.py")
    shutil.copy(DATA / "reference_gelu.py", root / "reference" / "gelu.py")
    files = [p for p in common.BENCH.rglob("*")
             if p.is_file() and "__pycache__" not in p.parts]
    before = {p: p.read_bytes() for p in files}
    monkeypatch.setattr(common, "use_compile_cache", lambda: "none")
    yield root
    assert {p: p.read_bytes() for p in files} == before


def gelu_cell(name: str):
    """A tiny cell of the ``gelu`` family.  Its limits are its own, set
    between its readings at this size on the CPU (3-4 seeds each): served
    ``logit_gap`` 0.002-0.02 against the fp8 control's 0.19-0.33, and
    ``grad_diff`` 0.010-0.012 against the control's 0.118-0.128."""
    bench, c, cfg, mix = cell(name)
    cfg["family"] = "gelu"
    cfg[cfg["keys"]["act"]] = "gelu_pytorch_tanh"
    cfg["limits"].update(logit_gap=0.1, grad_diff=0.05)
    return bench, c, cfg, mix


@pytest.mark.parametrize("control", [False, True], ids=["program", "fp8"])
@pytest.mark.parametrize("name,seconds", [("chatglm3-6b.chat", 2.0),
                                          ("smollm-135m.train", 0.5)])
def test_a_family_from_files_alone(gelu_dir, monkeypatch, name, seconds,
                                   control):
    bench, c, cfg, mix = gelu_cell(name)
    monkeypatch.setattr(common, "BENCH", gelu_dir)   # where loaders look
    res = bench_run.measure(cfg, mix, 2**33 + 41, seconds, False,
                            jax.devices()[:1], time.perf_counter(), control)
    out = bench_run.result_line(bench, c, res)
    assert out["correct"] is not control, out["checks"]
    m = res["m"]
    assert m["family"] == "gelu"
    assert Path(common.family(m).__file__).parent.parent == gelu_dir
    assert Path(common.reference(m).__file__).parent.parent == gelu_dir
    d, hd, L = m["d_model"], m["head_dim"], m["n_layers"]
    attn = d * (m["n_heads"] + 2 * m["n_kv"]) * hd + m["n_heads"] * hd * d
    assert flops.matmul_params(m) == \
        L * (attn + 2 * d * m["d_ff"]) + d * m["vocab"]


def test_the_family_loader_looks_where_it_is_pointed(tmp_path):
    (tmp_path / "families").mkdir()
    shutil.copy(DATA / "family_gelu.py", tmp_path / "families" / "gelu.py")
    fam = common.load_family("gelu", tmp_path)
    assert fam.REFERENCE == "gelu" and "w_up" in fam.PROGRAM_PATHS
    assert common.load_family("dense").REFERENCE == "dense"
    assert common.family({}) is common.load_family("dense")


INIT_FAMILY = '''
STACKED = ("b",)
PROGRAM_PATHS = {"a": ("x", "a"), "b": ("y", "b"), "c": ("x", "c"),
                 "d": ("d",)}


def shapes(m):
    return {"a": ((4, 8), "ones"), "b": ((3, 4, 8), 0.5),
            "c": ((8,), "zeros"), "d": ((2, 6), 2.0)}
'''


def test_every_kind_of_init(tmp_path, monkeypatch):
    """A family's inits: ones, zeros and a normal's deviation (one draw a
    layer for stacked names); and its tree."""
    import jax.numpy as jnp
    import numpy as np

    from bench import weights
    (tmp_path / "families").mkdir()
    (tmp_path / "families" / "inits.py").write_text(INIT_FAMILY)
    monkeypatch.setattr(common, "BENCH", tmp_path)
    m = {"family": "inits"}
    w = weights.make(m, jax.random.PRNGKey(0), jnp.float32)
    assert (np.asarray(w["a"]) == 1).all() and (np.asarray(w["c"]) == 0).all()
    assert w["d"].shape == (2, 6) and float(np.asarray(w["d"]).std()) > 1
    b = np.asarray(w["b"])
    assert b.shape == (3, 4, 8) and 0.3 < b.std() < 0.7
    assert not np.array_equal(b[0], b[1])
    tree = weights.to_program(w, m)
    assert set(tree) == {"x", "y", "d"} and tree["x"]["c"] is w["c"]
    assert weights.from_program(tree, m).keys() == w.keys()
