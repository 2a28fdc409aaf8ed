"""The controls on the CPU at a tiny size: the plain reference computed
in fp8, put in the program's place, goes through the comparison that
decides ``correct`` and fails it, for the serving and the training
cells; and a traced serving run reports its per-layer metrics."""
from __future__ import annotations

import pytest
from bench_tiny import run


def test_decode_cell_traced_run_reports_per_layer(monkeypatch):
    out = run(monkeypatch, "chatglm3-6b.decode", seconds=3.0, trace=True)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert 1 <= m["engine.rows_per_decode_step"]["value"] <= 4
    assert "device.compiles_in_window" not in m   # not a decode metric
    assert 0 < m["serve.mfu"]["value"] < 100
    # the CPU trace has no device plane: nothing to read, nothing reported
    assert "decode.hbm_roofline" not in m
    assert "device.idle_share.serve" not in m


@pytest.mark.parametrize("name", ["chatglm3-6b.chat", "chatglm3-6b.decode"])
def test_fp8_control_reads_above_the_program(monkeypatch, name):
    """With the fp8 reference's tokens compared in place of the served
    ones, ``correct`` comes out false."""
    out = run(monkeypatch, name, seconds=2.0, seed=17, control=True)
    assert not out["correct"], out["checks"]
    gap = out["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_fp8_train_control_reads_above_the_program(monkeypatch):
    """With the fp8 reference's three steps read in place of the
    program's, ``correct`` comes out false."""
    out = run(monkeypatch, "smollm-135m.train", seconds=0.5, seed=23,
              control=True)
    assert not out["correct"], out["checks"]
    diff = out["checks"]["grad_diff"]
    assert diff["value"] > diff["limit"]
