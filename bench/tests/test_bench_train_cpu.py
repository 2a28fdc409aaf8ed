"""The training cell end to end on the CPU at a tiny size: the program's
first three steps agree with the plain reference, and each fault the
cell can have makes ``correct`` false."""
from __future__ import annotations

import dataclasses

import pytest
from bench_tiny import run


def test_train_cell_is_correct(monkeypatch):
    out = run(monkeypatch, "smollm-135m.train", seconds=2.0)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0


def test_train_traced_run(monkeypatch):
    out = run(monkeypatch, "smollm-135m.train", seconds=1.0, trace=True)
    assert out["correct"], out["checks"]
    assert 0 < out["metrics"]["train.mfu"]["value"] < 100


def _wrap_step(monkeypatch, wrap):
    from repro import api
    build = api.Program.train_step

    def train_step(self, *a, **kw):
        step = build(self, *a, **kw)
        return dataclasses.replace(step, fn=wrap(step.fn))

    monkeypatch.setattr(api.Program, "train_step", train_step)


def _unchanged(fn):
    def step(params, opt, batch, i):
        _, _, met = fn(params, opt, batch, i)
        return params, opt, met
    return step


def _half_batch(fn):
    def step(params, opt, batch, i):
        lab = batch["labels"]
        lab = lab.at[lab.shape[0] // 2:].set(-100)
        return fn(params, opt, dict(batch, labels=lab), i)
    return step


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_faults_are_caught(monkeypatch, fault):
    _wrap_step(monkeypatch, fault)
    out = run(monkeypatch, "smollm-135m.train", seconds=0.5)
    assert not out["correct"], out["checks"]
