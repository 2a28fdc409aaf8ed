"""The per-layer metrics that read the engine's span counters and request
stamps, through a tiny serving cell on the CPU; and their readers on a
program that keeps neither, where they report nothing."""
from __future__ import annotations

import math
import time
from types import SimpleNamespace

import jax
from bench_tiny import cell

from bench import common, peaks
from bench import run as bench_run

READERS = ("engine.host_ms_per_step", "engine.queue_wait_p90_s",
           "engine.admit_to_token_p90_s")


def test_span_readers_on_a_tiny_chat_cell(monkeypatch):
    monkeypatch.setattr(common, "use_compile_cache", lambda: "none")
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    _, _, cfg, mix = cell("chatglm3-6b.chat")
    res = bench_run.measure(cfg, mix, 2**33 + 9, 3.0, False,
                            jax.devices()[:1], time.perf_counter())
    got = {n: common.load_metric_reader(n)(res) for n in READERS}
    assert all(v is not None and math.isfinite(v) and v >= 0
               for v in got.values()), got
    # the program stamps submitted_s at or after a request was due and
    # first_token_s before the harness stamps the token: its queue wait
    # and admission-to-token time fit inside the harness's TTFT
    w0, checked = res["w0"], 0
    for t in res["client"].all:
        if not t.tokens:
            continue
        r = t.req
        queue, service = r.admitted_s - r.submitted_s, \
            r.first_token_s - r.admitted_s
        assert queue >= 0 and service >= 0
        assert queue + service <= t.tokens[0] - (w0 + t.due)
        checked += 1
    assert checked >= 10
    a, b = res["stats0"]["spans"], res["stats1"]["spans"]
    iters = b["engine.iteration"]["count"] - a["engine.iteration"]["count"]
    assert iters > 0
    host_s = got["engine.host_ms_per_step"] * iters / 1e3
    assert host_s <= res["t_close"] - w0


def test_span_readers_report_nothing_without_spans_or_stamps():
    """A program that keeps no span counters and stamps no admission
    (the engine before them) gives the readers nothing to read."""
    req = SimpleNamespace(submitted_s=1.0, first_token_s=1.5)
    res = {"kind": "serve", "seconds": 10.0, "t_close": 11.0, "w0": 1.0,
           "stats0": {"decode_steps": 0}, "stats1": {"decode_steps": 5},
           "client": SimpleNamespace(all=[SimpleNamespace(
               due=0.0, req=req, tokens=[1.6])])}
    for name in READERS:
        assert common.load_metric_reader(name)(res) is None
        assert common.load_metric_reader(name)({"kind": "train"}) is None
