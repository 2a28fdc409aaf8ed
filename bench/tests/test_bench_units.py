"""CPU tests of the benchmark's own parts: the traffic generator, the
FLOP and byte counts, the trace reduction, the open-loop timing, and the
lookup of a cell's files by name."""
from __future__ import annotations

import json
import shutil
import statistics
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import common, flops, serve, trace, traffic  # noqa: E402

CHAT = {"kind": "open_loop", "rate_per_s": 2.0,
        "prompt": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                   "min": 32, "max": 1536},
        "output": {"dist": "uniform", "min": 16, "max": 48}}


# -- traffic -----------------------------------------------------------------


def test_every_seed_gets_the_same_schedule():
    a = traffic.plan_requests(CHAT, 30)
    b = traffic.plan_requests(CHAT, 30)
    assert [(r.prompt_len, r.output_len, r.due_s) for r in a] == \
        [(r.prompt_len, r.output_len, r.due_s) for r in b]
    win = [r for r in a if r.due_s < 30]
    assert len(win) == 60
    # another order of the same lengths
    c = traffic._plan(CHAT, traffic.SCHEDULE_SEED + 1, 60, 0)
    assert sorted(r.prompt_len for r in win) == \
        sorted(r.prompt_len for r in c)
    assert [r.prompt_len for r in win] != [r.prompt_len for r in c]


def test_the_seed_draws_the_tokens():
    assert np.array_equal(traffic.prompt_tokens(2**40 + 5, 3, 40, 100),
                          traffic.prompt_tokens(2**40 + 5, 3, 40, 100))
    assert not np.array_equal(traffic.prompt_tokens(1, 0, 40, 1000),
                              traffic.prompt_tokens(2, 0, 40, 1000))


def test_lengths_follow_their_distributions():
    ln = traffic.quantiles(CHAT["prompt"], 2001)
    assert statistics.median(ln) == 512
    assert ln.min() >= 32 and ln.max() == 1536
    # lognormal: one sigma above the median is 512 * e^0.8 = 1139.6
    assert abs(np.percentile(ln, 84.134) - 1139.6) < 2
    assert abs(np.percentile(ln, 15.866) - 512 / 2.2255) < 2
    un = traffic.quantiles({"dist": "uniform", "min": 16, "max": 48}, 3300)
    counts = np.bincount(un - 16)
    assert len(counts) == 33 and counts.min() == counts.max() == 100


def test_open_loop_arrivals():
    plan = traffic.plan_requests(CHAT, 30, drain_s=10)
    due = np.array([r.due_s for r in plan])
    assert np.all(np.diff(due) > 0)
    inside = due[due < 30]
    assert len(inside) == 60                   # rate x window
    gaps = np.diff(inside)
    assert abs(gaps.mean() - 0.5) < 0.05
    assert gaps.std() / gaps.mean() > 0.8      # exponential: cv near 1
    assert len(due) - len(inside) == 20        # rate x drain_s


# -- flops -------------------------------------------------------------------

M = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv": 1, "head_dim": 4,
     "d_ff": 16, "vocab": 10}


def test_flops_by_hand():
    # per layer: qkv 8*(2+2)*4=128, o 2*4*8=64, mlp 3*8*16=384 -> 576
    assert flops.matmul_params(M) == 2 * 576 + 8 * 10
    # attention: 4 * L * H * hd per key = 64 per key
    assert flops.attn_flops(M, 3) == 192
    # prefill of 3 tokens attends to 1 + 2 + 3 keys
    assert flops.prefill_flops(M, 3) == 2 * 1232 * 3 + 64 * 6
    # a chunk of positions 2..3 after 2 cached: 3 + 4 keys
    assert flops.prefill_flops(M, 4, start=2) == 2 * 1232 * 2 + 64 * 7
    assert flops.decode_flops(M, 5) == 2 * 1232 + 64 * 5
    assert flops.train_flops_per_token(M, 3) == 3 * (2 * 1232 + 64 * 2)
    assert flops.weight_bytes(M) == 2 * 1232
    assert flops.kv_bytes(M, 7) == 2 * 2 * 1 * 4 * 7 * 2


# -- trace -------------------------------------------------------------------


def _recorded(name: str):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(
        (Path(__file__).parent / "data" / name).read_text())


def test_trace_reduction_of_a_small_recorded_trace():
    """Overlap counted once, the last operation outside the window, gaps
    named by the host span over them.  These keys, which every per-layer
    reading rests on, hold their exact values; a trace with no programs
    and no collectives reads none."""
    r = trace.reduce(_recorded("small_trace.pbtxt"))
    assert r == {"busy_s": 4e-6, "window_s": 1e-5,
                 "device_ops": [["fusion.1", 3.5e-6],
                                ["_fused_add_rmsnorm_kernel", 1e-6]],
                 "idle_gaps": [["generator", 5e-6], ["engine.step", 1e-6]],
                 "n_ops": 3, "modules": {}, "collective_s": 0.0,
                 "exposed_collective_s": 0.0}


def test_collective_and_program_time_of_a_two_chip_trace():
    """By hand from the comment at the top of ``collective_trace.pbtxt``:
    chip 0 has 7 us of collectives (a synchronous all-reduce, and an async
    pair in flight from its start to its done), 3.5 us of it exposed;
    chip 1 has 3 us (an all-reduce wrapped in a fusion), 2 us exposed,
    under a loop that holds it and hides nothing.  Each is averaged over
    the two chips; a program the window's end cuts is left out."""
    r = trace.reduce(_recorded("collective_trace.pbtxt"))
    assert r["collective_s"] == pytest.approx(5e-6, abs=1e-15)
    assert r["exposed_collective_s"] == pytest.approx(2.75e-6, abs=1e-15)
    assert r["modules"] == {
        "jit_decode_t16": {"seconds": pytest.approx(8e-6), "count": 1.0},
        "jit_decode_t8": {"seconds": pytest.approx(2.5e-6), "count": 0.5}}
    assert r["busy_s"] == pytest.approx(10.5e-6)
    assert r["window_s"] == pytest.approx(20e-6)
    assert r["n_ops"] == 10


@pytest.mark.parametrize("hlo,kind", [
    ("%all-reduce.3 = f32[8]{0} all-reduce(%x), replica_groups={}",
     ("all-reduce", "")),
    ("%ar.1 = bf16[4,4096]{1,0} all-reduce-start(%y)",
     ("all-reduce", "start")),
    ("%ar.2 = bf16[4,4096]{1,0} all-reduce-done(%ar.1)",
     ("all-reduce", "done")),
    ("%all-reduce-fusion.2 = bf16[4]{0} fusion(%y), kind=kOutput",
     ("all-reduce", "")),
    ("%rs = bf16[2]{0} reduce-scatter(%z), dimensions={0}",
     ("reduce-scatter", "")),
    ("%ag.4 = bf16[8]{0} all-gather(%z), dimensions={0}", ("all-gather", "")),
    ("%a2a = bf16[8]{0} all-to-all(%z)", ("all-to-all", "")),
    ("%cp-start = (bf16[8]{0}, bf16[8]{0}) collective-permute-start(%z)",
     ("collective-permute", "start")),
    ("all-gather-done.7", ("all-gather", "done")),
    ("%fusion.3 = bf16[8,4096]{1,0:T(8,128)} fusion(%all-reduce.1)", None),
    ("%reduce.5 = f32[8]{0} reduce(%a, %b), dimensions={1}", None),
    ("_fused_add_rmsnorm_kernel", None)])
def test_collectives_are_told_by_opcode_or_wrapping_name(hlo, kind):
    assert trace.collective(hlo) == kind


def test_idle_share_reader():
    read = common.load_metric_reader("device.idle_share.serve")
    assert read({"trace": {"busy_s": 4.0, "window_s": 10.0,
                           "n_ops": 3}}) == pytest.approx(60.0)
    assert read({"trace": {"busy_s": 0.0, "window_s": 10.0,
                           "n_ops": 0}}) is None
    assert read({"trace": None}) is None


def test_decode_step_reader():
    """Device seconds of every decode tier's program over its runs; None
    where no decode program ran in the traced stretch."""
    read = common.load_metric_reader("device.decode_step_ms")
    mods = {"jit_decode_t16": {"seconds": 0.24, "count": 10.0},
            "jit_decode_t8": {"seconds": 0.04, "count": 2.0},
            "jit_prefill_b1_s1024": {"seconds": 0.5, "count": 1.0}}
    assert read({"trace": {"modules": mods}}) == pytest.approx(0.28 / 12 * 1e3)
    assert read({"trace": {"modules": {
        "jit_prefill_b1_s1024": mods["jit_prefill_b1_s1024"]}}}) is None
    assert read({"trace": {"modules": {}}}) is None
    assert read({"trace": None}) is None
    r = trace.reduce(_recorded("collective_trace.pbtxt"))
    assert read({"trace": r}) == pytest.approx(1e3 * 10.5e-6 / 1.5)


class _Tok:
    plan = traffic.Planned(0, 3, 4)
    tokens = [0.5, 1.5, 2.5, 3.5]     # output tokens 1..3 at 1.5, 2.5, 3.5


@pytest.mark.parametrize("count", [1, 4])
def test_decode_roofline_reads_the_device_busy_time(count):
    """Bytes the traced stretch's decode steps needed, over the device's
    busy time in it and the bandwidth of the cell's chips: the host's
    window does not enter."""
    read = common.load_metric_reader("decode.hbm_roofline")
    res = {"kind": "serve", "m": M, "t_trace": 1.0, "t_close": 3.0,
           "stats_trace": {"decode_steps": 10},
           "stats1": {"decode_steps": 12},
           "client": type("C", (), {"all": [_Tok()]}),
           "device": {"kind": "TPU v5 lite", "count": count},
           "trace": {"busy_s": 1e-6, "window_s": 2.0, "n_ops": 5}}
    need = 2 * flops.weight_bytes(M) + flops.kv_bytes(M, 4) \
        + flops.kv_bytes(M, 5)
    assert read(res) == pytest.approx(100 * need / 819e9 / 1e-6 / count)
    res["trace"] = {"busy_s": 0.0, "window_s": 2.0, "n_ops": 0}
    assert read(res) is None
    assert read(dict(res, trace=None)) is None


@pytest.mark.parametrize("name", ["serve.mfu", "train.mfu",
                                  "decode.hbm_roofline"])
def test_roofline_readers_divide_by_the_cells_chips(name):
    """Each share of a peak is over one chip's peak times the cell's
    chips: the same work on four chips reads a quarter, and one chip
    reads what the per-chip formula gives."""
    read = common.load_metric_reader(name)
    res = {"kind": "train" if name == "train.mfu" else "serve", "m": M,
           "mix": {"seq": 8}, "seconds": 4.0, "w0": 0.0,
           "numbers": {"train_tokens_per_s": 1e6},
           "t_trace": 1.0, "t_close": 3.0,
           "stats_trace": {"decode_steps": 10},
           "stats1": {"decode_steps": 12},
           "client": type("C", (), {"all": [_Tok()]}),
           "trace": {"busy_s": 1e-6, "window_s": 2.0, "n_ops": 5}}
    one, four = (read(dict(res, device={"kind": "TPU v5 lite",
                                        "count": n})) for n in (1, 4))
    assert one > 0 and four == pytest.approx(one / 4)
    if name == "train.mfu":
        assert one == 100.0 * flops.train_flops_per_token(M, 8) * 1e6 \
            / 197e12
    if name == "serve.mfu":
        work = flops.prefill_flops(M, 3) + sum(
            flops.decode_flops(M, 3 + j) for j in (1, 2, 3))
        assert one == pytest.approx(100.0 * work / 4.0 / 197e12)


# -- the open loop ------------------------------------------------------------


class _Req:
    def __init__(self, rid, n):
        self.rid, self.output, self.done_s, self.n = rid, [], 0.0, n

    @property
    def ok(self):
        return bool(self.done_s)


class SlowEngine:
    """Answers every request with one token per step; each step takes
    0.2 s, so requests due during a step are sent late."""

    def __init__(self):
        self.live = []

    def submit(self, req):
        self.live.append(req)

    def step(self):
        time.sleep(0.2)
        for r in list(self.live):
            r.output.append(1)
            if len(r.output) >= r.max_new_tokens:
                r.done_s = time.perf_counter()
                self.live.remove(r)


class _Tracer:
    span = staticmethod(lambda name: serve._no_span(name))

    def window_opened(self):
        pass

    def tick(self):
        pass


def test_open_loop_latency_counts_from_the_due_time(monkeypatch):
    import repro.serve as rs

    class Request(_Req):
        def __init__(self, rid, prompt, max_new_tokens):
            super().__init__(rid, len(prompt))
            self.prompt, self.max_new_tokens = prompt, max_new_tokens

    monkeypatch.setattr(rs, "Request", Request)
    mix = {"kind": "open_loop"}
    plan = [traffic.Planned(i, 4, 2, due_s=0.05 * i) for i in range(4)]
    d, w0, late = serve.run_window(SlowEngine(), mix, plan, 0, 0.3, 100,
                                   _Tracer(), lambda: None, drain_s=5)
    nums = serve.window_numbers(d, w0, 0.3, mix)
    # requests due at 0.05, 0.1 and 0.15 s wait for the first 0.2 s step
    assert max(late) > 0.04
    ttft = sorted(t.tokens[0] - (w0 + t.due) for t in d.all)
    assert ttft[-1] >= 0.2 + 0.2 - 0.15 - 0.01
    for t in d.all:
        assert t.tokens[0] - w0 >= t.due + 0.2 - 0.01
    assert nums["attempted"] == 4


def test_stagger_moves_every_row_once():
    ks = serve.stagger(16)
    assert sorted(set(ks)) == [6, 10, 14, 18, 22]
    assert ks[:8] == [6] * 8
    assert serve.stagger(1) == [6]


# -- cells resolve their files by name ---------------------------------------


def test_every_cell_resolves_its_files():
    bench = common.load_benchmark()
    names = [c["name"] for c in bench["configs"]]
    for cell in bench["workloads"]:
        assert cell["config"] in names
        cfg = common.load_config(bench, cell["config"])
        m = common.model_dims(cfg)
        assert m["d_model"] % m["n_heads"] == 0
        fam = common.family(m)
        fam.check_model(m)
        assert set(fam.shapes(m)) <= set(fam.PROGRAM_PATHS)
        assert set(fam.STACKED) | set(fam.SPLIT) <= set(fam.PROGRAM_PATHS)
        assert common.reference(m).logits
        assert common.load_traffic(cell["traffic"])["kind"]
        e2e = common.cell_metrics(bench, cell["name"], "end_to_end")
        assert "setup_s" in [x["name"] for x in e2e] and len(e2e) >= 2
        assert common.cell_metrics(bench, cell["name"], "per_layer")
    for spec in bench["per_layer"]:
        assert callable(common.load_metric_reader(spec["name"]))
        moved = next(x for x in bench["end_to_end"]
                     if x["name"] == spec["moves"])
        assert set(spec["workloads"]) <= set(
            moved.get("workloads", spec["workloads"]))


def test_a_cell_added_from_files_alone(tmp_path):
    """A new mix, configuration and metric are found by name, with no
    edit to any file that exists."""
    root = tmp_path / "repo"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    mix = dict(CHAT, kind="closed_loop", workers=2)
    (root / "bench" / "traffic" / "dummy.json").write_text(json.dumps(mix))
    cfg = json.loads((ROOT / "bench/configs/smollm-135m.json").read_text())
    cfg["name"] = "dummy-cfg"
    (root / "bench" / "configs" / "dummy-cfg.json").write_text(
        json.dumps(cfg))
    (root / "bench" / "metrics" / "dummy.metric.py").write_text(
        "def read(res):\n    return 42.0\n")
    bench["configs"].append(dict(bench["configs"][0], name="dummy-cfg",
                                 file="bench/configs/dummy-cfg.json"))
    bench["workloads"].append({"name": "dummy-cfg.dummy",
                               "config": "dummy-cfg", "traffic": "dummy",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "dummy.metric", "unit": "count",
                               "better": "lower",
                               "source": "program_counter",
                               "layer": "engine", "moves": "setup_s",
                               "workloads": ["dummy-cfg.dummy"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    b = common.load_benchmark(root)
    cell = common.find_cell(b, "dummy-cfg.dummy")
    cfg_path = root / next(c["file"] for c in b["configs"]
                           if c["name"] == cell["config"])
    assert json.loads(cfg_path.read_text())["name"] == "dummy-cfg"
    got = common.load_traffic(cell["traffic"], root / "bench")
    assert got["workers"] == 2
    per = common.cell_metrics(b, cell["name"], "per_layer")
    assert [p["name"] for p in per] == ["dummy.metric"]
    assert common.load_metric_reader("dummy.metric",
                                     root / "bench")({}) == 42.0
    for p, data in before.items():
        assert p.read_bytes() == data


def test_no_tpu_exits_nonzero_and_prints_nothing(capsys):
    from bench import run
    rc = run.main(["--workload", "smollm-135m.train", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc == 2
    assert capsys.readouterr().out == ""
