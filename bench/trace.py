"""The traced run: host spans, the profiler's trace of the last stretch
of the window, and its reduction to device busy time, top operations and
idle gaps named by what the host was doing.

The harness's own spans (``generator``, ``engine.step``, ``train.step``,
``stats``) are ``jax.profiler.TraceAnnotation``s, so they land in the
same trace as the device's operations.  A ``window`` span marks the
traced stretch: busy time, top operations and gaps are read inside it.
"""
from __future__ import annotations

import contextlib
import glob
import shutil
import tempfile
import time

HOST_SPANS = ("generator", "engine.step", "train.step", "stats")
TRACE_S = 5.0         # the traced stretch: the window's last seconds


class Tracer:
    """When enabled, traces the last ``TRACE_S`` of a window of
    ``seconds``: the trace starts at the first ``tick()`` after
    ``seconds - TRACE_S``, then calls ``on_start()``, and ends at
    ``stop()``, when the window closes, so that collecting the trace
    costs the window nothing.  Python function tracing stays off.
    Disabled, every method is a no-op."""

    def __init__(self, enabled: bool, seconds: float = 0.0):
        self.enabled = enabled
        self.seconds = seconds
        self.on_start = lambda: None
        self.active = False
        self.dir = None
        self._win = None
        self.t_open = 0.0

    def span(self, name: str):
        if self.active:
            import jax
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def window_opened(self):
        self.t_open = time.perf_counter()
        self.tick()

    def tick(self):
        if (not self.enabled or self.dir is not None
                or time.perf_counter() - self.t_open
                < self.seconds - TRACE_S):
            return
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._win = jax.profiler.TraceAnnotation("window")
        self._win.__enter__()
        self.active = True
        self.on_start()

    def stop(self):
        if not self.active:
            return
        import jax
        self._win.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False

    def reduce(self) -> dict | None:
        """The trace's reduction (see :func:`reduce`), then the trace is
        deleted."""
        if self.dir is None:
            return None
        try:
            files = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)
            if not files:
                return None
            from jax.profiler import ProfileData
            return reduce(ProfileData.from_file(files[0]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _union(intervals) -> float:
    total, end = 0.0, None
    start = None
    for a, b in sorted(intervals):
        if end is None or a > end:
            if end is not None:
                total += end - start
            start, end = a, b
        else:
            end = max(end, b)
    if end is not None:
        total += end - start
    return total


def _gaps(intervals, lo, hi):
    """Idle stretches (start, end) between the busy intervals."""
    out, cur = [], lo
    for a, b in sorted(intervals):
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        out.append((cur, hi))
    return out


def device_planes(pd):
    return [p for p in pd.planes if p.name.startswith("/device:")
            and "CPU" not in p.name]


CONTAINERS = ("while", "conditional", "call")


def short_name(hlo: str) -> str:
    """``%fusion.3 = bf16[8,4096]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.3 bf16[8,4096] fusion``: the instruction, its output shape
    without layout (``tuple`` for several) and its opcode."""
    name, _, rest = hlo.partition(" = ")
    if not rest:
        return hlo[:120]
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        shape, rest = "tuple", rest[i + 1:]
    else:
        head = rest.split(" ", 1)
        shape = head[0].split("{", 1)[0]
        rest = head[1] if len(head) > 1 else ""
    op = rest.strip().split("(", 1)[0].strip()
    return f"{name.lstrip('%')} {shape} {op}"[:120]


def op_events(plane) -> list:
    """(name, start_ns, end_ns) of the operations on a device plane: the
    line named ``XLA Ops``."""
    out = []
    for line in plane.lines:
        if line.name == "XLA Ops":
            for e in line.events:
                out.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return out


def host_spans(pd) -> list:
    out = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for e in line.events:
                if e.name in HOST_SPANS or e.name == "window":
                    out.append((e.name, e.start_ns, e.start_ns
                                + e.duration_ns))
    return out


def reduce(pd) -> dict:
    """Busy and window seconds, top operations and the longest idle gaps,
    each named by the host span that covers most of it.  Device times are
    averaged over the device planes that ran anything."""
    spans = host_spans(pd)
    wins = [(a, b) for n, a, b in spans if n == "window"]
    planes = [(p, op_events(p)) for p in device_planes(pd)]
    planes = [(p, ev) for p, ev in planes if ev]
    if not wins or not planes:
        return {"busy_s": 0.0, "window_s": 0.0, "device_ops": [],
                "idle_gaps": [], "n_ops": 0}
    lo, hi = wins[0]
    busy, totals, gaps = [], {}, []
    n_ops = 0
    for _, ev in planes:
        inside = [(n, max(a, lo), min(b, hi)) for n, a, b in ev
                  if b > lo and a < hi]
        n_ops += len(inside)
        busy.append(_union((a, b) for _, a, b in inside))
        for n, a, b in inside:
            short = short_name(n)
            if not short.split(" ")[-1].startswith(CONTAINERS):
                totals[short] = totals.get(short, 0) + (b - a)
        gaps.extend(_gaps([(a, b) for _, a, b in inside], lo, hi))
    k = len(planes)
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        cover = {}
        for n, sa, sb in spans:
            if n != "window":
                ov = min(b, sb) - max(a, sa)
                if ov > 0:
                    cover[n] = cover.get(n, 0) + ov
        name = max(cover, key=cover.get) if cover else "none"
        named.append([name, (b - a) / 1e9])
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    return {"busy_s": sum(busy) / k / 1e9, "window_s": (hi - lo) / 1e9,
            "device_ops": [[n, s / k / 1e9] for n, s in top],
            "idle_gaps": named, "n_ops": n_ops}
