"""The traced run: host spans, the profiler's trace of the last stretch
of the window, and its reduction to device busy time, top operations,
idle gaps named by what the host was doing, device time per program and
collective time.

The harness's own spans (``generator``, ``engine.step``, ``train.step``,
``stats``) are ``jax.profiler.TraceAnnotation``s, so they land in the
same trace as the device's operations.  A ``window`` span marks the
traced stretch: busy time, top operations and gaps are read inside it.
"""
from __future__ import annotations

import contextlib
import glob
import re
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


def _parts(hlo: str):
    """(instruction, output shape without layout, opcode) of an
    operation's HLO text, or None where the name is not HLO text."""
    name, _, rest = hlo.partition(" = ")
    if not rest:
        return None
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
    return name.lstrip("%"), shape, op


def short_name(hlo: str) -> str:
    """``%fusion.3 = bf16[8,4096]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.3 bf16[8,4096] fusion``: the instruction, its output shape
    without layout (``tuple`` for several) and its opcode."""
    parts = _parts(hlo)
    if parts is None:
        return hlo[:120]
    return " ".join(parts)[:120]


COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
               "collective-permute")


def collective(hlo: str):
    """(kind, phase) of a collective operation, phase ``start``, ``done``
    or ``""`` (synchronous), or None for any other operation.  Read from
    the opcode, and from the instruction's name where XLA wraps a
    collective in a fusion (``all-reduce-fusion.2``)."""
    parts = _parts(hlo)
    name, op = (parts[0], parts[2]) if parts else (hlo, "")
    base = re.sub(r"\.\d+$", "", name)
    for kind in COLLECTIVES:
        for word in (op, base):
            if kind in word:
                phase = word.rsplit("-", 1)[-1]
                return kind, phase if phase in ("start", "done") else ""
    return None


def _operand(hlo: str) -> str:
    """The first operand of an operation's HLO text (``%name``, with or
    without its shape before it), without ``%``; "" where none is named."""
    parts = _parts(hlo)
    got = parts and re.search(r"%([\w.\-]+)",
                              hlo.partition(f" {parts[2]}(")[2])
    return got.group(1) if got else ""


def collective_intervals(inside) -> tuple:
    """(collective intervals, other operations' intervals) of one plane's
    operations (name, start, end).  An async ``-start`` and the ``-done``
    that waits for it count as one interval from the start's beginning to
    the done's end: the collective is in flight all that time.  A done
    pairs with the start it names, else with the earliest open start of
    its kind; one whose partner lies outside the window counts alone.
    Loops and calls, which hold other operations, count as neither."""
    coll, other, open_ = [], [], {}
    for n, a, b in sorted(inside, key=lambda e: e[1]):
        c = collective(n)
        if c is None:
            if not short_name(n).split(" ")[-1].startswith(CONTAINERS):
                other.append((a, b))
        elif c[1] == "start":
            parts = _parts(n)
            open_[(parts[0] if parts else n)] = (c[0], a, b)
        elif c[1] == "done":
            key = _operand(n)
            if key not in open_:
                key = next((k for k, v in open_.items() if v[0] == c[0]),
                           None)
            start = open_.pop(key, None) if key is not None else None
            coll.append((start[1] if start else a, b))
        else:
            coll.append((a, b))
    coll.extend((a, b) for _, a, b in open_.values())
    return coll, other


def op_events(plane, line_name: str = "XLA Ops") -> list:
    """(name, start_ns, end_ns) of the events on a device plane's line:
    the operations (``XLA Ops``) or the programs (``XLA Modules``)."""
    out = []
    for line in plane.lines:
        if line.name == line_name:
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


def module_key(name: str) -> str:
    """A program's name without the id XLA appends
    (``jit_decode_t16(42)``, ``jit_decode_t16.3`` -> ``jit_decode_t16``)."""
    return re.sub(r"(\(\d+\)|\.\d+)$", "", name)


def reduce(pd) -> dict:
    """Busy and window seconds, top operations and the longest idle gaps,
    each named by the host span that covers most of it; per device
    program, the seconds and count of its executions that began and ended
    in the window (``modules``); the union of the collectives' intervals
    (``collective_s``) and the part of it in which no other operation ran
    (``exposed_collective_s``).  Device times and counts are averaged
    over the device planes that ran anything."""
    spans = host_spans(pd)
    wins = [(a, b) for n, a, b in spans if n == "window"]
    planes = [(p, op_events(p)) for p in device_planes(pd)]
    planes = [(p, ev) for p, ev in planes if ev]
    if not wins or not planes:
        return {"busy_s": 0.0, "window_s": 0.0, "device_ops": [],
                "idle_gaps": [], "n_ops": 0, "modules": {},
                "collective_s": 0.0, "exposed_collective_s": 0.0}
    lo, hi = wins[0]
    busy, totals, gaps = [], {}, []
    n_ops = 0
    modules, coll_s, exposed_s = {}, 0, 0
    for p, ev in planes:
        inside = [(n, max(a, lo), min(b, hi)) for n, a, b in ev
                  if b > lo and a < hi]
        n_ops += len(inside)
        busy.append(_union((a, b) for _, a, b in inside))
        for n, a, b in inside:
            short = short_name(n)
            if not short.split(" ")[-1].startswith(CONTAINERS):
                totals[short] = totals.get(short, 0) + (b - a)
        gaps.extend(_gaps([(a, b) for _, a, b in inside], lo, hi))
        coll, other = collective_intervals(inside)
        coll_s += _union(coll)
        exposed_s += _union(coll + other) - _union(other)
        for n, a, b in op_events(p, "XLA Modules"):
            if lo <= a and b <= hi:
                mod = modules.setdefault(module_key(n),
                                         {"seconds": 0, "count": 0})
                mod["seconds"] += b - a
                mod["count"] += 1
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
            "idle_gaps": named, "n_ops": n_ops,
            "modules": {n: {"seconds": v["seconds"] / k / 1e9,
                            "count": v["count"] / k}
                        for n, v in sorted(modules.items())},
            "collective_s": coll_s / k / 1e9,
            "exposed_collective_s": exposed_s / k / 1e9}
