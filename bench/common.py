"""What every cell shares: finding its files by name, seeds, percentiles,
compile counting and device facts.

The harness is driven by data.  ``BENCHMARK.json`` names a cell's
configuration and traffic mix; their files are
``bench/configs/<config>.json`` and ``bench/traffic/<traffic>.json``, and
each per-layer metric is ``bench/metrics/<metric>.py``.  A configuration
names its model family (``"family"``, ``dense`` where absent):
``bench/families/<family>.py``, whose plain reference is
``bench/reference/<REFERENCE>.py``.  Each loader looks in ``bench_dir``,
which defaults to :data:`BENCH`.
"""
from __future__ import annotations

import importlib.util
import json
import math
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[c['name'] for c in bench['workloads']]}")


def config_file(bench: dict, name: str) -> Path:
    for cfg in bench["configs"]:
        if cfg["name"] == name:
            return ROOT / cfg["file"]
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def load_config(bench: dict, name: str) -> dict:
    return json.loads(config_file(bench, name).read_text())


def load_traffic(name: str, bench_dir: Path | None = None) -> dict:
    path = (bench_dir or BENCH) / "traffic" / f"{name}.json"
    return json.loads(path.read_text())


_MODULES: dict = {}


def _load(kind: str, name: str, bench_dir: Path | None):
    """``bench/<kind>/<name>.py`` as a module, loaded once a process."""
    key = (bench_dir or BENCH, kind, name)
    if key not in _MODULES:
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"),
            key[0] / kind / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[key] = mod
    return _MODULES[key]


def load_metric_reader(name: str, bench_dir: Path | None = None):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    return _load("metrics", name, bench_dir).read


def load_family(name: str, bench_dir: Path | None = None):
    """The module ``bench/families/<name>.py``."""
    return _load("families", name, bench_dir)


def family(m: dict):
    """The family module of a model dict (:func:`model_dims`)."""
    return load_family(m.get("family", "dense"))


def reference(m: dict):
    """The plain reference module of a model dict's family."""
    return _load("reference", family(m).REFERENCE, None)


def cell_metrics(bench: dict, cell: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries a cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def model_dims(cfg: dict) -> dict:
    """The harness's names for a configuration's sizes, read through the
    file's ``keys`` map from the published names."""
    m = {ours: cfg[theirs] for ours, theirs in cfg["keys"].items()}
    m.setdefault("head_dim", m["d_model"] // m["n_heads"])
    m["family"] = cfg.get("family", "dense")
    return m


def cell_mesh(devices):
    """A ``("data", "model")`` mesh of shape (1, chips) over exactly the
    cell's devices, or None for one chip."""
    if len(devices) == 1:
        return None
    import jax
    return jax.make_mesh((1, len(devices)), ("data", "model"),
                         devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def seed_key(seed: int):
    """A JAX key for any whole number up to 2**64: low and high words."""
    import jax
    if seed < 0:
        raise ValueError(f"seed must be >= 0: {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed >> 32)


def quantile(values, q: float) -> float:
    """The ``q`` quantile (0..1) by linear interpolation; ``inf`` counts
    as a value above every other."""
    v = sorted(values)
    if not v:
        return math.nan
    pos = q * (len(v) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(v) - 1)
    if v[hi] == math.inf:
        return math.inf if pos > lo or v[lo] == math.inf else v[lo]
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


class CompileClock:
    """XLA compiles and persistent-cache hits, as JAX's monitoring events
    report them.  ``mark()`` returns counts to subtract later."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self) -> tuple:
        return (self.count, self.cache_hits, self.seconds)


def peak_bytes(devices) -> int | None:
    """``peak_bytes_in_use`` of the fullest device, or None where the
    backend does not report it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    if any(p is None for p in peaks):
        return None
    return max(peaks)


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR`` or, where that is unset, at the fixed
    directory ``<checkout>/.jax_cache``; cache every program."""
    import os

    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
