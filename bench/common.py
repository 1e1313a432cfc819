"""What every job of the benchmark shares: finding a cell's files by name,
the seed, the device, the compile cache, and counting compilations.

Nothing here imports JAX at module level, so a test can load it without
touching an accelerator's library.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import pathlib
import sys
import time
from typing import Any, Callable, Dict, Iterator, List, Optional

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"
# One fixed directory inside the checkout: the path is part of the cache key.
CACHE_DIR = BENCH / ".cache" / "jax"


def load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def load_module(path: pathlib.Path, name: Optional[str] = None):
    """Import a file of the benchmark by its path (metric files carry dots
    in their names, so they are not importable as modules). The module is
    entered in ``sys.modules`` under ``name``, as dataclasses need."""
    name = name or "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def named_module(config: dict, key: str, bench_dir: pathlib.Path = BENCH):
    """The module that a configuration file names under ``key``:
    ``<bench_dir>/<key>/<config[key]>.py`` ("reference": the model's plain
    reference; "work": its count of operations and bytes)."""
    if key not in config:
        raise KeyError(f"the configuration has no {key!r} key: it names the "
                       f"file bench/{key}/<name>.py that knows its model")
    path = bench_dir / key / f"{config[key]}.py"
    if not path.is_file():
        raise FileNotFoundError(f"the configuration's {key!r} key names "
                                f"{config[key]!r}, but {path} is not there")
    return load_module(path, f"bench_{key}_{path.stem.replace('-', '_')}")


class Cell:
    """One workload of BENCHMARK.json with the files its names point to:
    ``configs/<config>.json``, ``traffic/<traffic>.json`` and
    ``limits/<cell>.json``; the configuration names its reference and work
    modules (``reference_of``, ``work_of``)."""

    def __init__(self, name: str, bench: Optional[dict] = None,
                 bench_dir: pathlib.Path = BENCH):
        bench = bench if bench is not None else benchmark(bench_dir.parent)
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.bench_dir = bench_dir
        self.entry = entry
        self.bench = bench
        self.config_entry = next(c for c in bench["configs"]
                                 if c["name"] == entry["config"])
        self.config = load_json(bench_dir.parent / self.config_entry["file"])
        self.traffic = load_json(bench_dir / "traffic"
                                 / f"{entry['traffic']}.json")
        self.limits = load_json(bench_dir / "limits" / f"{name}.json")
        self.job = self.traffic["job"]
        self.chips = int(entry["chips"])

    @classmethod
    def from_parts(cls, name: str, bench: dict, config: dict, traffic: dict,
                   limits: dict, bench_dir: pathlib.Path = BENCH) -> "Cell":
        """A cell from given contents (tests build small ones)."""
        cell = cls.__new__(cls)
        cell.name, cell.bench, cell.bench_dir = name, bench, bench_dir
        cell.entry = next(w for w in bench["workloads"] if w["name"] == name)
        cell.config_entry = next(c for c in bench["configs"]
                                 if c["name"] == cell.entry["config"])
        cell.config, cell.traffic, cell.limits = config, traffic, limits
        cell.job = traffic["job"]
        cell.chips = int(cell.entry["chips"])
        return cell

    def end_to_end(self) -> List[dict]:
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> List[dict]:
        """Per-layer metrics read in this cell: those that list it."""
        return [m for m in self.bench["per_layer"]
                if self.name in m["workloads"]]


def reference_of(cell: Cell):
    """The cell's reference module (see ``bench/reference/spt.py``)."""
    return named_module(cell.config, "reference", cell.bench_dir)


def work_of(cell: Cell):
    """The cell's work module (see ``bench/work/spt.py``)."""
    return named_module(cell.config, "work", cell.bench_dir)


def seed_words(seed: int) -> tuple:
    """A seed of up to 64 bits as two 32-bit words (JAX's PRNGKey keeps
    only the low word of a larger integer)."""
    seed = int(seed) % (1 << 64)
    return seed >> 32, seed & 0xFFFFFFFF


def prng_key(seed: int):
    """The seed's key: PRNGKey of its low word, the high word folded in.
    Jitted code takes it as an argument, so that one compiled program
    serves every seed."""
    import jax
    hi, lo = seed_words(seed)
    return fold_high(jax.random.PRNGKey(lo), hi)


def fold_high(key, hi: int):
    import jax
    import numpy as np
    return jax.random.fold_in(key, np.uint32(hi))


def use_compile_cache() -> Optional[str]:
    """JAX's persistent compilation cache at the benchmark's fixed path;
    every program is kept, however small or quick to compile. Off the
    accelerator (the CPU tests) nothing is cached."""
    import jax
    if jax.default_backend() == "cpu":
        return None
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)  # no eviction
    return str(CACHE_DIR)


def device_info(devices) -> Dict[str, Any]:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts backend compilations while ``counting`` is on (a persistent
    cache hit is not a compilation)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        self.counting = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.counting and event == self.EVENT:
            self.count += 1

    @contextlib.contextmanager
    def window(self) -> Iterator[None]:
        self.counting = True
        try:
            yield
        finally:
            self.counting = False


def span(name: str):
    """A host span in the profiler's trace (a no-op when nothing traces)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def note(msg: str, t0: float) -> None:
    print(f"bench [{time.perf_counter() - t0:7.1f} s] {msg}",
          file=sys.stderr, flush=True)


def add_program_path() -> None:
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def relative_gap(got: float, want: float, floor: float) -> float:
    """|got - want| measured against max(|want|, floor)."""
    return abs(got - want) / max(abs(want), floor)


def worst_leaf_gap(got: Dict[str, float], want: Dict[str, float],
                   keep: Callable[[str], bool]) -> tuple:
    """Worst leaf of |got - want| / max(want, median of want), over the
    leaves ``keep`` admits. Returns (gap, leaf name)."""
    import statistics
    names = [k for k in want if keep(k)]
    med = statistics.median(want[k] for k in names)
    worst = max(names, key=lambda k: relative_gap(got[k], want[k], med))
    return relative_gap(got[worst], want[worst], med), worst


def median_leaf_gap(got: Dict[str, float], want: Dict[str, float],
                    keep: Callable[[str], bool]) -> float:
    """The median over the leaves ``keep`` admits of the same gap."""
    import statistics
    names = [k for k in want if keep(k)]
    med = statistics.median(want[k] for k in names)
    return statistics.median(relative_gap(got[k], want[k], med)
                             for k in names)
