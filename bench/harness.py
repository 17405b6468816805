"""What every cell's run shares: finding a cell's files by name, the key
drawn from ``--seed``, host spans, the traced window and the checks that
decide ``correct``.  Nothing here imports JAX at module level."""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib.util
import json
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CACHE_DIR = ROOT / ".jax_cache"        # fixed: the path is part of the key
TRACE_DIR = BENCH_DIR / "_trace"       # written by --trace 1 runs


# ----------------------------------------------------------------- lookup
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def load_module(path: Path):
    """The module in ``path``, loaded once per process (so the jitted
    functions of a runner or reference keep their compiled programs)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One ``workloads`` entry with its configuration, traffic mix and
    metrics resolved by name."""

    name: str
    spec: dict
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/traffic/<traffic>.json
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: Path

    def runner(self):
        return load_module(self.bench_dir / "runners"
                           / f"{self.traffic['runner']}.py")

    def reader(self, metric: str) -> Callable:
        return load_module(self.bench_dir / "metrics" / f"{metric}.py").read

    def reference(self):
        return load_module(self.bench_dir / "reference"
                           / f"{self.config['reference']}.py")

    def counts(self):
        """The configuration's count module: ``bench/counts/<counts>.py``,
        the dense decoder's where the configuration names none."""
        return load_module(self.bench_dir / "counts"
                           / f"{self.config.get('counts', 'dense')}.py")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, benchmark: Optional[dict] = None,
              bench_dir: Path = BENCH_DIR) -> Cell:
    bm = benchmark if benchmark is not None else \
        load_json(bench_dir.parent / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bm["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; known: {sorted(by_name)}")
    spec = by_name[name]
    config = {c["name"]: c for c in bm["configs"]}[spec["config"]]
    return Cell(
        name=name, spec=spec,
        config=load_json(bench_dir.parent / config["file"]),
        traffic=load_json(bench_dir / "traffic" / f"{spec['traffic']}.json"),
        end_to_end=[m for m in bm["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bm["per_layer"] if _reports(m, name)],
        bench_dir=bench_dir)


# ------------------------------------------------------------------ seeds
def seed_key(seed: int):
    """A PRNG key holding all 64 bits of ``seed`` (``PRNGKey`` would drop
    the high word of seeds past 2**32 without x64)."""
    import jax
    import jax.numpy as jnp
    seed %= 2 ** 64
    return jax.random.wrap_key_data(
        jnp.array([seed >> 32, seed & 0xFFFFFFFF], jnp.uint32),
        impl="threefry2x32")


def seed_rng(seed: int, stream: int = 0):
    import numpy as np
    return np.random.default_rng([seed % 2 ** 64, stream])


# ------------------------------------------------------------------ spans
class Spans:
    """Host spans around the benchmark's calls into the program: kept in
    memory (name -> seconds) and written into the profiler's trace as
    ``TraceAnnotation``\\ s, so idle gaps can be attributed to them."""

    def __init__(self):
        self.seconds: Dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench." + name):
            yield
        self.seconds[name] = (self.seconds.get(name, 0.0)
                              + time.perf_counter() - t0)

    def reset(self) -> None:
        self.seconds.clear()


@contextlib.contextmanager
def compiles_counted():
    """Count the XLA compilations inside the block; a timed window should
    have none."""
    import jax
    count = [0]

    def listen(event, duration_secs, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield count
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


# ------------------------------------------------------------------ checks
@dataclasses.dataclass
class Check:
    """One number compared with its limit: passes when ``value <= limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


# ------------------------------------------------------------ run context
@dataclasses.dataclass
class Context:
    """What a runner gets: the cell, the run's arguments, the clock it
    started on and the means to trace its window."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float
    spans: Spans = dataclasses.field(default_factory=Spans)
    trace_dir: Path = TRACE_DIR
    arch_overrides: Dict[str, Any] = dataclasses.field(default_factory=dict)
    traffic_overrides: Dict[str, Any] = dataclasses.field(
        default_factory=dict)
    _traced: bool = False

    @property
    def traffic(self) -> dict:
        return {**self.cell.traffic, **self.traffic_overrides}

    def arch(self):
        from repro.configs.base import ArchConfig
        return ArchConfig(**{**self.cell.config["arch"],
                             **self.arch_overrides})

    @contextlib.contextmanager
    def traced(self):
        """Trace the enclosed part of the window (``--trace 1`` only)."""
        if not self.trace or self._traced:
            yield
            return
        import jax
        import shutil
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(self.trace_dir))
        try:
            with self.spans("window"):
                yield
        finally:
            jax.profiler.stop_trace()
            self._traced = True


@dataclasses.dataclass
class Outcome:
    """What a runner returns.  ``counters`` feed the metric readers; the
    harness adds ``setup_s`` from ``window_start``."""

    window_start: float                 # perf_counter at the first timed work
    counters: Dict[str, float]
    checks: List[Check]
    attempted: int
    failed: int
    memory_peak_bytes: Optional[int] = None
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return all(c.ok for c in self.checks) and self.failed == 0


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None
