"""The metrics that read the program's own ``unimem.*`` spans: their
reduction on synthetic spans (clipping to the window, union of nested
spans, idle put down to the front end outside its step loops), and their
readers on a real trace taken on the CPU."""

import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from bench.metrics import _program  # noqa: E402

NEW = ["fence_wait_ms.offload", "plan_ms.offload", "runtime_host_ms.serve",
       "frontend_idle.serve"]


def reader(name):
    return harness.load_module(ROOT / "bench" / "metrics" / f"{name}.py").read


def run_with(counters=None, traced=True):
    return types.SimpleNamespace(trace=object() if traced else None,
                                 counters=counters or {"iterations": 1})


@pytest.mark.parametrize("name", NEW)
def test_reader_loads_and_reads_nothing_without_a_trace(name):
    assert reader(name)(run_with(traced=False)) is None


def test_spans_are_clipped_to_the_window_and_nested_ones_merge():
    host = [(10, 110, "bench.window"),
            (0, 20, "unimem.fence_wait"),              # half before
            (30, 50, "unimem.fence_wait"),
            (35, 40, "unimem.chain_wait"),             # nested
            (45, 60, "unimem.chain_wait"),             # overlaps the end
            (100, 130, "unimem.phase_exit"),           # half after
            (200, 300, "unimem.fence_wait"),           # outside
            (20, 25, "bench.runtime")]                 # not the program's
    ps = _program.reduce(host, [])
    assert (ps.lo, ps.hi, ps.window_ns) == (10, 110, 100)
    assert ps.spans["fence_wait"] == [(10, 20), (30, 50)]
    assert ps.union("fence_wait", "chain_wait") == [(10, 20), (30, 60)]
    assert ps.union_ns("fence_wait", "chain_wait") == 10 + 30
    assert ps.union_ns("phase_exit") == 10
    assert ps.count("fence_wait") == 2 and ps.count("plan") == 0
    assert "runtime" not in ps.spans


def test_no_program_span_or_no_window_reads_as_none():
    assert _program.reduce([(0, 10, "bench.window")], []) is None
    assert _program.reduce([(0, 10, "unimem.plan")], []) is None
    assert _program.reduce([(0, 10, "bench.window"),
                            (20, 30, "unimem.plan")], []) is None


def test_subtract():
    assert _program.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22)]) == \
        [(0, 2), (4, 8), (22, 30)]
    assert _program.subtract([(0, 10)], []) == [(0, 10)]
    assert _program.subtract([(0, 10)], [(-5, 15)]) == []


def test_idle_is_put_down_to_the_front_end_outside_its_step_loops():
    host = [(0, 100, "bench.window"),
            (10, 90, "unimem.generate"),
            (12, 15, "unimem.init_cache"),
            (20, 50, "unimem.prefill"),
            (50, 80, "unimem.decode"),
            (80, 85, "unimem.output")]
    ops = [(14, 16, "fusion"),        # init_cache's work: not idle
           (30, 40, "dot"),           # inside prefill: never counted
           (60, 95, "dot")]           # runs past the generate span
    ps = _program.reduce(host, ops)
    # generate outside the loops: 10-20 and 80-90; busy 14-16 and 80-90
    assert _program.idle_ns(ps, "generate", ("prefill", "decode")) == 8
    # without a generate span there is nothing to put idle down to
    assert _program.idle_ns(ps, "no_such_span", ()) == 0


def test_readers_on_a_cpu_trace(tmp_path, monkeypatch):
    """A session and a serving engine run under the profiler inside a
    ``bench.window`` span; every new reader reads a number from the
    trace, and the offload readers stay within the window."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import ArchConfig
    from repro.core import RuntimeConfig, UnimemRuntime
    from repro.core.tiers import TPU_V5E
    from repro.models import lm
    from repro.serve.engine import ServeEngine

    MB = 1024 ** 2
    rt = UnimemRuntime(TPU_V5E, RuntimeConfig(
        fast_capacity_bytes=3 * MB // 2, backend="jax_async",
        enable_partitioning=False))
    rt.register("a", jnp.ones((256 * 1024,), jnp.float32))
    rt.register("b", jnp.ones((256 * 1024,), jnp.float32))
    cfg = ArchConfig(name="t", family="dense", n_layers=1, d_model=32,
                     n_heads=2, n_kv_heads=1, head_dim=16, d_ff=64,
                     vocab_size=64)
    engine = ServeEngine(cfg, lm.init_params(cfg, jax.random.PRNGKey(0)),
                         max_seq=32, batch=2, runtime=UnimemRuntime(
                             TPU_V5E, RuntimeConfig(backend="jax_async")))
    prompts = jnp.zeros((2, 4), jnp.int32)
    engine.generate(prompts, 3)
    sweeps = 4
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(sweeps):
                with rt.iteration():
                    with rt.phase("p0", accesses={"a": 1e6}, elapsed=0.05):
                        pass
                    with rt.phase("p1", accesses={"b": 1e6}, elapsed=0.05):
                        pass
            for _ in range(2):
                engine.generate(prompts, 3)
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    run = run_with({"iterations": sweeps})
    got = {name: reader(name)(run) for name in NEW}
    assert all(v is not None and v >= 0.0 for v in got.values()), got
    ps = _program.load(run)
    assert ps.count("generate") == 2
    window_ms_per_sweep = ps.window_ns / 1e6 / sweeps
    assert got["fence_wait_ms.offload"] + got["plan_ms.offload"] <= \
        window_ms_per_sweep
    assert got["plan_ms.offload"] > 0.0
    assert 0.0 <= got["frontend_idle.serve"] <= 100.0
