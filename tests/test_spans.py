"""The runtime's and the serving front end's ``unimem.*`` spans, read back
from the profiler's own trace, and the fence stall a blocking backend
measures."""

import glob
import os
import time

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

from repro.core import (PAPER_DRAM_NVM, AsyncJaxTierBackend, RuntimeConfig,
                        UnimemRuntime, calibrate)

MB = 1024 ** 2
MACHINE = PAPER_DRAM_NVM.scaled(bw_scale=0.5, lat_scale=2.0)
CF = calibrate(MACHINE)


def unimem_spans(trace_dir):
    """(name without the prefix, start_ns, end_ns, args) of every
    ``unimem.*`` host event in the newest trace under ``trace_dir``."""
    path = max(glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                      "*", "*.xplane.pb")),
               key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("unimem."):
                    out.append((ev.name[len("unimem."):], ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def inside(span, spans, name):
    """True when ``span`` lies within some span called ``name``."""
    return any(n == name and s <= span[1] and span[2] <= e
               for n, s, e, _ in spans)


def alternating_session(backend=None):
    """Two 1 MB objects under a 1.5 MB fast tier, read in turn: the plan
    moves them between the tiers every iteration, each fetch at phase p1
    chained after the eviction that frees its space."""
    rt = UnimemRuntime(MACHINE, RuntimeConfig(
        fast_capacity_bytes=3 * MB // 2, backend="jax_async",
        enable_partitioning=False), backend=backend, cf=CF)
    rt.register("a", jnp.ones((256 * 1024,), jnp.float32))
    rt.register("b", jnp.ones((256 * 1024,), jnp.float32))
    return rt


def sweep(rt, iterations=5):
    for _ in range(iterations):
        with rt.iteration():
            with rt.phase("p0", accesses={"a": 1e6}, elapsed=0.05):
                pass
            with rt.phase("p1", accesses={"b": 1e6}, elapsed=0.05):
                pass
            with rt.phase("p2", accesses={"a": 1e3, "b": 1e3}, elapsed=0.05):
                pass


def test_session_spans_appear_and_nest(tmp_path):
    rt = alternating_session()
    with jax.profiler.trace(str(tmp_path)):
        sweep(rt)
    spans = unimem_spans(tmp_path)
    names = {n for n, *_ in spans}
    assert names == {"phase_enter", "phase_exit", "iteration_exit", "plan",
                     "settle", "copy_start", "fence_wait", "chain_wait"}
    for sp in spans:
        name, args = sp[0], sp[3]
        if name == "fence_wait":
            # a phase's fence, or the enactment of a freshly built plan
            assert inside(sp, spans, "phase_enter") or \
                inside(sp, spans, "plan")
            assert args["obj"] in ("a", "b") and args["bytes"] == MB
        elif name == "plan":
            assert inside(sp, spans, "iteration_exit")
            assert args["reason"] == "profile"
        elif name in ("settle", "chain_wait"):
            assert inside(sp, spans, "phase_enter") or \
                inside(sp, spans, "plan")
        elif name == "copy_start":
            assert args["dst"] in ("fast", "slow") and args["bytes"] == MB
        elif name in ("phase_enter", "phase_exit"):
            assert args["phase"] in ("p0", "p1", "p2")
    assert any(n == "fence_wait" and inside((n, s, e), spans, "phase_enter")
               for n, s, e, _ in spans)
    assert rt.stats()["plan_s"] > 0.0


def test_serve_spans_do_not_grow_with_tokens(tmp_path):
    from repro.configs.base import ArchConfig
    from repro.models import lm
    from repro.serve.engine import ServeEngine
    cfg = ArchConfig(name="t", family="dense", n_layers=1, d_model=32,
                     n_heads=2, n_kv_heads=1, head_dim=16, d_ff=64,
                     vocab_size=64)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    rt = UnimemRuntime(MACHINE, RuntimeConfig(backend="jax_async"), cf=CF)
    engine = ServeEngine(cfg, params, max_seq=32, batch=2, runtime=rt)
    prompts = jnp.zeros((2, 4), jnp.int32)
    engine.generate(prompts, 2)                     # compile outside
    counts = []
    for n_new in (2, 9):
        trace = tmp_path / str(n_new)
        with jax.profiler.trace(str(trace)):
            out = engine.generate(prompts, n_new)
        assert out.shape == (2, 4 + n_new)
        spans = unimem_spans(trace)
        front = [n for n, *_ in spans if n in ("generate", "init_cache",
                                               "prefill", "decode", "output")]
        assert sorted(front) == ["decode", "generate", "init_cache",
                                 "output", "prefill"]
        for sp in spans:
            if sp[0] != "generate":
                assert inside(sp, spans, "generate")
        gen = next(sp for sp in spans if sp[0] == "generate")
        assert gen[3] == {"prompt": 4, "new_tokens": n_new}
        counts.append(len(spans))
    assert counts[0] == counts[1]                   # no span per token


class SlowLeaf:
    """A leaf whose copy lands only after ``block_until_ready`` has slept
    ``seconds``."""

    nbytes = 0

    def __init__(self, seconds):
        self.seconds, self.ready = seconds, False

    def is_ready(self):
        return self.ready

    def block_until_ready(self):
        if not self.ready:
            time.sleep(self.seconds)
            self.ready = True
        return self


class SlowCopies(AsyncJaxTierBackend):
    """Every copy to ``dst`` also waits on a 20 ms leaf; ``chained``
    counts the copies started after such a copy had not yet landed."""

    def __init__(self, machine, dst):
        super().__init__(machine)
        self.dst, self.slowed, self.chained = dst, 0, 0

    def start_move(self, obj, dst, after=None):
        if after is not None and not after.landed and any(
                isinstance(l, SlowLeaf) for l in after.leaves):
            self.chained += 1
        h = super().start_move(obj, dst, after=after)
        if h is not None and dst == self.dst:
            h.leaves = [SlowLeaf(0.02)] + h.leaves
            self.slowed += 1
        return h


def test_blocking_fence_stall_is_measured():
    """A fetch the phase fences on blocks the host 20 ms: the fence stall
    counts it, and no such fetch counts as overlapped."""
    backend = SlowCopies(MACHINE, "fast")
    rt = alternating_session(backend)
    sweep(rt)
    assert backend.slowed >= 2
    assert rt.stats()["fence_stall_s"] >= 0.02 * backend.slowed
    mv = rt.mover.stats
    assert mv.overlapped_moves <= mv.n_moves - backend.slowed


def test_chained_eviction_wait_is_counted():
    """A fetch chained after a 20 ms eviction blocks the host in
    ``start_move``: the wait is counted in the fence stall, and the
    eviction it waited on does not count as overlapped."""
    backend = SlowCopies(MACHINE, "slow")
    rt = alternating_session(backend)
    sweep(rt)
    assert backend.chained >= 2
    assert backend.chain_wait_s >= 0.02 * backend.chained
    assert rt.stats()["fence_stall_s"] >= backend.chain_wait_s
    mv = rt.mover.stats
    assert mv.overlapped_moves <= mv.n_moves - backend.chained
