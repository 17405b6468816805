"""Runtime API v2: old-vs-new parity, session-context properties, backend
registry, and the registration/re-entry bug fixes.

The compatibility shims on ``UnimemRuntime`` must be *exactly* the old API:
a driver hand-rolling the Table-2 choreography (alloc / start_loop /
begin_iteration / phase_begin / phase_end / end_iteration) and a v2 driver
(register / ``with rt.iteration()`` / ``with rt.phase(name)`` with the
simulator's SimSource) must produce bit-identical placement plans and
identical steady-state virtual-time numbers on the committed scenario
matrix.
"""

import warnings

import pytest

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:                      # no hypothesis: seeded shim
    from _propcheck import st, given, settings

from repro.core import (PAPER_DRAM_NVM, AsyncJaxTierBackend,
                        ChannelSimBackend, CpuPoolBackend, JaxTierBackend,
                        ManualSource, RuntimeConfig, Session, SimTierBackend,
                        UnimemRuntime, available_backends, calibrate,
                        make_backend, register_backend)
from repro.core.data_objects import ObjectRegistry
from repro.sim import (NPB_WORKLOADS, SCENARIO_WORKLOADS,
                       SKEWED_SCENARIO_WORKLOADS, SimSource,
                       SimulationEngine)

MB = 1024 ** 2
MACHINE = PAPER_DRAM_NVM.scaled(bw_scale=0.5, lat_scale=2.0)
CF = calibrate(MACHINE)

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

#: parity coverage: one per scenario family + an NPB trace with chunking
PARITY_WORKLOADS = {
    "kv_serving": SCENARIO_WORKLOADS["kv_serving"],
    "moe_churn": SCENARIO_WORKLOADS["moe_churn"],
    "graph_chase": SCENARIO_WORKLOADS["graph_chase"],
    "graph_chase_skew": SKEWED_SCENARIO_WORKLOADS["graph_chase_skew"],
    "paged_serving": SKEWED_SCENARIO_WORKLOADS["paged_serving"],
    "cg": NPB_WORKLOADS["cg"],
}


def _config(mover: str = "slack") -> RuntimeConfig:
    return RuntimeConfig(fast_capacity_bytes=256 * MB, mover=mover,
                         drift_threshold=10.0)


def run_new_style(wl, *, iters: int = 8, mover: str = "slack"):
    """v2 driver: register + engine-driven iteration()/phase() contexts."""
    rt = UnimemRuntime(MACHINE, _config(mover), cf=CF)
    statics = wl.static_ref_counts()
    for n, s in wl.objects.items():
        rt.register(n, s, chunkable=wl.chunkable.get(n, False),
                    static_refs=statics.get(n))
    res = SimulationEngine(MACHINE, wl, runtime=rt).run(iters)
    return rt, res.iteration_times


def run_old_style(wl, *, iters: int = 8, mover: str = "slack"):
    """Pre-v2 driver: the Table-2 imperative choreography, hand-rolled the
    way sim/engine.py drove it before the session API existed."""
    cfg = _config(mover)
    rt = UnimemRuntime(MACHINE, cfg, cf=CF)
    for n, s in wl.objects.items():
        rt.alloc(n, size_bytes=s, chunkable=wl.chunkable.get(n, False))
    rt.start_loop([p.name for p in wl.phases],
                  static_refs=wl.static_ref_counts())
    clock = {"t": 0.0}
    backend = make_backend("sim", MACHINE, now_fn=lambda: clock["t"],
                           mover=cfg.mover, channels=cfg.copy_channels)
    rt.backend = backend
    rt.mover.backend = backend
    src = SimSource(MACHINE, wl, rt.registry)
    iter_times = []
    for _ in range(iters):
        rt.begin_iteration()
        t_iter = 0.0
        for i, ph in enumerate(wl.phases):
            stall = rt.phase_begin(i)
            s = src.collect(ph.name)
            clock["t"] += stall + s.elapsed
            t_iter += stall + s.elapsed
            rt.phase_end(i, elapsed=s.elapsed, accesses=s.accesses,
                         time_shares=s.time_shares,
                         access_bins=s.access_bins)
        rt.end_iteration()
        iter_times.append(t_iter)
    return rt, iter_times


@pytest.mark.parametrize("wl_name", sorted(PARITY_WORKLOADS))
def test_old_and_new_drivers_bit_identical(wl_name):
    """Acceptance: bit-identical plans and identical steady-state numbers
    from the deprecated imperative driver and the v2 session driver."""
    old_rt, old_times = run_old_style(PARITY_WORKLOADS[wl_name]())
    new_rt, new_times = run_new_style(PARITY_WORKLOADS[wl_name]())
    assert old_rt.plan is not None and new_rt.plan is not None
    assert old_rt.plan.moves == new_rt.plan.moves
    assert old_rt.plan.residents == new_rt.plan.residents
    assert (old_rt.plan.predicted_iteration_time
            == new_rt.plan.predicted_iteration_time)
    assert old_rt.plan.strategy == new_rt.plan.strategy
    assert old_times == new_times           # every virtual-time iteration
    # same final tier state, object by object (incl. discovered chunks)
    assert {o.name: o.tier for o in old_rt.registry} \
        == {o.name: o.tier for o in new_rt.registry}


def test_fifo_mover_parity():
    old_rt, old_times = run_old_style(PARITY_WORKLOADS["kv_serving"](),
                                      mover="fifo")
    new_rt, new_times = run_new_style(PARITY_WORKLOADS["kv_serving"](),
                                      mover="fifo")
    assert old_rt.plan.moves == new_rt.plan.moves
    assert old_times == new_times


def test_manual_source_matches_explicit_kwargs():
    """A ManualSource-fed session profiles identically to explicit
    per-phase keyword instrumentation."""
    def drive(use_source: bool):
        rt = Session(MACHINE, RuntimeConfig(fast_capacity_bytes=20 * MB,
                                            mover="fifo"), cf=CF)
        for n in ("a", "b"):
            rt.register(n, 12 * MB)
        acc = {"p0": {"a": 1e6}, "p1": {"b": 8e5}}
        if use_source:
            src = ManualSource()
            src.set("p0", accesses=acc["p0"], elapsed=0.1)
            src.set("p1", accesses=acc["p1"], elapsed=0.05)
            rt.attach_source(src)
        for _ in range(3):
            with rt.iteration():
                if use_source:
                    with rt.phase("p0"):
                        pass
                    with rt.phase("p1"):
                        pass
                else:
                    with rt.phase("p0", accesses=acc["p0"], elapsed=0.1):
                        pass
                    with rt.phase("p1", accesses=acc["p1"], elapsed=0.05):
                        pass
        return rt
    a, b = drive(True), drive(False)
    assert a.plan is not None
    assert a.plan.moves == b.plan.moves
    assert a.plan.predicted_iteration_time == b.plan.predicted_iteration_time


# ---------------------------------------------------------------------------
# session-context properties
# ---------------------------------------------------------------------------
def _session(cap_mb: int = 64) -> Session:
    return Session(MACHINE, RuntimeConfig(fast_capacity_bytes=cap_mb * MB,
                                          mover="fifo"), cf=CF)


def test_phase_auto_registers_on_first_use():
    rt = _session()
    rt.register("x", 8 * MB)
    with rt.iteration():
        with rt.phase("fwd", accesses={"x": 1e5}, elapsed=0.01):
            pass
        with rt.phase("bwd", accesses={"x": 2e5}, elapsed=0.02):
            pass
    assert rt.phase_names() == ["fwd", "bwd"]
    assert rt.plan is not None          # plan built after one iteration


@given(fail_phase=st.integers(0, 2), n_phases=st.integers(1, 3))
@settings(max_examples=20, deadline=None)
def test_phase_context_exception_safe(fail_phase, n_phases):
    """An exception inside a phase can never leave it open: the session
    accepts new phases afterwards and the crashed phase recorded nothing."""
    fail_phase = fail_phase % n_phases
    rt = _session()
    rt.register("x", 8 * MB)
    with pytest.raises(ValueError, match="boom"):
        with rt.iteration():
            for i in range(n_phases):
                with rt.phase(f"p{i}", accesses={"x": 1e5}, elapsed=0.01):
                    if i == fail_phase:
                        raise ValueError("boom")
    assert rt._open_phase is None
    assert rt._iter_open is False
    assert rt._events_this_iter == []   # abandoned iteration left no events
    # the session is reusable: a clean iteration still profiles and plans
    with rt.iteration():
        with rt.phase("p0", accesses={"x": 1e5}, elapsed=0.01):
            pass
    assert rt.plan is not None


def test_conditional_phase_after_plan_keeps_move_wrapping():
    """A phase auto-registered *after* the plan was built (a conditional
    eval/ckpt phase) must not change the modulus the plan's moves wrap
    with (regression: live n_phases re-wrapped trigger_phase=-1 moves
    onto the new phase, silently rerouting steady-state movement)."""
    def run(with_eval: bool):
        rt = _session(cap_mb=12)
        rt.register("hot", 10 * MB)
        rt.register("other", 10 * MB)
        moves_after_iter = []
        for step in range(8):
            with rt.iteration():
                with rt.phase("a", accesses={"hot": 1e6}, elapsed=0.1):
                    pass
                with rt.phase("b", accesses={"other": 8e5}, elapsed=0.1):
                    pass
                if with_eval and step >= 3:     # first seen mid-loop
                    with rt.phase("eval", accesses={"hot": 1e3},
                                  elapsed=0.1):
                        pass
            moves_after_iter.append(rt.mover.stats.n_moves)
        return rt, moves_after_iter

    base_rt, base_moves = run(False)
    eval_rt, eval_moves = run(True)
    assert base_rt.plan is not None
    # the hazard exists: the plan carries a previous-iteration trigger
    assert any(m.trigger_phase < 0 for m in base_rt.plan.moves)
    assert eval_rt._plan_n_phases == 2          # frozen at plan time
    assert eval_rt.phase_names() == ["a", "b", "eval"]
    # the conditional phase must not perturb the plan's movement schedule
    assert eval_moves == base_moves
    rt = _session()
    rt.register("x", 8 * MB)
    with rt.iteration():
        with rt.phase("outer", elapsed=0.01):
            with pytest.raises(RuntimeError, match="nest"):
                with rt.phase("inner", elapsed=0.01):
                    pass


def test_iteration_nesting_rejected():
    rt = _session()
    with rt.iteration():
        with pytest.raises(RuntimeError, match="nest"):
            with rt.iteration():
                pass


def test_phase_outside_iteration_rejected():
    rt = _session()
    with pytest.raises(RuntimeError, match="iteration"):
        with rt.phase("p0"):
            pass


def test_crashed_phase_not_folded_into_profile():
    rt = _session()
    rt.register("x", 8 * MB)
    try:
        with rt.iteration():
            with rt.phase("p0", accesses={"x": 1e9}, elapsed=123.0):
                raise RuntimeError("crash")
    except RuntimeError:
        pass
    assert rt.profiler.profile(0, "x") is None


# ---------------------------------------------------------------------------
# pytree-native registration + duplicate-name fix
# ---------------------------------------------------------------------------
def test_register_pytree_records_leaf_spans():
    import jax.numpy as jnp
    tree = {"w": jnp.ones((4, 8), jnp.float32),
            "b": jnp.ones((8,), jnp.float32)}
    rt = _session()
    obj = rt.register("layer", tree, manage_payload=False)
    assert obj.size_bytes == 4 * 8 * 4 + 8 * 4
    assert obj.payload is None          # manage_payload=False: sizes only
    spans = obj.leaf_spans
    assert len(spans) == 2
    offs = sorted((off, nb) for _, off, nb in spans)
    assert offs[0][0] == 0 and offs[0][1] + offs[1][1] == obj.size_bytes


def test_register_concrete_pytree_keeps_payload():
    import jax.numpy as jnp
    rt = _session()
    obj = rt.register("arr", jnp.ones((16,), jnp.float32))
    assert obj.payload is not None


def test_register_shape_structs_have_no_payload():
    import jax
    rt = _session()
    obj = rt.register("spec", {"a": jax.ShapeDtypeStruct((8, 8), "float32")})
    assert obj.payload is None
    assert obj.size_bytes == 8 * 8 * 4


def test_duplicate_register_raises_value_error():
    rt = UnimemRuntime(MACHINE, RuntimeConfig(fast_capacity_bytes=64 * MB),
                       cf=CF)
    rt.register("obj_a", 8 * MB)
    with pytest.raises(ValueError, match="obj_a"):
        rt.register("obj_a", 4 * MB)
    with pytest.raises(ValueError, match="obj_a"):
        rt.alloc("obj_a", size_bytes=4 * MB)   # deprecated shim, same check


def test_register_parent_of_live_chunks_raises():
    """Re-registering a name whose object was partitioned must fail loudly:
    a silent overwrite would orphan the live chunk state."""
    from repro.core.partition import partition_object
    reg = ObjectRegistry()
    reg.alloc("big", 100 * MB, chunkable=True)
    partition_object(reg, "big", 30 * MB)       # removes big, adds big#k
    assert "big" not in reg
    with pytest.raises(ValueError, match="big"):
        reg.alloc("big", 100 * MB)


# ---------------------------------------------------------------------------
# start_loop re-entry regression
# ---------------------------------------------------------------------------
def _drive_loop(rt, times, accs, iters=4):
    for _ in range(iters):
        rt.begin_iteration()
        for i, t in enumerate(times):
            rt.phase_begin(i)
            rt.phase_end(i, elapsed=t, accesses=accs[i])
        rt.end_iteration()


def test_start_loop_reentry_resets_plan_and_baselines():
    """A second start_loop on one runtime must not inherit the first loop's
    plan, monitor baselines, or accumulated profiles (regression for the
    re-entry bug: only _iteration/_profiling/graph/mover were reset)."""
    rt = UnimemRuntime(MACHINE,
                       RuntimeConfig(fast_capacity_bytes=20 * MB,
                                     mover="fifo",
                                     enable_initial_placement=False),
                       cf=CF)
    rt.alloc("a", size_bytes=10 * MB)
    rt.alloc("b", size_bytes=10 * MB)
    rt.start_loop(["p0", "p1"])
    _drive_loop(rt, [0.1, 0.05], [{"a": 1e6}, {"b": 5e5}])
    assert rt.plan is not None
    stale_plan = rt.plan
    assert rt.monitor._baseline            # baselines recorded

    rt.start_loop(["q0"])                  # second loop: new phase anatomy
    assert rt.plan is None                 # stale plan dropped
    assert rt.monitor._baseline == {}      # drift baselines reset
    assert rt.profiler.profile(0, "a") is None   # profiles reset
    assert rt.profiler.profile(1, "b") is None

    # the second loop profiles from scratch and plans on its own anatomy
    _drive_loop(rt, [0.2], [{"b": 2e6}])
    assert rt.plan is not None
    assert rt.plan is not stale_plan
    assert len(rt.plan.residents) == 1     # one-phase loop, not two


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------
def test_backend_registry_contents():
    names = available_backends()
    for expected in ("sim", "jax", "jax_async", "cpu_pool"):
        assert expected in names


def test_unknown_backend_raises_with_listing():
    with pytest.raises(ValueError, match="sim"):
        make_backend("cuda_streams", MACHINE)


def test_backend_reregistration_guard():
    with pytest.raises(ValueError, match="already registered"):
        register_backend("jax", lambda machine, **_: None)
    sentinel = object()
    register_backend("test_backend_tmp", lambda machine, **_: sentinel,
                     overwrite=True)
    assert make_backend("test_backend_tmp", MACHINE) is sentinel


def test_config_backend_string_resolves():
    assert isinstance(
        Session(MACHINE, RuntimeConfig(backend="jax")).backend,
        JaxTierBackend)
    assert isinstance(
        Session(MACHINE, RuntimeConfig(backend="jax_async")).backend,
        AsyncJaxTierBackend)
    sim = Session(MACHINE, RuntimeConfig(backend="sim", mover="slack"))
    assert isinstance(sim.backend, ChannelSimBackend)
    fifo = Session(MACHINE, RuntimeConfig(backend="sim", mover="fifo"))
    assert isinstance(fifo.backend, SimTierBackend)


def test_async_jax_backend_lands_on_settle_or_wait():
    import jax.numpy as jnp
    reg = ObjectRegistry()
    b = AsyncJaxTierBackend(MACHINE)
    obj = reg.alloc("x", 1024, payload=jnp.ones((256,), jnp.float32))
    h = b.start_move(obj, "fast")
    assert h is not None
    # wait fences per leaf and flips the tier
    assert b.wait(h) == 0.0
    assert obj.tier == "fast"
    # settle after landing is a no-op
    b.settle(0.0)
    assert obj.tier == "fast"
    assert b.landed_copies == {"fast": 1, "slow": 0}
    assert b.landed_bytes == 1024
    # logical (payload-free) objects flip immediately
    o2 = reg.alloc("y", 1024)
    assert b.start_move(o2, "fast") is None
    assert o2.tier == "fast"


def test_async_jax_backend_prunes_handles_on_wait():
    """wait()/complete() must drop the landed handle (and its leaf refs)
    even when the caller never settles — the FIFO mover's pattern
    (regression: unbounded _open growth pinning moved buffers)."""
    import jax.numpy as jnp
    reg = ObjectRegistry()
    b = AsyncJaxTierBackend(MACHINE)
    for i in range(4):
        obj = reg.alloc(f"o{i}", 256, payload=jnp.ones((64,), jnp.float32))
        b.wait(b.start_move(obj, "fast"))
    assert b._open == []


def test_async_jax_backend_chains_after_eviction():
    """A fetch chained after an eviction must not dispatch until the
    eviction landed (capacity ordering: no transient double-residency)."""
    import jax.numpy as jnp
    reg = ObjectRegistry()
    b = AsyncJaxTierBackend(MACHINE)
    victim = reg.alloc("victim", 256,
                       payload=jnp.ones((64,), jnp.float32), tier="fast")
    ev = b.start_move(victim, "slow")
    incoming = reg.alloc("incoming", 256,
                         payload=jnp.ones((64,), jnp.float32))
    b.start_move(incoming, "fast", after=ev)
    assert ev.landed and victim.tier == "slow"   # space freed first


def test_phase_overrides_are_per_field():
    """Explicit accesses must not discard the source's virtual elapsed or
    its access_bins (regression: all-or-nothing source bypass)."""
    rt = _session()
    rt.register("x", 8 * MB)
    src = ManualSource()
    src.set("p0", accesses={"x": 1e5}, elapsed=0.25,
            access_bins={"x": [3.0, 1.0]})
    rt.attach_source(src)
    with rt.iteration():
        with rt.phase("p0", accesses={"x": 7e5}) as pc:
            pass
    assert pc.elapsed == 0.25                    # source virtual time kept
    prof = rt.profiler.profile(0, "x")
    assert prof is not None and prof.phase_time == 0.25
    assert prof.bin_counts is not None           # source bins still flowed


def test_async_jax_backend_is_done_probe():
    """is_done must report completion without blocking (the slack mover's
    eviction path probes it so in-flight evictions stay off the fence)."""
    import jax.numpy as jnp
    reg = ObjectRegistry()
    b = AsyncJaxTierBackend(MACHINE)
    assert b.is_done(None)
    obj = reg.alloc("x", 256, payload=jnp.ones((64,), jnp.float32),
                    tier="fast")
    h = b.start_move(obj, "slow")
    for leaf in h.leaves:
        leaf.block_until_ready()
    assert b.is_done(h)                  # ready leaves: done, not landed
    b.settle(0.0)
    assert h.landed and b.is_done(h)


def test_async_jax_backend_settle_lands_ready_copies():
    import jax.numpy as jnp
    reg = ObjectRegistry()
    b = AsyncJaxTierBackend(MACHINE)
    obj = reg.alloc("x", 1024, payload={"w": jnp.ones((64,), jnp.float32)})
    h = b.start_move(obj, "fast")
    for leaf in h.leaves:                   # force readiness, then settle
        leaf.block_until_ready()
    b.settle(0.0)
    assert obj.tier == "fast" and h.landed


def test_cpu_pool_backend_registered_and_configurable():
    b = make_backend("cpu_pool", MACHINE, pool_workers=3)
    assert isinstance(b, CpuPoolBackend)
    rt = Session(MACHINE, RuntimeConfig(backend="cpu_pool"))
    assert isinstance(rt.backend, CpuPoolBackend)
    b.shutdown()
    rt.backend.shutdown()


def test_cpu_pool_backend_moves_and_lands_on_settle():
    """The memcpy pool copies numpy leaves on workers; the tier (and the
    relocated payload) flips only when the finished copy is settled or
    fenced — the same in-flight semantics as the async jax backend."""
    import numpy as np
    reg = ObjectRegistry()
    b = CpuPoolBackend(MACHINE, workers=2)
    try:
        src = np.arange(4096, dtype=np.float32)
        obj = reg.alloc("x", src.nbytes, payload={"w": src})
        h = b.start_move(obj, "fast")
        assert h is not None
        h.future.result()               # copy finished on the worker...
        assert obj.tier == "slow"       # ...but not yet landed
        b.settle(0.0)
        assert obj.tier == "fast" and h.landed
        moved = obj.payload["w"]
        assert moved is not src and np.array_equal(moved, src)
        # wait() fences, lands and returns the seconds it blocked;
        # logical objects flip immediately
        o2 = reg.alloc("y", 1024, payload={"w": np.ones(256, np.float32)})
        assert b.wait(b.start_move(o2, "fast")) >= 0.0
        assert b.wait(h) == 0.0         # landed already: nothing blocked
        assert o2.tier == "fast"
        o3 = reg.alloc("z", 1024)
        assert b.start_move(o3, "fast") is None and o3.tier == "fast"
        assert b._open == []            # landed handles pruned
    finally:
        b.shutdown()


def test_cpu_pool_backend_chains_after_eviction():
    """start_move(after=) orders a fetch behind the eviction freeing its
    space: the fetch's worker blocks on the eviction's copy, the caller
    never does, and is_done stays a non-blocking probe."""
    import numpy as np
    reg = ObjectRegistry()
    b = CpuPoolBackend(MACHINE, workers=1)      # one worker: strict order
    try:
        victim = reg.alloc("victim", 4096,
                           payload={"w": np.zeros(1024, np.float32)},
                           tier="fast")
        incoming = reg.alloc("incoming", 4096,
                             payload={"w": np.ones(1024, np.float32)})
        ev = b.start_move(victim, "slow")
        h = b.start_move(incoming, "fast", after=ev)
        assert b.is_done(None)
        b.complete(h)                   # fencing the fetch lands it
        assert incoming.tier == "fast"
        assert ev.future.done()         # predecessor necessarily finished
        b.settle(0.0)
        assert victim.tier == "slow"
    finally:
        b.shutdown()


def test_cpu_pool_backend_through_runtime_end_to_end():
    """A session on backend='cpu_pool' plans and migrates numpy-payload
    objects through the slack mover's settle/fence path."""
    import numpy as np
    rt = UnimemRuntime(MACHINE,
                       RuntimeConfig(fast_capacity_bytes=3 * MB // 2,
                                     backend="cpu_pool",
                                     enable_partitioning=False), cf=CF)
    hot = rt.register("hot", size_bytes=MB,
                      payload={"w": np.ones(MB // 4, np.float32)})
    cold = rt.register("cold", size_bytes=MB,
                       payload={"w": np.ones(MB // 4, np.float32)})
    for _ in range(4):
        with rt.iteration():
            with rt.phase("compute", accesses={"hot": 1e6}, elapsed=0.05):
                pass
            with rt.phase("update", accesses={"cold": 1e3}, elapsed=0.01):
                pass
    assert rt.plan is not None
    assert hot.tier == "fast"
    assert cold.tier == "slow"
    rt.backend.shutdown()


def test_async_backend_through_runtime_end_to_end():
    """A session on backend='jax_async' plans and moves real arrays; the
    slack mover's settle path lands tiers without explicit waits."""
    import jax.numpy as jnp
    rt = UnimemRuntime(MACHINE,
                       RuntimeConfig(fast_capacity_bytes=3 * MB // 2,
                                     backend="jax_async",
                                     enable_partitioning=False), cf=CF)
    hot = rt.register("hot", jnp.ones((256 * 1024,), jnp.float32))
    cold = rt.register("cold", jnp.ones((256 * 1024,), jnp.float32))
    for _ in range(4):
        with rt.iteration():
            with rt.phase("compute", accesses={"hot": 1e6}, elapsed=0.05):
                pass
            with rt.phase("update", accesses={"cold": 1e3}, elapsed=0.01):
                pass
    assert rt.plan is not None
    assert hot.tier == "fast"
    assert cold.tier == "slow"


@pytest.mark.parametrize("backend_cls", [JaxTierBackend, AsyncJaxTierBackend])
def test_jax_backends_refuse_a_memory_kind_the_device_lacks(backend_cls):
    """A move to a memory kind the device does not expose raises; it is
    never turned into a tier flip that moved no byte."""
    import dataclasses
    import jax.numpy as jnp
    machine = dataclasses.replace(
        MACHINE, slow=dataclasses.replace(MACHINE.slow,
                                          memory_kind="no_such_kind"))
    reg = ObjectRegistry()
    obj = reg.alloc("x", 256, payload=jnp.ones((64,), jnp.float32),
                    tier="fast")
    with pytest.raises(ValueError, match="no_such_kind"):
        backend_cls(machine).start_move(obj, "slow")
    assert obj.tier == "fast"
