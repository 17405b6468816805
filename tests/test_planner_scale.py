"""Serving-tick planner properties at scale: scoped replans bit-identical
to full rebuilds under randomized drift, dominance-bound (prune)
soundness, whole-decision global reuse, array-knapsack oracle parity
(numpy and forced-jax paths), entry-residency reconciliation, and
round-trips of the benefit/class decision caches."""

import random

import numpy as np
import pytest

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:                      # no hypothesis: seeded shim
    from _propcheck import st, given, settings

from test_policy import M, MB, build_chunk_fixture, plans_equal

from repro.core import CalibrationConstants, Planner, PlanProgram
from repro.core import knapsack
from repro.core.partition import resplit_refs
from repro.core.phase import PhaseTraceEvent


def _drift(reg, graph, prof, refs, times, phases, seed):
    """Shift the access *intensity* of ``phases`` (same reference sets,
    counts rescaled) and re-run the scoped attribution stages — the
    localized-drift tick the scoped replan path targets."""
    rng = random.Random(seed)
    prof.decay(0.25, phases=list(phases))
    for i in phases:
        prof.observe(PhaseTraceEvent(i, times[i], {
            k: v * rng.uniform(0.5, 2.0) for k, v in refs[i].items()}))
    prof.annotate_graph(graph)
    resplit_refs(graph, reg)


def _standing_plan(planner, graph, prof):
    local = planner.plan_local(graph, prof)
    glob = planner.plan_global(graph, prof)
    return local, glob


# ---------------------------------------------------------------------------
# scoped replan == full rebuild, randomized drift
# ---------------------------------------------------------------------------
@given(seed=st.integers(0, 1000))
@settings(max_examples=8, deadline=None)
def test_scoped_replan_bitidentical_under_random_drift(seed):
    """Property: after drifting a random subset of phases, the scoped
    replan (standing decisions + standing global rows) and a cold
    from-scratch rebuild produce the same plan — moves, residents,
    predicted time AND best-of-two winner."""
    rng = random.Random(seed ^ 0xD51F7)
    cap = rng.choice([64, 128, 256]) * MB
    reg, graph, prof, refs, times = build_chunk_fixture(
        300, seed=seed % 3)
    planner = Planner(M, reg, CalibrationConstants(), cap)
    local, glob = _standing_plan(planner, graph, prof)
    k = rng.choice([1, 1, 2, 3])
    phases = sorted(rng.sample(range(len(graph)), k))
    _drift(reg, graph, prof, refs, times, phases, seed)
    scoped = planner.plan(graph, prof,
                          standing=local.phase_decisions,
                          standing_global=glob.global_contribs,
                          standing_digest=local.graph_digest)
    full = Planner(M, reg, CalibrationConstants(), cap).plan(graph, prof)
    assert plans_equal(scoped, full)


def test_scoped_single_phase_drift_reuses_and_matches():
    """The serving-tick shape: one drifted phase out of 16 — everything
    else must be recognized as unchanged (local decisions and global
    rows both), and the plan must equal a cold rebuild's exactly."""
    n_phases = 16
    reg, graph, prof, refs, times = build_chunk_fixture(
        400, n_phases=n_phases)
    planner = Planner(M, reg, CalibrationConstants(), 128 * MB)
    local, glob = _standing_plan(planner, graph, prof)
    _drift(reg, graph, prof, refs, times, [n_phases - 1], seed=1)
    scoped = planner.plan(graph, prof,
                          standing=local.phase_decisions,
                          standing_global=glob.global_contribs,
                          standing_digest=local.graph_digest)
    full = Planner(M, reg, CalibrationConstants(), 128 * MB).plan(
        graph, prof)
    assert plans_equal(scoped, full)
    # every undrifted global row came from the standing contribs
    assert scoped.global_rows_reused >= n_phases - 1
    sl = planner.plan_local(graph, prof, standing=local.phase_decisions,
                            standing_digest=local.graph_digest)
    assert sum(1 for d in sl.phase_decisions if d.reused) >= n_phases - 1


# ---------------------------------------------------------------------------
# dominance bound + whole-decision reuse
# ---------------------------------------------------------------------------
def test_dominance_bound_prunes_soundly():
    """When the chooser's bound proves the global solve cannot win, the
    solve is skipped — and an independent, unpruned global solve indeed
    loses the best-of-two, so the pruned and unpruned choosers agree."""
    cap = 64 * MB
    reg, graph, prof, _, _ = build_chunk_fixture(300)
    planner = Planner(M, reg, CalibrationConstants(), cap)
    plan = planner.plan(graph, prof)
    assert plan.global_mode == "pruned"     # this fixture trips the bound
    assert plan.strategy == "local"
    fresh = Planner(M, reg, CalibrationConstants(), cap)
    local = fresh.plan_local(graph, prof)
    glob = fresh.plan_global(graph, prof)
    assert glob.global_mode == "solved"
    # the skipped solve could not have beaten local (ties go to local)
    assert glob.predicted_iteration_time >= local.predicted_iteration_time
    assert plans_equal(plan, local)


def test_unchanged_rebuild_reuses_whole_global_decision():
    """Zero drift: a second plan() on the same planner must hit the
    whole-decision memo (no re-solve) and return the identical plan."""
    reg, graph, prof, _, _ = build_chunk_fixture(300)
    planner = Planner(M, reg, CalibrationConstants(), 256 * MB)
    first = planner.plan(graph, prof)
    local = planner.plan_local(graph, prof)
    second = planner.plan(graph, prof)
    assert plans_equal(first, second)
    assert second.global_mode == "reused"
    sl = planner.plan_local(graph, prof, standing=local.phase_decisions,
                            standing_digest=local.graph_digest)
    assert all(d.reused for d in sl.phase_decisions)


# ---------------------------------------------------------------------------
# array knapsack == reference oracle
# ---------------------------------------------------------------------------
@given(seed=st.integers(0, 400))
@settings(max_examples=40, deadline=None)
def test_solve_arrays_matches_reference(seed):
    """The array entry point (values/sizes vectors, index output) returns
    exactly the reference solver's selection — negatives, zero-capacity
    and oversized items included."""
    rng = random.Random(seed)
    n = rng.randint(0, 60)
    its = [knapsack.Item(f"o{i}", rng.uniform(-2.0, 4.0),
                         rng.randint(1, 48 * MB)) for i in range(n)]
    cap = rng.randint(0, 256) * MB
    idx = knapsack.solve_arrays(
        np.array([it.value for it in its], dtype=np.float64),
        np.array([it.size_bytes for it in its], dtype=np.int64), cap)
    assert [its[i].name for i in idx] == knapsack.solve_reference(its, cap)


def test_solve_arrays_jax_path_matches_reference(monkeypatch):
    """Force the jitted lax.scan DP (off by default on CPU) above its
    work threshold and require the bit-packed keep rows to reproduce the
    reference selection exactly.  The numpy DP is made to fail, so the
    selection can only have come from the jitted kernel."""
    rng = random.Random(7)
    its = [knapsack.Item(f"o{i}", rng.uniform(-0.5, 2.0),
                         rng.randint(1, 4) * MB) for i in range(800)]
    cap = 256 * MB      # ~640 positive items x 16k cells: above _JAX_MIN_WORK
    values = np.array([it.value for it in its], dtype=np.float64)
    sizes = np.array([it.size_bytes for it in its], dtype=np.int64)

    def numpy_dp_must_not_run(*args):
        raise AssertionError("solve_arrays fell back to the numpy DP")

    monkeypatch.setattr(knapsack, "use_jax", True)
    monkeypatch.setattr(knapsack, "_numpy_dp", numpy_dp_must_not_run)
    idx = knapsack.solve_arrays(values, sizes, cap)
    assert [its[i].name for i in idx] == knapsack.solve_reference(its, cap)


# ---------------------------------------------------------------------------
# entry-residency reconciliation
# ---------------------------------------------------------------------------
def test_entry_shed_reconciles_overshoot():
    """An entry residency overshooting the budget (capacity shrank under
    a standing placement) is shed at phase 0: lowest-traffic unpinned
    residents demoted first, priced as evictions, identically on the
    vectorized and oracle paths."""
    cap = 64 * MB
    reg, graph, prof, _, _ = build_chunk_fixture(300)
    fast, total = [], 0
    for o in reg:
        if total >= 96 * MB:
            break
        o.tier = "fast"
        total += o.size_bytes
        fast.append(o)
    fast[0].pinned = True
    # mirror the shed rule: ascending (traffic, name), pinned skipped
    traffic = {o.name: sum(p.refs.get(o.name, 0.0) for p in graph)
               for o in fast}
    expected, left = [], total
    for o in sorted(fast, key=lambda o: (traffic[o.name], o.name)):
        if left <= cap:
            break
        if o.pinned:
            continue
        expected.append(o.name)
        left -= o.size_bytes
    assert expected, "fixture must actually overshoot"
    plans = {}
    for vec in (True, False):
        plan = Planner(M, reg, CalibrationConstants(), cap,
                       vectorized=vec).plan_local(graph, prof)
        shed = plan.moves[:len(expected)]
        assert [m.obj for m in shed] == expected
        assert all(m.dst == "slow" and m.needed_by == 0 for m in shed)
        assert all(m.est_unhidden_cost > 0.0 for m in shed)
        assert fast[0].name not in {m.obj for m in plan.moves
                                    if m.dst == "slow"}
        plans[vec] = plan
    assert plans_equal(plans[True], plans[False])


# ---------------------------------------------------------------------------
# decision-cache round-trip
# ---------------------------------------------------------------------------
def test_roundtrip_preserves_benefit_classes_and_cls_rows():
    """The gain-class caches ride the IR: phase decisions keep their
    per-object class maps and global rows their packed class vectors
    through JSON, and a replan from the deserialized standing state is
    still bit-identical with full reuse."""
    reg, graph, prof, _, _ = build_chunk_fixture(200)
    planner = Planner(M, reg, CalibrationConstants(), 256 * MB)
    local, glob = _standing_plan(planner, graph, prof)
    prog = PlanProgram.from_plan(
        local, policy="unimem", provenance=[], profile_epoch=prof.epoch,
        chunk_generation=reg.generation, capacity_bytes=256 * MB,
        phase_decisions=local.phase_decisions,
        global_contribs=glob.global_contribs,
        graph_digest=local.graph_digest)
    back = PlanProgram.from_json(prog.to_json())
    assert any(d.classes for d in prog.phase_decisions)
    for a, b in zip(back.phase_decisions, prog.phase_decisions):
        assert a.classes == b.classes
    assert any(g.cls_row is not None for g in prog.global_contribs)
    for a, b in zip(back.global_contribs, prog.global_contribs):
        if b.cls_row is None:
            assert a.cls_row is None
        else:
            assert np.array_equal(a.cls_row, b.cls_row)
            assert a.cls_row.dtype == b.cls_row.dtype
    replan = planner.plan(graph, prof,
                          standing=back.phase_decisions,
                          standing_global=back.global_contribs,
                          standing_digest=back.graph_digest)
    full = Planner(M, reg, CalibrationConstants(), 256 * MB).plan(
        graph, prof)
    assert plans_equal(replan, full)
