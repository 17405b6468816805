"""Benchmark suite — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows; ``derived`` carries the
figure-specific quantity (normalized slowdowns, overlap fractions, ...).

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only fig9
"""

from __future__ import annotations

import argparse
import pathlib
import sys
import time

from repro.core import PAPER_DRAM_NVM, calibrate
from repro.sim import (NPB_WORKLOADS, SCENARIO_WORKLOADS,
                       SKEWED_SCENARIO_WORKLOADS, lm_train_workload)
from repro.sim.workloads import graph_chase_skewed, kv_serving_skewed
from repro.core.tiers import TPU_V5E

from .common import (DEFAULT_DRAM, MB, run_static, run_unimem, run_xmen)

ROWS = []
RESULTS_DIR = pathlib.Path(__file__).resolve().parent / "results"
SAVE_RESULTS = False            # set by --save: refresh the committed CSVs
SCENARIO_FILTER = None          # set by --scenario: substring workload filter
CHAOS_SEED = 42                 # fixed seed: the committed chaos rows are
                                # a deterministic fault replay, not a sample


def emit(name: str, us: float, derived: str) -> None:
    row = f"{name},{us:.1f},{derived}"
    ROWS.append(row)
    print(row, flush=True)


def _scenario_selected(wl_name: str) -> bool:
    return SCENARIO_FILTER is None or SCENARIO_FILTER in wl_name


def write_rows(filename: str, prefix: str, must_contain: str = None,
               exclude: str = None) -> None:
    """With ``--save``, commit this run's rows matching ``prefix`` to
    results/<filename> (the nightly-regression baselines); default runs
    only print, so a casual local run never rewrites the committed CSVs.
    ``must_contain``/``exclude`` split row families sharing a prefix
    (``scenario_*_chaos`` goes to chaos.csv, everything else to
    scenarios.csv)."""
    if not SAVE_RESULTS:
        return
    if SCENARIO_FILTER is not None:
        print(f"# --scenario filter active: not rewriting {filename}",
              flush=True)
        return
    rows = [r for r in ROWS if r.startswith(prefix)
            and (must_contain is None or must_contain in r.split(",", 1)[0])
            and (exclude is None or exclude not in r.split(",", 1)[0])]
    if not rows:
        return
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    path = RESULTS_DIR / filename
    path.write_text("name,us_per_call,derived\n" + "\n".join(rows) + "\n")
    print(f"# wrote {len(rows)} rows -> {path}", flush=True)


# ---------------------------------------------------------------- Figs 2-3
def bench_tier_sweep() -> None:
    """NVM-only slowdown vs bandwidth (Fig 2) and latency (Fig 3)."""
    for knob, scales in (("bw", [1.0, 0.5, 0.25, 0.125]),
                         ("lat", [1.0, 2.0, 4.0, 8.0])):
        for wl_name, make in NPB_WORKLOADS.items():
            wl = make()
            for s in scales:
                m = (PAPER_DRAM_NVM.scaled(bw_scale=s) if knob == "bw"
                     else PAPER_DRAM_NVM.scaled(lat_scale=s))
                t0 = time.perf_counter()
                dram = run_static(m, wl, "fast", iters=6)
                nvm = run_static(m, wl, "slow", iters=6)
                us = (time.perf_counter() - t0) * 1e6
                ratio = nvm.steady_iteration_time / dram.steady_iteration_time
                emit(f"fig{2 if knob == 'bw' else 3}_{wl_name}_{knob}{s}",
                     us, f"nvm_over_dram={ratio:.3f}")


# ------------------------------------------------------------------- Fig 4
def bench_object_placement() -> None:
    """Per-object placement impact on SP (Fig 4): which objects are
    bandwidth- vs latency-sensitive."""
    from repro.core.data_objects import ObjectRegistry
    from repro.sim import SimulationEngine

    wl = NPB_WORKLOADS["sp"]()
    for nvm_cfg, mach in (("halfbw", PAPER_DRAM_NVM.scaled(bw_scale=0.5)),
                          ("4xlat", PAPER_DRAM_NVM.scaled(lat_scale=4.0))):
        dram = run_static(mach, wl, "fast", iters=6)
        nvm = run_static(mach, wl, "slow", iters=6)
        for target in (["in_buffer", "out_buffer"], ["lhs"], ["rhs"]):
            reg = ObjectRegistry()
            for n, s in wl.objects.items():
                reg.alloc(n, s, tier="fast" if n in target else "slow")
            t0 = time.perf_counter()
            res = SimulationEngine(mach, wl, registry=reg).run(6)
            us = (time.perf_counter() - t0) * 1e6
            emit(f"fig4_sp_{nvm_cfg}_{'+'.join(target)}", us,
                 f"norm={res.steady_iteration_time / dram.steady_iteration_time:.3f};"
                 f"nvm_only={nvm.steady_iteration_time / dram.steady_iteration_time:.3f}")


# ---------------------------------------------------------------- Figs 9-10
def bench_unimem_gap() -> None:
    """DRAM-only vs NVM-only vs X-Men vs Unimem (Figs 9-10)."""
    for fig, mach in (("fig9", PAPER_DRAM_NVM.scaled(bw_scale=0.5)),
                      ("fig10", PAPER_DRAM_NVM.scaled(lat_scale=4.0))):
        gaps = []
        for wl_name, make in NPB_WORKLOADS.items():
            wl = make()
            t0 = time.perf_counter()
            dram = run_static(mach, wl, "fast")
            nvm = run_static(mach, wl, "slow")
            xmen = run_xmen(mach, wl)
            uni, rt = run_unimem(mach, wl)
            us = (time.perf_counter() - t0) * 1e6
            d = dram.steady_iteration_time
            gaps.append(uni.steady_iteration_time / d - 1)
            emit(f"{fig}_{wl_name}", us,
                 f"nvm={nvm.steady_iteration_time / d:.3f};"
                 f"xmen={xmen.steady_iteration_time / d:.3f};"
                 f"unimem={uni.steady_iteration_time / d:.3f};"
                 f"strategy={rt.plan.strategy if rt.plan else 'none'}")
        emit(f"{fig}_average", 0.0,
             f"unimem_avg_gap={sum(gaps) / len(gaps) * 100:.1f}%"
             f";paper_claim={'3%' if fig == 'fig9' else '7%'}")


# ------------------------------------------------------------------ Fig 11
def bench_ablation() -> None:
    """Contribution of the four techniques (Fig 11): apply cumulatively
    (1) global search, (2) +local search, (3) +partitioning, (4) +initial
    placement."""
    from repro.core import RuntimeConfig

    mach = PAPER_DRAM_NVM.scaled(bw_scale=0.5)
    stages = [
        ("global", dict(enable_local_search=False, enable_partitioning=False,
                        enable_initial_placement=False)),
        ("+local", dict(enable_partitioning=False,
                        enable_initial_placement=False)),
        ("+partition", dict(enable_initial_placement=False)),
        ("+initial", dict()),
    ]
    for wl_name, make in NPB_WORKLOADS.items():
        wl = make()
        dram = run_static(mach, wl, "fast")
        nvm = run_static(mach, wl, "slow")
        base = nvm.steady_iteration_time
        derived = [f"nvm={base / dram.steady_iteration_time:.3f}"]
        t0 = time.perf_counter()
        for name, kw in stages:
            cfgr = RuntimeConfig(fast_capacity_bytes=DEFAULT_DRAM, **kw)
            res, _ = run_unimem(mach, wl, config=cfgr)
            derived.append(
                f"{name}="
                f"{res.steady_iteration_time / dram.steady_iteration_time:.3f}")
        us = (time.perf_counter() - t0) * 1e6
        emit(f"fig11_{wl_name}", us, ";".join(derived))


# ----------------------------------------------------------------- Table 4
def bench_migration_stats() -> None:
    mach = PAPER_DRAM_NVM.scaled(bw_scale=0.5)
    for wl_name, make in NPB_WORKLOADS.items():
        wl = make()
        t0 = time.perf_counter()
        res, rt = run_unimem(mach, wl)
        us = (time.perf_counter() - t0) * 1e6
        s = rt.stats()
        emit(f"table4_{wl_name}", us,
             f"migrations={s['n_moves']};"
             f"moved_mb={s['moved_bytes'] / MB:.0f};"
             f"overlap={100 * (s['overlap_fraction'] or 0):.0f}%;"
             f"strategy={s['strategy']}")


# ------------------------------------------------------------------ Fig 12
def bench_scaling() -> None:
    """Strong scaling (Fig 12): per-rank problem shrinks as ranks grow."""
    mach = PAPER_DRAM_NVM.scaled(bw_scale=0.6, lat_scale=1.89)  # Edison emu
    for ranks in (4, 8, 16, 32, 64):
        wl = NPB_WORKLOADS["cg"](scale=4.0 / ranks)
        t0 = time.perf_counter()
        dram = run_static(mach, wl, "fast")
        uni, rt = run_unimem(mach, wl)
        us = (time.perf_counter() - t0) * 1e6
        emit(f"fig12_cg_ranks{ranks}", us,
             f"unimem={uni.steady_iteration_time / dram.steady_iteration_time:.3f}")


# ------------------------------------------------------------------ Fig 13
def bench_dram_size() -> None:
    mach = PAPER_DRAM_NVM.scaled(bw_scale=0.5)
    for size_mb in (128, 256, 512):
        for wl_name in ("cg", "ft", "mg", "sp"):
            wl = NPB_WORKLOADS[wl_name]()
            t0 = time.perf_counter()
            dram = run_static(mach, wl, "fast")
            uni, _ = run_unimem(mach, wl, dram_bytes=size_mb * MB)
            us = (time.perf_counter() - t0) * 1e6
            emit(f"fig13_{wl_name}_dram{size_mb}mb", us,
                 f"unimem={uni.steady_iteration_time / dram.steady_iteration_time:.3f}")


# ------------------------------------------- beyond-paper: LM tiering (v5e)
def bench_lm_tiering() -> None:
    """Optimizer-state offload on the TPU tier model: nemotron-340b-like
    per-chip slice (the flagship dry-run cell, simulated end to end)."""
    GB = 1024 ** 3
    for name, layer_b, opt_b, act_b, layers in (
            ("nemotron340b_chip", 28 * MB, 166 * MB, 18 * MB, 96),
            ("dbrx132b_chip", 11 * MB, 64 * MB, 6 * MB, 40)):
        wl = lm_train_workload(n_layers=layers, layer_bytes=layer_b,
                               opt_bytes=opt_b, act_bytes=act_b,
                               name=name, compute_per_group_s=0.012)
        t0 = time.perf_counter()
        hbm_unlimited = run_static(TPU_V5E, wl, "fast", iters=6)
        host_all = run_static(TPU_V5E, wl, "slow", iters=6)
        uni, rt = run_unimem(TPU_V5E, wl,
                             dram_bytes=int(10 * GB), iters=8)
        us = (time.perf_counter() - t0) * 1e6
        d = hbm_unlimited.steady_iteration_time
        emit(f"lm_tiering_{name}", us,
             f"host_all={host_all.steady_iteration_time / d:.3f};"
             f"unimem={uni.steady_iteration_time / d:.3f};"
             f"overlap={100 * (rt.stats()['overlap_fraction'] or 0):.0f}%")


# ------------------------------------- scenario matrix: slack vs FIFO mover
def bench_scenarios() -> None:
    """Slack-aware async scheduler vs the FIFO phase-boundary mover on the
    steady-state-churn scenario matrix (KV-cache serving, MoE expert churn,
    pointer-chasing graph).  Reports per scenario: steady iteration time
    normalized to DRAM-only for each policy, absolute steady-state fence
    stall per iteration, and the slack engine's overlap fractions
    (move-count based and copy-time based).

    ``drift_threshold`` is pinned high so both movers execute the *same*
    plan — the comparison isolates the migration engine."""
    mach = PAPER_DRAM_NVM.scaled(bw_scale=0.5, lat_scale=2.0)
    for wl_name, make in SCENARIO_WORKLOADS.items():
        if not _scenario_selected(wl_name):
            continue
        wl = make()
        t0 = time.perf_counter()
        dram = run_static(mach, wl, "fast")
        nvm = run_static(mach, wl, "slow")
        results = {}
        for mover in ("fifo", "slack"):
            res, rt = run_unimem(mach, wl, mover=mover, drift_threshold=10.0)
            tail = res.phase_trace[len(res.phase_trace) // 2:]
            stall = (sum(p.stall_s for p in tail)
                     / (len(tail) / len(wl.phases)))
            results[mover] = (res, rt, stall)
        us = (time.perf_counter() - t0) * 1e6
        d = dram.steady_iteration_time
        (fifo, _, fifo_stall) = results["fifo"]
        (slack, srt, slack_stall) = results["slack"]
        s = srt.stats()
        emit(f"scenario_{wl_name}", us,
             f"nvm={nvm.steady_iteration_time / d:.3f};"
             f"fifo={fifo.steady_iteration_time / d:.3f};"
             f"slack={slack.steady_iteration_time / d:.3f};"
             f"speedup={fifo.steady_iteration_time / slack.steady_iteration_time:.3f};"
             f"fifo_stall_s={fifo_stall:.4f};"
             f"slack_stall_s={slack_stall:.4f};"
             f"overlap={s['overlap_fraction']:.2f};"
             f"overlap_time={(s['overlap_time_fraction'] or 0):.2f};"
             f"strategy={s['strategy']}")

    # skewed variants: hot-chunk pipeline (per-chunk attribution + skew-aware
    # partitioning, chunk_aware=True) vs PR 1's uniform-attribution slack
    # engine (chunk_aware=False) — both on the slack mover, same machine.
    for wl_name, make in SKEWED_SCENARIO_WORKLOADS.items():
        if not _scenario_selected(wl_name):
            continue
        wl = make()
        t0 = time.perf_counter()
        dram = run_static(mach, wl, "fast")
        nvm = run_static(mach, wl, "slow")
        uni, _ = run_unimem(mach, wl, drift_threshold=10.0, chunk_aware=False)
        hot, hrt = run_unimem(mach, wl, drift_threshold=10.0, chunk_aware=True)
        us = (time.perf_counter() - t0) * 1e6
        d = dram.steady_iteration_time
        s = hrt.stats()
        n_chunks = sum(1 for o in hrt.registry if o.parent is not None)
        emit(f"scenario_{wl_name}", us,
             f"nvm={nvm.steady_iteration_time / d:.3f};"
             f"uniform={uni.steady_iteration_time / d:.3f};"
             f"hotchunk={hot.steady_iteration_time / d:.3f};"
             f"speedup={uni.steady_iteration_time / hot.steady_iteration_time:.3f};"
             f"overlap={s['overlap_fraction']:.2f};"
             f"n_chunks={n_chunks};"
             f"strategy={s['strategy']}")

    # multi-resolution refinement (PR 5): the full multi-res mode
    # (adaptive re-binning plus its enactment-consistent solve — fine
    # chunks need the churn-guarded pricing, so the mode ships as one
    # switch) vs the legacy fixed-width pipeline at the SAME total bin
    # budget (64), on skewed workloads whose true densities carry
    # structure finer than one uniform bin.  Global search runs at its
    # default (on): since PR 6 prices global moves through the same
    # schedule-aware estimate as local ones, the best-of-two chooser no
    # longer hands global a free-movement advantage, so the rows need no
    # pin.  The committed gates enforce equal-or-better steady slack
    # (mr_gain >= 1) with hot-head chunks finer than one legacy bin
    # (hot_chunk_frac < 1).
    from repro.core.partition import chunk_spans

    mr_scenarios = (
        ("graph_chase_skew", lambda: graph_chase_skewed(density_bins=256)),
        ("kv_serving_skew",
         lambda: kv_serving_skewed(sub=16, window=4, taper=0.4)),
    )
    for wl_name, make in mr_scenarios:
        if not _scenario_selected(wl_name):
            continue
        wl = make()
        t0 = time.perf_counter()
        dram = run_static(mach, wl, "fast")
        common = dict(drift_threshold=10.0, chunk_aware=True,
                      histogram_bins=64, profile_iterations=3)
        uni, _ = run_unimem(mach, wl, **common)
        ref, rrt = run_unimem(mach, wl, histogram_refine=True, **common)
        us = (time.perf_counter() - t0) * 1e6
        d = dram.steady_iteration_time
        # finest fast-resident hot-head chunk vs one legacy (1/64) bin —
        # uncapped, so a regression past 1.0 is visible to the nightly
        # ceiling gate
        frac = float("inf")
        parents = sorted({o.parent for o in rrt.registry
                          if o.parent is not None})
        n_chunks = 0
        for par in parents:
            spans = chunk_spans(rrt.registry, par)
            n_chunks += len(spans)
            size = spans[-1][2]
            fast = [c.size_bytes for c, _, _ in spans if c.tier == "fast"]
            if fast:
                frac = min(frac, min(fast) / (size / 64))
        if frac == float("inf"):
            frac = 64.0         # nothing fast-resident: fail the ceiling
        emit(f"scenario_{wl_name}_mr", us,
             f"nvm={run_static(mach, wl, 'slow').steady_iteration_time / d:.3f};"
             f"uniform64={uni.steady_iteration_time / d:.3f};"
             f"refined={ref.steady_iteration_time / d:.3f};"
             f"mr_gain={uni.steady_iteration_time / ref.steady_iteration_time:.3f};"
             f"hot_chunk_frac={frac:.3f};"
             f"n_chunks={n_chunks}")

    # policy ablation (PR 5 + PR 6): the registry's clock/LRU baseline
    # and the calibrated planner (calibrate_feedback=True, PR 6's online
    # per-class CF folds) against the uncalibrated benefit-model planner,
    # one row per scenario.  LRU wins some rotations against the
    # *uncalibrated* model (fsdp_buckets books latency gains ~14x
    # optimistic and plans essentially no moves); the calibrated arm
    # closes that gap (``cal_parity`` = lru/unimem_cal, floor-gated at
    # 1.0 on fsdp_buckets) and ``pred_err`` records how honest the kept
    # model's prediction is (ceiling-gated where folds are kept; a
    # reverted epoch keeps the uncalibrated prediction, err ~1.0).
    for wl_name, make in {**SCENARIO_WORKLOADS,
                          **SKEWED_SCENARIO_WORKLOADS}.items():
        if not _scenario_selected(wl_name):
            continue
        wl = make()
        t0 = time.perf_counter()
        dram = run_static(mach, wl, "fast")
        uni, _ = run_unimem(mach, wl, drift_threshold=10.0)
        lru, _ = run_unimem(mach, wl, drift_threshold=10.0, policy="lru")
        cal, crt = run_unimem(mach, wl, drift_threshold=10.0,
                              calibrate_feedback=True)
        us = (time.perf_counter() - t0) * 1e6
        d = dram.steady_iteration_time
        cs = crt.stats()
        emit(f"scenario_{wl_name}_ablation", us,
             f"unimem={uni.steady_iteration_time / d:.3f};"
             f"lru={lru.steady_iteration_time / d:.3f};"
             f"unimem_cal={cal.steady_iteration_time / d:.3f};"
             f"lru_over_unimem="
             f"{lru.steady_iteration_time / uni.steady_iteration_time:.3f};"
             f"cal_parity="
             f"{lru.steady_iteration_time / cal.steady_iteration_time:.3f};"
             f"pred_err={(cs['pred_err'] if cs['pred_err'] is not None else -1):.3f};"
             f"n_folds={cs['n_recalibrations']}")

    # interval-guidance ablation (PR 6): Olson-style decayed interval
    # profiling (arxiv 2110.02150) as the third policy arm — recency
    # (lru) vs decayed frequency/density (interval) vs the calibrated
    # benefit model.  ``vs_nvm`` floors the rows: the guidance must keep
    # a real speedup over NVM-only or the gate fails loudly.
    for wl_name, make in {**SCENARIO_WORKLOADS,
                          **SKEWED_SCENARIO_WORKLOADS}.items():
        if not _scenario_selected(wl_name):
            continue
        wl = make()
        t0 = time.perf_counter()
        dram = run_static(mach, wl, "fast")
        nvm = run_static(mach, wl, "slow")
        uni, _ = run_unimem(mach, wl, drift_threshold=10.0)
        itv, irt = run_unimem(mach, wl, drift_threshold=10.0,
                              policy="interval")
        us = (time.perf_counter() - t0) * 1e6
        d = dram.steady_iteration_time
        emit(f"scenario_{wl_name}_interval", us,
             f"interval={itv.steady_iteration_time / d:.3f};"
             f"interval_over_unimem="
             f"{itv.steady_iteration_time / uni.steady_iteration_time:.3f};"
             f"vs_nvm="
             f"{nvm.steady_iteration_time / itv.steady_iteration_time:.3f};"
             f"moves={len(irt.plan.moves) if irt.plan else 0}")
    write_rows("scenarios.csv", "scenario_", exclude="_chaos")


# --------------------------- chaos: the scenario matrix under fault injection
def bench_chaos() -> None:
    """The full scenario matrix re-run under the gated chaos profile (5%
    transient start failures + one 8x straggler channel, fixed seed — a
    deterministic fault replay, not a sample).  Each row reports the
    degraded-mode slack engine against its own fault-free run
    (``vs_faultfree``, nightly floor 0.85): retries, degraded serves,
    rollbacks and straggler reissues absorb the faults, the channel
    health machine quarantines the straggler channel, and the post-run
    tier audit must stay violation-free (``audit_violations`` counts
    in-run audit violations plus any final-state divergence; the nightly
    ceiling pins it to zero)."""
    from repro.sim.workloads import chaos_gated_spec

    mach = PAPER_DRAM_NVM.scaled(bw_scale=0.5, lat_scale=2.0)
    for wl_name, make in {**SCENARIO_WORKLOADS,
                          **SKEWED_SCENARIO_WORKLOADS}.items():
        if not _scenario_selected(wl_name):
            continue
        wl = make()
        t0 = time.perf_counter()
        base, _ = run_unimem(mach, wl, mover="slack", drift_threshold=10.0)
        chaos, rt = run_unimem(mach, wl, mover="slack", drift_threshold=10.0,
                               fault_spec=chaos_gated_spec(seed=CHAOS_SEED))
        us = (time.perf_counter() - t0) * 1e6
        s = rt.stats()
        audit = rt.audit_tiers(heal=False)     # final-state reconciliation
        health = s["channel_health"]
        emit(f"scenario_{wl_name}_chaos", us,
             f"vs_faultfree={base.steady_iteration_time / chaos.steady_iteration_time:.3f};"
             f"audit_violations={s['n_audit_violations'] + len(audit.violations)};"
             f"retries={s['n_retries']};"
             f"degraded={s['n_degraded_serves']};"
             f"rollbacks={s['n_eviction_rollbacks']};"
             f"reissues={s['n_straggler_reissues']};"
             f"quarantined="
             f"{sum(1 for v in health.values() if v == 'quarantined')}")
    write_rows("chaos.csv", "scenario_", must_contain="_chaos")


# --------------------------- multi-tenant serving: QoS partition vs aggregate
def bench_tenants() -> None:
    """The tenancy layer's gated row: ``tenant_serving`` (one whale, three
    mid tenants, one cold archive) under the aggregate unimem solve vs the
    ``bandwidth_partition`` policy, against a DRAM-only reference.

    Per tenant, ``slack = dram_p99 / arm_p99`` (p99 of the per-iteration
    time summed over the tenant's phases, steady tail).  The gated
    quantities: ``tail_gain`` — the worst admitted non-whale tenant's
    slack ratio partition/unimem (nightly floor 1.15: partitioning must
    buy the long tail real p99 headroom) — and ``whale_ratio`` — the
    whale's same ratio (floor 0.95: without starving the whale).  The
    cold tenant is admission-demoted to serve-from-slow and excluded from
    the tail by the demotion record itself."""
    from repro.core.tenancy import per_tenant_p99
    from repro.sim.workloads import TENANT_SERVING_QOS, tenant_serving

    from .common import run_unimem_tenants

    mach = PAPER_DRAM_NVM.scaled(bw_scale=0.5, lat_scale=2.0)
    wl = tenant_serving()
    qos = TENANT_SERVING_QOS
    names = [ph.name for ph in wl.phases]
    iters = 20
    kw = dict(dram_bytes=192 * MB, iters=iters, copy_channels=7,
              drift_threshold=10.0)
    t0 = time.perf_counter()
    dram = run_static(mach, wl, "fast", iters=iters)
    uni, _ = run_unimem_tenants(mach, wl, qos, **kw)
    part, prt = run_unimem_tenants(mach, wl, qos,
                                   policy="bandwidth_partition", **kw)
    us = (time.perf_counter() - t0) * 1e6
    p_dram = per_tenant_p99(dram.phase_trace, names, qos)
    p_uni = per_tenant_p99(uni.phase_trace, names, qos)
    p_bp = per_tenant_p99(part.phase_trace, names, qos)
    slack_uni = {t: p_dram[t] / p_uni[t] for t in p_dram}
    slack_bp = {t: p_dram[t] / p_bp[t] for t in p_dram}
    admission = dict(getattr(prt.plan, "tenant_admission", None) or {})
    tail = [t for t in sorted(qos) if t != "whale" and t not in admission]
    tail_gain = min(slack_bp[t] / slack_uni[t] for t in tail)
    whale_ratio = slack_bp["whale"] / slack_uni["whale"]
    shares = dict(getattr(prt.plan, "tenant_shares", None) or {})
    channels = dict(getattr(prt.plan, "tenant_channels", None) or {})
    derived = [f"tail_gain={tail_gain:.3f}", f"whale_ratio={whale_ratio:.3f}"]
    for t in sorted(qos):
        derived.append(f"{t}_slack_uni={slack_uni[t]:.3f}")
        derived.append(f"{t}_slack_bp={slack_bp[t]:.3f}")
    derived.append(f"demoted={'+'.join(sorted(admission)) or 'none'}")
    derived.append(f"whale_share_mb={shares.get('whale', 0) / MB:.0f}")
    derived.append(f"whale_channels={len(channels.get('whale', []))}")
    emit("scenario_tenant_serving", us, ";".join(derived))
    write_rows("tenants.csv", "scenario_tenant")


# ------------------------------------- multi-host cluster coordination
def bench_multihost() -> None:
    """Multi-host tier management's gated row: ``moe_churn_multihost``
    (4 virtual hosts, one host's expert shard hot past DRAM capacity
    after router churn, peers idle with spare capacity).

    Host-local-only management leaves the hot host serving surplus
    experts from NVM; the cluster coordinator re-homes them to peers
    over the modeled interconnect (cross_host backend).  Gated
    quantities: ``hot_gain`` — the hot host's steady iteration time,
    local-only over coordinated (nightly floor 1.10) — and
    ``cluster_gain`` — the same ratio on the slowest host (the cluster's
    effective iteration time).  ``migration_ms`` records the one-time
    virtual-time cost of the pulls over the apportioned link pairs."""
    from repro.sim import ClusterSimulation, moe_churn_multihost

    machine, wl, links, knobs = moe_churn_multihost()
    sim = ClusterSimulation(machine, wl, links=links, **knobs)
    t0 = time.perf_counter()
    local = sim.run_local_only(12)
    coord = sim.run_coordinated(12)
    us = (time.perf_counter() - t0) * 1e6
    hot = "h0"
    hot_gain = local.steady_time(hot) / coord.steady_time(hot)
    cluster_gain = local.cluster_steady_time / coord.cluster_steady_time
    pulls = [m for m in coord.migrations if m.mode == "cross_host"]
    derived = [f"hot_gain={hot_gain:.3f}",
               f"cluster_gain={cluster_gain:.3f}",
               f"n_migrations={len(pulls)}",
               f"migrated_mb={sum(m.size_bytes for m in pulls) / MB:.0f}",
               f"migration_ms={coord.migration_s * 1e3:.2f}"]
    for h in wl.hosts():
        derived.append(f"{h}_local_ms={local.steady_time(h) * 1e3:.2f}")
        derived.append(f"{h}_coord_ms={coord.steady_time(h) * 1e3:.2f}")
    emit("multihost_moe_churn", us, ";".join(derived))
    write_rows("multihost.csv", "multihost_")


# ------------------------------ planner latency: vectorized vs pre-PR path
def bench_planner() -> None:
    """Plan-construction latency vs registry size.

    Builds a registry of N chunks (10 partitioned parents, parent-level
    profiles so every candidate exercises the chunk-attribution fallback —
    the planner's hot path), then times ``Planner.plan`` in both modes:
    ``legacy`` is the pre-optimization per-candidate scalar path with the
    bool-matrix knapsack, ``vectorized`` the batched numpy path with the
    packed-bitset knapsack.  Both produce identical plans."""
    import random

    from repro.core import (CalibrationConstants, PhaseProfiler, Planner,
                            build_phase_graph)
    from repro.core.data_objects import DataObject, ObjectRegistry
    from repro.core.partition import resplit_refs
    from repro.core.phase import PhaseTraceEvent

    mach = PAPER_DRAM_NVM.scaled(bw_scale=0.5)

    def build(n_objs: int, n_phases: int = 12, seed: int = 0):
        rng = random.Random(seed)
        reg = ObjectRegistry()
        n_parents = 10
        per = n_objs // n_parents
        for p in range(n_parents):
            for k in range(per):
                reg.register(DataObject(
                    name=f"par{p}#{k}", size_bytes=rng.randint(1, 4) * MB,
                    parent=f"par{p}", chunk_index=k))
        refs, times = [], []
        for _ in range(n_phases):
            r = {f"par{p}": rng.uniform(1e5, 1e7) for p in range(10)
                 if rng.random() < 0.7}
            refs.append(r)
            times.append(rng.uniform(0.01, 0.2))
        graph = build_phase_graph(
            [(f"ph{i}", rr) for i, rr in enumerate(refs)], times=times)
        prof = PhaseProfiler(mach, seed=seed)
        for i, rr in enumerate(refs):
            prof.observe(PhaseTraceEvent(i, times[i], dict(rr)))
        prof.annotate_graph(graph)
        resplit_refs(graph, reg)    # parent refs -> size-fraction chunk refs
        return reg, graph, prof, refs, times

    def timed(fn, repeats):
        """Run ``fn`` ``repeats`` times; return (last result, best µs,
        median µs).  Best-of-k is what the gates compare (least noisy);
        the median rides along so a single lucky run is visible."""
        ts, out = [], None
        for _ in range(repeats):
            t0 = time.perf_counter()
            out = fn()
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return out, ts[0] * 1e6, ts[len(ts) // 2] * 1e6

    for n in (100, 500, 2000):
        reg, graph, prof, _, _ = build(n)
        plans, best, med = {}, {}, {}
        for mode, vec in (("vectorized", True), ("legacy", False)):
            def cold_plan(vec=vec):
                # fresh planner per repeat: this row times the *cold*
                # build (cross-tick caches are the replan rows' job)
                return Planner(mach, reg, CalibrationConstants(),
                               DEFAULT_DRAM, vectorized=vec).plan(graph, prof)
            plans[mode], best[mode], med[mode] = timed(
                cold_plan, 3 if n <= 500 else 2)
        equal = (plans["vectorized"].moves == plans["legacy"].moves
                 and plans["vectorized"].predicted_iteration_time
                 == plans["legacy"].predicted_iteration_time)
        if not equal:   # the oracle guarantee must hold at benchmark scale
            raise RuntimeError(
                f"vectorized plan diverged from the scalar oracle at n={n}")
        emit(f"planner_n{n}", best["vectorized"],
             f"legacy_us={best['legacy']:.0f};"
             f"vectorized_us={best['vectorized']:.0f};"
             f"median_us={med['vectorized']:.0f};"
             f"speedup={best['legacy'] / best['vectorized']:.1f};"
             f"seed=0;plans_equal={equal}")

    # vectorized-only cold build at 20k chunks (the scalar path takes
    # minutes at this scale, so no legacy comparison / speedup key)
    n = 20000
    reg, graph, prof, _, _ = build(n)
    plan20k, b, m = timed(lambda: Planner(
        mach, reg, CalibrationConstants(), DEFAULT_DRAM).plan(graph, prof), 2)
    emit(f"planner_n{n}", b,
         f"vectorized_us={b:.0f};median_us={m:.0f};seed=0;"
         f"legacy=skipped_at_scale;strategy={plan20k.strategy}")

    # ---- scoped replan vs full rebuild, single-phase intensity drift ----
    # The fixture mirrors a layered training loop (32 phases — modest next
    # to lm_train_workload's 72 at 96 layers / 4 per group).  The drift is
    # a single phase's access *intensity* shifting (same reference set,
    # counts scaled, time held) — the localized-drift case the scoped
    # response targets.  The scoped replan must (a) produce exactly the
    # plan a from-scratch rebuild produces and (b) stay far under the
    # serving-tick budget (nightly: scoped_us ceiling at 20k chunks,
    # scoped_speedup floor at 2k, greuse_frac floor at 20k).
    def replan_row(n, full_repeats, scoped_repeats, n_phases=32):
        reg, graph, prof, refs, times_ = build(n, n_phases=n_phases)
        rng = random.Random(1)
        planner = Planner(mach, reg, CalibrationConstants(), DEFAULT_DRAM)
        local = planner.plan_local(graph, prof)
        glob = planner.plan_global(graph, prof)
        drift = n_phases - 1
        prof.decay(0.25, phases=[drift])
        drifted_refs = {k: v * rng.uniform(0.5, 2.0)
                        for k, v in refs[drift].items()}
        prof.observe(PhaseTraceEvent(drift, times_[drift], drifted_refs))
        prof.annotate_graph(graph)
        resplit_refs(graph, reg)

        def full_rebuild():
            # fresh planner: the cost of replanning with no standing
            # state at all (cold caches, every phase solved)
            return Planner(mach, reg, CalibrationConstants(),
                           DEFAULT_DRAM).plan(graph, prof)

        def scoped_replan():
            # production ticks each see *new* drift, so drop the
            # whole-decision memo between repeats: every repeat pays
            # the row-reuse + drifted-phase solve path, never a
            # memoized whole-plan lookup
            planner._global_memo = None
            return planner.plan(graph, prof,
                                standing=local.phase_decisions,
                                standing_global=glob.global_contribs,
                                standing_digest=local.graph_digest)

        full, best_full, _ = timed(full_rebuild, full_repeats)
        scoped, best_scoped, med_scoped = timed(scoped_replan, scoped_repeats)
        equal = (full.moves == scoped.moves
                 and full.residents == scoped.residents
                 and full.predicted_iteration_time
                 == scoped.predicted_iteration_time
                 and full.strategy == scoped.strategy)
        if not equal:   # scoped replans are bit-identical, or the run dies
            raise RuntimeError(
                f"scoped replan diverged from the full rebuild at n={n}")
        sl = planner.plan_local(graph, prof, standing=local.phase_decisions,
                                standing_digest=local.graph_digest)
        reused = sum(1 for d in sl.phase_decisions if d.reused)
        emit(f"planner_replan_n{n}", best_scoped,
             f"full_us={best_full:.0f};"
             f"scoped_us={best_scoped:.0f};"
             f"median_scoped_us={med_scoped:.0f};"
             f"scoped_speedup={best_full / best_scoped:.1f};"
             f"reused={reused}/{n_phases};"
             f"greuse_frac={scoped.global_rows_reused / n_phases:.3f};"
             f"global_mode={scoped.global_mode};"
             f"seed=0;plans_equal={equal}")

    replan_row(2000, full_repeats=3, scoped_repeats=5)
    replan_row(20000, full_repeats=2, scoped_repeats=5)
    replan_row(100000, full_repeats=1, scoped_repeats=3)    # smoke scale
    write_rows("planner_latency.csv", "planner_")


BENCHES = {
    "fig2_3": bench_tier_sweep,
    "fig4": bench_object_placement,
    "fig9_10": bench_unimem_gap,
    "fig11": bench_ablation,
    "table4": bench_migration_stats,
    "fig12": bench_scaling,
    "fig13": bench_dram_size,
    "lm_tiering": bench_lm_tiering,
    "scenarios": bench_scenarios,
    "chaos": bench_chaos,
    "tenants": bench_tenants,
    "multihost": bench_multihost,
    "planner": bench_planner,
}


def main() -> None:
    global SAVE_RESULTS, SCENARIO_FILTER
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--scenario", default=None,
                    help="substring filter on scenario workload names "
                         "(scenarios/chaos benches); filtered runs never "
                         "rewrite the committed CSVs")
    ap.add_argument("--save", action="store_true",
                    help="rewrite the committed baseline CSVs under "
                         "benchmarks/results/ with this run")
    args = ap.parse_args()
    SAVE_RESULTS = args.save
    SCENARIO_FILTER = args.scenario
    print("name,us_per_call,derived")
    for name, fn in BENCHES.items():
        if args.only and args.only not in name:
            continue
        fn()


if __name__ == "__main__":
    main()
