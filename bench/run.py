"""Run one cell of ``BENCHMARK.json`` on the accelerator.

  python3 bench/run.py --workload chatglm3-6b.offload --seed 7 \\
      --seconds 10 --trace 0

Set-up (weights drawn on the device from ``--seed``, every shape warmed)
runs first; then the runner named by the cell's traffic file measures for
``--seconds``.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiler trace of the window.
The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced) and, last, ``checks``: each number that decided ``correct`` with
its limit, also printed as the last lines of standard error.  Without a
TPU, or with fewer chips than the cell asks for, it exits 2 and prints no
result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, peaks, xplane  # noqa: E402


class Run:
    """What a metric reader sees."""

    def __init__(self, ctx, outcome, summary, device_kind):
        self.traffic = ctx.traffic
        self.arch = ctx.arch()
        self.counts = ctx.cell.counts()
        self.counters = outcome.counters
        self.extra = outcome.extra
        self.trace = summary
        self.device_kind = device_kind

    @property
    def peak(self) -> dict:
        return peaks.peak_for(self.device_kind)


def execute(ctx: harness.Context, device) -> dict:
    """Set up, measure and check one cell; return the result line."""
    outcome = ctx.cell.runner().run(ctx)
    outcome.counters["setup_s"] = outcome.window_start - ctx.t_start
    summary = None
    if ctx.trace:
        summary = xplane.summarize(xplane.load(str(ctx.trace_dir)),
                                   "bench.window")
    run = Run(ctx, outcome, summary, device.device_kind)
    metrics = {}
    for m in (ctx.cell.per_layer if ctx.trace else ctx.cell.end_to_end):
        value = ctx.cell.reader(m["name"])(run)
        if value is None:
            if not ctx.trace:
                raise RuntimeError(f"end-to-end metric {m['name']} read "
                                   "nothing")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    import jax
    dev_info = {"platform": device.platform, "kind": device.device_kind,
                "count": len(jax.devices()),
                "memory_peak_bytes": outcome.memory_peak_bytes}
    line = {"correct": outcome.correct, "attempted": outcome.attempted,
            "failed": outcome.failed, "metrics": metrics, "device": dev_info}
    if summary is not None:
        dev_info["busy_s"] = summary.busy_s
        dev_info["window_s"] = summary.window_s
        line["breakdown"] = {"device_ops": summary.device_ops,
                             "idle_gaps": summary.idle_gaps}
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in outcome.checks}
    line["_extra"] = outcome.extra
    return line


def emit(line: dict) -> None:
    extra = line.pop("_extra", {})
    print(f"run: {json.dumps(extra, default=str)}", file=sys.stderr)
    for name, c in line["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {name}: {c['value']} (limit {c['limit']}) {verdict}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = harness.find_cell(args.workload)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(harness.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    need = cell.spec["chips"]
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"bench: needs {need} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    ctx = harness.Context(cell=cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t_start=T_START)
    emit(execute(ctx, devices[0]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
