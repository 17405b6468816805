"""Smoke test of the tiered serving path on one TPU chip.

Drives the main path once through the entry points a user calls, at the
full width of gemma-2b (18 layers, d_model 2048, vocab 256000; 5.01 GB of
bf16 weights drawn from ``--seed``):

1. device check: the first JAX device must be a TPU;
2. serve: ``ServeEngine`` with a ``UnimemRuntime`` on the ``jax_async``
   copy backend answers a few greedy requests, and its tokens must equal
   those of the same engine without a runtime.  The engine registers its
   weights and KV cache by size only, so this phase moves no payload;
3. tier moves: every parameter leaf (5.01 GB) is registered as a runtime
   object carrying its array, starting in ``pinned_host``.  A fast tier
   smaller than their total and phases whose accesses rotate make the plan
   fetch and evict them through the ``jax_async`` backend.  At least one
   fetch and one eviction must land (every landed copy moves a payload);
   once no copy is in flight, every leaf must sit in the memory kind of
   its object's tier; after all leaves are brought back to ``device`` they
   must be bit-identical to the originals, and a request served from them
   must give the reference tokens.

Only if every check passes does the last line of output read
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``;
any failure raises and exits non-zero.  Times printed on the way are
smoke-test timings, not benchmark results.

  python chip_smoke.py                          # on a TPU host
  JAX_PLATFORMS=cpu python chip_smoke.py --tiny # rehearsal at a toy size
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import ManualSource, RuntimeConfig, UnimemRuntime  # noqa: E402
from repro.core.tiers import TPU_V5E  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.serve.engine import ServeEngine  # noqa: E402

KIND_OF_TIER = {"fast": TPU_V5E.fast.memory_kind,
                "slow": TPU_V5E.slow.memory_kind}
N_REQUESTS = 4          # greedy requests served with and without the runtime
N_ITERATIONS = 4        # runtime iterations of the tier-move phase


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def parse_args() -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse every phase at the reduced gemma config "
                         "on any device; prints no result line")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args()


# --------------------------------------------------------------- phases
def device_check(tiny: bool) -> jax.Device:
    devs = jax.devices()
    dev = devs[0]
    kinds = sorted(m.kind for m in dev.addressable_memories())
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)} memory_kinds={kinds}", flush=True)
    if not tiny:
        check(dev.platform == "tpu",
              f"needs a TPU, JAX found {dev.platform!r}")
    check(set(KIND_OF_TIER.values()) <= set(kinds),
          f"device lacks the tier memory kinds {sorted(KIND_OF_TIER.values())}")
    return dev


def timed_generate(engine: ServeEngine, prompts, n_new: int):
    t0 = time.perf_counter()
    out = jax.block_until_ready(engine.generate(prompts, n_new))
    return np.asarray(out), time.perf_counter() - t0


def first_step_seconds(engine: ServeEngine, cfg, batch: int,
                       max_seq: int) -> float:
    """Trace + compile + one decode step of a fresh engine's jit."""
    cache = lm.init_cache(cfg, batch, max_seq)
    tok = jnp.zeros((batch,), jnp.int32)
    t0 = time.perf_counter()
    jax.block_until_ready(engine.step(engine.params, cache, tok,
                                      jnp.int32(0)))
    return time.perf_counter() - t0


def serve(cfg, params, prompts, n_new: int, max_seq: int):
    """Greedy requests through the engine with and without the runtime;
    returns the reference tokens."""
    n_req, batch = prompts.shape[:2]
    ref = ServeEngine(cfg, params, max_seq=max_seq, batch=batch)
    rt = UnimemRuntime(TPU_V5E, RuntimeConfig(backend="jax_async"))
    tiered = ServeEngine(cfg, params, max_seq=max_seq, batch=batch,
                         runtime=rt)
    for name, eng in (("reference", ref), ("runtime", tiered)):
        print(f"serve: {name} engine first decode step (trace + compile + "
              f"1 step) {first_step_seconds(eng, cfg, batch, max_seq):.3f} s",
              flush=True)
    ref_tokens = []
    for r in range(n_req):
        want, t_ref = timed_generate(ref, prompts[r], n_new)
        got, t_rt = timed_generate(tiered, prompts[r], n_new)
        check(got.shape == (batch, prompts.shape[2] + n_new),
              f"request {r}: output shape {got.shape}")
        check(np.array_equal(got, want),
              f"request {r}: tokens with the runtime differ from without")
        ref_tokens.append(want)
        print(f"serve: request {r} batch={batch} prompt={prompts.shape[2]} "
              f"new={n_new}: tokens equal; smoke latency (not a benchmark) "
              f"reference {t_ref:.3f} s, with runtime {t_rt:.3f} s",
              flush=True)
    # the engine registers its weights and cache by size only
    # (manage_payload=False), so serving moves no payload: real copies
    # happen only in the tier-move phase
    st = rt.stats()
    print(f"serve: runtime iterations={st['iteration']} "
          f"plan={st['strategy']} copies issued={st['n_moves']} "
          f"bytes moved={st['moved_bytes']} payload bytes landed="
          f"{rt.backend.landed_bytes} (sizes-only registration)", flush=True)
    return ref_tokens


def leaf_kind(obj) -> str:
    kinds = {l.sharding.memory_kind
             for l in jax.tree_util.tree_leaves(obj.payload)}
    check(len(kinds) == 1, f"{obj.name}: leaves in several kinds {kinds}")
    return kinds.pop()


def tier_moves(dev, params):
    """Register every parameter leaf as a payload-carrying object in
    pinned_host, let the plan fetch and evict them, and return the leaves
    brought back to device (same tree structure as ``params``)."""
    named, treedef = jax.tree_util.tree_flatten_with_path(params)
    names = [jax.tree_util.keystr(p) for p, _ in named]
    sizes = {n: l.size * l.dtype.itemsize for n, (_, l) in zip(names, named)}
    total = sum(sizes.values())
    capacity = total // 2
    rt = UnimemRuntime(TPU_V5E, RuntimeConfig(
        backend="jax_async", fast_capacity_bytes=capacity,
        enable_partitioning=False))
    host = jax.sharding.SingleDeviceSharding(
        dev, memory_kind=KIND_OF_TIER["slow"])
    t0 = time.perf_counter()
    objs = {n: rt.register(n, jax.device_put(l, host))
            for n, (_, l) in zip(names, named)}
    # the async backend lands copies by polling is_ready(): it has to turn
    # true with no fence in sight
    pending = [l for o in objs.values()
               for l in jax.tree_util.tree_leaves(o.payload)]
    while pending and time.perf_counter() - t0 < 120.0:
        pending = [l for l in pending if not l.is_ready()]
        time.sleep(0.001)
    check(not pending, "is_ready() turns true without a fence")
    check(all(leaf_kind(o) == KIND_OF_TIER["slow"] and o.tier == "slow"
              for o in objs.values()), "leaves start in pinned_host")
    print(f"tier moves: {len(objs)} objects, {total} bytes put in "
          f"{KIND_OF_TIER['slow']} and seen ready by is_ready() polling in "
          f"{time.perf_counter() - t0:.3f} s; fast capacity {capacity} "
          f"bytes", flush=True)

    # three phases whose accessed leaves rotate: each needs about half of
    # the weights, a different half each time (accesses are cache lines
    # read for 64 tokens; a phase lasts as long as streaming its bytes
    # from the host would)
    def group(*keys):
        return [n for n in names if any(k in n for k in keys)]
    phases = {"embed_attn": group("embed", "attn", "ln"),
              "mlp_in": group("w_gate", "w_up"),
              "mlp_out": group("w_down", "embed")}
    tokens = 64
    src = ManualSource()
    for ph, members in phases.items():
        nbytes = sum(sizes[n] for n in members)
        src.set(ph, accesses={n: tokens * sizes[n] / 512 for n in members},
                elapsed=tokens * nbytes / TPU_V5E.slow.bw)
    rt.attach_source(src)

    t0 = time.perf_counter()
    for _ in range(N_ITERATIONS):
        with rt.iteration():
            for ph in phases:
                with rt.phase(ph):
                    pass
    rt.mover.drain()            # land every copy still in flight
    rt.backend.settle()
    elapsed = time.perf_counter() - t0
    st = rt.stats()
    landed = rt.backend.landed_copies
    print(f"tier moves: {N_ITERATIONS} iterations in {elapsed:.3f} s; copies "
          f"issued={st['n_moves']} bytes moved={st['moved_bytes']}; landed "
          f"fetches={landed['fast']} evictions={landed['slow']} "
          f"({rt.backend.landed_bytes} bytes)", flush=True)
    check(landed["fast"] >= 1 and landed["slow"] >= 1,
          "at least one fetch and one eviction landed")
    check(st["n_degraded_serves"] == 0 and st["n_eviction_rollbacks"] == 0
          and st["n_retries"] == 0, "no copy failed, was retried or was "
          "rolled back")
    for n, o in objs.items():
        check(leaf_kind(o) == KIND_OF_TIER[o.tier],
              f"{n}: tier {o.tier} but bytes in {leaf_kind(o)}")
    print(f"tier moves: every leaf's memory kind matches its tier "
          f"({sum(o.tier == 'fast' for o in objs.values())} fast, "
          f"{sum(o.tier == 'slow' for o in objs.values())} slow)", flush=True)

    for o in objs.values():                 # everything back to device
        if o.tier == "slow":
            rt.backend.wait(rt.backend.start_move(o, "fast"))
    check(all(o.tier == "fast" and leaf_kind(o) == KIND_OF_TIER["fast"]
              for o in objs.values()), "every leaf back in device memory")
    return jax.tree_util.tree_unflatten(
        treedef, [objs[n].payload for n in names])


@jax.jit
def _bits_equal(a, b):
    """Per-leaf bit identity (a bitcast compare: NaNs and -0.0 count)."""
    def same(x, y):
        u = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
        return jnp.array_equal(jax.lax.bitcast_convert_type(x, u),
                               jax.lax.bitcast_convert_type(y, u))
    return jax.tree_util.tree_map(same, a, b)


# ----------------------------------------------------------------- main
def main() -> None:
    args = parse_args()
    cache_dir = enable_compile_cache()
    dev = device_check(args.tiny)
    print(f"compile cache: {cache_dir}", flush=True)

    cfg = get_config("gemma-2b")
    if args.tiny:
        cfg = cfg.reduced()
        batch, prompt, n_new, max_seq = 2, 8, 8, 32
    else:
        batch, prompt, n_new, max_seq = 8, 128, 64, 2048
    key_w, key_p = jax.random.split(jax.random.PRNGKey(args.seed))
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(lambda k: lm.init_params(cfg, k))(key_w))
    n_bytes = sum(l.size * l.dtype.itemsize
                  for l in jax.tree_util.tree_leaves(params))
    print(f"params: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"vocab={cfg.vocab_size} {n_bytes} bytes, seed={args.seed}, "
          f"init {time.perf_counter() - t0:.3f} s", flush=True)
    prompts = jax.random.randint(key_p, (N_REQUESTS, batch, prompt),
                                 0, cfg.vocab_size, jnp.int32)

    ref_tokens = serve(cfg, params, prompts, n_new, max_seq)

    moved = tier_moves(dev, params)
    same = jax.tree_util.tree_leaves(_bits_equal(moved, params))
    check(all(bool(s) for s in same),
          "parameters bit-identical after the round trips")
    print(f"tier moves: all {len(same)} leaves bit-identical to the "
          f"originals", flush=True)
    again, _ = timed_generate(
        ServeEngine(cfg, moved, max_seq=max_seq, batch=batch),
        prompts[0], n_new)
    check(np.array_equal(again, ref_tokens[0]),
          "tokens from the moved parameters equal the reference")
    print("tier moves: request served from the moved parameters gives the "
          "reference tokens", flush=True)

    if args.tiny:
        print("rehearsal passed (reduced config; not a chip result)")
        return
    devs = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
