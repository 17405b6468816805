"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the step function (train_step for ``train_*``, prefill_step for
     ``prefill_*``, serve/decode_step for ``decode_*`` / ``long_*``),
  2. lowers it with ShapeDtypeStruct inputs (no allocation) under explicit
     in/out shardings on the production mesh,
  3. compiles, prints ``memory_analysis()`` (fit proof) and
     ``cost_analysis()`` (roofline inputs),
  4. extracts per-collective byte counts from the compiled HLO, and
  5. re-lowers two reduced-layer probes to extrapolate loop-body costs to
     the full layer count (XLA's cost analysis counts a ``lax.scan`` body
     once — verified experimentally).

HBM-infeasible cells (nemotron-4-340b train on one pod) run in *offload
mode*: the fused step is split into a grads program plus per-slice optimizer
programs whose fp32 state the Unimem runtime keeps on the host tier and
streams through HBM (the paper's technique making the infeasible feasible).

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  python -m repro.launch.dryrun --all --multi-pod both --out experiments/
"""

import argparse
import dataclasses
import functools
import json
import math
import os
import re
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCHS, SHAPES, get_config
from ..configs.base import ArchConfig, ShapeConfig
from ..distributed import sharding as shd
from ..models import lm
from ..optim import AdamWConfig, init_opt_state
from ..serve.engine import build_decode_step
from ..train.step import auto_microbatches, build_grads_step, build_train_step
from .mesh import make_production_mesh

HBM_PER_CHIP = 16 * 1024 ** 3
DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
               "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "c64": 8,
               "f8e4m3fn": 1, "f8e5m2": 1, "s16": 2, "u16": 2}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


# ---------------------------------------------------------------------------
def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    B, S = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    if shape.kind in ("train", "prefill"):
        out = {"tokens": sds((B, S), jnp.int32),
               "labels": sds((B, S), jnp.int32)}
        if cfg.frontend:
            out["frontend"] = sds((B, cfg.frontend_tokens, cfg.d_model),
                                  jnp.bfloat16)
        return out
    return {"token": sds((B,), jnp.int32), "pos": sds((), jnp.int32)}


def _tree_sds(tree):
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)


def _params_shapes(cfg: ArchConfig):
    return _tree_sds(jax.eval_shape(
        functools.partial(lm.init_params, cfg), jax.random.PRNGKey(0)))


def _bytes_of(tree) -> int:
    return sum(int(np.prod(l.shape)) * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(tree))


# ---------------------------------------------------------------------------
def parse_collectives(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Sum result-tensor bytes per collective kind (per-device program)."""
    stats = {c: {"count": 0, "bytes": 0.0} for c in COLLECTIVES}
    pat = re.compile(
        r"=\s+(?:\()?([a-z0-9]+)\[([0-9,]*)\][^)]*?\)?\s+"
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
        r"collective-permute)(?:-start)?\(")
    for m in pat.finditer(hlo_text):
        dt, dims, kind = m.groups()
        size = DTYPE_BYTES.get(dt, 4)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        stats[kind]["count"] += 1
        stats[kind]["bytes"] += n * size
    return stats


def emulation_shadow_bytes(hlo_text: str) -> int:
    """Lower-bound the CPU backend's dtype-emulation overhead.

    The CPU backend computes bf16/fp8 in fp32/fp16, and loop-invariant code
    motion hoists the converted copies out of layer loops — so the compiled
    module holds an f32 twin of bf16 weight stacks and an f16 twin of fp8
    caches that a bf16/fp8-native TPU would never materialize.  Detected as
    same-dims tensors present in both the wide and the narrow dtype; the
    wide copy is counted once."""
    dims_by_dtype: Dict[str, set] = {}
    for m in re.finditer(r"([a-z0-9]+)\[([0-9,]+)\]", hlo_text):
        dims_by_dtype.setdefault(m.group(1), set()).add(m.group(2))

    def nbytes(dims: str, size: int) -> int:
        n = 1
        for d in dims.split(","):
            n *= int(d)
        return n * size

    shadow = 0
    for dims in dims_by_dtype.get("f32", set()) \
            & dims_by_dtype.get("bf16", set()):
        b = nbytes(dims, 4)
        if b > 64 * 1024 ** 2:
            shadow += b
    for dims in dims_by_dtype.get("f16", set()) \
            & dims_by_dtype.get("f8e4m3fn", set()):
        b = nbytes(dims, 2)
        if b > 64 * 1024 ** 2:
            shadow += b
    return shadow


def _reduced_layer_counts(cfg: ArchConfig) -> Tuple[int, int]:
    if cfg.block_pattern == "mamba_shared_attn":
        ids = cfg.hybrid_ids        # each probe ends before an application
        return ids[1], ids[2]
    if cfg.block_pattern == "xlstm":
        g = cfg.slstm_every or 2
        return g, 2 * g
    return 1, 2


def _cost(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    ca = ca or {}
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0))}


# ---------------------------------------------------------------------------
def build_cell(cfg: ArchConfig, shape: ShapeConfig, mesh,
               *, microbatches: Optional[int] = None,
               offload: bool = False, remat: bool = True,
               opt_cfg: Optional[AdamWConfig] = None,
               kv_dtype=jnp.bfloat16, flat_dp: bool = False):
    """Returns (jitted_fn, example_args) ready to .lower(*args)."""
    from ..models.common import set_mesh_hint
    set_mesh_hint(mesh)
    shd.set_flat_dp(flat_dp)
    dp = shd.mesh_axis_size(mesh, shd.dp_axes(mesh))
    tp = shd.mesh_axis_size(mesh, "model")
    pshapes = _params_shapes(cfg)
    pspecs = shd.param_specs(mesh, pshapes)
    psh = shd.shardings(mesh, pspecs)
    ins = input_specs(cfg, shape)

    if shape.kind == "train":
        opt_cfg = opt_cfg or AdamWConfig()
        mb = microbatches or auto_microbatches(
            cfg, shape.global_batch, shape.seq_len, dp, tp)
        bspecs = shd.batch_specs(mesh, cfg, shape)
        bsh = {k: NamedSharding(mesh, bspecs[k]) for k in ins}
        if offload:
            step = build_grads_step(cfg, microbatches=mb, remat=remat)
            jitted = jax.jit(step, in_shardings=(psh, bsh),
                             out_shardings=(psh, None))
            return jitted, (pshapes, ins), {"microbatches": mb,
                                            "mode": "offload-grads"}
        oshapes = _tree_sds(jax.eval_shape(
            functools.partial(init_opt_state, cfg=opt_cfg), pshapes))
        ospecs = shd.opt_specs(mesh, oshapes, pshapes, pspecs)
        osh = shd.shardings(mesh, ospecs)
        step = build_train_step(cfg, opt_cfg, microbatches=mb, remat=remat)
        jitted = jax.jit(step, in_shardings=(psh, osh, bsh),
                         out_shardings=(psh, osh, None),
                         donate_argnums=(0, 1))
        return jitted, (pshapes, oshapes, ins), {"microbatches": mb,
                                                 "mode": "fused"}

    if shape.kind == "prefill":
        bspecs = shd.batch_specs(mesh, cfg, shape)
        bsh = {k: NamedSharding(mesh, bspecs[k]) for k in ins}

        def prefill_step(params, batch):
            logits, _ = lm.forward(params, cfg, batch["tokens"],
                                   batch.get("frontend"), remat=False)
            return logits

        logit_sh = NamedSharding(mesh, shd.fit(
            mesh, (shape.global_batch, shape.seq_len, cfg.vocab_size),
            shd.dp_axes(mesh), None, "model"))
        jitted = jax.jit(prefill_step, in_shardings=(psh, bsh),
                         out_shardings=logit_sh)
        return jitted, (pshapes, ins), {"mode": "prefill"}

    # decode: one new token against a seq_len cache
    cache_shapes = _tree_sds(jax.eval_shape(
        lambda _: lm.init_cache(cfg, shape.global_batch, shape.seq_len,
                                kv_dtype=kv_dtype),
        0))
    cspecs = shd.cache_specs(mesh, cfg, cache_shapes, shape.global_batch)
    csh = shd.shardings(mesh, cspecs)
    batch_ok = shape.global_batch % dp == 0
    tok_spec = shd.fit(mesh, (shape.global_batch,),
                       shd.dp_axes(mesh) if batch_ok else None)
    tok_sh = NamedSharding(mesh, tok_spec)
    logits_sh = NamedSharding(mesh, shd.fit(
        mesh, (shape.global_batch, cfg.vocab_size),
        shd.dp_axes(mesh) if batch_ok else None, "model"))
    step = build_decode_step(cfg)
    jitted = jax.jit(step,
                     in_shardings=(psh, csh, tok_sh, NamedSharding(mesh, P())),
                     out_shardings=(tok_sh, logits_sh, csh),
                     donate_argnums=(1,))
    args = (pshapes, cache_shapes, ins["token"], ins["pos"])
    return jitted, args, {"mode": "decode",
                          "kv_dtype": str(jnp.dtype(kv_dtype))}


# ---------------------------------------------------------------------------
#: operand layout per cell mode: which positional args of the jitted step are
#: registered Unimem objects (name) vs unregistered inputs (None -> leaf
#: count taken from the example tree)
_ATTRIBUTION_OPERANDS = {
    "fused": ("params", "opt_state", None),
    "offload-grads": ("params", None),
    "prefill": ("params", None),
    "decode": ("params", "kv_cache", None, None),
}


def unimem_attribution(compiled, args, mode: str,
                       n_bins: int = 64) -> Dict[str, Any]:
    """Map the compiled cell's per-op operand footprints onto Unimem data
    objects (the TPU attribution analogue: no PEBS on TPU, so per-chunk
    ``access_bins`` come from XLA cost analysis instead — and feed the
    exact same profiler pipeline the simulator drives).

    Registers each managed arg tree pytree-natively (recording leaf byte
    spans), binds the compiled program through
    :class:`~repro.core.instrumentation.XlaCostAnalysisSource`, and returns
    a JSON-able summary of the measured per-object access histograms."""
    from ..core.instrumentation import XlaCostAnalysisSource
    from ..core.session import Session
    from ..core.tiers import TPU_V5E

    sess = Session(TPU_V5E)
    operands = []
    for name, tree in zip(_ATTRIBUTION_OPERANDS[mode], args):
        if name is None:
            operands.append(tree)
        else:
            sess.register(name, tree, chunkable=(name != "params"))
            operands.append(name)
    src = XlaCostAnalysisSource(sess, n_bins=n_bins)
    sample = src.bind("step", compiled, operands)
    out: Dict[str, Any] = {}
    for obj, acc in sorted(sample.accesses.items()):
        bins = np.asarray((sample.access_bins or {}).get(obj, []))
        entry: Dict[str, Any] = {"accesses": float(acc)}
        if bins.size and bins.sum() > 0:
            w = bins / bins.sum()
            entry["n_bins"] = int(bins.size)
            entry["nonzero_bins"] = int((bins > 0).sum())
            entry["peak_over_mean"] = float(w.max() * bins.size)
            entry["bins"] = [round(float(x), 6) for x in w]
        out[obj] = entry
    return out


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             probes: bool = True, verbose: bool = True,
             flat_dp: bool = False,
             attribution: bool = False) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cfg.shape_applicable(shape)
    cell_id = f"{cfg.name}|{shape_name}|{'2x16x16' if multi_pod else '16x16'}"
    if not ok:
        return {"cell": cell_id, "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = math.prod(mesh.devices.shape)

    # offload mode when fused optimizer state leaves too little headroom
    # (the Unimem planner's host-tier placement of fp32 master/moments)
    opt_cfg = AdamWConfig()
    state_bytes = cfg.n_params() * (2 + 12)          # bf16 + fp32 master/m/v
    offload = (shape.kind == "train"
               and state_bytes / n_chips > 0.35 * HBM_PER_CHIP)

    t0 = time.time()
    microbatches = None
    kv_dtype = jnp.bfloat16
    for attempt in range(4):
        jitted, args, info = build_cell(cfg, shape, mesh, offload=offload,
                                        opt_cfg=opt_cfg,
                                        microbatches=microbatches,
                                        kv_dtype=kv_dtype, flat_dp=flat_dp)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        mem = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "generated_code_bytes": int(ma.generated_code_size_in_bytes),
        }
        mem["peak_bytes"] = (mem["argument_bytes"] + mem["output_bytes"]
                             + mem["temp_bytes"] - mem["alias_bytes"])
        if mem["peak_bytes"] <= 0.95 * HBM_PER_CHIP:
            break
        if shape.kind == "train" \
                and info.get("microbatches", 1) < shape.global_batch:
            # fit loop: double the microbatch count and recompile
            microbatches = info.get("microbatches", 1) * 2
        elif shape.kind == "decode" and kv_dtype == jnp.bfloat16:
            # fit loop: fp8 KV cache (halves cache HBM)
            kv_dtype = jnp.float8_e4m3fn
        else:
            break
    compile_s = time.time() - t0
    cost_full = _cost(compiled)
    hlo_text = compiled.as_text()
    coll = parse_collectives(hlo_text)
    # distinct tensors can share a dims-string, so cap the shadow estimate
    # at 80% of temp (the shadows are always temps)
    shadow = min(emulation_shadow_bytes(hlo_text),
                 int(0.8 * mem["temp_bytes"]))
    mem["emulation_shadow_bytes"] = shadow
    mem["peak_tpu_estimate_bytes"] = mem["peak_bytes"] - shadow

    result: Dict[str, Any] = {
        "cell": cell_id, "status": "ok", "mode": info["mode"],
        "n_chips": n_chips, "compile_s": round(compile_s, 2),
        "microbatches": info.get("microbatches"),
        "memory": mem, "cost_raw": cost_full, "collectives_raw": coll,
        "fits_hbm": mem["peak_bytes"] <= HBM_PER_CHIP,
        "fits_hbm_tpu_estimate":
            mem["peak_tpu_estimate_bytes"] <= HBM_PER_CHIP,
    }

    if attribution:
        # hardware-path instrumentation: per-object access_bins from the
        # compiled program's operand footprints (ROADMAP "TPU attribution
        # analogue") — the same sample stream the simulator's SimSource
        # produces, so it flows through the identical profiler pipeline
        result["unimem_attribution"] = unimem_attribution(
            compiled, args, info["mode"])

    if offload:
        result["offload"] = offload_programs(cfg, shape, mesh, opt_cfg)
        # device residency proof = grads program peak + streamed slice
        result["fits_hbm"] = (mem["peak_bytes"]
                              + result["offload"]["slice_peak_bytes"]
                              <= HBM_PER_CHIP)

    if probes:
        result["roofline_inputs"] = cost_probes(cfg, shape, mesh,
                                                offload=offload)

    if verbose:
        print(f"[{cell_id}] {result['mode']} compile={compile_s:.1f}s "
              f"peak={mem['peak_bytes']/2**30:.2f}GiB "
              f"fits={result['fits_hbm']}")
        print("  memory_analysis:", {k: f"{v/2**30:.3f}GiB"
                                     for k, v in mem.items()
                                     if k != 'generated_code_bytes'})
        print("  cost_analysis(raw):", cost_full)
    return result


def cost_probes(cfg: ArchConfig, shape: ShapeConfig, mesh,
                *, offload: bool) -> Dict[str, Any]:
    """Two reduced-layer lowers -> per-layer deltas -> full-model totals."""
    L1, L2 = _reduced_layer_counts(cfg)
    out = {}
    for L in (L1, L2):
        c = dataclasses.replace(cfg, n_layers=L)
        jitted, args, _ = build_cell(c, shape, mesh, microbatches=1,
                                     offload=offload, remat=True)
        compiled = jitted.lower(*args).compile()
        cost = _cost(compiled)
        coll = parse_collectives(compiled.as_text())
        out[f"L{L}"] = {"cost": cost, "collectives": coll}
    L = cfg.n_layers
    c1, c2 = out[f"L{L1}"], out[f"L{L2}"]

    def extrap(a, b):
        per_layer = (b - a) / (L2 - L1)
        return b + per_layer * (L - L2)

    flops = extrap(c1["cost"]["flops"], c2["cost"]["flops"])
    hbytes = extrap(c1["cost"]["bytes"], c2["cost"]["bytes"])
    coll_bytes = {}
    for kind in COLLECTIVES:
        coll_bytes[kind] = extrap(c1["collectives"][kind]["bytes"],
                                  c2["collectives"][kind]["bytes"])
    return {"probe_layers": [L1, L2], "flops_per_device": flops,
            "bytes_per_device": hbytes, "collective_bytes": coll_bytes,
            "probes": out}


# ---------------------------------------------------------------------------
def offload_programs(cfg: ArchConfig, shape: ShapeConfig, mesh,
                     opt_cfg: AdamWConfig,
                     n_slices: int = 12) -> Dict[str, Any]:
    """Per-slice optimizer-update program (host-tier state streamed through
    HBM by the Unimem mover).  Compiles one representative slice."""
    from ..optim.adamw import adamw_update

    L_slice = max(1, cfg.n_layers // n_slices)
    c = dataclasses.replace(cfg, n_layers=L_slice)
    pshapes = _params_shapes(c)
    # drop embed/head (they get their own slice; blocks dominate)
    blocks = {k: v for k, v in pshapes.items() if "blocks" in k}
    pspecs = shd.param_specs(mesh, blocks)
    psh = shd.shardings(mesh, pspecs)
    oshapes = _tree_sds(jax.eval_shape(
        functools.partial(init_opt_state, cfg=opt_cfg), blocks))
    ospecs = shd.opt_specs(mesh, oshapes, blocks, pspecs)
    osh = shd.shardings(mesh, ospecs)
    gsh = jax.tree_util.tree_map(
        lambda s: s, psh)   # grads shard like params

    def upd(params, opt_state, grads):
        new_p, new_o, _ = adamw_update(grads, params, opt_state, opt_cfg,
                                       jnp.float32(1e-4))
        return new_p, new_o

    jitted = jax.jit(upd, in_shardings=(psh, osh, gsh),
                     out_shardings=(psh, osh), donate_argnums=(0, 1))
    compiled = jitted.lower(blocks, oshapes, blocks).compile()
    ma = compiled.memory_analysis()
    peak = int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    n_chips = math.prod(mesh.devices.shape)
    slice_state = _bytes_of(oshapes) / n_chips
    return {
        "n_slices": n_slices, "layers_per_slice": L_slice,
        "slice_peak_bytes": peak,
        "slice_state_bytes_per_chip": int(slice_state),
        "host_resident_bytes_per_chip": int(
            cfg.n_params() * 12 / n_chips),
        "note": "fp32 master+moments live on host tier; the Unimem mover "
                "streams slices through HBM overlapped with backward "
                "(paper Fig 5/6 trigger-point schedule)",
    }


# ---------------------------------------------------------------------------
def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["off", "on", "both"],
                    default="off")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--attribution", action="store_true",
                    help="emit per-object Unimem access_bins from XLA "
                         "cost-analysis operand footprints")
    ap.add_argument("--flat-dp", action="store_true",
                    help="fold the model axis into DP (small-model profile)")
    ap.add_argument("--out", default=None, help="directory for JSON results")
    args = ap.parse_args()
    # 512 virtual CPU devices for the production meshes; XLA reads the flag
    # when the backend first starts, which is the first device query below
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

    cells = []
    archs = sorted(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    pods = {"off": [False], "on": [True], "both": [False, True]}[args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in pods:
                cells.append((a, s, mp))

    results = []
    for a, s, mp in cells:
        try:
            r = run_cell(a, s, multi_pod=mp, probes=not args.no_probes,
                         flat_dp=args.flat_dp, attribution=args.attribution)
        except Exception as e:  # noqa: BLE001 — report and continue
            r = {"cell": f"{a}|{s}|{'2x16x16' if mp else '16x16'}",
                 "status": "error", "error": f"{type(e).__name__}: {e}"}
            print(f"[{r['cell']}] ERROR {r['error']}")
        results.append(r)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            fn = r["cell"].replace("|", "_").replace("/", "_") + ".json"
            with open(os.path.join(args.out, fn), "w") as f:
                json.dump(r, f, indent=2)

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\n== dry-run summary: {n_ok} ok, {n_skip} skipped "
          f"(documented), {n_err} errors ==")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
