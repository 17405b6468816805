"""Training launcher.

CPU-scale smoke runs use reduced configs; the production path is the same
code under a real TPU mesh.

  python -m repro.launch.train --arch yi-6b --reduced --steps 50
"""

from __future__ import annotations

import argparse

from ..configs import get_config
from ..optim import AdamWConfig
from ..train.loop import TrainConfig, train
from .compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--moments", choices=["float32", "bfloat16", "int8"],
                    default="float32")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    tcfg = TrainConfig(steps=args.steps, global_batch=args.batch,
                       seq_len=args.seq_len, lr=args.lr,
                       microbatches=args.microbatches,
                       checkpoint_dir=args.checkpoint_dir)
    opt = AdamWConfig(lr=args.lr, moments_dtype=args.moments)
    result = train(cfg, tcfg, opt)
    print(f"final loss: {result.losses[-1]:.4f} "
          f"(first: {result.losses[0]:.4f}); "
          f"mean step {1e3 * sum(result.step_times[1:]) / max(1, len(result.step_times) - 1):.0f} ms")
    print("unimem:", result.runtime_stats)


if __name__ == "__main__":
    main()
