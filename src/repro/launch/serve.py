"""Serving launcher: batched greedy generation on a reduced config.

  python -m repro.launch.serve --arch gemma --reduced --batch 4 --new 32
"""

from __future__ import annotations

import argparse
import time

import jax

from ..configs import get_config
from ..models import lm
from ..serve.engine import ServeEngine
from .compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=256)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, max_seq=args.max_seq,
                         batch=args.batch)
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    t0 = time.perf_counter()
    out = engine.generate(prompts, args.new)
    dt = time.perf_counter() - t0
    total = engine.stats.prefill_tokens + engine.stats.decode_tokens
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({total / dt:.0f} tok/s incl. prefill)")
    print("sample:", out[0, :24].tolist())


if __name__ == "__main__":
    main()
