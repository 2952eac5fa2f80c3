"""Serving launcher: continuous-batching engine over synthetic requests.

  python -m repro.launch.serve --arch mamba2-1.3b-smoke --requests 16 \
      --slots 4 --max-new 12
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.models import api, get_config
from repro.serve import Engine, Request
from .compile_cache import use_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b-smoke")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=12)
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = get_config(args.arch)
    params = api.init(jax.random.PRNGKey(0), cfg)
    engine = Engine(cfg, params, slots=args.slots, max_seq=args.max_seq)
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        n = int(rng.integers(4, 16))
        engine.submit(Request(uid=i,
                              prompt=rng.integers(0, cfg.vocab_size, n),
                              max_new_tokens=args.max_new))
    t0 = time.time()
    done = engine.run_until_drained()
    dt = time.time() - t0
    toks = sum(len(r.output) for r in done)
    print(f"served {len(done)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s); stats={engine.stats()}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
