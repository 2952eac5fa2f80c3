"""Training launcher.

  python -m repro.launch.train --arch qwen2-1.5b-smoke --steps 50 \
      --mesh 1x1 --batch 8 --seq 64

On this CPU container only smoke-scale configs execute; the full configs
train through the same code path on a real pod (same mesh axes, same
sharding rules — the dry-run proves they lower/compile at scale).
"""
from __future__ import annotations

import argparse

from repro.models import get_config
from repro.train.data import DataConfig
from repro.train.loop import LoopConfig, run
from repro.train.optim import OptConfig
from .compile_cache import use_compile_cache
from .mesh import make_mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b-smoke")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 2x4")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = get_config(args.arch)
    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model"))
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.batch)
    report = run(cfg, mesh, data_cfg,
                 opt_cfg=OptConfig(lr=args.lr, total_steps=args.steps,
                                   warmup_steps=max(1, args.steps // 10)),
                 loop_cfg=LoopConfig(total_steps=args.steps,
                                     ckpt_every=args.ckpt_every,
                                     ckpt_dir=args.ckpt_dir))
    print(f"final loss {report.final_loss:.4f} after {report.final_step} "
          f"steps (restarts={report.restarts})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
