"""End-to-end driver: train a ~100M-param qwen2-family LM for a few
hundred steps on CPU with the production code path (deliverable b).

  PYTHONPATH=src python examples/train_lm.py [--steps 300]

The config is the qwen2-1.5b architecture scaled to ~100M params
(8 layers, d_model=512, GQA kv=2, SwiGLU, QKV bias — same family,
same code path as the full config the dry-run compiles for 256 chips).
Loss on the synthetic Markov stream should drop well below the uniform
baseline ln(V).
"""
import argparse
import math

from repro.launch.mesh import make_mesh
from repro.models import get_config
from repro.train.data import DataConfig
from repro.train.loop import LoopConfig, run
from repro.train.optim import OptConfig


def config_100m():
    return get_config("qwen2-1.5b").replace(
        name="qwen2-100m", num_layers=8, d_model=512, num_heads=8,
        num_kv_heads=2, head_dim=64, d_ff=1536, vocab_size=8192,
        dtype="float32", attn_impl="ref", seq_shard_activations=False,
        fsdp=False)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()

    cfg = config_100m()
    n = cfg.param_count()
    print(f"training {cfg.name}: {n / 1e6:.1f}M params, "
          f"{args.steps} steps, batch {args.batch} x seq {args.seq}")
    mesh = make_mesh((1, 1), ("data", "model"))
    report = run(
        cfg, mesh,
        DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                   global_batch=args.batch, structure=31),
        opt_cfg=OptConfig(lr=3e-4, total_steps=args.steps,
                          warmup_steps=args.steps // 20),
        loop_cfg=LoopConfig(total_steps=args.steps, ckpt_every=100,
                            ckpt_dir="ckpt/train_lm", log_every=20))
    uniform = math.log(cfg.vocab_size)
    print(f"uniform baseline {uniform:.3f}; "
          f"first loss {report.losses[0]:.3f}; "
          f"final loss {report.final_loss:.3f}")
    assert report.final_loss < report.losses[0]


if __name__ == "__main__":
    main()
