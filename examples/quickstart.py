"""Quickstart: the full public API in one file, CPU-runnable.

  PYTHONPATH=src python examples/quickstart.py

1. pick an assigned architecture (reduced config), init params;
2. train a few steps with the fault-tolerant loop (AdamW, checkpoints);
3. serve a few requests with the continuous-batching engine;
4. dry-run style analysis: lower the step, parse the machine-level HLO,
   replay it on the MGSim-TPU system model and print the roofline.
"""
import tempfile

import jax
import numpy as np

from repro.core import SINGLE_POD, analyze, build_terms, simulate
from repro.launch.mesh import make_mesh
from repro.models import api, get_config
from repro.serve import Engine, Request
from repro.train.data import DataConfig
from repro.train.loop import LoopConfig, run
from repro.train.optim import OptConfig

ARCH = "qwen2-1.5b-smoke"


def main():
    cfg = get_config(ARCH)
    mesh = make_mesh((1, 1), ("data", "model"))

    # ---- 2. train -------------------------------------------------------
    print(f"== training {ARCH} ==")
    # fresh checkpoint dir: a leftover checkpoint at step 20 would resume
    # past the loop and train zero steps
    with tempfile.TemporaryDirectory(prefix="quickstart_ckpt_") as ckpt_dir:
        report = run(cfg, mesh,
                     DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                global_batch=4),
                     opt_cfg=OptConfig(lr=1e-3, total_steps=20,
                                       warmup_steps=2),
                     loop_cfg=LoopConfig(total_steps=20, ckpt_every=10,
                                         ckpt_dir=ckpt_dir, log_every=5))
    print(f"loss {report.losses[0]:.3f} -> {report.final_loss:.3f}")

    # ---- 3. serve -------------------------------------------------------
    print("== serving ==")
    params = api.init(jax.random.PRNGKey(0), cfg)
    engine = Engine(cfg, params, slots=2, max_seq=64)
    for i in range(4):
        engine.submit(Request(uid=i,
                              prompt=np.arange(3 + i, dtype=np.int32),
                              max_new_tokens=5))
    done = engine.run_until_drained()
    print(f"served {len(done)} requests; first output: {done[0].output}")

    # ---- 4. analyze -----------------------------------------------------
    print("== machine-level analysis (MGSim-TPU) ==")
    batch = {"tokens": jax.ShapeDtypeStruct((4, 32), jax.numpy.int32),
             "targets": jax.ShapeDtypeStruct((4, 32), jax.numpy.int32)}
    compiled = jax.jit(lambda p, b: api.loss(p, cfg, b)).lower(
        jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0), cfg)),
        batch).compile()
    cost = analyze(compiled.as_text())
    terms = build_terms(f"{ARCH}/quickstart", "(1,1)", 1,
                        compiled.cost_analysis(), cost, SINGLE_POD)
    rep = simulate(cost=cost, spec=SINGLE_POD, device_limit=1)
    print(f"flops={terms.flops_per_device:.3g} "
          f"hbm={terms.hbm_bytes_per_device:.3g}B "
          f"dominant={terms.dominant}")
    print(f"simulated step time on a v5e chip: {rep.time_s * 1e3:.3f} ms "
          f"(util {rep.compute_util:.2f})")


if __name__ == "__main__":
    main()
