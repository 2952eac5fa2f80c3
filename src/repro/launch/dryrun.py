import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run (assignment deliverable e).

For every (architecture x input-shape x mesh) cell:
  1. build the production mesh (single-pod 16x16 / multi-pod 2x16x16),
  2. jit the real train_step / prefill / serve_step with the U-mode
     shardings and ``.lower()`` it on ShapeDtypeStruct inputs,
  3. ``.compile()`` — the SPMD partitioner must accept every sharding,
  4. print ``compiled.memory_analysis()`` (fits?) and
     ``compiled.cost_analysis()`` (FLOPs/bytes),
  5. parse the per-device HLO for collective payload bytes (trip-count
     scaled) and emit a JSON row for §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out results.json]
"""
import argparse
import json
import sys
import time
import traceback

import jax

from repro.configs import ASSIGNED, SHAPES, cell_applicable, input_specs
from repro.core import analyze, build_terms, SINGLE_POD, MULTI_POD
from repro.core.roofline import (attention_flops, model_flops_decode,
                                 model_flops_prefill, model_flops_train)
from repro.launch.mesh import make_production_mesh
from repro.models import get_config
from repro.sharding import umode
from repro.train.optim import OptConfig


def lower_cell(cfg, cell, mesh):
    sds = input_specs(cfg, cell)
    with mesh:
        if cell.kind == "train":
            return umode.lower_train_step(cfg, mesh, sds, OptConfig())
        return umode.lower_serve_step(cfg, mesh, cell.kind, sds, cell=cell)


def model_flops_for(cfg, cell):
    n_active = cfg.active_param_count()
    B, S = cell.global_batch, cell.seq_len
    if cell.kind == "train":
        attn = 3 * attention_flops(B, S, cfg.num_heads, cfg.hd,
                                   cfg.num_layers) if cfg.num_heads else 0.0
        return model_flops_train(n_active, B * S) + attn
    if cell.kind == "prefill":
        attn = attention_flops(B, S, cfg.num_heads, cfg.hd,
                               cfg.num_layers) if cfg.num_heads else 0.0
        return model_flops_prefill(n_active, B * S, attn)
    # decode: one token/seq; KV read flops = 2*2*S*K*hd*H? -> QK^T+PV per layer
    kv_flops = (4.0 * B * cfg.num_heads * S * cfg.hd * cfg.num_layers
                if cfg.num_heads else 0.0)
    return model_flops_decode(n_active, B, kv_flops)


def run_cell(arch: str, shape: str, multi_pod: bool, verbose: bool = True):
    cfg = get_config(arch)
    cell = SHAPES[shape]
    ok, why = cell_applicable(cfg, cell)
    row = {"arch": arch, "shape": shape,
           "mesh": "(2,16,16)" if multi_pod else "(16,16)",
           "chips": 512 if multi_pod else 256}
    if not ok:
        row.update(status="skipped", reason=why)
        return row
    spec = MULTI_POD if multi_pod else SINGLE_POD
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        lowered = lower_cell(cfg, cell, mesh)
        compiled = lowered.compile()
    except Exception as e:  # a failure here is a bug in our sharding
        row.update(status="FAILED", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        return row
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    hlo_cost = analyze(compiled.as_text())
    terms = build_terms(
        cell=f"{arch}/{shape}", mesh_name=row["mesh"], chips=row["chips"],
        cost_analysis=ca, hlo_cost=hlo_cost, spec=spec,
        model_flops_global=model_flops_for(cfg, cell))
    row.update(
        status="ok", compile_s=round(t_compile, 1),
        argument_bytes_per_device=getattr(mem, "argument_size_in_bytes", None),
        output_bytes_per_device=getattr(mem, "output_size_in_bytes", None),
        temp_bytes_per_device=getattr(mem, "temp_size_in_bytes", None),
        peak_bytes_per_device=(
            getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "temp_size_in_bytes", 0)),
        flops_per_device=terms.flops_per_device,
        hbm_bytes_per_device=terms.hbm_bytes_per_device,
        collective_bytes_per_device=terms.coll_bytes_per_device,
        collective_bytes_by_kind=terms.coll_bytes_by_kind,
        t_compute=terms.t_compute, t_memory=terms.t_memory,
        t_collective=terms.t_collective,
        t_collective_sim=terms.t_collective_sim,
        dominant=terms.dominant, bound_time=terms.bound_time,
        roofline_fraction=terms.roofline_fraction,
        model_flops_global=terms.model_flops_global,
        useful_ratio=terms.useful_ratio,
        unknown_trip_counts=hlo_cost.unknown_trip_counts,
    )
    if verbose:
        print(f"--- {arch}/{shape} {row['mesh']} ---")
        print(f"  memory_analysis: {mem}")
        print(f"  cost_analysis: flops={ca.get('flops', 0):.4g} "
              f"bytes={ca.get('bytes accessed', 0):.4g}")
        print(f"  roofline: compute={terms.t_compute:.4g}s "
              f"memory={terms.t_memory:.4g}s "
              f"collective(spec)={terms.t_collective:.4g}s "
              f"collective(sim)={terms.t_collective_sim:.4g}s "
              f"dominant={terms.dominant} "
              f"useful={terms.useful_ratio:.2f} "
              f"roofline%={100 * terms.roofline_fraction:.1f}",
              flush=True)
    return row


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    rows = []
    if args.all:
        archs = ASSIGNED
        shapes = list(SHAPES)
    else:
        archs = [args.arch] if args.arch else ASSIGNED
        shapes = [args.shape] if args.shape else list(SHAPES)
    for arch in archs:
        for shape in shapes:
            row = run_cell(arch, shape, args.multi_pod)
            rows.append(row)
            if row["status"] == "FAILED":
                print(f"FAILED {arch}/{shape}: {row['error']}",
                      file=sys.stderr, flush=True)
            elif row["status"] == "skipped":
                print(f"skipped {arch}/{shape}: {row['reason']}", flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1, default=str)
        print(f"wrote {args.out}")
    failed = [r for r in rows if r["status"] == "FAILED"]
    print(f"\n{len(rows)} cells: {sum(r['status'] == 'ok' for r in rows)} ok, "
          f"{sum(r['status'] == 'skipped' for r in rows)} skipped, "
          f"{len(failed)} FAILED")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
