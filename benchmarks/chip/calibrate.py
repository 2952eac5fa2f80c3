"""Readings that a serving cell's correctness limit is set from, in one
process: for each seed, weights from the seed, one window of the cell's
own traffic, and on the same sample of finished requests the widest logit
gap of the program's tokens and of the control's.  The control is the
plain reference computed with every matrix product in fp8 (e4m3, one
scale per row or column), the step below the configuration's bf16.

    python3 benchmarks/chip/calibrate.py --workload qwen2-1.5b.chat \
        --seeds 1,2,3 --seconds 40

The lower reading is the largest program gap over the seeds, the upper
the smallest control gap; ``limits/<cell>.json`` lies between them.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import common  # noqa: E402
from benchmarks.chip import generator as gen  # noqa: E402
from benchmarks.chip.sweep import reset  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = common.find_cell(args.workload)
    common.device_check(cell.chips)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    serve = cell.module("drivers", "serve")
    seeds = [int(s) for s in args.seeds.split(",")]
    cfg, params, engine = serve.setup(cell, seeds[0])
    prog_all, ctrl_all = [], []
    for seed in seeds:
        params = engine.params = serve.make_weights(cell, cfg, seed)
        reset(engine)
        arrivals = gen.make_arrivals(cell.traffic, args.seconds, seed,
                                     cfg.vocab_size)
        w = serve.play(engine, arrivals, args.seconds,
                       gen.lead_in_s(cell.traffic))
        picked = serve.sample(w.served, cell.traffic["check"]["sample"], seed)
        prog, ctrl = serve.gaps(cell, params, picked, control=True)
        prog_all.append(max(prog))
        ctrl_all.append(max(ctrl))
        print("calibrate: " + json.dumps({
            "seed": seed, "requests": len(picked),
            "tokens": sum(len(r.req.output) for r in picked),
            "program_max_gap": max(prog), "control_max_gap": max(ctrl),
            "program_each": prog, "control_each": ctrl,
            "bad_outputs": serve.bad_outputs(w.served, cfg.vocab_size)}),
            flush=True)
    print("calibrate summary: " + json.dumps({
        "lower_reading": max(prog_all), "upper_reading": min(ctrl_all),
        "seeds": seeds}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
