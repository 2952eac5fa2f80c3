"""Find a serving cell's knee once: offer Poisson arrivals at several rates
with the cell's own lengths and engine, one window per rate, in one
process (one set-up), and print what each rate gave.

    python3 benchmarks/chip/sweep.py --workload qwen2-1.5b.chat \
        --rates 3,4,5,6,7,8 --seconds 30 --seed 11

The knee is the highest rate at which the backlog does not grow through
the window.  It is found when a cell is defined and written into the
cell's traffic file; no benchmark run searches for it.
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


def reset(engine) -> None:
    """Drop every request the engine holds; admission rewrites a slot."""
    engine.queue.clear()
    engine.active.clear()
    engine.remaining.clear()
    engine._finished_early = []


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=11)
    args = ap.parse_args(argv)
    cell = common.find_cell(args.workload)
    common.device_check(cell.chips)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    serve = cell.module("drivers", "serve")
    cfg, params, engine = serve.setup(cell, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = dict(cell.traffic,
                       arrivals={"kind": "poisson", "rate_rps": rate})
        arrivals = gen.make_arrivals(traffic, args.seconds, args.seed,
                                     cfg.vocab_size)
        reset(engine)
        w = serve.play(engine, arrivals, args.seconds,
                       gen.lead_in_s(cell.traffic))
        lat = serve.latencies(w)
        pct = lambda k, q: common.percentile(lat[k], q) if lat[k] else None
        row = {"rate_rps": rate, "arrived": len(w.recs),
               "finished": sum(1 for r in w.recs if r.req and r.req.done),
               "backlog_end": len(engine.queue),
               "active_end": len(engine.active),
               "out_tok_s": w.tokens / w.window_s,
               "busy_at_open": w.busy_at_open,
               "backlog_open": w.queued_at_open,
               "ttft_p50_ms": pct("ttft", 50), "ttft_p85_ms": pct("ttft", 85),
               "ttft_p95_ms": pct("ttft", 95),
               "tpot_p50_ms": pct("tpot", 50), "tpot_p85_ms": pct("tpot", 85),
               "tpot_p95_ms": pct("tpot", 95),
               "queue_wait_p95_ms": pct("queue_wait", 95),
               "steps": w.steps, "prefills": w.prefills,
               "window_s": w.window_s}
        print("sweep: " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
