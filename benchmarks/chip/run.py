"""Run one cell of the chip benchmark and print its result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

One process, one cell: check the chip, build the cell from the files that
BENCHMARK.json names, warm it, measure for ``--seconds``, check the answers
against the plain reference, and print one JSON object as the last line of
standard output.  With ``--trace 0`` its metrics are the cell's end-to-end
metrics; with ``--trace 1`` the window's last seconds run under the
profiler (``tracing.SPAN_S``) and the metrics are the cell's per-layer
metrics, each read by ``metrics/<name>.py``.  Each number compared for ``correct`` is printed
beside its limit, last on standard error and last in the result line.

A run that finds no TPU, fewer chips than the cell asks for, or a chip not
in ``peaks.json`` exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import common  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"


def per_layer(cell: common.Cell, record) -> dict:
    """Each per-layer metric of the cell, read by its own reader; a reader
    that finds nothing returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        value = cell.module("metrics", m["name"]).read(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(cell: common.Cell, dev: dict, res: dict, trace: bool) -> dict:
    if trace:
        metrics = per_layer(cell, res["record"])
    else:
        metrics = {m["name"]: {"value": float(res["metrics"][m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    checks = res["checks"]
    line = {"correct": all(v <= lim for v, lim in checks.values()),
            "attempted": res["attempted"], "failed": res["failed"],
            "metrics": metrics,
            "device": dict(dev, memory_peak_bytes=res["memory_peak_bytes"])}
    summary = res["record"].trace
    if trace and summary:
        line["device"].update(busy_s=summary["busy_s"],
                              window_s=summary["window_s"])
        line["breakdown"] = {"device_ops": summary["device_ops"],
                             "idle_gaps": summary["idle_gaps"]}
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = common.find_cell(args.workload)
        dev, peaks = common.device_check(cell.chips)
    except common.BenchError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache
    import jax
    use_compile_cache()
    # every program, however quick to compile, goes into the cache, so a
    # second run of the cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    driver = cell.module("drivers", cell.traffic["driver"])
    res = driver.run(cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), t_start=T_START,
                     trace_dir=TRACE_DIR / args.workload)
    res["record"].peaks = peaks
    line = result_line(cell, dev, res, bool(args.trace))
    for k, v in res["notes"].items():
        print(f"{k}: {json.dumps(v)}", file=sys.stderr)
    for k, c in line["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
