"""Who owns each idle and each busy millisecond of a traced serving run:
the program's own spans, programs and scopes, read from the profiler's
trace, and the per-layer numbers they give.

    python3 benchmarks/chip/attribution.py --workload qwen2-1.5b.chat \
        --seed <n> [--seconds 51] [--out <file>.json]

A run is the cell's traced run (``run.py --trace 1``): the same set-up,
traffic, window and profiled last ``tracing.SPAN_S`` seconds, without the
correctness check.  Where ``tracing.load`` keeps only the benchmark's own
spans, this loader also keeps the program's (``serve.*`` from
``serve.Engine``, ``host.gc`` from ``GcSpans``) with their arguments, and
each chip's ``XLA Modules`` line.  ``reduce`` then gives, over the traced
span:

* ``span_self_s``, ``span_total_s``, ``span_n``: for each span name, the
  time in which it was the innermost span open, its whole time, and how
  many started in the span;
* ``idle_by_span_s``: all of the first chip's idle time, split by the
  innermost span open ("no span" outside every span but ``traced``);
* ``module_s``: device time of each program (``jit_serve_decode``, ...);
* ``scope_s``: device self time of the operations inside
  ``jit_serve_decode`` intervals (a loop's time less that of the
  operations it runs), by the named scope that the compiled decode
  program's ``op_name`` metadata gives each instruction
  (``scopes_from_hlo``; the TPU's op events carry no such name).  An
  instruction without ``op_name`` takes the scope of the operation it runs
  inside; one whose ``op_name`` holds no scope counts as ``(no scope)``.

The result is printed as one JSON line: ``tracing.reduce``'s keys and
these, the engine's counters over the traced span (``Engine.stats()``
differences), the readers of ``metrics/`` named in ``READERS`` on that
record, garbage collections in the window, how late the generator ran, and
the tokens per second of the window (to set against an untraced run of the
same seed).
"""
from __future__ import annotations

import bisect
import dataclasses
import gc
import json
import pathlib
import re
import shutil
import sys
import time
import types
import typing

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import common, tracing  # noqa: E402
from benchmarks.chip import generator as gen  # noqa: E402
from benchmarks.chip.tracing import clip, subtract, total, union  # noqa: E402

PROGRAM_SPAN = re.compile(r"^serve\.|^host\.gc$")
MODULES_LINE = "XLA Modules"
DECODE = "jit_serve_decode"
NO_SCOPE = "(no scope)"
# op_name parts that are JAX's own (control flow, remat) and not scopes
_MARKS = {"while", "body", "cond", "closed_call", "checkpoint", "remat"}
_INSTR = re.compile(r'^\s*(?:ROOT )?(%\S+) = .*?op_name="([^"]*)"', re.M)
READERS = ("engine_queue_wait_p95_ms", "admit_ms_per_prefill.serve",
           "prefill_dev_ms.serve", "decode_dev_ms_per_step.serve",
           "retire_ms_per_step.serve", "host_syncs_per_step.serve",
           "batch_per_step.serve", "attention_dev_ms_per_step.decode")
TRACE_DIR = ROOT / ".bench_trace" / "attribution"

Span = typing.Tuple[str, float, float, dict]


@dataclasses.dataclass
class ProgramTrace:
    ops: typing.Dict[int, typing.List[typing.Tuple[str, float, float]]]
    spans: typing.List[Span]            # benchmark's and program's, seconds
    modules: typing.Dict[int, typing.List[typing.Tuple[str, float, float]]]

    def benchmark_view(self) -> tracing.Trace:
        """The trace as ``tracing.reduce`` reads it, every span kept, so
        its idle gaps are labelled by the innermost span of either kind."""
        return tracing.Trace(ops=self.ops,
                             spans=[s[:3] for s in self.spans])


def module_name(event_name: str) -> str:
    """``jit_serve_decode(1234)`` -> ``jit_serve_decode``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def load(out_dir: pathlib.Path) -> ProgramTrace:
    from jax.profiler import ProfileData
    files = sorted(pathlib.Path(out_dir).rglob("*.xplane.pb"))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {out_dir}")
    ops: dict = {}
    modules: dict = {}
    spans = []
    for path in files:
        for plane in ProfileData.from_file(str(path)).planes:
            m = tracing.DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if m and line.name == tracing.OPS_LINE:
                    ops.setdefault(int(m.group(1)), []).extend(
                        (tracing.op_name(e.name), e.start_ns * 1e-9,
                         e.end_ns * 1e-9) for e in line.events)
                elif m and line.name == MODULES_LINE:
                    modules.setdefault(int(m.group(1)), []).extend(
                        (module_name(e.name), e.start_ns * 1e-9,
                         e.end_ns * 1e-9) for e in line.events)
                elif not m:
                    for e in line.events:
                        if e.name in tracing.SPANS:
                            spans.append((e.name, e.start_ns * 1e-9,
                                          e.end_ns * 1e-9, {}))
                        elif PROGRAM_SPAN.match(e.name):
                            spans.append((e.name, e.start_ns * 1e-9,
                                          e.end_ns * 1e-9, dict(e.stats)))
    return ProgramTrace(ops=ops, spans=spans, modules=modules)


def scope_of(op_name: str) -> str:
    """``jit(serve_decode)/while/body/closed_call/attention/kv_update/
    vmap()/scatter`` -> ``attention/kv_update``: the named scopes in the
    path, without the program, JAX's own parts (control flow,
    transformations, einsum specs) and the primitive."""
    parts = op_name.split(";")[0].split("/")[:-1]
    keep = [p for p in parts if p not in _MARKS
            and not re.search(r"[(),\[\]'>;= ]", p)]
    return "/".join(keep) or NO_SCOPE


def scopes_from_hlo(hlo_text: str) -> typing.Dict[str, str]:
    """Instruction name -> named scope, from a compiled program's HLO text."""
    return {name: scope_of(op) for name, op in _INSTR.findall(hlo_text)}


# --------------------------------------------------------------------------
# nesting
# --------------------------------------------------------------------------

def nesting(events: typing.Sequence[typing.Tuple[str, float, float]]
            ) -> typing.Tuple[list, list, list]:
    """For events of one thread or device line (children nest in parents):
    each event's duration less the part its child events cover, the index
    of its parent (-1 at the top), and an order that puts parents first."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [b - a for _, a, b in events]
    parent = [-1] * len(events)
    stack: list = []
    for i in order:
        _, a, b = events[i]
        while stack and events[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            parent[i] = stack[-1]
            own[stack[-1]] -= min(b, events[stack[-1]][2]) - a
        stack.append(i)
    return own, parent, order


def self_intervals(spans: typing.Sequence[typing.Tuple[str, float, float]]
                   ) -> typing.List[typing.List[tracing.Interval]]:
    """For each span, the intervals in which it is the innermost open."""
    _, parent, _ = nesting(spans)
    children: typing.Dict[int, list] = {}
    for i, p in enumerate(parent):
        if p >= 0:
            children.setdefault(p, []).append(spans[i][1:])
    return [subtract([(a, b)], union(clip(children.get(i, []), a, b)))
            for i, (_, a, b) in enumerate(spans)]


def overlap(intervals, disjoint: typing.List[tracing.Interval],
            starts: typing.List[float]) -> float:
    """Time of ``intervals`` inside the sorted disjoint ``disjoint``, whose
    starts are ``starts``."""
    out = 0.0
    for a, b in intervals:
        j = max(0, bisect.bisect_right(starts, a) - 1)
        while j < len(disjoint) and disjoint[j][0] < b:
            out += max(0.0, min(b, disjoint[j][1]) - max(a, disjoint[j][0]))
            j += 1
    return out


# --------------------------------------------------------------------------
# reduction
# --------------------------------------------------------------------------

def reduce(trace: ProgramTrace, chips: typing.Sequence[int],
           scopes: typing.Dict[str, str] = None) -> dict:
    lo, hi = tracing.window_of(trace.benchmark_view())
    n = len(chips)
    inside = [s[:3] for s in trace.spans if s[2] > lo and s[1] < hi]
    spans = [(name, max(a, lo), min(b, hi)) for name, a, b in inside]
    self_iv = self_intervals(spans)
    self_s: dict = {}
    total_s: dict = {}
    count: dict = {}
    for (name, start, _), (_, a, b), iv in zip(inside, spans, self_iv):
        if name == "traced":
            continue
        self_s[name] = self_s.get(name, 0.0) + total(iv)
        total_s[name] = total_s.get(name, 0.0) + b - a
        count[name] = count.get(name, 0) + (start >= lo)

    first = [(a, b) for _, a, b in trace.ops.get(chips[0], [])]
    idle = tracing.gaps(union(clip(first, lo, hi)), lo, hi)
    starts = [a for a, _ in idle]
    idle_by: dict = {}
    for (name, _, _), iv in zip(spans, self_iv):
        label = "no span" if name == "traced" else name
        t = overlap(iv, idle, starts)
        if t:
            idle_by[label] = idle_by.get(label, 0.0) + t

    module_s: dict = {}
    scope_s: dict = {}
    for c in chips:
        by_name: dict = {}
        for name, a, b in trace.modules.get(c, []):
            if b > lo and a < hi:
                by_name.setdefault(name, []).append((a, b))
        for name, iv in by_name.items():
            module_s[name] = module_s.get(name, 0.0) + \
                total(union(clip(iv, lo, hi))) / n
        decode = union(clip(by_name.get(DECODE, []), lo, hi))
        if scopes is None or not decode:
            continue
        dstarts = [a for a, _ in decode]

        def in_decode(a: float) -> bool:
            j = bisect.bisect_right(dstarts, a) - 1
            return j >= 0 and a < decode[j][1]
        evs = [(name, max(a, lo), min(b, hi))
               for name, a, b in trace.ops.get(c, [])
               if b > lo and a < hi and in_decode(max(a, lo))]
        own, parent, order = nesting(evs)
        scope = [NO_SCOPE] * len(evs)
        for i in order:
            # an instruction with no op_name (the loop a scatter becomes,
            # say) belongs to the operation it runs inside
            name = evs[i][0].split(" ")[0]
            scope[i] = scopes.get(name, scope[parent[i]] if parent[i] >= 0
                                  else NO_SCOPE)
            scope_s[scope[i]] = scope_s.get(scope[i], 0.0) + own[i] / n
    return {"span_self_s": self_s, "span_total_s": total_s, "span_n": count,
            "idle_by_span_s": idle_by, "module_s": module_s,
            "scope_s": scope_s}


# --------------------------------------------------------------------------
# capture
# --------------------------------------------------------------------------

class ProgramCapture(tracing.Capture):
    """``tracing.Capture`` that keeps the program's spans and the chips'
    module lines, and the engine's counters when the profile starts and
    stops."""

    def __init__(self, out_dir: pathlib.Path, engine,
                 span_s: float = tracing.SPAN_S) -> None:
        super().__init__(out_dir, True, span_s)
        self.engine = engine
        self.program = None
        self.stats = []

    def start(self) -> None:
        self.stats.append(self.engine.stats())
        super().start()

    def stop(self) -> None:
        if not self.on:
            return
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.on = False
        self.stats.append(self.engine.stats())
        self.program = load(self.out_dir)
        self.trace = self.program.benchmark_view()
        shutil.rmtree(self.out_dir, ignore_errors=True)

    def counters(self) -> dict:
        """Each numeric counter's change over the profiled span."""
        a, b = self.stats
        return {k: b[k] - a[k] for k in a
                if k in b and k not in ("active", "queued")}


class GcSpans:
    """While open, each garbage collection of the process writes a
    ``host.gc`` span (on the profiler's clock, when it runs) and is timed on
    the host clock."""

    def __init__(self) -> None:
        self.pauses: typing.List[typing.Tuple[float, float, int]] = []
        self._span = None
        self._t = 0.0

    def _callback(self, phase: str, info: dict) -> None:
        from jax.profiler import TraceAnnotation
        if phase == "start":
            self._t = time.perf_counter()
            self._span = TraceAnnotation("host.gc",
                                         generation=info["generation"])
            self._span.__enter__()
        elif self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
            self.pauses.append((self._t, time.perf_counter(),
                                info["generation"]))

    def __enter__(self) -> "GcSpans":
        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._callback)

    def summary(self, lo: float, hi: float) -> dict:
        """Collections that started between ``lo`` and ``hi``."""
        inside = [(b - a, g) for a, b, g in self.pauses if lo <= a < hi]
        return {"collections": len(inside),
                "by_generation": {g: sum(1 for _, x in inside if x == g)
                                  for g in sorted({g for _, g in inside})},
                "longest_ms": max((d for d, _ in inside), default=0.0) * 1e3,
                "total_ms": sum(d for d, _ in inside) * 1e3}


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out", type=pathlib.Path)
    args = ap.parse_args(argv)
    try:
        cell = common.find_cell(args.workload)
        dev, _ = common.device_check(cell.chips)
    except common.BenchError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    serve = cell.module("drivers", cell.traffic["driver"])
    cfg, params, engine = serve.setup(cell, args.seed)
    arrivals = gen.make_arrivals(cell.traffic, args.seconds, args.seed,
                                 cfg.vocab_size)
    tracer = ProgramCapture(TRACE_DIR / args.workload, engine)
    with GcSpans() as gcs:
        w = serve.play(engine, arrivals, args.seconds,
                       gen.lead_in_s(cell.traffic), tracer)
    t = time.perf_counter()
    hlo = engine._decode.lower(engine.params, engine.cache,
                               engine.last_token).compile().as_text()
    hlo_s = time.perf_counter() - t
    chips = [d.id for d in jax.devices()[:cell.chips]]
    summary = tracing.reduce(tracer.trace, chips)
    summary.update(reduce(tracer.program, chips, scopes_from_hlo(hlo)))
    record = types.SimpleNamespace(
        cell=cell, window=w, latency=serve.latencies(w), chips=cell.chips,
        trace=summary, engine=tracer.counters())
    lag = record.latency["lag"]
    out = {"workload": args.workload, "seed": args.seed, "device": dev,
           "metrics": {name: cell.module("metrics", name).read(record)
                       for name in READERS},
           "out_tok_s": w.tokens / w.window_s, "window_s": w.window_s,
           "generator_lag_ms_max": max(lag) if lag else None,
           "gc": gcs.summary(w.t0, w.t_stop), "engine": record.engine,
           "decode_hlo_s": hlo_s, "trace": summary}
    text = json.dumps(out)
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
