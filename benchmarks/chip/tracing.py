"""Profiler capture of the end of the measured window, and its reduction
to numbers.

A traced run profiles the last ``SPAN_S`` seconds of the window (all of
it, if it is shorter), in a span named ``traced``.  The driver wraps its
calls in ``TraceAnnotation`` spans (names in ``SPANS``), and the program
writes its own (``serve.*`` from ``serve.Engine``, with their arguments)
and names its compiled programs (``jit_serve_decode``, ...); ``GcSpans``
adds a ``host.gc`` span for each garbage collection.  The profiler keeps
a bounded number of device events, about 4.7 M on a v5e, which the
qwen2-1.5b serving cells fill in some 13 s (about 12 k events a decode
step); ``SPAN_S`` holds under half of that, so a faster decode still
fits and every run is read over the same seconds at the end of the
window.  A trace that runs out before its span ends is an error, not a
shorter span.

``load`` reads the ``.xplane.pb`` the profiler writes, with nothing but
JAX, into a ``Trace``: each chip's operations (``XLA Ops``) and programs
(``XLA Modules``), and the driver's and the program's host spans, on the
profiler's one clock.  ``reduce`` then gives, over the traced span:

* busy time: the union of the intervals in which an operation ran on a
  chip, averaged over the chips used; idle share is 1 - busy / span;
* exposed collective time: time in all-gather, reduce-scatter, all-reduce,
  all-to-all or collective-permute ops (by the op's own name, not its
  operands') during which no other operation ran on that chip, averaged
  over the chips;
* the device operations that took most time, and the longest idle gaps of
  the first chip, each labelled by the innermost span open at its middle,
  the program's spans included, and by its start within the traced span;
* ``span_self_s``, ``span_total_s``, ``span_n``: for each span name, the
  time in which it was the innermost span open, its whole time, and how
  many started in the traced span;
* ``idle_by_span_s``: all of the first chip's idle time, split by the
  innermost span open ("no span" outside every span but ``traced``);
* ``module_s``: device time of each program, by its name;
* ``scope_s``: device self time of the operations inside ``DECODE``
  intervals (a loop's time less that of the operations it runs), by the
  named scope that the compiled decode program's ``op_name`` metadata
  gives each instruction (``scopes_from_hlo``; the TPU's op events carry
  no such name).  An instruction without ``op_name`` takes the scope of
  the operation it runs inside; one whose ``op_name`` holds no scope
  counts as ``(no scope)``.

Spans, programs and scopes are read by name, never by configuration: a
model whose layers run in a scope of their own (``ssm``, ``moe``) shows
it in ``scope_s`` as it is, and a per-layer metric of it is one more
reader in ``metrics/``, with no change here.
"""
from __future__ import annotations

import bisect
import dataclasses
import gc
import pathlib
import re
import shutil
import time
import typing

SPAN_S = 6.0
SPANS = ("traced", "generator wait", "submit", "engine.step", "next batch")
PROGRAM_SPAN = re.compile(r"^serve\.|^host\.gc$")
COLLECTIVE = re.compile(
    r"all-gather|reduce-scatter|all-reduce|all-to-all|collective-permute")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# a TPU op event is named by its whole HLO instruction; the part before
# " = " names the op, and its result type follows
HLO = re.compile(r"^(%?[^ =]+) = ([a-z0-9]+\[[0-9,]*\])?")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DECODE = "jit_serve_decode"
NO_SCOPE = "(no scope)"
# op_name parts that are JAX's own (control flow, remat) and not scopes
_MARKS = {"while", "body", "cond", "closed_call", "checkpoint", "remat"}
_INSTR = re.compile(r'^\s*(?:ROOT )?(%\S+) = .*?op_name="([^"]*)"', re.M)

Interval = typing.Tuple[float, float]
Event = typing.Tuple[str, float, float]
Span = typing.Tuple[str, float, float, dict]     # name, start, end, args


@dataclasses.dataclass
class Trace:
    ops: typing.Dict[int, typing.List[Event]]
    spans: typing.List[Span]            # driver's and program's, seconds
    modules: typing.Dict[int, typing.List[Event]] = dataclasses.field(
        default_factory=dict)


class Capture:
    """Profiles from ``start`` to ``stop`` into ``out_dir`` when
    ``enabled``, and holds the ``Trace`` afterwards (else None).  Where
    ``counters`` is given, it is called as the profile starts and stops,
    and ``counters_delta`` gives the change of each numeric counter."""

    def __init__(self, out_dir: pathlib.Path, enabled: bool,
                 span_s: float = SPAN_S,
                 counters: typing.Optional[
                     typing.Callable[[], dict]] = None) -> None:
        self.out_dir, self.enabled, self.span_s = out_dir, enabled, span_s
        self.counters = counters
        self.on = False
        self.trace = None
        self._span = None
        self._stats: typing.List[dict] = []

    def start_at(self, t0: float, t_end: float) -> float:
        """When to start, for a window from ``t0`` to ``t_end``."""
        return max(t0, t_end - self.span_s) if self.enabled else float("inf")

    def start(self) -> None:
        import jax
        if self.counters:
            self._stats.append(self.counters())
        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.out_dir), profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("traced")
        self._span.__enter__()
        self.on = True

    def stop(self) -> None:
        if not self.on:
            return
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.on = False
        if self.counters:
            self._stats.append(self.counters())
        self.trace = load(self.out_dir)
        shutil.rmtree(self.out_dir, ignore_errors=True)

    def counters_delta(self) -> typing.Optional[dict]:
        """Each numeric counter's change over the profiled span (the gauges
        ``active`` and ``queued`` left out); None before a whole span."""
        if len(self._stats) != 2:
            return None
        a, b = self._stats
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


def op_name(event_name: str) -> str:
    """``%copy.3 = bf16[28,32]{...} copy(...)`` -> ``%copy.3 bf16[28,32]``;
    a name that is no HLO instruction stays as it is."""
    m = HLO.match(event_name)
    if not m:
        return event_name
    return f"{m.group(1)} {m.group(2)}" if m.group(2) else m.group(1)


def module_name(event_name: str) -> str:
    """``jit_serve_decode(1234)`` -> ``jit_serve_decode``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def load(out_dir: pathlib.Path) -> Trace:
    from jax.profiler import ProfileData
    files = sorted(pathlib.Path(out_dir).rglob("*.xplane.pb"))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {out_dir}")
    ops: dict = {}
    modules: dict = {}
    spans = []
    for path in files:
        for plane in ProfileData.from_file(str(path)).planes:
            m = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if m and line.name == OPS_LINE:
                    ops.setdefault(int(m.group(1)), []).extend(
                        (op_name(e.name), e.start_ns * 1e-9, e.end_ns * 1e-9)
                        for e in line.events)
                elif m and line.name == MODULES_LINE:
                    modules.setdefault(int(m.group(1)), []).extend(
                        (module_name(e.name), e.start_ns * 1e-9,
                         e.end_ns * 1e-9) for e in line.events)
                elif not m:
                    for e in line.events:
                        if e.name in SPANS:
                            spans.append((e.name, e.start_ns * 1e-9,
                                          e.end_ns * 1e-9, {}))
                        elif PROGRAM_SPAN.match(e.name):
                            spans.append((e.name, e.start_ns * 1e-9,
                                          e.end_ns * 1e-9, dict(e.stats)))
    return Trace(ops=ops, spans=spans, modules=modules)


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
# interval arithmetic
# --------------------------------------------------------------------------

def union(intervals: typing.Iterable[Interval]) -> typing.List[Interval]:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals, lo: float, hi: float) -> typing.List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: typing.List[Interval], b: typing.List[Interval]
             ) -> typing.List[Interval]:
    """Parts of the disjoint sorted intervals ``a`` outside those of ``b``."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, cur = j, lo
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def gaps(busy: typing.List[Interval], lo: float, hi: float
         ) -> typing.List[Interval]:
    return subtract([(lo, hi)], busy)


def overlap(intervals, disjoint: typing.List[Interval],
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
# nesting
# --------------------------------------------------------------------------

def nesting(events: typing.Sequence[Event]) -> typing.Tuple[list, list, list]:
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


def self_intervals(spans: typing.Sequence[Event]
                   ) -> typing.List[typing.List[Interval]]:
    """For each span, the intervals in which it is the innermost open."""
    _, parent, _ = nesting(spans)
    children: typing.Dict[int, list] = {}
    for i, p in enumerate(parent):
        if p >= 0:
            children.setdefault(p, []).append(spans[i][1:])
    return [subtract([(a, b)], union(clip(children.get(i, []), a, b)))
            for i, (_, a, b) in enumerate(spans)]


# --------------------------------------------------------------------------
# reduction
# --------------------------------------------------------------------------

def window_of(trace: Trace) -> Interval:
    w = [(a, b) for name, a, b, *_ in trace.spans if name == "traced"]
    if len(w) != 1:
        raise RuntimeError(f"expected one 'traced' span, found {len(w)}")
    return w[0]


def label_at(spans, t: float) -> str:
    """The innermost span (other than the traced one) open at t."""
    best = None
    for name, a, b, *_ in spans:
        if name != "traced" and a <= t < b and (best is None
                                                 or b - a < best[1]):
            best = (name, b - a)
    return best[0] if best else "no span"


def cut_short(trace: Trace, chips, lo: float, hi: float) -> float:
    """Seconds by which the device trace ends before the traced span does,
    while the host went on stepping (``engine.step`` spans, each of which
    runs device work, after the last recorded operation): the profiler's
    event buffer ran out.  0 where it did not."""
    last = [max((b for _, a, b in trace.ops.get(c, []) if a < hi),
                default=None) for c in chips]
    if None in last:
        return 0.0
    cut = min(last)
    stepped = any(name == "engine.step" and a > cut
                  for name, a, b, *_ in trace.spans if a < hi)
    return hi - cut if stepped and cut < hi else 0.0


def _spans(trace: Trace, lo: float, hi: float,
           idle: typing.List[Interval]) -> dict:
    """Self time, whole time and count of each span name over
    ``lo``-``hi``, and the sorted disjoint ``idle`` intervals split by the
    innermost span open."""
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
    starts = [a for a, _ in idle]
    idle_by: dict = {}
    for (name, _, _), iv in zip(spans, self_iv):
        label = "no span" if name == "traced" else name
        t = overlap(iv, idle, starts)
        if t:
            idle_by[label] = idle_by.get(label, 0.0) + t
    return {"span_self_s": self_s, "span_total_s": total_s, "span_n": count,
            "idle_by_span_s": idle_by}


def _programs(trace: Trace, chips, lo: float, hi: float,
              scopes: typing.Optional[typing.Dict[str, str]]) -> dict:
    """Device time by program, and the decode's self time by scope, over
    ``lo``-``hi``, averaged over the chips."""
    n = len(chips)
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
    return {"module_s": module_s, "scope_s": scope_s}


def reduce(trace: Trace, chips: typing.Sequence[int],
           scopes: typing.Dict[str, str] = None, top: int = 10) -> dict:
    """The traced span's numbers (module docstring); ``scope_s`` is empty
    unless the decode's ``scopes`` are given."""
    lo, hi = window_of(trace)
    cut = cut_short(trace, chips, lo, hi)
    if cut:
        raise RuntimeError(
            f"the device trace ends {cut:.2f} s before the traced span of "
            f"{hi - lo:.2f} s ({sum(len(v) for v in trace.ops.values())} "
            "operations): the profiler's buffer ran out; shorten SPAN_S")
    window = hi - lo
    busy, exposed, by_op = [], [], {}
    first_busy = None
    for c in chips:
        evs = [(n, a, b) for n, a, b in trace.ops.get(c, []) if b > lo and a < hi]
        u = union(clip([(a, b) for _, a, b in evs], lo, hi))
        busy.append(total(u))
        coll = union(clip([(a, b) for n, a, b in evs if COLLECTIVE.search(n)],
                          lo, hi))
        comp = union(clip([(a, b) for n, a, b in evs
                           if not COLLECTIVE.search(n)], lo, hi))
        exposed.append(total(subtract(coll, comp)))
        for n, a, b in evs:
            by_op[n] = by_op.get(n, 0.0) + (min(b, hi) - max(a, lo))
        if first_busy is None:
            first_busy = u
    n = len(chips)
    spans = [s for s in trace.spans if s[2] > lo and s[1] < hi]
    idle = gaps(first_busy or [], lo, hi)
    longest = sorted(idle, key=lambda g: g[0] - g[1])
    return {
        "op_count": sum(len(trace.ops.get(c, [])) for c in chips),
        "window_s": window,
        "steps": sum(1 for name, a, b, *_ in trace.spans
                     if name == "engine.step" and lo <= a and b <= hi),
        "busy_s": sum(busy) / n,
        "idle_share": 1.0 - sum(busy) / n / window,
        "collective_exposed_s": sum(exposed) / n,
        "device_ops": sorted(([k, v / n] for k, v in by_op.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[f"{label_at(spans, (a + b) / 2)} at {a - lo:.2f} s",
                       b - a] for a, b in longest[:top]],
        # where the first chip's recorded operations start and end,
        # relative to the traced span
        "ops_from_to_s": ([first_busy[0][0] - lo, first_busy[-1][1] - lo]
                          if first_busy else None),
        "span_s": {name: total(union((a, b) for s, a, b, *_ in spans
                                     if s == name))
                   for name in SPANS if name != "traced"},
        **_spans(trace, lo, hi, idle),
        **_programs(trace, chips, lo, hi, scopes),
    }
