"""Profiler capture of the end of the measured window, and its reduction
to numbers.

A traced run profiles the last ``SPAN_S`` seconds of the window (all of
it, if it is shorter), in a span named ``traced``, and wraps its calls in
``TraceAnnotation`` spans (names in ``SPANS``).  The profiler keeps a
bounded number of device events, about 4.7 M on a v5e, which a serving
window fills in some 25 s; ``SPAN_S`` is set well inside that, so every
run is read over the same seconds at the end of the window.  A trace that
runs out before its span ends is an error, not a shorter span.

The reduction reads the ``.xplane.pb`` the profiler writes, with nothing
but JAX, into a ``Trace``: device operations per chip and the benchmark's
host spans, on the profiler's one clock.  ``reduce`` then gives, over the
traced span:

* busy time: the union of the intervals in which an operation ran on a
  chip, averaged over the chips used; idle share is 1 - busy / span;
* exposed collective time: time in all-gather, reduce-scatter, all-reduce,
  all-to-all or collective-permute ops (by the op's own name, not its
  operands') during which no other operation ran on that chip, averaged
  over the chips;
* the device operations that took most time, and the longest idle gaps of
  the first chip, each labelled by the innermost host span open at its
  middle and by its start within the traced span.
"""
from __future__ import annotations

import dataclasses
import pathlib
import re
import shutil
import typing

SPAN_S = 10.0
SPANS = ("traced", "generator wait", "submit", "engine.step", "next batch")
COLLECTIVE = re.compile(
    r"all-gather|reduce-scatter|all-reduce|all-to-all|collective-permute")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# a TPU op event is named by its whole HLO instruction; the part before
# " = " names the op, and its result type follows
HLO = re.compile(r"^(%?[^ =]+) = ([a-z0-9]+\[[0-9,]*\])?")
OPS_LINE = "XLA Ops"

Interval = typing.Tuple[float, float]


@dataclasses.dataclass
class Trace:
    ops: typing.Dict[int, typing.List[typing.Tuple[str, float, float]]]
    spans: typing.List[typing.Tuple[str, float, float]]   # seconds


class Capture:
    """Profiles from ``start`` to ``stop`` into ``out_dir`` when
    ``enabled``, and holds the ``Trace`` afterwards (else None)."""

    def __init__(self, out_dir: pathlib.Path, enabled: bool,
                 span_s: float = SPAN_S) -> None:
        self.out_dir, self.enabled, self.span_s = out_dir, enabled, span_s
        self.on = False
        self.trace = None
        self._span = None

    def start_at(self, t0: float, t_end: float) -> float:
        """When to start, for a window from ``t0`` to ``t_end``."""
        return max(t0, t_end - self.span_s) if self.enabled else float("inf")

    def start(self) -> None:
        import jax
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
        self.trace = load(self.out_dir)
        shutil.rmtree(self.out_dir, ignore_errors=True)


def op_name(event_name: str) -> str:
    """``%copy.3 = bf16[28,32]{...} copy(...)`` -> ``%copy.3 bf16[28,32]``;
    a name that is no HLO instruction stays as it is."""
    m = HLO.match(event_name)
    if not m:
        return event_name
    return f"{m.group(1)} {m.group(2)}" if m.group(2) else m.group(1)


def load(out_dir: pathlib.Path) -> Trace:
    from jax.profiler import ProfileData
    files = sorted(pathlib.Path(out_dir).rglob("*.xplane.pb"))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {out_dir}")
    ops: dict = {}
    spans = []
    for path in files:
        for plane in ProfileData.from_file(str(path)).planes:
            m = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if m and line.name == OPS_LINE:
                    ops.setdefault(int(m.group(1)), []).extend(
                        (op_name(e.name), e.start_ns * 1e-9, e.end_ns * 1e-9)
                        for e in line.events)
                elif not m:
                    spans.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                                 for e in line.events if e.name in SPANS)
    return Trace(ops=ops, spans=spans)


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


# --------------------------------------------------------------------------
# reduction
# --------------------------------------------------------------------------

def window_of(trace: Trace) -> Interval:
    w = [(a, b) for name, a, b in trace.spans if name == "traced"]
    if len(w) != 1:
        raise RuntimeError(f"expected one 'traced' span, found {len(w)}")
    return w[0]


def label_at(spans, t: float) -> str:
    """The innermost benchmark span (other than the traced one) open at t."""
    best = None
    for name, a, b in spans:
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
                  for name, a, b in trace.spans if a < hi)
    return hi - cut if stepped and cut < hi else 0.0


def reduce(trace: Trace, chips: typing.Sequence[int], top: int = 10) -> dict:
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
    idle = sorted(gaps(first_busy or [], lo, hi), key=lambda g: g[0] - g[1])
    return {
        "op_count": sum(len(trace.ops.get(c, [])) for c in chips),
        "window_s": window,
        "steps": sum(1 for name, a, b in trace.spans
                     if name == "engine.step" and lo <= a and b <= hi),
        "busy_s": sum(busy) / n,
        "idle_share": 1.0 - sum(busy) / n / window,
        "collective_exposed_s": sum(exposed) / n,
        "device_ops": sorted(([k, v / n] for k, v in by_op.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[f"{label_at(spans, (a + b) / 2)} at {a - lo:.2f} s",
                       b - a] for a, b in idle[:top]],
        # where the first chip's recorded operations start and end,
        # relative to the traced span
        "ops_from_to_s": ([first_busy[0][0] - lo, first_busy[-1][1] - lo]
                          if first_busy else None),
        "span_s": {name: total(union((a, b) for s, a, b in spans
                                     if s == name))
                   for name in SPANS if name != "traced"},
    }
