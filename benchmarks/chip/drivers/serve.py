"""Serving driver: ``serve.Engine`` under open-loop traffic.

Set-up makes the weights on the device from the seed, builds the engine
the traffic file asks for, and warms it: one request of every prompt
length the mix can send, and enough of them to touch every slot, so the
window compiles nothing.  Then one thread plays the arrivals on their
schedule: requests that fall due are submitted, and while any is
unfinished the engine steps.  The mix's lead-in (``lead_in_s``) comes
first and brings the engine to its steady load; the window follows.  The
latencies are those of the requests due in the window, the tokens per
second those delivered in it.  A request's times are taken from when it
was due, so a late submission counts in its latency.

After the window the engine's state is freed and the plain reference
checks a sample of the requests finished by then (lead-in ones too),
drawn from the seed with the longest among them: at every served token,
how far the token's logit lies below the reference's best
(``max_logit_gap``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time
import types
import typing

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from benchmarks.chip import common, tracing
from benchmarks.chip import generator as gen
from repro.models import api, get_config
from repro.serve import Engine, Request

clock = time.perf_counter


@dataclasses.dataclass
class Rec:
    """One arrival and what the window saw of it (host clock, seconds)."""
    arrival: gen.Arrival
    due: float = 0.0                # absolute due time
    req: Request = None
    t_submit: float = None
    t_admit: float = None           # start of the engine.step that admitted it
    t_first: float = None
    t_last: float = None
    seen: int = 0                   # tokens delivered
    seen0: int = 0                  # of them, before the window opened


def program_config(cell: common.Cell):
    """The program's registered configuration with the options the program
    offers set as the configuration file states them, checked field by
    field against the file."""
    c = cell.config
    cfg = dataclasses.replace(get_config(c["program_arch"]),
                              **cell.reference.program_options(c))
    for field, want in cell.reference.program_fields(c).items():
        got = getattr(cfg, field)
        if got != want:
            raise common.BenchError(
                f"{c['program_arch']}: the program runs {field}={got!r}, "
                f"the configuration states {want!r}")
    return cfg


def make_weights(cell: common.Cell, cfg, seed: int):
    params = cell.reference.init(common.jax_key(seed), cell.config)
    want = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0), cfg))
    got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    if jax.tree.map(lambda x: (x.shape, x.dtype), want) != got:
        raise common.BenchError("the reference's weight tree differs from "
                                "the program's api.init")
    return params


def warm_up(engine: Engine, traffic: dict, vocab: int, seed: int) -> int:
    """Serve one request of every prompt length the mix can send, and at
    least one per slot, so that every program the window runs exists."""
    rng = np.random.default_rng((seed, 2))
    lens = gen.prompt_buckets(traffic["prompt"])
    n = max(engine.slots, len(lens))
    for i in range(n):
        engine.submit(Request(uid=-1 - i, max_new_tokens=3,
                              prompt=rng.integers(0, vocab, lens[i % len(lens)]
                                                  ).astype(np.int32)))
    engine.run_until_drained()
    jax.block_until_ready(engine.cache)
    return n


def play(engine: Engine, arrivals: typing.List[gen.Arrival],
         seconds: float, lead_s: float = 0.0,
         tracer: tracing.Capture = None) -> types.SimpleNamespace:
    """Offer the arrivals on their schedule: those of the lead-in (due
    before 0) from ``lead_s`` seconds before the window, then the window's
    for ``seconds``.  The window is counted from the first loop turn at or
    after its scheduled start, so a step under way then counts before it."""
    recs = [Rec(a) for a in arrivals]
    live: typing.List[Rec] = []
    i = 0
    steps0, prefills0 = engine.steps, engine.prefills
    busy_at_open = queued_at_open = 0
    t_begin = clock()
    t0 = t_begin + lead_s
    t_end = t0 + seconds
    t_trace = tracer.start_at(t0, t_end) if tracer else float("inf")
    for r in recs:
        r.due = t0 + r.arrival.due_s
    t_open = None
    while True:
        now = clock()
        if now >= t_end:
            break
        if t_open is None and now >= t0:
            t_open = now
            steps0, prefills0 = engine.steps, engine.prefills
            busy_at_open = len(engine.active)
            queued_at_open = len(engine.queue)
            for r in recs:
                r.seen0 = r.seen
        if now >= t_trace and not tracer.on:
            tracer.start()
        if i < len(recs) and recs[i].due <= now:
            with TraceAnnotation("submit"):
                while i < len(recs) and recs[i].due <= now:
                    r = recs[i]
                    r.t_submit = now
                    r.req = Request(uid=r.arrival.uid,
                                    prompt=r.arrival.prompt,
                                    max_new_tokens=r.arrival.max_new_tokens)
                    if engine.submit(r.req):
                        live.append(r)
                    i += 1
        if live:
            t_step = clock()
            with TraceAnnotation("engine.step"):
                engine.step()
            t_done = clock()
            with TraceAnnotation("next batch"):
                for r in live:
                    n = len(r.req.output)
                    if n != r.seen:
                        if r.seen == 0:
                            r.t_admit, r.t_first = t_step, t_done
                        r.t_last, r.seen = t_done, n
                live = [r for r in live if not r.req.done]
        else:
            wake = min(recs[i].due if i < len(recs) else t_end, t_end)
            if t_open is None:
                wake = min(wake, t0)
            if tracer and not tracer.on:
                wake = min(wake, t_trace)
            with TraceAnnotation("generator wait"):
                time.sleep(max(0.0, wake - clock()))
    t_stop = clock()
    if tracer:
        tracer.stop()
    return types.SimpleNamespace(
        recs=[r for r in recs if t0 <= r.due <= t_stop],
        lead=[r for r in recs if r.due < t0],
        t_begin=t_begin, t0=t0, t_stop=t_stop, window_s=t_stop - t_open,
        tokens=sum(r.seen - r.seen0 for r in recs), served=recs,
        busy_at_open=busy_at_open, queued_at_open=queued_at_open,
        steps=engine.steps - steps0, prefills=engine.prefills - prefills0)


def latencies(w) -> dict:
    """Per-request latencies in ms.  A request that arrived in the window
    and has no token yet counts at its wait so far; TPOT takes every
    request with two tokens or more, finished or not."""
    ttft = [((r.t_first or w.t_stop) - r.due) * 1e3 for r in w.recs]
    wait = [((r.t_admit or w.t_stop) - r.due) * 1e3 for r in w.recs]
    tpot = [(r.t_last - r.t_first) / (r.seen - 1) * 1e3
            for r in w.recs if r.seen >= 2]
    lag = [(r.t_submit - r.due) * 1e3 for r in w.recs if r.t_submit]
    return {"ttft": ttft, "tpot": tpot, "queue_wait": wait, "lag": lag}


def served_flops(counts, c: dict, recs) -> int:
    """Model operations of every token served in the window, whenever its
    request arrived (from counts/): the prompt where the first token came
    in the window, and each decoded token k (k >= 1) at position S + k - 1."""
    total = 0
    for r in recs:
        S = len(r.arrival.prompt)
        if r.seen and not r.seen0:
            total += counts.prefill_flops(c, S)
        total += sum(counts.decode_flops(c, S + k - 1)
                     for k in range(max(1, r.seen0), r.seen))
    return total


# --------------------------------------------------------------------------
# correctness
# --------------------------------------------------------------------------

def sample(recs, k: int, seed: int) -> typing.List[Rec]:
    """The longest finished request and k-1 others drawn from the seed."""
    done = [r for r in recs if r.req is not None and r.req.done
            and not r.req.rejected]
    if not done:
        return []
    size = lambda r: len(r.arrival.prompt) + len(r.req.output)
    longest = max(done, key=size)
    rest = [r for r in done if r is not longest]
    pick = np.random.default_rng((seed, 1)).choice(
        len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[j] for j in sorted(pick)]


def gap_fn(cell: common.Cell, control: bool):
    """Jitted: for one padded sequence, the gap at each served position
    between the reference's best logit and the served token's, and, for
    the control, the same gap of the token the fp8 reference puts first."""
    ref, c = cell.reference, cell.config

    def f(params, tokens, rows, served):
        logits = ref.logits_at(params, c, tokens, rows)
        best = jnp.max(logits, -1)
        gap = best - jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
        if not control:
            return gap, gap
        low = jnp.argmax(ref.logits_at(params, c, tokens, rows, low=True), -1)
        return gap, best - jnp.take_along_axis(logits, low[:, None], -1)[:, 0]
    return jax.jit(f)


def gaps(cell: common.Cell, params, picked: typing.List[Rec],
         control: bool = False) -> typing.Tuple[list, list]:
    """Widest gap of each picked request: the program's, and the control's
    (equal to the program's unless ``control``)."""
    pad = cell.traffic["engine"]["max_seq"]
    rows_max = cell.traffic["output"]["max"]
    f = gap_fn(cell, control)
    prog, ctrl = [], []
    for r in picked:
        p, o = np.asarray(r.arrival.prompt), np.asarray(r.req.output)
        S, n = len(p), len(o)
        tokens = np.zeros(pad, np.int32)
        tokens[:S + n - 1] = np.concatenate([p, o[:-1]])
        rows = np.zeros(rows_max, np.int32)
        rows[:n] = np.arange(S - 1, S + n - 1)
        served = np.zeros(rows_max, np.int32)
        served[:n] = o
        g, gc_ = f(params, tokens, rows, served)
        prog.append(float(np.max(np.asarray(g)[:n])))
        ctrl.append(float(np.max(np.asarray(gc_)[:n])))
    return prog, ctrl


def bad_outputs(recs, vocab: int) -> int:
    """Finished requests whose answer is not as long as asked, or holds a
    token outside the vocabulary."""
    return sum(1 for r in recs if r.req is not None and r.req.done
               and not r.req.rejected and (
                   len(r.req.output) != r.arrival.max_new_tokens
                   or any(not 0 <= t < vocab for t in r.req.output)))


def reduce_traced(engine: Engine, trace: tracing.Trace,
                  chips: typing.List[int]) -> typing.Tuple[dict, float]:
    """The traced span's reduction, with the decode's operations mapped to
    their scopes through its compiled program, and the seconds that
    program's HLO took to come back (a compile-cache hit)."""
    t = clock()
    hlo = engine._decode.lower(engine.params, engine.cache,
                               engine.last_token).compile().as_text()
    hlo_s = clock() - t
    return tracing.reduce(trace, chips, tracing.scopes_from_hlo(hlo)), hlo_s


# --------------------------------------------------------------------------

def setup(cell: common.Cell, seed: int):
    cfg = program_config(cell)
    eng = cell.traffic["engine"]
    params = make_weights(cell, cfg, seed)
    engine = Engine(cfg, params, slots=eng["slots"], max_seq=eng["max_seq"])
    warm_up(engine, cell.traffic, cfg.vocab_size, seed)
    return cfg, params, engine


def run(cell: common.Cell, seed: int, seconds: float, trace: bool,
        t_start: float, trace_dir) -> dict:
    cfg, params, engine = setup(cell, seed)
    arrivals = gen.make_arrivals(cell.traffic, seconds, seed, cfg.vocab_size)
    tracer = tracing.Capture(trace_dir, trace, counters=engine.stats)
    gcs = tracing.GcSpans() if trace else contextlib.nullcontext()
    with common.CompileEvents() as compiles, gcs:
        w = play(engine, arrivals, seconds, gen.lead_in_s(cell.traffic),
                 tracer)
    setup_s = w.t_begin - t_start
    devices = jax.devices()[:cell.chips]
    mem = common.memory_peak_bytes(devices)
    lat = latencies(w)
    c = cell.config
    record = types.SimpleNamespace(
        cell=cell, window=w, latency=lat, chips=cell.chips, trace=None,
        served_flops=served_flops(cell.counts, c, w.served))
    if tracer.trace:
        record.trace, hlo_s = reduce_traced(engine, tracer.trace,
                                            [d.id for d in devices])
        record.engine = tracer.counters_delta()
        record.gc = gcs.summary(w.t0, w.t_stop)
    notes = {
        "window": {"window_s": w.window_s, "lead_in_s": w.t0 - w.t_begin,
                   "lead_in_arrived": len(w.lead),
                   "arrived": len(w.recs),
                   "submitted": sum(1 for r in w.recs if r.t_submit),
                   "finished": sum(1 for r in w.recs if r.req and r.req.done),
                   "steps": w.steps, "prefills": w.prefills,
                   "tokens": w.tokens,
                   "busy_slots_at_open": w.busy_at_open,
                   "queued_at_open": w.queued_at_open,
                   "busy_slots_at_end": len(engine.active),
                   "queued_at_end": len(engine.queue)},
        "generator_lag_ms": {q: common.percentile(lat["lag"], p)
                             if lat["lag"] else None
                             for q, p in (("p50", 50), ("p95", 95),
                                          ("max", 100))},
        "latency_ms": {k: {"n": len(lat[k]), **{
            f"p{q}": common.percentile(lat[k], q) for q in (50, 85, 95)}}
            for k in ("ttft", "tpot") if lat[k]},
        "compiles_in_window": {"lowered": compiles.lowered,
                               "compiled": compiles.compiled,
                               "cache_hits": compiles.cache_hits},
    }
    if record.trace:
        notes["trace_events"] = {
            "device_ops": record.trace["op_count"],
            "device_modules": sum(map(len, tracer.trace.modules.values())),
            "host_spans": len(tracer.trace.spans)}
        notes["trace"] = dict(record.trace, engine=record.engine,
                              gc=record.gc, decode_hlo_s=hlo_s)

    # correctness: free the engine's state, then run the reference
    picked = sample(w.served, cell.traffic["check"]["sample"], seed)
    engine.cache = engine.last_token = None
    del engine
    gc.collect()
    prog, _ = gaps(cell, params, picked)
    notes["check_sample"] = {"requests": len(picked),
                             "tokens": sum(len(r.req.output) for r in picked),
                             "max_logit_gap_each": prog}
    limits = cell.limits
    checks = {
        "max_logit_gap": (max(prog) if prog else float("inf"),
                          limits["max_logit_gap"]),
        "bad_outputs": (bad_outputs(w.served, cfg.vocab_size), 0),
        "finished_missing": (0 if picked else 1, 0),
    }
    metrics = {
        "ttft_p85_ms": common.percentile(lat["ttft"], 85),
        "tpot_p85_ms": common.percentile(lat["tpot"], 85),
        "out_tok_s": w.tokens / w.window_s,
        "setup_s": setup_s,
    }
    return {"metrics": metrics, "record": record, "checks": checks,
            "attempted": len(w.recs),
            "failed": sum(1 for r in w.recs if r.req and r.req.rejected),
            "memory_peak_bytes": mem, "notes": notes}
