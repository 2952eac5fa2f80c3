"""Executor backend tests: registry, cross-executor bit-identity
(threads vs procs vs serial, healthy and fault-injected), shard-state
sync-back, FaultInjector through the process boundary, worker-crash
surfacing, and the strict-window guard raising across processes."""
import os

import pytest

from repro.core import (Component, Connection, Engine, EXECUTORS,
                        LookaheadScheduler, ProcExecutor, SystemSpec,
                        ThreadExecutor, make_executor, simulate)

SMALL = SystemSpec(pod_shape=(2, 2))

EXECUTOR_VARIANTS = ("threads", "procs")
SCHED_X_EXEC = [(s, e) for s in ("batch", "lookahead", "bounded")
                for e in EXECUTOR_VARIANTS]


# -- registry ----------------------------------------------------------------

def test_executor_registry():
    assert "threads" in EXECUTORS and "procs" in EXECUTORS
    assert isinstance(make_executor("threads"), ThreadExecutor)
    assert isinstance(make_executor("procs"), ProcExecutor)
    inst = ThreadExecutor(max_workers=2)
    assert make_executor(inst) is inst


def test_procs_refuses_to_fork_a_tpu_holder(monkeypatch):
    from repro.core.engine.executor import procs
    monkeypatch.setattr(procs, "_holds_tpu", lambda: True)
    with pytest.raises(RuntimeError, match="cannot share this process's TPU"):
        ProcExecutor(max_workers=2).prepare([])


def test_unknown_executor_raises():
    with pytest.raises(ValueError, match="unknown executor"):
        make_executor("gpu")


def test_scheduler_describe_reports_executor():
    eng = Engine(scheduler="lookahead", executor="procs")
    eng.register(Sink("a")).schedule("tick", 10)
    eng.run()
    desc = eng.scheduler.describe()
    assert desc["executor"]["name"] == "procs"
    assert desc["executor"]["processes"] >= 1


# -- cross-executor bit-identity ---------------------------------------------

class Sink(Component):
    def __init__(self, name):
        super().__init__(name)
        self.received = 0

    def handle(self, event):
        self.received += 1


def _build_jitter(scheduler, executor=None, n=8, ticks=80):
    from benchmarks.engine_scalability import JitterNode
    eng = Engine(scheduler=scheduler, executor=executor)
    nodes = [eng.register(JitterNode(f"n{i}", i, ticks, send_every=20))
             for i in range(n)]
    for i in range(n):
        conn = eng.register(Connection(f"ring{i}", latency_s=4e-9))
        conn.plug(nodes[i].port("out")).plug(nodes[(i + 1) % n].port("in"))
    for nd in nodes:
        nd.start()
    end = eng.run()
    return [(nd.sig, nd.count, nd.received) for nd in nodes], end, eng


@pytest.mark.parametrize("scheduler,executor", SCHED_X_EXEC)
def test_executors_bit_identical_on_divergent_trace(scheduler, executor):
    """The divergent-latency trace under every scheduler x executor must
    match serial bit-for-bit -- for procs this also exercises the
    end-of-run shard-state sync (the asserted node state lives in worker
    processes until then)."""
    oracle, end_s, eng_s = _build_jitter("serial")
    got, end_p, eng_p = _build_jitter(scheduler, executor)
    assert got == oracle and end_p == end_s
    assert eng_p.events_processed == eng_s.events_processed


@pytest.mark.parametrize("executor", EXECUTOR_VARIANTS)
def test_executors_identical_on_event_fabric(executor):
    """Full-system event-fabric replay: SimReport summaries (timing,
    metrics-hook busy time, link utilization) must be identical across
    executors -- under procs that covers engine-hook ``merge_shard`` and
    fabric component state shipped back from the workers."""
    kw = dict(cost=_ar_cost(), spec=SMALL, device_limit=None,
              fabric="event")
    oracle = simulate(scheduler="serial", **kw)
    rep = simulate(scheduler="lookahead", executor=executor, **kw)
    assert rep.summary() == oracle.summary()
    assert rep.executor == executor
    assert oracle.compute_busy_s > 0     # the metrics hook saw the run


@pytest.mark.parametrize("executor", EXECUTOR_VARIANTS)
def test_analytic_link_report_survives_executor(executor):
    """The analytic controller debits its backend's topology counters;
    under procs those live in the shard replica, and the report must
    read through the synced-back controller -- a procs run used to
    return an empty link_report while serial had the debits."""
    kw = dict(cost=_ar_cost(), spec=SMALL, device_limit=None,
              fabric="analytic")
    oracle = simulate(scheduler="serial", **kw)
    assert oracle.link_report["hottest_links"]        # debits present
    rep = simulate(scheduler="batch", executor=executor, **kw)
    assert rep.link_report == oracle.link_report
    assert rep.summary() == oracle.summary()


def _ar_cost():
    from repro.core.hlo import CollectiveRecord, HloCost, TraceOp
    ops, colls = [], []
    for i in range(3):
        ops.append(TraceOp("compute", f"mm{i}", flops=2e9, hbm_bytes=1e6))
        rec = CollectiveRecord("all-reduce", f"ar{i}", 2e5, int(2e5),
                               int(2e5), [[0, 1, 2, 3]])
        colls.append(rec)
        ops.append(TraceOp("collective", f"ar{i}", collective=rec))
    return HloCost(collectives=colls, trace=ops)


@pytest.mark.parametrize("executor", EXECUTOR_VARIANTS)
def test_fault_injection_through_executor(executor):
    """A straggler-link plan must perturb a procs run exactly like a
    serial run: the FaultInjector hook replica fires inside the shard
    worker (EVENT_START still wraps every event), flips the replica's
    fault flags, and the effect -- plus the flags themselves -- ship
    back in the state sync."""
    faults = {"fabric.pod0.ici[0,0]+x": [(0.0, "slow", 6.0)]}
    kw = dict(cost=_ar_cost(), spec=SMALL, device_limit=None,
              fabric="event")
    healthy = simulate(scheduler="serial", **kw)
    oracle = simulate(scheduler="serial", faults=faults, **kw)
    rep = simulate(scheduler="lookahead", executor=executor,
                   faults=faults, **kw)
    assert rep.summary() == oracle.summary()
    assert rep.time_s > healthy.time_s   # the fault actually fired


@pytest.mark.parametrize("scheduler,executor", SCHED_X_EXEC)
def test_transient_fault_bit_identity(scheduler, executor):
    """A flapping link (docs/faults.md ``transient``) drops transfers on
    the floor, so their acks never return and the affected rings stall
    mid-collective.  That idle gap is exactly where bounded-lag horizons
    run furthest ahead of the global floor -- per-cluster windows must
    still replay the stall bit-identically to serial, on both executors
    (under procs the fault hook replica fires inside the shard
    worker)."""
    faults = {"fabric.pod0.ici[0,1]+x": [(10e-6, "transient", 40e-6)]}
    kw = dict(cost=_ar_cost(), spec=SMALL, device_limit=None,
              fabric="event")
    healthy = simulate(scheduler="serial", **kw)
    oracle = simulate(scheduler="serial", faults=faults, **kw)
    rep = simulate(scheduler=scheduler, executor=executor,
                   faults=faults, **kw)
    assert rep.summary() == oracle.summary()
    assert oracle.summary() != healthy.summary()  # the fault bit


def _rerun_engine(executor):
    """Two runs on one engine: the second must resume from the state
    the first left behind (under procs: the state synced back from the
    first run's workers seeds the second run's fork)."""
    eng = Engine(scheduler="lookahead" if executor else "serial",
                 executor=executor)
    a, b = eng.register(Sink("a")), eng.register(Sink("b"))
    conn = eng.register(Connection("c", latency_s=1e-6))
    conn.plug(a.port("x")).plug(b.port("x"))
    a.schedule("tick", 100)
    b.schedule("tick", 150)
    eng.run()
    mid = (a.received, b.received)
    a.schedule("tock", 50)
    b.schedule("tock", 75)
    end = eng.run()
    return mid, (a.received, b.received), end, eng.events_processed


@pytest.mark.parametrize("executor", EXECUTOR_VARIANTS)
def test_engine_rerun_resumes_from_synced_state(executor):
    assert _rerun_engine(executor) == _rerun_engine(None)


def _partial_then_resume(executor, n=6, ticks=60):
    """run(until_ps=...) then drain: the horizon cuts mid-trace, so the
    first run ends with committed events (request payloads included)
    still in the parent queue -- under procs those payloads lived in
    the (now gone) first-run workers and must have been materialized
    by the state sync for the second run's fresh workers to decode."""
    from benchmarks.engine_scalability import JitterNode
    eng = Engine(scheduler="lookahead" if executor else "serial",
                 executor=executor)
    nodes = [eng.register(JitterNode(f"n{i}", i, ticks, send_every=10))
             for i in range(n)]
    for i in range(n):
        conn = eng.register(Connection(f"ring{i}", latency_s=4e-9))
        conn.plug(nodes[i].port("out")).plug(nodes[(i + 1) % n].port("in"))
    for nd in nodes:
        nd.start()
    eng.run(until_ps=ticks * 300 // 2)
    mid = [(nd.sig, nd.count, nd.received) for nd in nodes]
    end = eng.run()
    return mid, [(nd.sig, nd.count, nd.received) for nd in nodes], end


@pytest.mark.parametrize("executor", EXECUTOR_VARIANTS)
def test_partial_run_then_resume(executor):
    oracle = _partial_then_resume(None)
    assert oracle[0] != oracle[1]        # the horizon really cut mid-trace
    assert _partial_then_resume(executor) == oracle


class Spray(Component):
    """Ticks and pings both ring neighbors with a distinct payload --
    one source cluster posting to two *different* destination clusters
    (and, mod 3 workers, two different destination workers) per round."""

    def __init__(self, name, ticks):
        super().__init__(name)
        self.ticks = ticks
        self.count = 0
        self.sig = 0

    def start(self):
        self.schedule("tick", 100)

    def handle(self, event):
        if event.kind == "tick":
            self.count += 1
            from repro.core import Request
            for pname in ("fwd", "bwd"):
                self.port(pname).send(Request(
                    src=self.port(pname), dst=None, kind="ping",
                    size_bytes=8, payload=(self.name, pname, self.count)))
            if self.count < self.ticks:
                self.schedule("tick", 137)
        elif event.kind == "request":
            self.sig = hash((self.sig, self.engine.now,
                             event.payload.payload))


def test_partial_run_resume_keeps_blob_payloads_apart_three_workers():
    """One worker's same-round blobs to two different destination
    workers must not collide in the parent's stranded-payload pool
    after a partial run (they once shared a (src, seq) key, and resume
    delivered one destination's payloads to both).  Forced to 3 worker
    processes because on <= 2 a source worker only ever has one foreign
    destination."""
    from repro.core import ProcExecutor

    def go(executor):
        eng = Engine(scheduler="lookahead" if executor else "serial",
                     executor=executor)
        n = 6
        nodes = [eng.register(Spray(f"s{i}", 40)) for i in range(n)]
        for i in range(n):
            for pname, j in (("fwd", (i + 1) % n), ("bwd", (i - 1) % n)):
                conn = eng.register(
                    Connection(f"{pname}{i}", latency_s=1e-6))
                conn.plug(nodes[i].port(pname)).plug(
                    nodes[j].port(f"in{pname}{i}"))
        for nd in nodes:
            nd.start()
        eng.run(until_ps=40 * 137 // 2)
        eng.run()
        return [(nd.sig, nd.count) for nd in nodes]

    ex = ProcExecutor(max_workers=4)
    ex._max_procs = 3                    # oversubscribed on 2 cpus: fine
    assert go(ex) == go(None)


class Staller(Component):
    """Emits kind='stall' self-events (what StallHook counts)."""

    def start(self):
        for d in (100, 200, 300):
            self.schedule("stall", d, payload="x")

    def handle(self, event):
        pass


@pytest.mark.parametrize("executor", EXECUTOR_VARIANTS)
def test_engine_hook_state_not_double_counted_across_reruns(executor):
    """Workers fork with the parent's pre-run hook state; merging that
    baseline back would multiply a previous run's counters by the
    worker count.  Mergeable hooks therefore accumulate into
    ``fresh_shard`` replicas worker-side."""
    from repro.core import StallHook

    def go(ex):
        eng = Engine(scheduler="lookahead" if ex else "serial", executor=ex)
        hook = StallHook()
        eng.accept_hook(hook)
        s = eng.register(Staller("s"))
        s.start()
        eng.run()
        first = dict(hook.stalls)
        s.schedule("stall", 50, payload="y")
        eng.run()
        return first, dict(hook.stalls)

    assert go(executor) == go(None) == ({"x": 3}, {"x": 3, "y": 1})


@pytest.mark.parametrize("executor", EXECUTOR_VARIANTS)
def test_component_level_hook_merges_back(executor):
    """A mergeable hook attached to a *component* (not the engine)
    fires inside the owning shard worker; its observations must fold
    back into the parent's hook instance like engine-level ones."""
    from repro.core import StallHook

    def go(ex):
        eng = Engine(scheduler="lookahead" if ex else "serial", executor=ex)
        s = eng.register(Staller("s"))
        other = eng.register(Sink("o"))
        other.schedule("tick", 10)
        hook = StallHook()
        s.accept_hook(hook)
        s.start()
        eng.run()
        return dict(hook.stalls)

    assert go(executor) == go(None) == {"x": 3}


def test_procs_clamps_idle_worker_processes():
    """Fewer clusters than workers must not fork permanently idle
    processes -- each would hold a full engine replica for nothing."""
    eng = Engine(scheduler="lookahead", max_workers=4, executor="procs")
    eng.register(Sink("only")).schedule("tick", 10)
    eng.run()
    assert eng.scheduler.executor.processes == 1


@pytest.mark.parametrize("executor", EXECUTOR_VARIANTS)
def test_limited_connection_backpressure_through_executor(executor):
    """DP-6 backpressure (bounded queue, notify_available wakes, slot
    reservations) is stateful connection machinery fused into one
    cluster -- under procs it runs wholesale inside one shard worker,
    with the wake events' connection payloads crossing rounds as
    shard-resident references."""
    from repro.core import LimitedConnection
    from tests.test_sim_engine import Producer, Sink as CountingSink

    def run(scheduler, ex=None):
        eng = Engine(scheduler=scheduler, executor=ex)
        prod = eng.register(Producer("p", total=25))
        sink = eng.register(CountingSink("s"))
        conn = eng.register(LimitedConnection(
            "lim", bandwidth=1e9, latency_s=1e-6, capacity=3))
        conn.plug(prod.port("out")).plug(sink.port("in"))
        prod.start()
        eng.run()
        return (prod.sent, prod.rejected, prod.notified, sink.received,
                eng.events_processed)

    oracle = run("serial")
    got = run("lookahead", executor)
    assert got == oracle
    assert oracle[1] > 0 and oracle[2] > 0   # backpressure actually engaged


# -- failure surfacing -------------------------------------------------------

class Suicider(Component):
    """Kills its own process mid-handler -- a worker hard crash."""

    def start(self):
        self.schedule("tick", 100)

    def handle(self, event):
        os._exit(7)


def test_worker_crash_surfaces_as_engine_error():
    eng = Engine(scheduler="lookahead", executor="procs")
    eng.register(Suicider("boom")).start()
    with pytest.raises(RuntimeError, match="died mid-run"):
        eng.run()


class Thrower(Component):
    def start(self):
        self.schedule("tick", 100)

    def handle(self, event):
        raise ValueError("handler exploded")


def test_worker_exception_propagates_with_traceback():
    eng = Engine(scheduler="lookahead", executor="procs")
    eng.register(Thrower("t")).start()
    with pytest.raises(RuntimeError, match="handler exploded"):
        eng.run()


class Rogue(Component):
    """Posts a zero-latency event at a foreign cluster -- the lookahead
    safety violation, which must raise across the process boundary."""

    def __init__(self, name, victim):
        super().__init__(name)
        self.victim = victim

    def start(self):
        self.schedule("go", 0)

    def handle(self, event):
        from repro.core import Event
        self.engine.post(Event(time=self.engine.now,
                               component=self.victim, kind="attack"))


def test_strict_window_guard_raises_through_procs():
    sched = LookaheadScheduler(max_workers=2)
    sched.executor_spec = "procs"
    eng = Engine(scheduler=sched)
    victim = eng.register(Sink("v"))
    victim.schedule("tick", 100)
    rogue = eng.register(Rogue("r", victim))
    conn = eng.register(Connection("c", latency_s=1e-6))
    conn.plug(rogue.port("x")).plug(victim.port("x"))
    rogue.start()
    with pytest.raises(RuntimeError, match="lookahead safety violation"):
        eng.run()
