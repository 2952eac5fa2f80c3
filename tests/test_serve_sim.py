"""Open-loop serving simulation: generator determinism, slot-ledger
properties, latency accounting exactness, the scheduler x executor x
fabric bit-identity matrix (healthy + fault-injected), and the
fault-produces-the-tail assertions.  See docs/serving.md."""
import numpy as np
import pytest

from repro.core import SystemSpec
from repro.serve.sim import (GENERATORS, ServeSizing, ServingScenario,
                             ServingSystem, SlotLedger, TenantSpec,
                             build_scenario, make_requests, run_serving)

SMALL = SystemSpec(pod_shape=(2, 2))

EXECUTOR_VARIANTS = ("threads", "procs")
SCHED_X_EXEC = [(s, e) for s in ("batch", "lookahead", "bounded")
                for e in EXECUTOR_VARIANTS]

STRAGGLER_LINK = {"fabric.pod0.ici[0,1]+x": [(0.0, "slow", 32.0)]}


def _scenario(seed=3, rate=800.0, duration=0.006, **kw):
    scen = build_scenario(SMALL, rate_rps=rate, duration_s=duration,
                          seed=seed, **kw)
    assert scen is not None
    return scen


_oracles: dict = {}


def _oracle(key, **kw):
    """Serial-scheduler reference runs, one sim per distinct config."""
    if key not in _oracles:
        _oracles[key] = run_serving(_scenario(), spec=SMALL, **kw)
    return _oracles[key]


# --------------------------------------------------------------------------
# arrival-trace generators: seeded determinism + rate sanity
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(GENERATORS))
def test_generator_deterministic_and_ordered(name):
    gen = GENERATORS[name]
    a = gen(500.0, 0.1, seed=7)
    b = gen(500.0, 0.1, seed=7)
    assert np.array_equal(a, b)                      # same seed, same trace
    c = gen(500.0, 0.1, seed=8)
    assert not np.array_equal(a, c)                  # seed actually matters
    assert len(a) > 0
    assert np.all(np.diff(a) > 0)                    # strictly increasing
    assert 0.0 < a[0] and a[-1] < 0.1                # inside the window


def test_poisson_mean_interarrival_bound():
    t = GENERATORS["poisson"](1000.0, 2.0, seed=0)
    mean_gap = np.diff(t).mean()
    assert 0.8e-3 < mean_gap < 1.25e-3               # ~1/rate


def test_bursty_rate_between_states():
    # MMPP alternates rate/4 and rate*4; long-run mean stays in between
    t = GENERATORS["bursty"](1000.0, 2.0, seed=0)
    assert 1000.0 * 2.0 / 4.5 < len(t) < 1000.0 * 2.0 * 4.5
    # and it is actually burstier than Poisson: CV^2 of gaps > 1
    gaps = np.diff(t)
    cv2 = gaps.var() / gaps.mean() ** 2
    assert cv2 > 1.2


def test_diurnal_rate_bounds_and_modulation():
    rate, dur = 1000.0, 2.0
    t = GENERATORS["diurnal"](rate, dur, seed=0, depth=0.8, period_s=dur)
    assert 0.5 * rate * dur < len(t) < 1.5 * rate * dur
    # first half-period runs above the base rate, second half below
    first, second = (t < dur / 2).sum(), (t >= dur / 2).sum()
    assert first > 1.3 * second


def test_make_requests_deterministic_and_ranged():
    times = GENERATORS["poisson"](500.0, 0.05, seed=1)
    a = make_requests(times, seed=2, prompt_range=(8, 16),
                      decode_range=(2, 5))
    b = make_requests(times, seed=2, prompt_range=(8, 16),
                      decode_range=(2, 5))
    assert a == b
    assert all(8 <= r.prompt_len <= 16 for r in a)
    assert all(2 <= r.decode_len <= 5 for r in a)
    assert [r.uid for r in a] == list(range(len(a)))
    assert make_requests(times, seed=3)[0] != a[0]


def test_unknown_generator_rejected():
    with pytest.raises(ValueError, match="unknown arrival generator"):
        build_scenario(SMALL, arrival="lognormal")


# --------------------------------------------------------------------------
# slot ledger: capacity as pure accounting
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_ledger_random_interleaving_invariants(seed):
    rng = np.random.default_rng(seed)
    led = SlotLedger(capacity=4)
    waiting = list(range(60))
    seated: list = []
    while waiting or seated:
        if seated and (not waiting or not led.has_free()
                       or rng.uniform() < 0.5):
            uid = seated.pop(rng.integers(len(seated)))
            led.release(uid)
        else:
            uid = waiting.pop(0)
            led.admit(uid)
            seated.append(uid)
        assert led.in_use <= led.capacity            # never over capacity
        assert led.in_use == len(seated)
    assert led.completed == set(range(60))           # none lost
    assert led.peak <= 4 and led.in_use == 0


def test_ledger_rejects_misuse():
    led = SlotLedger(2)
    led.admit(0)
    with pytest.raises(ValueError, match="already seated"):
        led.admit(0)
    led.admit(1)
    with pytest.raises(RuntimeError, match="no free slot"):
        led.admit(2)
    led.release(0)
    with pytest.raises(ValueError, match="already completed"):
        led.admit(0)                                 # uids never come back
    with pytest.raises(ValueError, match="double-completed"):
        led.release(0)
    with pytest.raises(ValueError, match="not seated"):
        led.release(9)
    with pytest.raises(ValueError, match="capacity"):
        SlotLedger(0)


def test_ledger_lowest_free_slot_first():
    led = SlotLedger(3)
    assert [led.admit(u) for u in (10, 11, 12)] == [0, 1, 2]
    led.release(11)
    led.release(10)
    assert led.admit(13) == 0                        # lowest freed slot


def test_ledger_hypothesis_capacity_and_conservation():
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(cap=st.integers(1, 8),
           actions=st.lists(st.tuples(st.booleans(), st.integers(0, 63)),
                            max_size=120))
    def run(cap, actions):
        led = SlotLedger(cap)
        seated: set = set()
        for admit, pick in actions:
            if admit and led.has_free():
                uid = next((u for u in range(64)
                            if u not in seated and u not in led.completed),
                           None)
                if uid is None:
                    continue
                led.admit(uid)
                seated.add(uid)
            elif seated:
                uid = sorted(seated)[pick % len(seated)]
                led.release(uid)
                seated.remove(uid)
            assert led.in_use <= cap
            assert led.in_use + len(led.free) == cap  # slots conserved
            assert set(led.seated) == seated
            assert not (seated & led.completed)       # no double life

    run()


def test_ledger_hypothesis_queue_plus_service_is_e2e():
    from hypothesis import given, settings, strategies as st

    # The sim stores integer-ps stamps; queue + prefill + decode must
    # reconstruct end-to-end latency with zero residue for ANY stamps
    # (this is why _ReqLog keeps ints and never converts to seconds).
    @settings(max_examples=100, deadline=None)
    @given(arrival=st.integers(0, 10**15), queue=st.integers(0, 10**12),
           prefill=st.integers(1, 10**12), decode=st.integers(0, 10**12))
    def run(arrival, queue, prefill, decode):
        admit = arrival + queue
        first = admit + prefill
        done = first + decode
        assert (admit - arrival) + (first - admit) + (done - first) \
            == done - arrival
        assert float(done - arrival) / 1e12 == (done - arrival) / 1e12

    run()


# --------------------------------------------------------------------------
# scenario construction + system validation
# --------------------------------------------------------------------------

def test_build_scenario_places_disjoint_row_blocks():
    scen = _scenario()
    assert [t.devices for t in scen.tenants] == [(0, 1), (2, 3)]
    assert build_scenario(SMALL, tenants=3) is None   # no row per tenant
    big = build_scenario(SystemSpec(pod_shape=(4, 4)), tenants=2)
    assert [t.devices for t in big.tenants] == [
        tuple(range(0, 8)), tuple(range(8, 16))]


def test_overlapping_or_out_of_range_tenants_rejected():
    t0 = _scenario().tenants[0]
    overlap = ServingScenario("bad", (t0, t0))
    with pytest.raises(ValueError, match="two tenants"):
        ServingSystem(overlap, SMALL)
    import dataclasses
    off = dataclasses.replace(t0, devices=(0, 99))
    with pytest.raises(ValueError, match="outside"):
        ServingSystem(ServingScenario("bad", (off,)), SMALL)


def test_sizing_is_exact_integers():
    t = _scenario().tenants[0]
    s = ServeSizing(t)
    for b in range(1, t.slots + 1):
        assert isinstance(s.ar_bytes(b), int)
        assert s.ar_bytes(b) == b * s.ar_bytes(1)     # linear in batch
    assert s.prefill_flops(32) == 2 * s.prefill_flops(16)


# --------------------------------------------------------------------------
# serving run: accounting exactness + capacity + open-loop behavior
# --------------------------------------------------------------------------

def test_latency_breakdown_sums_exactly():
    sys = ServingSystem(_scenario(), SMALL)
    sys.run()
    checked = 0
    for server in sys.servers:
        for rec in server.recs.values():
            assert rec.done_ps is not None            # everything drains
            q = rec.admit_ps - rec.arrival_ps
            p = rec.first_ps - rec.admit_ps
            d = rec.done_ps - rec.first_ps
            assert q >= 0 and p > 0 and d >= 0
            assert q + p + d == rec.done_ps - rec.arrival_ps  # int-exact
            checked += 1
    assert checked == sum(len(t.requests) for t in _scenario().tenants)


def test_report_counts_and_goodput():
    rep = _oracle(("analytic", "none"))
    assert rep.offered == rep.completed + rep.in_flight + rep.queued
    assert rep.completed == rep.offered               # drained run
    assert rep.goodput_rps > 0 and rep.offered_rps > 0
    assert rep.p50_s <= rep.p99_s <= rep.max_s
    assert all(1 <= p <= 4 for p in rep.peak_slots)
    assert rep.devices == 4 and rep.tenants == 2
    assert len(rep.tenant_p99_s) == 2


def test_summary_excludes_execution_fields():
    rep = _oracle(("analytic", "none"))
    s = rep.summary()
    assert "scheduler" not in s and "executor" not in s
    assert "p99_s" in s and "per_request" in s


def test_slots_cap_batch_and_queueing_appears_under_overload():
    calm = run_serving(_scenario(seed=5, rate=300.0, slots=2),
                       spec=SMALL)
    slam = run_serving(_scenario(seed=5, rate=4000.0, slots=2),
                       spec=SMALL)
    assert all(p <= 2 for p in slam.peak_slots)       # capacity respected
    assert max(slam.peak_slots) == 2                  # and actually reached
    assert slam.queue_mean_s > calm.queue_mean_s      # admission waited
    assert slam.p99_s > calm.p99_s                    # the knee, in small


def test_collective_count_matches_iterations():
    dense = _oracle(("analytic", "none"))
    assert dense.collectives_completed == dense.iterations * 4
    moe = run_serving(_scenario(moe=True), spec=SMALL)
    assert moe.collectives_completed == moe.iterations * 6  # +2 a2a
    assert moe.summary() != dense.summary()


# --------------------------------------------------------------------------
# bit-identity matrix: scheduler x executor x fabric, healthy + faulted
# --------------------------------------------------------------------------

@pytest.mark.parametrize("fabric", ("analytic", "event"))
@pytest.mark.parametrize("sched,executor", SCHED_X_EXEC)
def test_serving_bit_identity(sched, executor, fabric):
    oracle = _oracle((fabric, "none"), fabric=fabric)
    rep = run_serving(_scenario(), spec=SMALL, scheduler=sched,
                      executor=executor, max_workers=2, fabric=fabric)
    assert rep.summary() == oracle.summary()
    assert rep.scheduler == sched and rep.executor == executor


@pytest.mark.parametrize("sched,executor",
                         [("batch", "threads"), ("lookahead", "procs"),
                          ("bounded", "procs")])
def test_serving_bit_identity_under_fault(sched, executor):
    oracle = _oracle(("event", "straggler"), fabric="event",
                     faults=STRAGGLER_LINK)
    rep = run_serving(_scenario(), spec=SMALL, scheduler=sched,
                      executor=executor, max_workers=2, fabric="event",
                      faults=STRAGGLER_LINK)
    assert rep.summary() == oracle.summary()
    assert oracle.summary() != _oracle(("event", "none"),
                                       fabric="event").summary()


# --------------------------------------------------------------------------
# the fabric, not the generator, produces the tail
# --------------------------------------------------------------------------

def test_straggler_link_raises_event_p99():
    healthy = _oracle(("event", "none"), fabric="event")
    faulted = _oracle(("event", "straggler"), fabric="event",
                      faults=STRAGGLER_LINK)
    assert faulted.p99_s > healthy.p99_s
    # the faulted link is on tenant 0's ring; its tail takes the hit,
    # tenant 1 is bit-unchanged (its links are disjoint)
    assert faulted.tenant_p99_s[0] > healthy.tenant_p99_s[0]
    assert faulted.tenant_p99_s[1] == healthy.tenant_p99_s[1]
    assert faulted.completed == healthy.completed     # degraded, not broken


def test_analytic_run_is_unchanged_and_rejects_link_plans():
    a = _oracle(("analytic", "none"))
    b = run_serving(_scenario(), spec=SMALL)          # fresh run, same seed
    assert a.summary() == b.summary()                 # generator-stable
    with pytest.raises(ValueError, match="require fabric='event'"):
        run_serving(_scenario(), spec=SMALL, fabric="analytic",
                    faults=STRAGGLER_LINK)


def test_transient_link_stalls_only_the_affected_tenant():
    rep = run_serving(
        _scenario(), spec=SMALL, fabric="event",
        faults={"fabric.pod0.ici[0,1]+x": [(1e-3, "transient", 1e-3)]})
    healthy = _oracle(("event", "none"), fabric="event")
    assert rep.completed < healthy.completed          # dropped chunks stall
    assert rep.in_flight + rep.queued > 0             # the ring never drains
    # tenant 1 shares no link with the fault: completes its whole trace
    assert rep.tenant_p99_s[1] == healthy.tenant_p99_s[1]


def test_chip_straggler_degrades_analytic_and_event_alike():
    healthy = _oracle(("analytic", "none"))
    slow = run_serving(_scenario(), spec=SMALL,
                       faults={"chip0.core": [(0.0, "slow", 4.0)]})
    assert slow.tenant_p99_s[0] > healthy.tenant_p99_s[0]
    assert slow.mean_s > healthy.mean_s


# --------------------------------------------------------------------------
# sweep integration
# --------------------------------------------------------------------------

def test_sweep_exposes_serving_scenarios():
    from tools import sweep
    assert {"serving_poisson", "serving_overload", "serving_burst",
            "serving_diurnal", "serving_moe"} <= set(sweep.SCENARIOS)
    cfgs = sweep.expand_grid({"scenario": ["serving_poisson"],
                              "topology": ["pod2x2"],
                              "scheduler": ["serial"],
                              "fabric": ["analytic"],
                              "faults": ["none", "slow_link"]})
    # slow_link needs the event fabric: only the healthy combo expands
    assert len(cfgs) == 1


def test_sweep_runs_serving_config_with_latency_row():
    from tools import sweep
    cfg = sweep.expand_grid({"scenario": ["serving_poisson"],
                             "topology": ["pod2x2"],
                             "scheduler": ["serial"],
                             "fabric": ["analytic"],
                             "faults": ["none"]})[0]
    row = sweep.run_config(cfg)
    assert row["p99_s"] > row["p50_s"] > 0
    assert row["completed"] == row["offered"] > 0
    assert row["goodput_rps"] > 0
    assert "error" not in row


# --------------------------------------------------------------------------
# recovery layer: detection -> abort -> re-mesh -> requeue (docs/faults.md)
# --------------------------------------------------------------------------

DEADLINE = 5e-4
KILL = {"chip1.prog": [(3e-3, "fail", None)]}          # tenant 0, mid-trace
REJOIN = {"chip1.prog": [(2e-3, "fail", None), (4e-3, "recover", None)]}


def _rec_oracle(key, **kw):
    """Serial reference runs for the recovery matrix (cached like
    _oracle; recovery runs are slower, so one sim per config)."""
    if key not in _oracles:
        _oracles[key] = run_serving(_scenario(), spec=SMALL,
                                    deadline_s=DEADLINE, recovery=True, **kw)
    return _oracles[key]


def test_ledger_evict_reclaims_seat_without_retiring_uid():
    led = SlotLedger(2)
    led.admit(7)
    led.admit(8)
    assert led.evict(7) == 0
    assert led.in_use == 1 and 7 not in led.completed
    assert led.admit(7) == 0                          # re-admit works
    led.release(7)
    with pytest.raises(ValueError, match="already completed"):
        led.evict(7)                                  # done is done
    with pytest.raises(ValueError, match="not seated"):
        led.evict(9)
    assert led.evict(8) == 1 and led.in_use == 0


def test_recovery_serves_through_chip_kill():
    rep = _rec_oracle(("rec", "analytic", "kill"), faults=KILL)
    assert rep.offered == rep.completed + rep.dropped  # zero stuck
    assert rep.retries > 0 and rep.recoveries >= 1
    assert rep.chip_deaths == 1 and rep.collective_timeouts >= 1
    # availability dips for the tenant that lost a chip, nobody else
    assert rep.tenant_availability[0] < 1.0
    assert rep.tenant_availability[1] == 1.0
    assert rep.tenant_outage_s[0] > 0 and rep.tenant_outage_s[1] == 0
    assert rep.outage_windows[0] and not rep.outage_windows[1]
    assert rep.goodput_in_outage_rps < rep.goodput_outside_outage_rps


@pytest.mark.parametrize("fabric", ("analytic", "event"))
@pytest.mark.parametrize("sched,executor", SCHED_X_EXEC)
def test_recovery_bit_identity_mid_recovery(sched, executor, fabric):
    """The hard invariant: death + abort + re-mesh + requeue all ride
    engine events, so every scheduler x executor reproduces the serial
    oracle bit-for-bit *while* the trace recovers."""
    oracle = _rec_oracle(("rec", fabric, "kill"), fabric=fabric, faults=KILL)
    rep = run_serving(_scenario(), spec=SMALL, scheduler=sched,
                      executor=executor, max_workers=2, fabric=fabric,
                      deadline_s=DEADLINE, recovery=True, faults=KILL)
    assert rep.summary() == oracle.summary()
    assert rep.retries == oracle.retries > 0


def test_recovery_cross_fabric_behavioral_identity():
    """Analytic and event price these small rings identically, so even
    mid-recovery only the fabric-artifact fields may differ."""
    a = _rec_oracle(("rec", "analytic", "kill"), faults=KILL).summary()
    e = _rec_oracle(("rec", "event", "kill"), fabric="event",
                    faults=KILL).summary()
    skip = ("events", "fabric", "link_report", "link_utilization")
    assert {k: v for k, v in a.items() if k not in skip} \
        == {k: v for k, v in e.items() if k not in skip}


def test_rejoin_rolls_the_chip_back_in():
    rep = run_serving(_scenario(), spec=SMALL, deadline_s=DEADLINE,
                      recovery=True, faults=REJOIN)
    assert rep.rejoins == 1 and rep.chip_deaths == 1
    assert rep.completed == rep.offered                # everything drains
    assert rep.retries > 0
    # the rejoin re-mesh itself is loss-free: nothing gets dropped
    assert rep.dropped == 0


def test_transient_link_served_through_with_recovery():
    """PR 8 left this stalling forever (in_flight + queued > 0); with a
    deadline + recovery the lost chunks surface as a timeout, the
    iteration retries, and the trace completes -- no chip is falsely
    declared dead (the roster was complete; the fabric stalled)."""
    rep = run_serving(
        _scenario(), spec=SMALL, fabric="event", deadline_s=DEADLINE,
        recovery=True,
        faults={"fabric.pod0.ici[0,1]+x": [(1e-3, "transient", 1e-3)]})
    assert rep.completed == rep.offered
    assert rep.retries >= 1 and rep.recoveries >= 1
    assert rep.chip_deaths == 0


def test_deadline_threads_through_run_serving_healthy():
    """deadline_s alone (recovery=False) must not perturb a healthy run:
    no timeouts, identical latency behavior (only the engine's internal
    event count may differ -- deadline events exist now)."""
    base = _oracle(("analytic", "none"))
    rep = run_serving(_scenario(), spec=SMALL, deadline_s=DEADLINE,
                      recovery=False)
    assert rep.collective_timeouts == 0
    assert rep.p99_s == base.p99_s and rep.completed == base.completed
    assert rep.retries == rep.recoveries == rep.chip_deaths == 0


def test_detection_only_mode_counts_timeouts_but_stalls():
    """recovery=False keeps PR 8 semantics under a kill: the signal
    fires, nobody reacts, the tenant stalls -- the explicit contrast
    that motivates the recovery layer."""
    rep = run_serving(_scenario(), spec=SMALL, deadline_s=DEADLINE,
                      recovery=False, faults=KILL)
    assert rep.collective_timeouts >= 1
    assert rep.completed < rep.offered
    assert rep.retries == 0 and rep.recoveries == 0


def test_heartbeat_detects_death_on_collective_free_tenant():
    """Single-chip tenants never run collectives, so the deadline signal
    can't fire -- only the heartbeat probe path can declare the death.
    The dead tenant's unserviceable requests stay queued (there is no
    surviving chip to re-mesh onto) but the run still terminates."""
    tiny = SystemSpec(pod_shape=(2, 1))
    scen = build_scenario(tiny, rate_rps=800.0, duration_s=0.006, seed=3)
    assert [t.devices for t in scen.tenants] == [(0,), (1,)]
    rep = run_serving(scen, spec=tiny, deadline_s=DEADLINE, recovery=True,
                      faults={"chip0.prog": [(2e-3, "fail", None)]})
    assert rep.chip_deaths == 1 and rep.collective_timeouts == 0
    assert rep.tenant_availability[0] < 1.0
    assert rep.tenant_availability[1] == 1.0
    assert rep.queued > 0                              # dead tenant's tail
    assert rep.completed + rep.queued == rep.offered
