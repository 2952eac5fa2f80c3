"""Vectorized pricing parity: repro.fabric.pricing vs the scalar oracle.

The contract is EXACT float equality (``==``, no tolerance): the numpy
kernels mirror the scalar expression trees in
``Topology.price_point`` operand for operand, so any drift -- a
re-associated sum, a float32 sneaking in -- is a bug, not a rounding
artifact.  Also covers: ``price()`` purity (no link-counter debits),
``debit_links`` explicitness, the batched analytic flush producing
bit-identical runs and link reports, and the once-per-op
``replica_groups={}`` free-pricing warning.
"""
import warnings

import numpy as np
import pytest

from repro.core import SystemSpec, simulate
from repro.core.hlo import CollectiveRecord, HloCost, TraceOp
from repro.core.hw import ChipSpec
from repro.core.topology import Topology, parse_replica_groups
import repro.core.topology as topology_mod
from repro.fabric import AnalyticFabric, pricing

SPECS = {
    "pod4x4": SystemSpec(pod_shape=(4, 4)),
    "pod8x8x2": SystemSpec(pod_shape=(8, 8), num_pods=2),
    "pod4x8x4": SystemSpec(pod_shape=(4, 8), num_pods=4),
    "slow_ici": SystemSpec(pod_shape=(4, 4),
                           chip=ChipSpec(ici_link_bandwidth=25e9)),
}
PAYLOADS = (64.0, 4096.0, 1e6, 4e6, 64e6, 1e9)
SIZES = (1, 2, 4, 8, 16, 64)


# -- exact parity, point by point --------------------------------------------

@pytest.mark.parametrize("spec_name", sorted(SPECS))
@pytest.mark.parametrize("cls", pricing.CLASSES)
@pytest.mark.parametrize("kind", pricing.KINDS)
def test_vectorized_equals_scalar_exactly(spec_name, kind, cls):
    spec = SPECS[spec_name]
    if cls == "cross_pod" and spec.num_pods < 2:
        pytest.skip("cross_pod needs >= 2 pods")
    topo = Topology(spec)
    points = [(B, n) for B in PAYLOADS for n in SIZES]
    B = np.array([p[0] for p in points])
    n = np.array([float(p[1]) for p in points])
    vec = pricing.price(kind, cls, B, n,
                        pricing.FabricParams.from_spec(spec))
    scalar = np.array([topo.price_point(kind, cls, float(b), int(m))
                       for b, m in points])
    # exact: same expression trees, same doubles -- not approx
    assert np.array_equal(vec, scalar), \
        f"drift at {np.nonzero(vec != scalar)[0][:5]}"


def test_stacked_config_grid_parity():
    """One price() call over a (config x traffic) grid via
    FabricParams.stack must equal per-spec scalar pricing."""
    specs = [SPECS[k] for k in sorted(SPECS)]
    params = pricing.FabricParams.stack(specs).reshape((len(specs), 1))
    B = np.array([4096.0, 1e6, 64e6])
    n = np.array([4.0, 8.0, 16.0])
    vec = pricing.price("all-reduce", "block_2d", B, n, params)
    assert vec.shape == (len(specs), 3)
    for i, spec in enumerate(specs):
        topo = Topology(spec)
        for j in range(3):
            assert vec[i, j] == topo.price_point(
                "all-reduce", "block_2d", float(B[j]), int(n[j]))


def test_singleton_groups_price_zero():
    out = pricing.price("all-reduce", "ring_x", np.array([1e6, 1e6]),
                        np.array([1.0, 0.0]),
                        pricing.FabricParams.from_spec(SPECS["pod4x4"]))
    assert np.array_equal(out, np.zeros(2))


def test_price_collectives_matches_scalar_api():
    """The batched-flush entry point must be bit-equal to the scalar
    live path Topology.price(kind, nbytes, [group])."""
    spec = SPECS["pod8x8x2"]
    topo = Topology(spec)
    items = []
    for kind in pricing.KINDS:
        items += [(kind, 1e6, tuple(range(8))),            # ring_x row
                  (kind, 4e6, tuple(range(0, 64, 8))),     # ring_y col
                  (kind, 2e6, tuple(range(16))),           # 2-D block
                  (kind, 8e6, (0, 64)),                    # cross-pod
                  (kind, 1e6, (3,))]                       # singleton
    vec = pricing.price_collectives(topo, items)
    for t, (kind, nbytes, group) in zip(vec, items):
        assert float(t) == topo.price(kind, nbytes, [list(group)])


def test_encode_rejects_unknown_names():
    with pytest.raises(ValueError, match="unknown collective kind"):
        pricing.encode_kinds(["all-reduce", "all-shuffle"])
    with pytest.raises(ValueError, match="unknown group class"):
        pricing.encode_classes(["ring_z"])


# -- hypothesis fuzz ---------------------------------------------------------

def test_fuzz_parity():
    from hypothesis import given, settings, strategies as st

    spec = SPECS["pod8x8x2"]
    topo = Topology(spec)
    params = pricing.FabricParams.from_spec(spec)

    @settings(max_examples=200, deadline=None)
    @given(kind=st.sampled_from(pricing.KINDS),
           cls=st.sampled_from(pricing.CLASSES),
           B=st.floats(min_value=1.0, max_value=1e12),
           n=st.integers(min_value=0, max_value=4096))
    def check(kind, cls, B, n):
        vec = pricing.price(kind, cls, np.array([B]), np.array([float(n)]),
                            params)
        assert float(vec[0]) == topo.price_point(kind, cls, B, n)

    check()


# -- purity: price() never debits, debit_links() always does -----------------

def test_price_is_pure_debit_is_explicit():
    topo = Topology(SPECS["pod4x4"])
    group = [list(range(4))]
    before = {k: l.bytes_total for k, l in topo.links.items()}
    t = topo.price("all-reduce", 1e6, group)
    assert t > 0
    assert {k: l.bytes_total for k, l in topo.links.items()} == before
    topo.debit_links("all-reduce", 1e6, group)
    after = {k: l.bytes_total for k, l in topo.links.items()}
    assert after != before
    # price + debit_links == the composed legacy entry point
    topo2 = Topology(SPECS["pod4x4"])
    assert topo2.collective_time_s("all-reduce", 1e6, group) == t
    assert {k: l.bytes_total for k, l in topo2.links.items()} == after


# -- batched analytic flush: bit-identity + unchanged link report ------------

def _mixed_cost(spec):
    cost = HloCost()
    X = spec.pod_shape[1]
    rows = [[y * X + x for x in range(X)]
            for y in range(spec.pod_shape[0])]
    every = [list(range(spec.total_chips))]
    for i in range(4):
        cost.trace.append(TraceOp("compute", f"c{i}", flops=1e9,
                                  hbm_bytes=1e7))
        for name, kind, nbytes, groups in (
                (f"ar{i}", "all-reduce", 1e6, every),
                (f"ag{i}", "all-gather", 2e6, rows),
                (f"a2a{i}", "all-to-all", 4e6, [rows[0]])):
            rec = CollectiveRecord(kind, name, nbytes, int(nbytes),
                                   int(nbytes), groups)
            cost.collectives.append(rec)
            cost.trace.append(TraceOp("collective", name, collective=rec))
    return cost


@pytest.mark.parametrize("scheduler", ["serial", "batch", "lookahead"])
def test_batched_pricing_bit_identical(scheduler):
    """The vectorized same-timestep flush must not move a single
    timestamp: batched and unbatched analytic runs produce identical
    SimReport summaries (link_report included) for every scheduler."""
    spec = SPECS["pod8x8x2"]
    cost = _mixed_cost(spec)
    batched = simulate(cost=cost, spec=spec, scheduler=scheduler,
                       device_limit=None, fabric=AnalyticFabric(spec))
    unbatched = simulate(cost=cost, spec=spec, scheduler=scheduler,
                         device_limit=None,
                         fabric=AnalyticFabric(spec, batch_pricing=False))
    b, u = batched.summary(), unbatched.summary()
    # the flush events themselves are extra engine events -- an
    # execution artifact, like batch_widths; every physical quantity
    # (timestamps, link bytes, utilization) must match exactly
    assert b.pop("events") >= u.pop("events")
    assert b == u


def test_batched_run_actually_batches():
    spec = SPECS["pod8x8x2"]
    fabric = AnalyticFabric(spec)
    simulate(cost=_mixed_cost(spec), spec=spec, device_limit=None,
             fabric=fabric)
    desc = fabric.describe()
    assert desc["batch_pricing"] is True
    assert desc["batched_pricings"] > 0
    # batching means fewer flushes than pricings
    assert desc["pricing_flushes"] < desc["batched_pricings"]


def test_link_report_unchanged_by_vectorized_path():
    """Satellite regression: debit_links still charges every byte the
    pre-split collective_time_s charged -- the occupancy report after a
    (batched) analytic run equals the unbatched one's exactly."""
    spec = SPECS["pod8x8x2"]
    cost = _mixed_cost(spec)
    a = simulate(cost=cost, spec=spec, device_limit=None,
                 fabric=AnalyticFabric(spec))
    b = simulate(cost=cost, spec=spec, device_limit=None,
                 fabric=AnalyticFabric(spec, batch_pricing=False))
    assert a.link_report == b.link_report
    assert a.link_report["hottest_links"]      # non-trivial report


# -- replica_groups={} free-pricing warning ----------------------------------

def test_empty_replica_groups_warns_once_per_op():
    topology_mod._warned_empty_groups.clear()
    attr = "replica_groups={}"
    with pytest.warns(UserWarning, match="priced as FREE") as rec:
        parse_replica_groups(attr, op="all-reduce.7")
    assert "all-reduce.7" in str(rec[0].message)
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # second time: silent
        assert parse_replica_groups(attr, op="all-reduce.7") == []
    # a different op warns again
    with pytest.warns(UserWarning, match="all-gather.2"):
        parse_replica_groups(attr, op="all-gather.2")
    topology_mod._warned_empty_groups.clear()
