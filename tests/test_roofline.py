"""Roofline math + the cost_analysis per-device convention check."""
import pytest

from conftest import run_with_devices
from repro.core import SystemSpec, build_terms
from repro.core.hlo import CollectiveRecord, HloCost
from repro.core.roofline import (attention_flops, model_flops_train,
                                 fmt_seconds)

SPEC = SystemSpec()


def _cost(coll=0.0):
    c = HloCost(flops=197e12, hbm_bytes=819e9)
    if coll:
        c.collectives.append(CollectiveRecord(
            "all-reduce", "ar", coll, int(coll), int(coll),
            [list(range(16))]))
    return c


def test_terms_unit_times():
    t = build_terms("x/y", "(16,16)", 256, {}, _cost(), SPEC)
    assert t.t_compute == pytest.approx(1.0)
    assert t.t_memory == pytest.approx(1.0)
    assert t.dominant in ("compute", "memory")


def test_collective_term_spec_formula():
    t = build_terms("x/y", "(16,16)", 256, {}, _cost(coll=50e9), SPEC)
    assert t.t_collective == pytest.approx(1.0)   # 50e9 B / 50e9 B/s
    assert t.t_collective_sim > 0


def test_dominant_and_fraction():
    c = HloCost(flops=197e12, hbm_bytes=8.19e12)  # memory 10x compute
    t = build_terms("x/y", "(16,16)", 256, {}, c, SPEC)
    assert t.dominant == "memory"
    assert t.roofline_fraction == pytest.approx(0.1)


def test_useful_ratio():
    c = HloCost(flops=2e12, hbm_bytes=1.0)
    t = build_terms("x/y", "(16,16)", 256, {}, c, SPEC,
                    model_flops_global=256 * 1e12)
    assert t.useful_ratio == pytest.approx(0.5)


def test_model_flops_train_6nd():
    assert model_flops_train(1e9, 1e6) == 6e15


def test_attention_flops_causal_half():
    full = attention_flops(2, 128, 4, 64, 3, causal=False)
    assert attention_flops(2, 128, 4, 64, 3, causal=True) == full / 2


def test_fmt_seconds():
    assert fmt_seconds(0.0025) == "2.5ms"
    assert fmt_seconds(3.2) == "3.2s"


def test_cost_analysis_is_per_device():
    """XLA's cost_analysis reports the PER-DEVICE partitioned module —
    the convention core/roofline.py documents and relies on."""
    out = run_with_devices(8, """
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
mesh = make_mesh((8,), ("x",))
sh = NamedSharding(mesh, P("x", None))
M = 1024
a = jax.ShapeDtypeStruct((M, M), jnp.float32, sharding=sh)
b = jax.ShapeDtypeStruct((M, M), jnp.float32,
                         sharding=NamedSharding(mesh, P(None, None)))
comp = jax.jit(lambda x, y: x @ y).lower(a, b).compile()
flops = comp.cost_analysis()["flops"]
global_flops = 2 * M**3
ratio = flops / global_flops
# per-device: ratio ~ 1/8; global would be ~1
assert 0.06 < ratio < 0.26, ratio
print("PER_DEVICE_RATIO", ratio)
""")
    assert "PER_DEVICE_RATIO" in out
