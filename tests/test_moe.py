"""MoE dispatch/combine invariants (property-based where it matters)."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.models import get_config
from repro.models import moe as M

CFG = get_config("dbrx-132b-smoke")
RNG = jax.random.PRNGKey(3)


def test_capacity_is_mxu_padded():
    assert M.capacity(1024, CFG) % 8 == 0
    assert M.capacity(1024, CFG) >= 1024 * CFG.experts_per_token \
        / CFG.num_experts


def test_route_topk_normalized():
    p = M.init_moe(RNG, CFG)
    x = jax.random.normal(RNG, (64, CFG.d_model))
    idx, w, aux = M.route(p, x, CFG)
    assert idx.shape == (64, CFG.experts_per_token)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-5)
    assert float(aux) > 0


def test_dispatch_positions_unique_per_expert():
    """No two assignments may land in the same (expert, slot)."""
    idx = jnp.asarray([[0, 1], [0, 2], [0, 3], [1, 2]])
    dispatch, pos, keep = M.build_dispatch(idx, T=4, E=4, C=8)
    taken = [(int(e), int(p)) for e, p in
             zip(idx.reshape(-1), pos) if p < 8]
    assert len(taken) == len(set(taken))


def test_capacity_drops_excess():
    idx = jnp.zeros((10, 1), jnp.int32)        # all tokens pick expert 0
    dispatch, pos, keep = M.build_dispatch(idx, T=10, E=2, C=4)
    assert int(keep.sum()) == 4                # only capacity survives
    assert int((dispatch[0] < 10).sum()) == 4


@given(st.integers(0, 100))
@settings(max_examples=8, deadline=None)
def test_moe_identity_when_experts_identical(seed):
    """Property: if all experts compute f, MoE(x) == f(x) for any routing
    (gates sum to 1), provided nothing is dropped."""
    cfg = CFG.replace(capacity_factor=float(cfg_cap()))
    rng = jax.random.PRNGKey(seed)
    p = M.init_moe(rng, cfg)
    one = {k: v for k, v in p.items()}
    # make every expert identical to expert 0
    for k in ("wg", "wu", "wd"):
        one[k] = jnp.broadcast_to(p[k][:1], p[k].shape)
    x = jax.random.normal(rng, (32, cfg.d_model))
    y, _ = M.moe_ffn(one, x, cfg)
    ref = M.expert_ffn({k: one[k][:1] for k in ("wg", "wu", "wd")},
                       x[None])[0]
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               atol=1e-4, rtol=1e-3)


def cfg_cap():
    return CFG.num_experts / CFG.experts_per_token   # no-drop capacity


def test_moe_grads_flow_to_router_and_experts():
    p = M.init_moe(RNG, CFG)
    x = jax.random.normal(RNG, (16, CFG.d_model))
    g = jax.grad(lambda pp: M.moe_ffn(pp, x, CFG)[0].sum())(p)
    assert float(jnp.abs(g["router"]).sum()) > 0
    assert float(jnp.abs(g["wd"]).sum()) > 0
