"""Pallas kernels vs ref.py oracles — shape/dtype sweeps + properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def _rand(shape, dtype=jnp.float32, k=0):
    return jax.random.normal(jax.random.fold_in(KEY, k), shape, jnp.float32
                             ).astype(dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,K,hd", [
    (1, 128, 4, 4, 64),        # MHA
    (2, 256, 8, 2, 64),        # GQA 4:1
    (1, 384, 6, 2, 32),        # uneven heads, S % bq != 0 via bq=128
    (2, 128, 16, 16, 128),     # wide MHA, hd=128
])
def test_flash_attention_shapes(B, S, H, K, hd):
    q, k, v = (_rand((B, S, H, hd), k=1), _rand((B, S, K, hd), k=2),
               _rand((B, S, K, hd), k=3))
    got = ops.flash_attention(q, k, v, causal=True, bq=128, bk=128)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


def test_flash_attention_bf16():
    q, k, v = (_rand((1, 128, 4, 64), jnp.bfloat16, 1),
               _rand((1, 128, 2, 64), jnp.bfloat16, 2),
               _rand((1, 128, 2, 64), jnp.bfloat16, 3))
    got = ops.flash_attention(q, k, v, causal=True).astype(jnp.float32)
    want = ref.flash_attention_ref(q, k, v, causal=True).astype(jnp.float32)
    np.testing.assert_allclose(got, want, atol=3e-2, rtol=3e-2)


def test_flash_attention_noncausal():
    q, k, v = (_rand((1, 128, 4, 32), k=4), _rand((1, 128, 4, 32), k=5),
               _rand((1, 128, 4, 32), k=6))
    got = ops.flash_attention(q, k, v, causal=False)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


def test_attention_rows_convex_combination():
    """Property: each output is a convex combination of V rows, so it
    lies inside V's coordinate-wise range."""
    q, k = _rand((1, 128, 2, 32), k=7), _rand((1, 128, 2, 32), k=8)
    v = _rand((1, 128, 2, 32), k=9)
    out = ops.flash_attention(q, k, v, causal=True)
    assert float(jnp.max(out)) <= float(jnp.max(v)) + 1e-4
    assert float(jnp.min(out)) >= float(jnp.min(v)) - 1e-4


# ---------------------------------------------------------------------------
# SSD
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("R,H,Q,P,N", [(2, 2, 64, 32, 16),
                                       (1, 4, 128, 64, 64),
                                       (3, 1, 32, 16, 8)])
def test_ssd_chunk_kernel(R, H, Q, P, N):
    x = _rand((R, H, Q, P), k=10)
    dt = jax.nn.softplus(_rand((R, H, Q), k=11))
    A = -jnp.exp(_rand((H,), k=12))
    cs = jnp.cumsum(dt * A[None, :, None], axis=-1)
    Bm, Cm = _rand((R, H, Q, N), k=13), _rand((R, H, Q, N), k=14)
    y1, s1 = ops.ssd_chunk_kernel(x, dt, cs, Bm, Cm)
    y2, s2 = ref.ssd_chunk_ref(x, dt, cs, Bm, Cm)
    np.testing.assert_allclose(y1, y2, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(s1, s2, atol=1e-4, rtol=1e-4)


def test_ssd_pallas_matches_reference_scan():
    from repro.models.ssm import ssd_reference
    B, L, H, P, N = 2, 128, 4, 32, 16
    x = _rand((B, L, H, P), k=15)
    dt = jax.nn.softplus(_rand((B, L, H), k=16))
    A = -jnp.exp(_rand((H,), k=17))
    Bm, Cm = _rand((B, L, 1, N), k=18), _rand((B, L, 1, N), k=19)
    y1 = ops.ssd_pallas(x, dt, A, Bm, Cm, chunk=32)
    y2 = ssd_reference(x, dt, A, Bm, Cm, chunk=32)
    np.testing.assert_allclose(y1, y2, atol=1e-3, rtol=1e-3)


def test_ssd_matches_naive_recurrence():
    """SSD == the literal sequential state-space recurrence (the real
    semantic oracle, independent of chunking)."""
    from repro.models.ssm import ssd_reference
    B, L, H, P, N = 1, 24, 2, 8, 4
    x = np.asarray(_rand((B, L, H, P), k=20), np.float64)
    dt = np.asarray(jax.nn.softplus(_rand((B, L, H), k=21)), np.float64)
    A = np.asarray(-jnp.exp(_rand((H,), k=22)), np.float64)
    Bm = np.asarray(_rand((B, L, 1, N), k=23), np.float64)
    Cm = np.asarray(_rand((B, L, 1, N), k=24), np.float64)
    S = np.zeros((B, H, N, P))
    y_naive = np.zeros((B, L, H, P))
    for t in range(L):
        dA = np.exp(dt[:, t] * A[None, :])                    # (B,H)
        S = dA[..., None, None] * S + np.einsum(
            "bgn,bh,bhp->bhnp", Bm[:, t], dt[:, t], x[:, t])
        y_naive[:, t] = np.einsum("bgn,bhnp->bhp", Cm[:, t], S)
    y = ssd_reference(jnp.asarray(x, jnp.float32),
                      jnp.asarray(dt, jnp.float32),
                      jnp.asarray(A, jnp.float32),
                      jnp.asarray(Bm, jnp.float32),
                      jnp.asarray(Cm, jnp.float32), chunk=8)
    np.testing.assert_allclose(np.asarray(y), y_naive, atol=2e-4, rtol=2e-3)


# ---------------------------------------------------------------------------
# rmsnorm / stencil / bitonic
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,d", [(64, 256), (100, 512), (1, 128)])
def test_rmsnorm(rows, d):
    x, w = _rand((rows, d), k=25), _rand((d,), k=26)
    np.testing.assert_allclose(ops.rmsnorm(x, w, block_rows=32),
                               ref.rmsnorm_ref(x, w), atol=1e-5, rtol=1e-4)


@given(st.integers(1, 6))
@settings(max_examples=6, deadline=None)
def test_rmsnorm_scale_invariance(p):
    """Property: rmsnorm(c*x) == rmsnorm(x) for any positive scale c."""
    x, w = _rand((16, 64), k=27), _rand((64,), k=28)
    c = float(2 ** p)
    np.testing.assert_allclose(ops.rmsnorm(c * x, w), ops.rmsnorm(x, w),
                               atol=1e-4, rtol=1e-3)


@pytest.mark.parametrize("H,W,K", [(128, 64, 3), (256, 128, 5), (64, 64, 3)])
def test_stencil(H, W, K):
    img, kern = _rand((H, W), k=29), _rand((K, K), k=30)
    got = ops.stencil2d(img, kern, block_rows=min(64, H))
    np.testing.assert_allclose(got, ref.stencil2d_ref(img, kern),
                               atol=1e-4, rtol=1e-4)


def test_bitonic_stage_matches_ref():
    x = _rand((2048,), k=31)
    for size, dist in [(2, 1), (8, 4), (64, 16), (2048, 256)]:
        got = ops.bitonic_stage(x, dist, size, block=512) if dist < 512 \
            else ref.bitonic_stage_ref(x, dist, size)
        want = ref.bitonic_stage_ref(x, dist, size)
        np.testing.assert_allclose(got, want)


@given(st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_bitonic_full_sort_property(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(0, 1, 256).astype(np.float32))
    y = np.asarray(ref.bitonic_sort_ref(x))
    np.testing.assert_allclose(y, np.sort(np.asarray(x)))
