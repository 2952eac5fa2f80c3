"""Gradient compression: quantization bounds + error feedback."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.train import compress


def test_quantize_roundtrip_error_bound():
    g = jax.random.normal(jax.random.PRNGKey(0), (64, 128))
    q, scale = compress.quantize(g)
    deq = compress.dequantize(q, scale)
    # per-row max error <= scale/2 (= rowmax/254)
    err = jnp.max(jnp.abs(deq - g), axis=-1)
    bound = jnp.max(jnp.abs(g), axis=-1) / 127.0
    assert bool(jnp.all(err <= bound * 0.5 + 1e-7))


def test_error_feedback_unbiased_over_time():
    """Sum of compressed grads converges to sum of true grads."""
    rng = np.random.default_rng(0)
    g_true = jnp.asarray(rng.normal(0, 1, (8, 64)).astype(np.float32))
    err = jnp.zeros_like(g_true)
    acc = jnp.zeros_like(g_true)
    for _ in range(50):
        g_hat, err = compress.compress_with_feedback(g_true, err)
        acc = acc + g_hat
    np.testing.assert_allclose(np.asarray(acc / 50), np.asarray(g_true),
                               atol=2e-3)


@given(st.integers(0, 50))
@settings(max_examples=10, deadline=None)
def test_quantize_idempotent_on_grid(seed):
    """Property: re-quantizing a dequantized tensor is exact."""
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.normal(0, 1, (4, 32)).astype(np.float32))
    q, s = compress.quantize(g)
    deq = compress.dequantize(q, s)
    q2, s2 = compress.quantize(deq)
    np.testing.assert_allclose(np.asarray(compress.dequantize(q2, s2)),
                               np.asarray(deq), atol=1e-6)


def test_wire_bytes_4x_saving():
    tree = {"a": jnp.zeros((128, 256)), "b": jnp.zeros((64,))}
    comp, unc = compress.wire_bytes(tree)
    assert unc == (128 * 256 + 64) * 4
    assert comp < unc / 3.5                   # ~4x minus scale overhead


def test_compressed_psum_multidevice(run=None):
    """compressed_psum over a pod axis == exact mean within int8 error."""
    from conftest import run_with_devices
    out = run_with_devices(4, """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.train import compress
mesh = make_mesh((4,), ("pod",))
g = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 64))
def local(gl):
    mean, err = compress.compressed_psum(gl[0], "pod")
    return mean[None], err
fn = jax.shard_map(local, mesh=mesh, in_specs=(P("pod", None, None),),
                   out_specs=(P("pod", None, None), P("pod", None)),
                   check_vma=False)
with mesh:
    mean, err = fn(g)
true = jnp.mean(g, axis=0)
for i in range(4):
    e = float(jnp.max(jnp.abs(mean[i] - true)))
    assert e < 0.05, e
print("COMPRESSED_PSUM_OK")
""")
    assert "COMPRESSED_PSUM_OK" in out
