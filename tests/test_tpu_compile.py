"""Compile the chip path for a described TPU v5e, with no chip attached.

Nothing here runs: each test lowers and compiles for a v5e:2x2 topology
that the TPU compiler describes, which catches what interpret mode
cannot (tiling, VMEM limits, programs that do not fit).  The kernels pass
``interpret=False`` because ``jax.default_backend()`` sees the CPU here.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.models import api, get_config

V5E_HBM_BYTES = 16 * 10 ** 9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _is_mosaic_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_for_v5e(one_chip):
    cfg = get_config("qwen2-1.5b")
    S = 4096
    q = _sds((1, S, cfg.num_heads, cfg.hd), jnp.bfloat16, one_chip)
    kv = _sds((1, S, cfg.num_kv_heads, cfg.hd), jnp.bfloat16, one_chip)
    compiled = jax.jit(lambda q, k, v: ops.flash_attention(
        q, k, v, causal=True, interpret=False)).lower(q, kv, kv).compile()
    assert _is_mosaic_kernel(compiled)


def test_rmsnorm_compiles_for_v5e(one_chip):
    d = get_config("qwen2-1.5b").d_model
    x = _sds((4096, d), jnp.bfloat16, one_chip)
    w = _sds((d,), jnp.bfloat16, one_chip)
    compiled = jax.jit(lambda x, w: ops.rmsnorm(
        x, w, interpret=False)).lower(x, w).compile()
    assert _is_mosaic_kernel(compiled)


def test_ssd_chunk_kernel_compiles_for_v5e(one_chip):
    cfg = get_config("mamba2-1.3b")
    R, H, Q = 16, cfg.ssm_heads, cfg.ssm_chunk
    P, N = cfg.ssm_head_dim, cfg.ssm_state
    f32 = jnp.float32
    args = [_sds(s, f32, one_chip) for s in
            [(R, H, Q, P), (R, H, Q), (R, H, Q), (R, H, Q, N), (R, H, Q, N)]]
    compiled = jax.jit(lambda *a: ops.ssd_chunk_kernel(
        *a, interpret=False)).lower(*args).compile()
    assert _is_mosaic_kernel(compiled)


def test_qwen2_decode_step_fits_one_v5e(one_chip):
    """The serve engine's decode program at full depth and published
    widths, 4 slots x 2048 cache rows, fits one chip's HBM."""
    cfg = get_config("qwen2-1.5b")
    slots, max_seq = 4, 2048
    params = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: api.init_cache(cfg, slots, max_seq))
    cache["pos"] = jax.ShapeDtypeStruct((slots,), jnp.int32)
    place = lambda tree: jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, one_chip), tree)
    compiled = jax.jit(lambda p, c, t: api.decode_step(p, cfg, c, t)).lower(
        place(params), place(cache),
        _sds((slots,), jnp.int32, one_chip)).compile()
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 3.5e9 < need < V5E_HBM_BYTES, need
