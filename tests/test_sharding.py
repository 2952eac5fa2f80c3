"""Sharding rules + U-mode/D-mode lowering on multi-device meshes."""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from conftest import run_with_devices
from repro.models import api, get_config
from repro.sharding import specs


def test_param_rules_shape_match():
    cfg = get_config("qwen2-1.5b-smoke")
    shapes = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0), cfg))
    tree = specs.param_specs(cfg, shapes)
    flat_shapes = jax.tree.leaves(shapes)
    flat_specs = jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_shapes) == len(flat_specs)
    for sh, sp in zip(flat_shapes, flat_specs):
        assert len(sp) <= sh.ndim


def test_attention_tp_rules():
    cfg = get_config("internlm2-20b")
    shapes = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0), cfg))
    tree = specs.param_specs(cfg, shapes)
    assert tree["layers"]["attn"]["wq"] == P(None, "data", "model")
    assert tree["layers"]["attn"]["wo"] == P(None, "model", "data")
    assert tree["embed"] == P("model", "data")
    assert tree["layers"]["ln1"] == P()


def test_moe_expert_rules():
    cfg = get_config("dbrx-132b")
    shapes = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0), cfg))
    tree = specs.param_specs(cfg, shapes)
    assert tree["layers"]["moe"]["wg"][1] == "model"     # experts -> EP
    assert tree["layers"]["moe"]["router"] == P(None, None, None)


def test_cache_rules_sp_vs_heads():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    cfg = get_config("qwen2-1.5b")         # kv=2 < 16 -> seq sharded
    cache = jax.eval_shape(lambda: api.init_cache(cfg, 128, 1024))
    tree = specs.cache_specs_tree(cfg, cache, FakeMesh())
    assert tree["k"][2] == "model" and tree["k"][3] is None
    cfg2 = get_config("zamba2-7b")         # kv=32 % 16 == 0 -> head sharded
    cache2 = jax.eval_shape(lambda: api.init_cache(cfg2, 128, 1024))
    tree2 = specs.cache_specs_tree(cfg2, cache2, FakeMesh())
    assert tree2["k"][3] == "model"


def test_umode_lowering_all_families_8dev():
    out = run_with_devices(8, """
import jax
from repro.models import get_config
from repro.sharding import umode
from repro.configs.shapes import ShapeCell, input_specs
from repro.train.optim import OptConfig
mesh = make_mesh((2, 4), ("data", "model"))
cell = ShapeCell("t", 64, 8, "train")
for name in ["qwen2-1.5b-smoke", "dbrx-132b-smoke", "mamba2-1.3b-smoke",
             "zamba2-7b-smoke", "whisper-base-smoke",
             "llava-next-34b-smoke"]:
    cfg = get_config(name)
    with mesh:
        comp = umode.lower_train_step(cfg, mesh, input_specs(cfg, cell),
                                      OptConfig()).compile()
        assert comp.cost_analysis().get("flops", 0) > 0
print("LOWER_OK")
""")
    assert "LOWER_OK" in out


def test_umode_execution_matches_single_device():
    """The distributed train step computes the SAME loss as 1 device."""
    out = run_with_devices(8, """
import jax, jax.numpy as jnp, numpy as np
from repro.models import get_config, api
from repro.sharding import umode
from repro.train import optim
cfg = get_config("qwen2-1.5b-smoke")
params = api.init(jax.random.PRNGKey(0), cfg)
batch = {"tokens": (jnp.arange(8*32).reshape(8, 32) * 3) % cfg.vocab_size,
         "targets": jnp.ones((8, 32), jnp.int32)}
single = float(api.loss(params, cfg, batch))
mesh = make_mesh((2, 4), ("data", "model"))
with mesh:
    step, st_sh_fn, b_sh_fn = umode.make_train_step(cfg, mesh,
                                                    optim.OptConfig())
    state = optim.init_state(params)
    st_sh = st_sh_fn(jax.eval_shape(lambda: state))
    state = jax.tree.map(lambda x, s: jax.device_put(x, s), state, st_sh)
    state, metrics = jax.jit(step, donate_argnums=0)(state, batch)
dist = float(metrics["loss"])
assert abs(single - dist) < 1e-2, (single, dist)
print("LOSS_MATCH", single, dist)
""")
    assert "LOSS_MATCH" in out


def test_dmode_tp_matches_umode_8dev():
    out = run_with_devices(8, """
import jax, jax.numpy as jnp
from repro.models import get_config, api
from repro.sharding import dmode
cfg = get_config("qwen2-1.5b-smoke")
p = api.init(jax.random.PRNGKey(0), cfg)
batch = {"tokens": jnp.arange(2*16).reshape(2,16) % cfg.vocab_size,
         "targets": jnp.ones((2,16), jnp.int32)}
mesh = make_mesh((2, 4), ("data", "model"))
with mesh:
    d = float(dmode.tp_loss(cfg, mesh)(p, batch))
u = float(api.loss(p, cfg, batch))
assert abs(u - d) < 2e-3, (u, d)
print("DMODE_MATCH")
""")
    assert "DMODE_MATCH" in out


def test_production_mesh_512():
    out = run_with_devices(512, """
from repro.launch.mesh import make_production_mesh
m1 = make_production_mesh()
assert dict(m1.shape) == {"data": 16, "model": 16}
m2 = make_production_mesh(multi_pod=True)
assert dict(m2.shape) == {"pod": 2, "data": 16, "model": 16}
print("MESH_OK", len(jax.devices()))
""")
    assert "MESH_OK 512" in out
