"""U-mode: the unified-logical-device programming model (paper's U-MGPU).

One `jax.jit` over the whole mesh; GSPMD owns intermediate placement.
The programmer declares *only* input/output shardings (+ a few
`with_sharding_constraint` hints for SP residuals and MoE expert
buffers); the compiler decides every collective.  This is the U-MGPU
analog the case study compares against D-mode (explicit shard_map).

Builders return (step_fn, in_shardings, out_shardings) ready for
``.lower(...)`` in the dry-run or direct execution in the trainers.
"""
from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import api
from repro.models.base import ModelConfig
from repro.train import optim
from . import specs


def _ns(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def make_ctx(cfg: ModelConfig, mesh: Mesh) -> dict:
    """Sharding-constraint callables threaded into the model forward."""
    ctx = {}
    if cfg.seq_shard_activations:
        sp = NamedSharding(mesh, specs.activation_spec(cfg, mesh))
        ctx["sp"] = lambda h: jax.lax.with_sharding_constraint(h, sp)
    if cfg.family == "moe":
        ep = NamedSharding(mesh, P("model", None, None))
        ctx["ep"] = lambda xe: jax.lax.with_sharding_constraint(xe, ep)
        # NOTE: embedding the D-mode shard_map MoE inside the U-mode step
        # (make_moe_shard_map) was tried and REFUTED at full scale — the
        # shard_map boundary resharding inside scan+remat exploded
        # collectives 20x (EXPERIMENTS.md §Perf qwen3 iteration 2b).
        # Grouped dispatch + ep constraints is the winning configuration.
    if cfg.family in ("ssm", "hybrid") and cfg.ssm_heads % \
            specs.model_axis_size(mesh) == 0:
        dp = specs.dp_axes(mesh)

        def bh(x, b_axis, h_axis):
            spec = [None] * x.ndim
            spec[b_axis] = dp
            spec[h_axis] = "model"
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(*spec)))
        ctx["bh"] = bh
    return ctx


def make_moe_shard_map(cfg: ModelConfig, mesh: Mesh):
    """Paper's D-MGPU lesson applied inside U-mode: the MoE block runs as
    an embedded shard_map (explicit all_to_all dispatch, dmode.ep_moe_ffn)
    instead of letting GSPMD place it.  GSPMD lowers the expert exchange
    to a model-axis ALL-GATHER of every group's dispatch buffer — 16x the
    bytes of the all-to-all a discrete program writes (§Perf qwen3-moe
    iteration 2; 5.4 GB vs 0.34 GB per layer per device)."""
    from . import dmode

    def local(pl, xl):
        y, aux = dmode.ep_moe_ffn(pl, xl, cfg)
        return y, jax.lax.pmean(jax.lax.pmean(aux, "model"), "data")

    p_specs = {"router": P(None, None), "wg": P("model", None, None),
               "wu": P("model", None, None), "wd": P("model", None, None)}
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(p_specs, P(("data", "model"), None)),
                       out_specs=(P(("data", "model"), None), P()),
                       check_vma=False)

    def moe_sm(p_layer, x2d):
        return fn(p_layer, x2d)
    return moe_sm


# --------------------------------------------------------------------------
# training
# --------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, mesh: Mesh,
                    opt_cfg: optim.OptConfig = None):
    """Returns (train_step, state_shardings, batch_specs_fn).

    train_step(state, batch) -> (state, metrics); state is donated.
    """
    opt_cfg = opt_cfg or optim.OptConfig()
    ctx = make_ctx(cfg, mesh)
    k = max(1, cfg.microbatches)

    def train_step(state, batch):
        if k == 1:
            def loss_of(p):
                return api.loss(p, cfg, batch, ctx=ctx)
            loss, grads = jax.value_and_grad(loss_of)(state["params"])
        else:
            # gradient accumulation: activation peak scales 1/k; grads
            # accumulate in f32 (one params-sized buffer)
            micro = jax.tree.map(
                lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]),
                batch)

            def one(p, mb):
                return jax.value_and_grad(
                    lambda q: api.loss(q, cfg, mb, ctx=ctx))(p)

            def acc_step(carry, mb):
                loss_acc, g_acc = carry
                loss, g = one(state["params"], mb)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return (loss_acc + loss, g_acc), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state["params"])
            (loss, grads), _ = jax.lax.scan(acc_step, (0.0, zeros), micro)
            loss = loss / k
            grads = jax.tree.map(lambda g: g / k, grads)
        state, metrics = optim.adamw_update(state, grads, opt_cfg)
        return state, {"loss": loss, **metrics}

    def state_shardings(state_shape):
        return _ns(mesh, specs.state_specs(cfg, state_shape))

    def batch_shardings(batch_shape):
        return _ns(mesh, specs.batch_specs(cfg, batch_shape, mesh))

    return train_step, state_shardings, batch_shardings


def lower_train_step(cfg: ModelConfig, mesh: Mesh, batch_sds: dict,
                     opt_cfg: optim.OptConfig = None):
    """Lower (not run) the full train step for ShapeDtypeStruct inputs —
    the dry-run entry point.  Returns the jax `Lowered` object."""
    step, state_sh_fn, batch_sh_fn = make_train_step(cfg, mesh, opt_cfg)
    params_shape = jax.eval_shape(
        lambda: api.init(jax.random.PRNGKey(0), cfg))
    state_shape = _state_shape(params_shape)
    st_sh = state_sh_fn(state_shape)
    bt_sh = batch_sh_fn(batch_sds)
    out_metrics_sh = {"loss": NamedSharding(mesh, P()),
                      "grad_norm": NamedSharding(mesh, P()),
                      "lr": NamedSharding(mesh, P())}
    jitted = jax.jit(step,
                     in_shardings=(st_sh, bt_sh),
                     out_shardings=(st_sh, out_metrics_sh),
                     donate_argnums=(0,))
    state_in = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        state_shape, st_sh)
    batch_in = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        batch_sds, bt_sh)
    return jitted.lower(state_in, batch_in)


def _state_shape(params_shape):
    f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
    return {"params": params_shape,
            "mu": jax.tree.map(f32, params_shape),
            "nu": jax.tree.map(f32, params_shape),
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------

def make_prefill(cfg: ModelConfig, mesh: Mesh):
    def prefill_step(params, cache, batch):
        return api.prefill(params, cfg, cache, batch)
    return prefill_step


def make_decode_step(cfg: ModelConfig, mesh: Mesh):
    def decode(params, cache, token):
        return api.decode_step(params, cfg, cache, token)
    return decode


def lower_serve_step(cfg: ModelConfig, mesh: Mesh, kind: str,
                     batch_sds: dict, cell=None):
    """Lower prefill or decode for the dry-run.

    decode: inputs are (params, cache, token) with the cache at the
    cell's full depth — "one new token with a KV cache of seq_len".
    Prefill always uses blocked attention (no backward pass, and the
    full (S_shard x S) score tile would not fit at 32k for the wide
    archs); training honors cfg.attn_impl.
    """
    if kind == "prefill" and cfg.num_heads and cfg.attn_impl == "ref":
        cfg = cfg.replace(attn_impl="blocked")
    from repro.configs.shapes import cache_specs
    params_shape = jax.eval_shape(
        lambda: api.init(jax.random.PRNGKey(0), cfg))
    p_sh = _ns(mesh, specs.param_specs(cfg, params_shape))
    params_in = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        params_shape, p_sh)
    cache_shape = cache_specs(cfg, cell)
    c_sh = _ns(mesh, specs.cache_specs_tree(cfg, cache_shape, mesh))
    cache_in = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        cache_shape, c_sh)
    logit_sh = NamedSharding(mesh, P(specs.dp_axes(mesh)
                                     if cell.global_batch > 1 else None,
                                     "model"))
    if kind == "prefill":
        fn = make_prefill(cfg, mesh)
        b_sh = _ns(mesh, specs.batch_specs(cfg, batch_sds, mesh))
        batch_in = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            batch_sds, b_sh)
        jitted = jax.jit(fn, out_shardings=(logit_sh, c_sh),
                         donate_argnums=(1,))
        return jitted.lower(params_in, cache_in, batch_in)
    fn = make_decode_step(cfg, mesh)
    tok_spec = P(specs.dp_axes(mesh)) if cell.global_batch > 1 else P()
    tok_in = jax.ShapeDtypeStruct(
        batch_sds["token"].shape, batch_sds["token"].dtype,
        sharding=NamedSharding(mesh, tok_spec))
    jitted = jax.jit(fn, out_shardings=(logit_sh, c_sh), donate_argnums=(1,))
    return jitted.lower(params_in, cache_in, tok_in)
