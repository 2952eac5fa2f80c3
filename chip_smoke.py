"""Smoke run of the MGMark half on the chip, through its normal entry points.

    python chip_smoke.py                # one TPU chip: serve, train, kernels
    python chip_smoke.py --four-chips   # four TPU chips: U-mode vs D-mode

The one-chip run, in one process and in this order:

* device check: the first device must be a TPU; there is no CPU fallback;
* serve: ``serve.Engine`` over qwen2-1.5b at full depth and published
  widths (random bf16 weights from ``--seed``) answers a few requests,
  and one request's prefill and first decode step match ``api.forward``;
* train: ``train.loop.run`` takes a few steps of qwen2-1.5b at published
  widths, with depth cut so AdamW state fits beside the weights; the
  loss is finite and falls;
* kernels: flash attention, rmsnorm and the SSD chunk kernel compiled by
  Mosaic (``interpret=False``) match their ``kernels/ref.py`` oracles.

``--four-chips`` runs only what exists across chips: the qwen2-1.5b loss
in U-mode (GSPMD) and D-mode (explicit collectives) on a (1, 4) mesh
against the plain loss on one device, and every ``patterns`` workload in
both modes on a (4,) mesh against its oracle.

Any failed check raises, so the exit code is non-zero and the result line
is not printed.  The last line of standard output is the JSON result.
Wall times printed include compilation; the script reports no rates.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import shutil
import sys
import time
import typing

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import ops as kops, ref as kref  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import api, get_config  # noqa: E402
from repro.patterns import WORKLOADS, run_workload  # noqa: E402
from repro.serve import Engine, Request  # noqa: E402
from repro.sharding import dmode, specs, umode  # noqa: E402
from repro.train import loop  # noqa: E402
from repro.train.data import DataConfig, SyntheticLM  # noqa: E402
from repro.train.optim import OptConfig  # noqa: E402

# The platform the device check demands.  Only a test on a CPU host
# changes it (and INTERPRET below); the program has no option for either.
PLATFORM = "tpu"
# Kernels are compiled by Mosaic, never interpreted.
INTERPRET = False

# bf16 has 8 significand bits: one rounding costs at most 2^-8 relative.
BF16_EPS = 2.0 ** -8


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What each phase runs.  The defaults are the real sizes."""
    arch: str = "qwen2-1.5b"
    # serve: two prompt lengths only, since Engine compiles its prefill
    # once per prompt length
    slots: int = 4
    max_seq: int = 2048
    prompt_lens: tuple = (128, 512)
    requests: int = 6
    new_tokens: int = 24
    # train: published widths, depth cut so that bf16 weights, grads and
    # two f32 AdamW moments fit one 16 GB chip (the compiled step needs
    # 12.6 GB at 12 layers and 14.9 GB at 16, by memory_analysis for v5e)
    train_layers: int = 12
    train_batch: int = 4
    train_seq: int = 512
    train_steps: int = 6
    # kernels at the widths of the model they serve
    attn_seq: int = 4096
    norm_rows: int = 4096
    ssd_arch: str = "mamba2-1.3b"
    ssd_chunks: int = 16
    # four chips
    four_batch: int = 2
    four_seq: int = 512
    # workload -> size; None runs each at its Table-2 size for 4 devices
    pattern_sizes: typing.Optional[dict] = None


SIZES = Sizes()


class SmokeFailure(AssertionError):
    """A phase produced a wrong or missing result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def report(phase: str, **fields) -> dict:
    print(f"{phase}: " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)
    return fields


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def device_check(need: int) -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    report("device", **dev)
    check(dev["platform"] == PLATFORM,
          f"JAX found no {PLATFORM} device (first device is "
          f"{dev['platform']!r}); this script never falls back")
    check(dev["count"] >= need, f"need {need} devices, found {dev['count']}")
    return dev


def _rel_l2(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def serve_phase(sz: Sizes, seed: int) -> dict:
    cfg = get_config(sz.arch)
    params = api.init(jax.random.PRNGKey(seed), cfg)
    engine = Engine(cfg, params, slots=sz.slots, max_seq=sz.max_seq)
    rng = np.random.default_rng(seed)
    reqs = [Request(uid=i, max_new_tokens=sz.new_tokens,
                    prompt=rng.integers(
                        0, cfg.vocab_size,
                        sz.prompt_lens[i % len(sz.prompt_lens)]
                    ).astype(np.int32))
            for i in range(sz.requests)]
    for r in reqs:
        check(engine.submit(r), f"request {r.uid} rejected")
    done = engine.run_until_drained()
    check(sorted(r.uid for r in done) == [r.uid for r in reqs],
          f"answered {len(done)} of {len(reqs)} requests")
    for r in reqs:
        check(len(r.output) == sz.new_tokens,
              f"request {r.uid}: {len(r.output)} of {sz.new_tokens} tokens")
        check(all(0 <= t < cfg.vocab_size for t in r.output),
              f"request {r.uid}: token outside the vocab")

    # The first request was admitted into slot 0.  Replay its prefill and
    # first decode step through the engine's own compiled programs, and
    # compare both logit rows with a full forward over the same tokens.
    r0 = reqs[0]
    S = len(r0.prompt)
    V = cfg.vocab_size
    logits_p, mini = engine._prefill(
        params, api.init_cache(cfg, 1, sz.max_seq),
        {"tokens": jnp.asarray(r0.prompt)[None]})
    engine._splice(mini, 0, S)
    tok = jnp.zeros((sz.slots,), jnp.int32).at[0].set(r0.output[0])
    logits_d, _ = engine._decode(params, engine.cache, tok)
    seq = jnp.asarray(np.append(r0.prompt, r0.output[0]), jnp.int32)[None]
    full, _ = jax.jit(api.forward, static_argnums=1)(params, cfg,
                                                     {"tokens": seq})
    err_p = _rel_l2(logits_p[0, :V], full[0, S - 1, :V])
    err_d = _rel_l2(logits_d[0, :V], full[0, S, :V])
    # Each layer rounds the residual stream to bf16, and the three programs
    # order their sums differently; errors of at most one rounding per
    # layer accumulate to no more than num_layers * 2^-8 (normwise).
    tol = cfg.num_layers * BF16_EPS
    out = report("serve", arch=cfg.name, layers=cfg.num_layers,
                 d_model=cfg.d_model, params=api.param_count(params),
                 slots=sz.slots, max_seq=sz.max_seq,
                 prompt_lens=list(sz.prompt_lens), requests=len(done),
                 new_tokens=sz.new_tokens, decode_steps=engine.steps,
                 prefill_rel_err=err_p, decode_rel_err=err_d, tol=tol)
    check(err_p <= tol, f"prefill logits off by {err_p} (tol {tol})")
    check(err_d <= tol, f"decode logits off by {err_d} (tol {tol})")
    # the engine's tokens are the argmax of the logits it computed
    check(int(jnp.argmax(logits_p[0])) == r0.output[0],
          "prefill token differs from the engine's")
    check(int(jnp.argmax(logits_d[0])) == r0.output[1],
          "first decode token differs from the engine's")
    return out


def train_phase(sz: Sizes, seed: int, out_dir: pathlib.Path) -> dict:
    cfg = get_config(sz.arch).replace(num_layers=sz.train_layers)
    mesh = make_mesh((1, 1), ("data", "model"))
    ckpt_dir = out_dir / "ckpt"
    shutil.rmtree(ckpt_dir, ignore_errors=True)   # never resume a stale run
    steps = sz.train_steps
    rep = loop.run(
        cfg, mesh,
        DataConfig(vocab_size=cfg.vocab_size, seq_len=sz.train_seq,
                   global_batch=sz.train_batch, seed=seed),
        opt_cfg=OptConfig(lr=1e-3, warmup_steps=1, total_steps=steps,
                          schedule="constant"),
        # no checkpoint inside the run: it would copy ~10 GB of state
        loop_cfg=loop.LoopConfig(total_steps=steps, ckpt_every=steps + 1,
                                 ckpt_dir=str(ckpt_dir), async_ckpt=False),
        verbose=False)
    out = report("train", arch=sz.arch, layers_kept=cfg.num_layers,
                 d_model=cfg.d_model, batch=sz.train_batch, seq=sz.train_seq,
                 steps=rep.steps_run, losses=rep.losses)
    check(rep.steps_run == steps, f"ran {rep.steps_run} of {steps} steps")
    check(all(np.isfinite(rep.losses)), "non-finite loss")
    check(rep.losses[-1] < rep.losses[0], "loss did not fall")
    return out


def _kernel_err(name: str, got, want, tol: float, **shape) -> dict:
    got = np.asarray(jnp.asarray(got, jnp.float32), np.float64)
    want = np.asarray(jnp.asarray(want, jnp.float32), np.float64)
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    out = report(f"kernel {name}", interpret=INTERPRET, max_abs_err=err,
                 max_abs_ref=scale, tol=tol * scale, **shape)
    check(np.all(np.isfinite(got)), f"{name}: non-finite output")
    check(err <= tol * scale, f"{name}: max error {err} > {tol * scale}")
    return out


def kernel_phase(sz: Sizes, seed: int) -> dict:
    cfg = get_config(sz.arch)
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 12)
    normal = lambda k, shape, dt: jax.random.normal(k, shape,
                                                    jnp.float32).astype(dt)
    # The oracles run at full f32 matmul precision; the TPU's default
    # would round their f32 operands to bf16.  The kernels' own error is
    # then a few bf16 roundings (of operands fed to the MXU and of the
    # output): 4 * 2^-8 of the output's scale.
    tol = 4 * BF16_EPS
    out = {}
    bf16 = jnp.bfloat16
    S, H, K, hd = sz.attn_seq, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    q = normal(ks[0], (1, S, H, hd), bf16)
    k = normal(ks[1], (1, S, K, hd), bf16)
    v = normal(ks[2], (1, S, K, hd), bf16)
    got = kops.flash_attention(q, k, v, causal=True, interpret=INTERPRET)
    with jax.default_matmul_precision("highest"):
        want = kref.flash_attention_ref(q, k, v, causal=True)
    out["flash_attention"] = _kernel_err(
        "flash_attention", got, want, tol, seq=S, heads=H, kv_heads=K, hd=hd)

    d = cfg.d_model
    x = normal(ks[3], (sz.norm_rows, d), bf16)
    w = (1.0 + 0.1 * normal(ks[4], (d,), jnp.float32)).astype(bf16)
    got = kops.rmsnorm(x, w, eps=cfg.norm_eps, interpret=INTERPRET)
    want = kref.rmsnorm_ref(x, w, eps=cfg.norm_eps)
    out["rmsnorm"] = _kernel_err("rmsnorm", got, want, tol,
                                 rows=sz.norm_rows, d=d)

    scfg = get_config(sz.ssd_arch)
    R, Hs, Q, P, N = (sz.ssd_chunks, scfg.ssm_heads, scfg.ssm_chunk,
                      scfg.ssm_head_dim, scfg.ssm_state)
    f32 = jnp.float32
    xs = normal(ks[5], (R, Hs, Q, P), f32)
    dt = jax.nn.softplus(normal(ks[6], (R, Hs, Q), f32))
    A = -jnp.exp(normal(ks[7], (Hs,), f32))
    cs = jnp.cumsum(dt * A[None, :, None], axis=-1)
    Bm = normal(ks[8], (R, Hs, Q, N), f32)
    Cm = normal(ks[9], (R, Hs, Q, N), f32)
    y, st = kops.ssd_chunk_kernel(xs, dt, cs, Bm, Cm, interpret=INTERPRET)
    with jax.default_matmul_precision("highest"):
        y_ref, st_ref = kref.ssd_chunk_ref(xs, dt, cs, Bm, Cm)
    dims = dict(arch=scfg.name, chunks=R, heads=Hs, Q=Q, P=P, N=N)
    out["ssd_y"] = _kernel_err("ssd_chunk y", y, y_ref, tol, **dims)
    out["ssd_state"] = _kernel_err("ssd_chunk state", st, st_ref, tol, **dims)
    return out


def _bytes_in_use() -> list:
    """Per-device bytes in use; zeros where the backend keeps no count
    (the CPU backend used by tests)."""
    return [(d.memory_stats() or {}).get("bytes_in_use", 0)
            for d in jax.devices()]


def four_chip_phase(sz: Sizes, seed: int) -> dict:
    cfg = get_config(sz.arch)
    mesh = make_mesh((1, 4), ("data", "model"))
    base = _bytes_in_use()
    # U-mode placement; the unsharded copy made on device 0 is freed once
    # the sharded one exists
    params = api.init(jax.random.PRNGKey(seed), cfg)
    params = jax.device_put(params, specs.to_shardings(
        mesh, specs.param_specs(cfg, params)))
    jax.block_until_ready(params)
    placed = [b - b0 for b, b0 in zip(_bytes_in_use(), base)]
    report("placement", mode="umode", mesh=dict(mesh.shape),
           param_bytes=sum(x.nbytes for x in jax.tree.leaves(params)),
           bytes_in_use_per_device=placed)
    check(PLATFORM != "tpu" or min(placed) > 0,
          "a device holds none of the weights")

    batch = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                   seq_len=sz.four_seq,
                                   global_batch=sz.four_batch,
                                   seed=seed)).global_batch(0)
    ctx = umode.make_ctx(cfg, mesh)
    with mesh:
        u_loss = float(jax.jit(
            lambda p, b: api.loss(p, cfg, b, ctx=ctx))(params, batch))
        d_params = jax.device_put(
            params, specs.to_shardings(mesh, dmode.tp_param_specs(cfg)))
        d_loss = float(jax.jit(dmode.tp_loss(cfg, mesh))(d_params, batch))
    del d_params
    one = jax.devices()[0]
    p1 = jax.device_put(params, one)
    s_loss = float(jax.jit(lambda p, b: api.loss(p, cfg, b))(
        p1, jax.device_put(batch, one)))
    del p1
    # The three programs differ only in how sums are split over chips and
    # rounded to bf16; their losses agree within one bf16 rounding of the
    # loss itself.
    tol = BF16_EPS * abs(s_loss)
    out = report("four-chip loss", arch=cfg.name, layers=cfg.num_layers,
                 batch=sz.four_batch, seq=sz.four_seq, single=s_loss,
                 umode=u_loss, dmode=d_loss, tol=tol)
    check(all(np.isfinite([s_loss, u_loss, d_loss])), "non-finite loss")
    check(abs(u_loss - s_loss) <= tol, "U-mode loss disagrees")
    check(abs(d_loss - s_loss) <= tol, "D-mode loss disagrees")
    del params

    pmesh = make_mesh((4,), ("dev",))
    out["patterns"] = {}
    for name, mod in WORKLOADS.items():
        size = (sz.pattern_sizes[name] if sz.pattern_sizes
                else mod.default_size(4))
        for rep in run_workload(name, pmesh, size):
            report(f"pattern {name}", mode=rep.mode, pattern=rep.pattern,
                   size=size, correct=rep.correct, max_err=rep.max_err,
                   collective_bytes=rep.collective_bytes)
            check(rep.correct, f"{name} {rep.mode}: max error {rep.max_err}")
            out["patterns"][f"{name}/{rep.mode}"] = rep.max_err
    return out


# --------------------------------------------------------------------------

class _CacheEvents:
    """Counts persistent compilation cache hits and writes."""
    HIT = "/jax/compilation_cache/cache_hits"
    WRITE = "/jax/compilation_cache/cache_misses"

    def __init__(self) -> None:
        self.hits = self.writes = 0

    def __call__(self, event: str, **_) -> None:
        self.hits += event == self.HIT
        self.writes += event == self.WRITE


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip U-mode/D-mode phase")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(HERE / "smoke_out"),
                    help="directory for the JSON record and the "
                         "train phase's checkpoint directory")
    args = ap.parse_args(argv)
    dev = device_check(4 if args.four_chips else 1)
    out_dir = pathlib.Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    cache = _CacheEvents()
    jax.monitoring.register_event_listener(cache)
    record = {"device": dev, "compile_cache": use_compile_cache(),
              "seed": args.seed}
    try:
        phases = ([("four_chips", lambda: four_chip_phase(SIZES, args.seed))]
                  if args.four_chips else
                  [("serve", lambda: serve_phase(SIZES, args.seed)),
                   ("train", lambda: train_phase(SIZES, args.seed, out_dir)),
                   ("kernels", lambda: kernel_phase(SIZES, args.seed))])
        for name, run in phases:
            t0 = time.perf_counter()
            record[name] = run()
            record[name]["wall_s_incl_compile"] = time.perf_counter() - t0
            report(name, wall_s_incl_compile=record[name][
                "wall_s_incl_compile"])
    finally:
        jax.monitoring.unregister_event_listener(cache)
    record["compile_cache_hits"] = cache.hits
    record["compile_cache_writes"] = cache.writes
    report("compile cache", dir=record["compile_cache"], hits=cache.hits,
           writes=cache.writes)
    name = "chip_smoke_4.json" if args.four_chips else "chip_smoke.json"
    (out_dir / name).write_text(json.dumps(record, indent=1, default=str))
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
