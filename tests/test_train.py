"""Training substrate: optimizer, schedules, checkpointing, FT loop."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train import optim
from repro.train.checkpoint import CheckpointManager
from repro.train.data import DataConfig, SyntheticLM


def test_adamw_converges_on_quadratic():
    params = {"w": jnp.asarray([5.0, -3.0, 2.0])}
    state = optim.init_state(params)
    cfg = optim.OptConfig(lr=0.2, weight_decay=0.0, warmup_steps=1,
                          total_steps=200, schedule="constant")
    for _ in range(150):
        g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(state["params"])
        state, m = optim.adamw_update(state, g, cfg)
    assert float(jnp.max(jnp.abs(state["params"]["w"]))) < 0.05


def test_grad_clip_bounds_norm():
    g = {"a": jnp.full((10,), 100.0)}
    clipped, norm = optim.clip_by_global_norm(g, 1.0)
    assert float(norm) > 100
    assert float(optim.global_norm(clipped)) <= 1.0 + 1e-5


def test_lr_schedule_warmup_and_decay():
    cfg = optim.OptConfig(lr=1.0, warmup_steps=10, total_steps=100,
                          schedule="cosine")
    lrs = [float(optim.lr_at(cfg, jnp.asarray(s))) for s in
           [0, 5, 10, 50, 99]]
    assert lrs[0] < lrs[1] < lrs[2]            # warmup rises
    assert lrs[2] > lrs[3] > lrs[4]            # cosine decays
    assert lrs[4] < 0.01


def test_moments_are_f32_under_bf16_params():
    params = {"w": jnp.zeros((4, 4), jnp.bfloat16)}
    st = optim.init_state(params)
    assert st["mu"]["w"].dtype == jnp.float32
    assert st["nu"]["w"].dtype == jnp.float32


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def _state():
    return {"params": {"w": jnp.arange(6.0).reshape(2, 3),
                       "nested": {"b": jnp.ones((4,), jnp.bfloat16)}},
            "step": jnp.asarray(7, jnp.int32)}


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    st = _state()
    mgr.save(7, st)
    got, manifest = mgr.restore()
    assert manifest["step"] == 7
    np.testing.assert_array_equal(got["params"]["w"], st["params"]["w"])
    assert got["params"]["nested"]["b"].dtype == np.dtype("bfloat16") or \
        got["params"]["nested"]["b"].dtype == jnp.bfloat16


def test_checkpoint_atomicity(tmp_path):
    """A half-written (tmp) checkpoint is never visible."""
    mgr = CheckpointManager(str(tmp_path))
    os.makedirs(tmp_path / "step_00000099.tmp")
    (tmp_path / "step_00000099.tmp" / "junk.npy").write_bytes(b"xx")
    assert mgr.latest_step() is None
    mgr.save(3, _state())
    assert mgr.latest_step() == 3


def test_checkpoint_gc_keeps_last_k(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _state())
    assert mgr.all_steps() == [3, 4]


def test_async_checkpoint(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    mgr.save(5, _state())
    mgr.wait()
    assert mgr.latest_step() == 5


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_deterministic():
    cfg = DataConfig(vocab_size=128, seq_len=32, global_batch=8, seed=1)
    a = SyntheticLM(cfg).global_batch(3)
    b = SyntheticLM(cfg).global_batch(3)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])


def test_data_shard_consistency():
    """Sharded generation == slicing the global batch (elastic restart)."""
    cfg = DataConfig(vocab_size=128, seq_len=16, global_batch=8, seed=2)
    data = SyntheticLM(cfg)
    full = data.global_batch(5)
    for shard, n in [(0, 4), (3, 4), (1, 2)]:
        piece = data.host_shard(5, shard, n)
        per = 8 // n
        np.testing.assert_array_equal(
            piece["tokens"], full["tokens"][shard * per:(shard + 1) * per])


def test_data_targets_shifted():
    cfg = DataConfig(vocab_size=64, seq_len=16, global_batch=2, seed=0)
    b = SyntheticLM(cfg).global_batch(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["targets"][:, :-1])


# ---------------------------------------------------------------------------
# fault-tolerant loop (single-device mesh; smoke model)
# ---------------------------------------------------------------------------

def test_loop_restart_after_failure(tmp_path):
    from repro.launch.mesh import make_mesh
    from repro.models import get_config
    from repro.train.loop import LoopConfig, run
    cfg = get_config("qwen2-1.5b-smoke")
    mesh = make_mesh((1, 1), ("data", "model"))
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=2)
    rep = run(cfg, mesh, dc,
              opt_cfg=optim.OptConfig(lr=1e-3, total_steps=14,
                                      warmup_steps=2),
              loop_cfg=LoopConfig(total_steps=14, ckpt_every=5,
                                  ckpt_dir=str(tmp_path), async_ckpt=False,
                                  log_every=100),
              fault_schedule={8: RuntimeError("injected node failure")},
              verbose=False)
    assert rep.restarts == 1
    assert rep.final_step == 14
    # replayed steps 5..8 after restoring the step-5 checkpoint
    assert rep.steps_run > 14 - 1
    assert np.isfinite(rep.final_loss)


def test_loop_step_error_propagates(tmp_path, monkeypatch):
    """An error raised by the step itself is not a node failure: it leaves
    run() at once, without a restore or a retry."""
    from repro.launch.mesh import make_mesh
    from repro.models import get_config
    from repro.train import loop
    real_build = loop.build
    calls = []

    def build(*args, **kw):
        state, _, st_sh, batch_sh = real_build(*args, **kw)

        def step(state, batch):
            calls.append(1)
            if len(calls) > 1:
                pytest.fail("the step was retried after a real error")
            raise FloatingPointError("step blew up")
        return state, step, st_sh, batch_sh

    monkeypatch.setattr(loop, "build", build)
    cfg = get_config("qwen2-1.5b-smoke")
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=2)
    with pytest.raises(FloatingPointError, match="step blew up"):
        loop.run(cfg, make_mesh((1, 1), ("data", "model")), dc,
                 loop_cfg=loop.LoopConfig(total_steps=4, ckpt_every=2,
                                          ckpt_dir=str(tmp_path),
                                          async_ckpt=False),
                 verbose=False)
    assert len(calls) == 1


def test_loop_elastic_remesh(tmp_path):
    from repro.launch.mesh import make_mesh
    from repro.models import get_config
    from repro.train.loop import LoopConfig, run
    cfg = get_config("qwen2-1.5b-smoke")
    mesh = make_mesh((1, 1), ("data", "model"))
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=2)
    rep = run(cfg, mesh, dc,
              opt_cfg=optim.OptConfig(lr=1e-3, total_steps=8,
                                      warmup_steps=2),
              loop_cfg=LoopConfig(total_steps=8, ckpt_every=4,
                                  ckpt_dir=str(tmp_path), async_ckpt=False,
                                  log_every=100),
              remesh_schedule={4: make_mesh((1, 1), ("data", "model"))},
              verbose=False)
    assert rep.remesh_events == 1
    assert rep.final_step == 8
