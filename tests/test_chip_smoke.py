"""chip_smoke.py end to end on the CPU at a tiny size, and the compile
cache it places.

The script demands a TPU and Mosaic-compiled kernels; these tests set its
module-level PLATFORM and INTERPRET for the CPU host and shrink SIZES.
"""
import json
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from conftest import REPO, SRC, run_with_devices
from repro.launch import compile_cache

TINY = dict(arch="qwen2-1.5b-smoke", slots=2, max_seq=64,
            prompt_lens=(8, 16), requests=3, new_tokens=4,
            train_layers=1, train_batch=2, train_seq=16, train_steps=3,
            attn_seq=256, norm_rows=64, ssd_arch="mamba2-1.3b-smoke",
            ssd_chunks=2, four_batch=2, four_seq=16,
            pattern_sizes={"aes": 8192, "km": 2048, "fir": 8192, "sc": 128,
                           "gd": 2048, "mt": 128, "bs": 2048})


@pytest.fixture
def on_cpu(monkeypatch, tmp_path):
    monkeypatch.setattr(chip_smoke, "PLATFORM", "cpu")
    monkeypatch.setattr(chip_smoke, "INTERPRET", True)
    monkeypatch.setattr(chip_smoke, "SIZES", chip_smoke.Sizes(**TINY))
    # JAX read the variable (unset) at import, so the cache stays off here
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    return tmp_path


def _last_json(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


def test_one_chip_phases_on_cpu(on_cpu, capsys):
    assert chip_smoke.main(["--out", str(on_cpu)]) == 0
    out = capsys.readouterr().out
    assert _last_json(out) == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    record = json.loads((on_cpu / "chip_smoke.json").read_text())
    assert record["serve"]["requests"] == TINY["requests"]
    assert record["train"]["layers_kept"] == TINY["train_layers"]
    assert {"flash_attention", "rmsnorm", "ssd_y", "ssd_state"} <= set(
        record["kernels"])
    assert not list((on_cpu / "ckpt").iterdir())     # no checkpoint written


def test_no_tpu_fails_before_any_phase(monkeypatch, tmp_path, capsys):
    monkeypatch.setattr(chip_smoke, "SIZES", chip_smoke.Sizes(**TINY))
    with pytest.raises(chip_smoke.SmokeFailure, match="no tpu device"):
        chip_smoke.main(["--out", str(tmp_path)])
    out = capsys.readouterr().out
    assert '"ok"' not in out and "serve:" not in out


def test_script_exits_nonzero_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_four_chip_phase_on_4_cpu_devices(tmp_path):
    out = run_with_devices(4, f"""
sys.path.insert(0, {REPO!r})
os.environ["JAX_COMPILATION_CACHE_DIR"] = {str(tmp_path / "cache")!r}
import chip_smoke
chip_smoke.PLATFORM = "cpu"
chip_smoke.SIZES = chip_smoke.Sizes(**{TINY!r})
chip_smoke.main(["--four-chips", "--out", {str(tmp_path)!r}])
""")
    assert _last_json(out)["device"]["count"] == 4
    assert "four-chip loss:" in out
    record = json.loads((tmp_path / "chip_smoke_4.json").read_text())
    assert len(record["four_chips"]["patterns"]) == 2 * len(
        TINY["pattern_sizes"])


# ---------------------------------------------------------------------------
# compile cache placement
# ---------------------------------------------------------------------------

def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.use_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_dir_is_used_and_hit(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, a first run writes its compiled
    program there and a second process with the same program hits it."""
    code = """
import jax
from repro.launch.compile_cache import use_compile_cache
hits = []
jax.monitoring.register_event_listener(
    lambda e, **_: hits.append(e) if e.endswith("cache_hits") else None)
print("DIR", use_compile_cache())
jax.jit(lambda x: (x @ x.T).sum())(jax.numpy.ones((64, 64)))
print("HITS", len(hits))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=SRC)
    runs = [subprocess.run([sys.executable, "-c", code], env=env, timeout=120,
                           capture_output=True, text=True) for _ in range(2)]
    for r in runs:
        assert r.returncode == 0, r.stderr[-2000:]
        assert f"DIR {tmp_path}" in r.stdout
    assert os.listdir(tmp_path)
    assert "HITS 0" in runs[0].stdout and "HITS 0" not in runs[1].stdout
