"""The harness's cell loop at a tiny size on the CPU, with the platform
steered in the test: the result line, the device check, the correctness
control and the faults that the check must catch."""
import json
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[3]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "src"))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import chipbench_tiny  # noqa: E402
from benchmarks.chip import common, generator  # noqa: E402
from benchmarks.chip.sweep import reset  # noqa: E402
from repro.serve import engine as engine_mod  # noqa: E402

CELLS = ["tiny-qwen2.chat", "tiny-qwen2-untied.chat"]


@pytest.fixture
def bench(monkeypatch, tmp_path):
    """A tiny benchmark root, found by the harness as the real one is."""
    root = chipbench_tiny.make_root(tmp_path)
    monkeypatch.setattr(common, "HERE", root)
    monkeypatch.setattr(common, "ROOT", tmp_path)
    monkeypatch.setattr(common, "PLATFORM", "cpu")
    # JAX read the variable (unset) at import, so the cache stays off here
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    run = common.load_module(REPO / "benchmarks" / "chip" / "run.py")
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path / "trace")
    return run


def go(run, capsys, cell, trace=0, seed=3_000_000_001):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds", "2",
                   "--trace", str(trace)])
    out, err = capsys.readouterr()
    return rc, out, err


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loop_result_line(bench, capsys, cell):
    rc, out, err = go(bench, capsys, cell)
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 8
    assert set(line["metrics"]) == {"ttft_p85_ms", "tpot_p85_ms", "out_tok_s",
                                    "setup_s"}
    assert line["device"] == {"platform": "cpu",
                              "kind": jax.devices()[0].device_kind,
                              "count": 1, "memory_peak_bytes": 0}
    # each number compared, beside its limit, last on standard error
    tail = err.strip().splitlines()[-3:]
    assert [t.split(":")[0] for t in tail] == [
        "check max_logit_gap", "check bad_outputs", "check finished_missing"]
    assert "compiles_in_window: " in err and "generator_lag_ms: " in err
    assert "latency_ms: " in err
    notes = {l.split(": ", 1)[0]: l.split(": ", 1)[1]
             for l in err.splitlines() if ": {" in l}
    assert json.loads(notes["compiles_in_window"])["lowered"] == 0


def test_traced_run_reports_per_layer(bench, capsys):
    rc, out, err = go(bench, capsys, CELLS[0], trace=1)
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    # the CPU has no device plane: the readers of device metrics find
    # nothing and their metrics are left out, never reported as 0; the
    # program's spans, stamps and counters are read as on the chip
    assert set(line["metrics"]) == {
        "mfu.serve", "engine_queue_wait_p95_ms", "admit_ms_per_prefill.serve",
        "retire_ms_per_step.serve", "host_syncs_per_step.serve",
        "batch_per_step.serve"}
    assert line["device"]["window_s"] > 0
    assert "breakdown" in line and line["correct"] is True


def test_lead_in_served_and_not_counted(bench):
    """The lead-in's requests are served before the window and count in no
    latency; the window's tokens are those delivered after it opened."""
    c = common.find_cell(CELLS[0])
    serve = c.module("drivers", "serve")
    cfg, params, engine = serve.setup(c, 8)
    arrivals = generator.make_arrivals(c.traffic, 2.0, 8, cfg.vocab_size)
    w = serve.play(engine, arrivals, 2.0, generator.lead_in_s(c.traffic))
    assert w.lead and all(r.due < w.t0 for r in w.lead)
    assert len(w.recs) == 8 and all(r.due >= w.t0 for r in w.recs)
    assert any(r.seen0 for r in w.lead)
    assert w.tokens == sum(r.seen - r.seen0 for r in w.served) > 0
    assert w.t0 - w.t_begin == pytest.approx(1.0)
    assert len(serve.latencies(w)["ttft"]) == 8


def test_no_tpu_fails_without_result(monkeypatch, tmp_path, capsys):
    root = chipbench_tiny.make_root(tmp_path)
    monkeypatch.setattr(common, "HERE", root)
    monkeypatch.setattr(common, "ROOT", tmp_path)
    run = common.load_module(REPO / "benchmarks" / "chip" / "run.py")
    rc, out, err = go(run, capsys, CELLS[0])
    assert rc != 0 and out == ""
    assert "no tpu device" in err


def test_unknown_device_kind_fails(bench, capsys):
    (common.HERE / "peaks.json").write_text(json.dumps(
        {"source": "test", "chips": {"TPU v9 imaginary": {}}}))
    rc, out, err = go(bench, capsys, CELLS[0])
    assert rc != 0 and out == ""
    assert "not in peaks.json" in err


def test_script_exits_nonzero_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(REPO / "benchmarks" / "chip" / "run.py"),
         "--workload", "qwen2-1.5b.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        env=env, cwd=REPO)
    assert proc.returncode != 0 and proc.stdout == ""


# --------------------------------------------------------------------------
# the control and the faults
# --------------------------------------------------------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_where_program_passes(bench, cell):
    """The plain reference computed in fp8, in the program's place, reads a
    wider gap than the limit on every seed; the program reads less."""
    c = common.find_cell(cell)
    serve = c.module("drivers", "serve")
    cfg, params, engine = serve.setup(c, 5)
    for seed in (5, 6, 7):
        params = engine.params = serve.make_weights(c, cfg, seed)
        reset(engine)
        arrivals = generator.make_arrivals(c.traffic, 2.0, seed, cfg.vocab_size)
        w = serve.play(engine, arrivals, 2.0, generator.lead_in_s(c.traffic))
        picked = serve.sample(w.served, c.traffic["check"]["sample"], seed)
        prog, ctrl = serve.gaps(c, params, picked, control=True)
        assert max(prog) <= c.limits["max_logit_gap"] < max(ctrl)


def _alter_tokens(engine):
    """Every decoded token altered where it is produced: the logits the
    decode returns are rolled by one along the vocabulary."""
    decode = engine._decode

    def broken(p, c, t):
        logits, cache = decode(p, c, t)
        return np.roll(np.asarray(logits), 1, axis=-1), cache
    engine._decode = broken


def _state_unchanged(engine):
    """The decode step returns the cache it was given."""
    decode = engine._decode

    def broken(p, c, t):
        logits, _ = decode(p, c, t)
        return logits, c
    engine._decode = broken


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_reads_not_correct(bench, capsys, monkeypatch, cell, fault):
    init = engine_mod.Engine.__init__

    def patched(self, *a, **k):
        init(self, *a, **k)
        fault(self)
    monkeypatch.setattr(engine_mod.Engine, "__init__", patched)
    rc, out, err = go(bench, capsys, cell)
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["checks"]["max_logit_gap"]["value"] > \
        line["checks"]["max_logit_gap"]["limit"]
