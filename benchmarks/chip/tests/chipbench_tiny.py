"""A tiny copy of the chip benchmark for CPU tests: the benchmark's own
drivers, references, counts and metric readers, with a tiny qwen2
configuration (with its output head tied, and untied), a short traffic mix
and a peaks entry for the CPU host, in a directory of the test's own."""
from __future__ import annotations

import json
import pathlib
import shutil

import jax

CHIP = pathlib.Path(__file__).resolve().parents[1]

QWEN = {"name": "tiny-qwen2", "source": "test", "reference": "dense_lm",
        "program_arch": "qwen2-1.5b-smoke", "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
        "attention_bias": True, "tie_word_embeddings": True,
        "dtype": "float32"}
QWEN_UNTIED = dict(QWEN, name="tiny-qwen2-untied", tie_word_embeddings=False)


def traffic(arrivals=None, prompt_max=48, multiple=16) -> dict:
    return {"driver": "serve", "engine": {"slots": 4, "max_seq": 96},
            "lead_in_s": 1.0,
            "arrivals": arrivals or {"kind": "poisson", "rate_rps": 4.0},
            "prompt": {"median": 24, "sigma": 0.5, "min": 8,
                       "max": prompt_max, "multiple": multiple},
            "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
            "check": {"sample": 3}}


def make_root(tmp: pathlib.Path, limit: float = 1e-3) -> pathlib.Path:
    """A benchmark root under ``tmp`` with the cells ``tiny-qwen2.chat``
    (tied head, as the published model) and ``tiny-qwen2-untied.chat``;
    returns the directory that stands for benchmarks/chip (BENCHMARK.json
    lies beside it)."""
    root = tmp / "chip"
    for d in ("drivers", "refs", "metrics"):
        shutil.copytree(CHIP / d, root / d)
    (root / "configs").mkdir()
    (root / "counts").mkdir()
    (root / "traffic").mkdir()
    (root / "limits").mkdir()
    for cfg in (QWEN, QWEN_UNTIED):
        (root / "configs" / f"{cfg['name']}.json").write_text(json.dumps(cfg))
        shutil.copy(CHIP / "counts" / "qwen2-1.5b.py",
                    root / "counts" / f"{cfg['name']}.py")
        (root / "limits" / f"{cfg['name']}.chat.json").write_text(
            json.dumps({"max_logit_gap": limit}))
    (root / "traffic" / "chat.json").write_text(json.dumps(traffic()))
    kind = jax.devices()[0].device_kind
    (root / "peaks.json").write_text(json.dumps({"source": "test", "chips": {
        kind: {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}}))
    bench = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
    cells = [f"{c['name']}.chat" for c in (QWEN, QWEN_UNTIED)]
    bench["workloads"] = [
        {"name": n, "config": n.split(".")[0], "traffic": "chat",
         "chips": 1, "why": "test"} for n in cells]
    # the tiny cells report what the chat cell reports
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = (cells if "qwen2-1.5b.chat" in m["workloads"]
                              else [])
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
