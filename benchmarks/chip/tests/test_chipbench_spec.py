"""BENCHMARK.json and the files it names: every name well formed, every
file the harness looks up by name present, every cell reporting set-up
time, another end-to-end metric and a per-layer metric."""
import json
import pathlib
import re

REPO = pathlib.Path(__file__).resolve().parents[3]
CHIP = REPO / "benchmarks" / "chip"
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][1] == "benchmarks/chip/run.py"
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_units_and_bounds():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics + BENCH["workloads"]
             + BENCH["configs"]]
    assert all(NAME.match(n) for n in names), names
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}


def test_every_file_found_by_name():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in configs.values():
        spec = json.loads((REPO / c["file"]).read_text())
        assert spec["name"] == c["name"]
        assert spec["reduced"] == c["reduced"]
        assert (CHIP / "refs" / f"{spec['reference']}.py").is_file()
        assert (CHIP / "counts" / f"{c['name']}.py").is_file()
    for w in BENCH["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        traffic = json.loads((CHIP / "traffic" / f"{w['traffic']}.json")
                             .read_text())
        assert (CHIP / "drivers" / f"{traffic['driver']}.py").is_file()
        assert (CHIP / "limits" / f"{w['name']}.json").is_file()
    for m in BENCH["per_layer"]:
        assert (CHIP / "metrics" / f"{m['name']}.py").is_file()


def test_every_cell_reports_enough():
    applies = lambda m, w: "workloads" not in m or w in m["workloads"]
    for w in (x["name"] for x in BENCH["workloads"]):
        e2e = {m["name"] for m in BENCH["end_to_end"] if applies(m, w)}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = [m for m in BENCH["per_layer"] if applies(m, w)]
        assert layer and all(m["moves"] in e2e for m in layer)
