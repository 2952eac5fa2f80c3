"""Lookups by name, the device check and small statistics shared by the
chip benchmark's modules.  Nothing here imports the program under test."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
import typing

HERE = pathlib.Path(__file__).resolve().parent
# benchmarks/chip/common.py -> the checkout's root
ROOT = HERE.parents[1]

# The platform the device check demands.  Only a test on a CPU host changes
# it; the benchmark has no option for it.
PLATFORM = "tpu"


class BenchError(RuntimeError):
    """A run that cannot give a result: no chip, an unknown chip, a missing
    file.  The run exits non-zero and prints no result line."""


def load_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    return json.loads(path.read_text())


_MODULES: dict = {}


def load_module(path: pathlib.Path, name: str = None):
    """Import a file by path, once; the names of data-driven files hold '.'
    and '-', so they are not importable by name."""
    path = pathlib.Path(path).resolve()
    if path in _MODULES:
        return _MODULES[path]
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    name = name or "chipbench_" + "_".join(
        part.replace(".", "_").replace("-", "_")
        for part in path.parts[-2:])[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    _MODULES[path] = mod
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with the files it names."""
    name: str
    chips: int
    config: dict            # configs/<config>.json
    traffic: dict           # traffic/<traffic>.json
    limits: dict            # limits/<cell>.json
    end_to_end: list        # the end-to-end metrics this cell reports
    per_layer: list         # the per-layer metrics this cell reports
    root: pathlib.Path = HERE

    def module(self, kind: str, name: str):
        return load_module(self.root / kind / f"{name}.py")

    @property
    def reference(self):
        return self.module("refs", self.config["reference"])

    @property
    def counts(self):
        return self.module("counts", self.config["name"])


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(workload: str, bench_file: pathlib.Path = None,
              root: pathlib.Path = None) -> Cell:
    root = root or HERE
    bench = load_json(bench_file or ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    w = cells[workload]
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if _applies(m, workload) and m["moves"] in e2e_names]
    return Cell(name=workload, chips=int(w["chips"]),
                config=load_json(root / "configs" / f"{w['config']}.json"),
                traffic=load_json(root / "traffic" / f"{w['traffic']}.json"),
                limits=load_json(root / "limits" / f"{workload}.json"),
                end_to_end=e2e, per_layer=layer, root=root)


# --------------------------------------------------------------------------
# device
# --------------------------------------------------------------------------

def device_check(chips: int, peaks_file: pathlib.Path = None) -> dict:
    """The devices JAX sees, checked: the platform must be the chip's, there
    must be as many as the cell asks for, and the kind must be in the peaks
    table.  Returns the device record and the chip's peaks."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print("device: " + json.dumps(dev), file=sys.stderr, flush=True)
    if dev["platform"] != PLATFORM:
        raise BenchError(f"JAX found no {PLATFORM} device (first device is "
                         f"{dev['platform']!r}); the benchmark never falls "
                         "back")
    if dev["count"] < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{dev['count']}")
    peaks = load_json(peaks_file or HERE / "peaks.json")["chips"]
    if dev["kind"] not in peaks:
        raise BenchError(f"device kind {dev['kind']!r} is not in peaks.json "
                         f"(has {sorted(peaks)})")
    dev["count"] = chips
    return dev, peaks[dev["kind"]]


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``; 0 where the backend
    keeps no count (the CPU backend of the tests)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class CompileEvents:
    """Counts programs lowered and compiled while installed, from JAX's
    monitoring events: lowering happens for every new program, whether its
    binary then comes from the persistent cache or from the compiler."""
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        self.lowered = self.compiled = self.cache_hits = 0

    def _duration(self, event: str, *_, **__) -> None:
        self.lowered += event == self.LOWER
        self.compiled += event == self.COMPILE

    def _event(self, event: str, **_) -> None:
        self.cache_hits += event == self.HIT

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc) -> None:
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self._duration)
        monitoring.unregister_event_listener(self._event)


# --------------------------------------------------------------------------
# seeds and statistics
# --------------------------------------------------------------------------

def jax_key(seed: int):
    """A JAX key from any whole number below 2**64: both 32-bit halves are
    folded in, so seeds above 2**31 stay distinct."""
    import jax
    key = jax.random.key(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def percentile(values: typing.Sequence[float], q: float) -> float:
    """The q-th percentile (0-100) with linear interpolation between order
    statistics; a single value is its own percentile."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))
