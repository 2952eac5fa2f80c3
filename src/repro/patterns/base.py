"""MGMark-TPU common harness.

Every workload module exposes:
    reference(...)            -- numpy/jnp oracle (DP-4: data validates)
    run_umode(mesh, ...)      -- one jit over the mesh, GSPMD placement
    run_dmode(mesh, ...)      -- shard_map, every collective explicit
    PATTERN                   -- its collaborative-execution pattern
    default_size(n_devices)   -- Table-2 sizing (4-device column scaled)

`evaluate` runs one mode, checks the output against the oracle, parses
the compiled HLO for collective traffic and prices it on the system
model — the three numbers Fig. 9 plots (time, traffic, correctness).
"""
from __future__ import annotations

import dataclasses
import typing

import jax
import numpy as np

from repro.core import SystemSpec, analyze, simulate

PATTERNS = ("partitioned", "adjacent", "gather", "scatter", "irregular")


@dataclasses.dataclass
class PatternReport:
    name: str
    mode: str                       # "umode" | "dmode"
    pattern: str
    correct: bool
    max_err: float
    collective_bytes: float         # per-device, from compiled HLO
    bytes_by_kind: dict
    sim_time_s: float               # timeline simulation on the system model
    compute_util: float
    flops: float
    hbm_bytes: float

    def row(self) -> str:
        return (f"{self.name:6s} {self.mode:6s} {self.pattern:12s} "
                f"ok={self.correct} coll={self.collective_bytes:12.4g}B "
                f"t_sim={self.sim_time_s * 1e3:9.3f}ms "
                f"util={self.compute_util:.2f}")


def evaluate(name: str, pattern: str, mode: str, jitted, args,
             oracle: np.ndarray, spec: SystemSpec = None,
             atol: float = 2e-2, device_limit: int = 8) -> PatternReport:
    """Run a compiled pattern workload, validate + price it."""
    lowered = jitted.lower(*args)
    compiled = lowered.compile()
    out = np.asarray(jax.device_get(compiled(*args)))
    oracle = np.asarray(oracle)
    if np.issubdtype(out.dtype, np.floating):
        err = float(np.max(np.abs(out.astype(np.float64)
                                  - oracle.astype(np.float64))))
    else:
        err = float(np.max(np.abs(out.astype(np.int64)
                                  - oracle.astype(np.int64))))
    cost = analyze(compiled.as_text())
    spec = spec or SystemSpec(pod_shape=(1, jax.device_count()))
    rep = simulate(cost=cost, spec=spec, device_limit=device_limit)
    ca = compiled.cost_analysis()
    return PatternReport(
        name=name, mode=mode, pattern=pattern,
        correct=bool(err <= atol), max_err=err,
        collective_bytes=cost.collective_bytes,
        bytes_by_kind=cost.collective_bytes_by_kind(),
        sim_time_s=rep.time_s, compute_util=rep.compute_util,
        flops=max(float(ca.get("flops", 0.0)), cost.flops),
        hbm_bytes=max(float(ca.get("bytes accessed", 0.0)), cost.hbm_bytes))
