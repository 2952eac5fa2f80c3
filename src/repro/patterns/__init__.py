"""MGMark-TPU: the paper's benchmark suite on the multi-pod TPU model.

Seven workloads across the five collaborative-execution patterns
(paper Sec. 5):

  AES  partitioned   KM  partitioned   FIR  adjacent   SC  adjacent
  GD   gather        MT  scatter       BS   irregular

Each module: reference oracle + run in U-mode (jit/GSPMD — the paper's
U-MGPU) and D-mode (shard_map, explicit collectives — D-MGPU).
"""
from . import aes, base, bs, fir, gd, km, mt, sc
from .base import PatternReport, evaluate

WORKLOADS = {"aes": aes, "km": km, "fir": fir, "sc": sc, "gd": gd,
             "mt": mt, "bs": bs}


def run_workload(name: str, mesh, size: int, **evaluate_kw):
    """Run workload ``name`` at ``size`` in U-mode, then D-mode, on a
    mesh with axis "dev"; each output is checked against the oracle by
    :func:`evaluate`.  Returns the two :class:`PatternReport`s."""
    import jax.numpy as jnp
    mod = WORKLOADS[name]
    args = mod.make_args(size)
    if name == "aes":        # the oracle takes the key, the program its schedule
        plain, key, rk, sb = args
        oracle = mod.reference(plain, key)
        args = (plain, rk, sb)
    else:
        oracle = mod.reference(*args)
    jargs = tuple(jnp.asarray(a) for a in args)
    with mesh:
        return [evaluate(name, mod.PATTERN, mode, make(mesh), jargs, oracle,
                         **evaluate_kw)
                for mode, make in (("umode", mod.make_umode),
                                   ("dmode", mod.make_dmode))]


__all__ = ["aes", "base", "bs", "fir", "gd", "km", "mt", "sc",
           "WORKLOADS", "PatternReport", "evaluate", "run_workload"]
