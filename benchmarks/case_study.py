import os
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
"""Fig. 9 analog — U-mode vs D-mode on the MGMark-TPU suite (4 devices).

The paper's case study: for each workload, cross-device traffic and
execution time under the unified (U-MGPU) vs discrete (D-MGPU)
programming model on a 4-GPU box.  Here: jit/GSPMD vs shard_map on a
4-chip slice, traffic parsed from the compiled HLO, time from the
timeline simulator.  Expected replication of the paper's lesson:
  * Partitioned (AES/KM): both modes near-zero traffic;
  * D-mode <= U-mode traffic everywhere (explicit placement wins);
  * traffic correlates with simulated time.
"""
import sys

import jax
import numpy as np

from repro.launch.mesh import make_mesh


def main() -> int:
    from repro.patterns import WORKLOADS, run_workload
    mesh = make_mesh((4,), ("dev",))
    sizes = {"aes": 64 * 1024, "km": 32 * 1024, "fir": 64 * 1024,
             "sc": 512, "gd": 16 * 1024, "mt": 512, "bs": 32 * 1024}
    print("name,us_per_call,derived")
    rows = []
    for name in WORKLOADS:
        for rep in run_workload(name, mesh, sizes[name]):
            rows.append(rep)
            print(f"{name}_{rep.mode},{rep.sim_time_s * 1e6:.2f},"
                  f"coll_bytes={rep.collective_bytes:.0f}"
                  f"|pattern={rep.pattern}|correct={rep.correct}")
    # paper-lesson checks
    by = {(r.name, r.mode): r for r in rows}
    d_wins = sum(by[(n, "dmode")].collective_bytes
                 <= by[(n, "umode")].collective_bytes + 1
                 for n in WORKLOADS)
    aes_zero = by[("aes", "dmode")].collective_bytes == 0
    # traffic/time correlation across workloads (D-mode)
    t = np.array([by[(n, "dmode")].sim_time_s for n in WORKLOADS])
    b = np.array([by[(n, "dmode")].collective_bytes for n in WORKLOADS])
    corr = float(np.corrcoef(b, t)[0, 1]) if b.std() > 0 else 0.0
    print(f"# D-mode traffic <= U-mode: {d_wins}/{len(WORKLOADS)}")
    print(f"# AES partitioned zero-traffic: {aes_zero}")
    print(f"# corr(traffic, sim_time) across workloads: {corr:.2f}")
    ok = all(r.correct for r in rows)
    print(f"# all outputs match oracles: {ok}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
