import os
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
"""The paper's case study as a runnable example (deliverable b):
U-MGPU vs D-MGPU across the five collaborative-execution patterns.

  PYTHONPATH=src python examples/pattern_study.py

Prints, per workload x mode: oracle-checked correctness, cross-device
traffic from the compiled HLO, and simulated execution time on the
4-chip system model — the Fig. 9 bars in table form, plus the paper's
four design lessons evaluated against our numbers.
"""
import jax
import numpy as np

from repro.launch.mesh import make_mesh


def main():
    from repro.patterns import WORKLOADS, run_workload
    mesh = make_mesh((4,), ("dev",))
    sizes = {"aes": 64 * 1024, "km": 32 * 1024, "fir": 64 * 1024,
             "sc": 512, "gd": 16 * 1024, "mt": 512, "bs": 32 * 1024}
    rows = []
    for name in WORKLOADS:
        rows.extend(run_workload(name, mesh, sizes[name]))
    print(f"{'workload':9s} {'pattern':12s} {'mode':6s} {'ok':3s} "
          f"{'traffic(B)':>12s} {'sim time':>10s}")
    for r in rows:
        print(f"{r.name:9s} {r.pattern:12s} {r.mode:6s} "
              f"{'yes' if r.correct else 'NO ':3s} "
              f"{r.collective_bytes:12.0f} {r.sim_time_s * 1e6:8.1f}us")

    by = {(r.name, r.mode): r for r in rows}
    print("\npaper lessons, evaluated:")
    print(f" 1. partitioned => zero traffic: AES D-mode "
          f"{by[('aes', 'dmode')].collective_bytes:.0f} B")
    savings = [(n, by[(n, 'umode')].collective_bytes
                - by[(n, 'dmode')].collective_bytes) for n in WORKLOADS]
    print(f" 2. explicit placement saves traffic on: "
          f"{[n for n, s in savings if s > 0]}")
    # 3. traffic <-> time correlation: compare the U-D deltas per workload
    #    (the paper's Fig. 9 claim is about the same workload under more
    #    vs less cross-device traffic, not across unlike algorithms)
    db = np.array([by[(n, 'umode')].collective_bytes
                   - by[(n, 'dmode')].collective_bytes for n in WORKLOADS])
    dt = np.array([by[(n, 'umode')].sim_time_s
                   - by[(n, 'dmode')].sim_time_s for n in WORKLOADS])
    corr = np.corrcoef(db, dt)[0, 1] if db.std() > 0 else float("nan")
    print(f" 3. corr(extra traffic, extra time) U vs D = {corr:.2f} "
          f"(paper: 'strongly correlated')")
    print(f" 4. traffic-heaviest pattern under the unified model: "
          f"{max(WORKLOADS, key=lambda n: by[(n, 'umode')].collective_bytes)}"
          f" (paper: Irregular/BS)")


if __name__ == "__main__":
    main()
