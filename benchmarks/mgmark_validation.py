import os
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
"""Fig. 7 analog — full-workload validation of the system model.

The paper validates MGSim against real-GPU wall time (5.5% mean error).
No TPU is attached here, so the golden reference is the analytic
roofline bound of each compiled workload (compute/memory/collective
terms from the real per-device HLO); the simulator must land close to
it while adding queueing/serialization effects on top.  Reported per
workload: simulated time, analytic bound, ratio (>= 1, close to 1 for
the bandwidth-dominated ones).
"""
import sys

import jax
from repro.launch.mesh import make_mesh
import jax.numpy as jnp

from repro.core import SINGLE_POD, SystemSpec, analyze, simulate
from repro.core.roofline import collective_sim_time


def main() -> int:
    from repro.patterns import WORKLOADS
    mesh = make_mesh((4,), ("dev",))
    spec = SystemSpec(pod_shape=(1, 4))
    sizes = {"aes": 64 * 1024, "km": 32 * 1024, "fir": 64 * 1024,
             "sc": 512, "gd": 16 * 1024, "mt": 512, "bs": 32 * 1024}
    print("name,us_per_call,derived")
    worst = 0.0
    with mesh:
        for name, mod in WORKLOADS.items():
            args = mod.make_args(sizes[name])
            if name == "aes":
                plain, key, rk, sb = args
                jargs = (jnp.asarray(plain), jnp.asarray(rk),
                         jnp.asarray(sb))
            else:
                jargs = tuple(jnp.asarray(a) for a in args)
            compiled = mod.make_dmode(mesh).lower(*jargs).compile()
            cost = analyze(compiled.as_text())
            rep = simulate(cost=cost, spec=spec, device_limit=None)
            c = spec.chip
            bound = (cost.flops / c.peak_bf16_flops
                     + cost.hbm_bytes / c.hbm_bandwidth
                     + collective_sim_time(cost, spec))
            ratio = rep.time_s / max(bound, 1e-12)
            print(f"{name},{rep.time_s * 1e6:.2f},"
                  f"bound_us={bound * 1e6:.2f}|ratio={ratio:.2f}")
            worst = max(worst, ratio)
    print(f"# max sim/bound ratio: {worst:.2f} "
          f"(1.0 = at the roofline; launch overheads push it above)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
