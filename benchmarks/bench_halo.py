"""Paper Tables I-III: Cartesian halo-exchange bandwidth, sequential vs
concurrent vs chunked (multi-channel) schedules, across face sizes."""

from __future__ import annotations

from benchmarks.common import TIMER_SNIPPET, run_on_devices

SCRIPT = TIMER_SNIPPET + r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.comm import CommConfig, Communicator
from repro.core.halo import HaloSpec, halo_bytes

# 3-D Cartesian communicator on 8 ranks (2x2x2), like the paper's 2^4 grid
mesh = jax.make_mesh((2, 2, 2), ("x", "y", "z"),
                     axis_types=(AxisType.Auto,) * 3)
SPECS = [HaloSpec("x", 0), HaloSpec("y", 1), HaloSpec("z", 2)]
comm = Communicator(mesh, CommConfig(data_axes=("x", "y", "z"), channels=4))

print("schedule,local_vol,bytes_per_rank,us_per_exchange,mb_s")
for L in [8, 16, 24]:
    shape = (2*L, 2*L, 2*L, 16)   # global lattice, 16 'spin' components
    x = jnp.ones(shape, jnp.float32)
    spec_in = P("x", "y", "z", None)
    nbytes = halo_bytes((L, L, L, 16), SPECS, 4)
    for sched in ["sequential", "concurrent", "chunked"]:
        def fn(xl, s=sched):
            h = comm.halo_exchange(xl, SPECS, schedule=s)
            # consume all faces so nothing is dead-code eliminated
            return sum(v.sum() for v in h.values())
        g = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=spec_in,
                                  out_specs=P(), check_vma=False))
        sec = time_call(g, x)
        print(f"{sched},{L}^3,{nbytes},{sec*1e6:.1f},{nbytes/sec/1e6:.1f}")
"""


def run() -> str:
    return run_on_devices(SCRIPT)


if __name__ == "__main__":
    print(run())
