"""Paper Tables V/VI: end-application stencil (Wilson-like sparse matrix)
throughput vs local volume — the halo exchange feeding a real computation."""

from __future__ import annotations

from benchmarks.common import TIMER_SNIPPET, run_on_devices

SCRIPT = TIMER_SNIPPET + r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.comm import CommConfig, Communicator
from repro.core.halo import HaloSpec

mesh = jax.make_mesh((2, 2, 2), ("x", "y", "z"),
                     axis_types=(AxisType.Auto,) * 3)
SPECS = [HaloSpec("x", 0), HaloSpec("y", 1), HaloSpec("z", 2)]
C = 12  # components (su3 spinor-ish)
comm = Communicator(mesh, CommConfig(data_axes=("x", "y", "z"), channels=2))

def stencil(xl, schedule):
    h = comm.halo_exchange(xl, SPECS, schedule=schedule)
    y = 6.0 * xl
    for d, (ax, dim) in enumerate([("x",0),("y",1),("z",2)]):
        lo = h[(ax, "-")]; hi = h[(ax, "+")]
        up = jnp.concatenate([lo, xl], axis=dim)
        dn = jnp.concatenate([xl, hi], axis=dim)
        n = xl.shape[dim]
        y = y - jax.lax.slice_in_dim(up, 0, n, axis=dim) \
              - jax.lax.slice_in_dim(dn, 1, n+1, axis=dim)
    return y

print("schedule,local_vol,gflop_s_per_rank")
for L in [8, 16, 24]:
    x = jnp.ones((2*L, 2*L, 2*L, C), jnp.float32)
    flops_per_rank = 7 * 2 * (L**3) * C   # 6 neighbour adds + scale, fused mul-add
    for sched in ["sequential", "concurrent"]:
        g = jax.jit(jax.shard_map(lambda v, s=sched: stencil(v, s), mesh=mesh,
                                  in_specs=P("x","y","z",None),
                                  out_specs=P("x","y","z",None),
                                  check_vma=False))
        sec = time_call(g, x)
        print(f"{sched},{L}^3,{flops_per_rank/sec/1e9:.3f}")
"""


def run() -> str:
    return run_on_devices(SCRIPT)


if __name__ == "__main__":
    print(run())
