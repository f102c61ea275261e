"""Paper Figures 3/4: non-communication overhead (alloc/copy/local-sum) and
the fraction of time spent communicating, before/after optimisation.

Decomposition: ``collective_only`` times the ring on a pre-fused buffer
(pure comm); the full reducer adds bucketise/debucketise (the paper's
alloc+copy analogue).  The 'original' path pays per-tensor overhead."""

from __future__ import annotations

from benchmarks.common import TIMER_SNIPPET, run_on_devices

SCRIPT = TIMER_SNIPPET + r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.comm import CommConfig, Communicator
from repro.core import ring
from repro.core.ring import RingConfig

mesh = jax.make_mesh((2, 4), ("pod", "data"), axis_types=(AxisType.Auto,) * 2)
rng = np.random.RandomState(0)

def workload(total, k=32):
    sizes = np.full(k, total // k)
    sizes[0] += total - sizes.sum()
    return {f"g{i}": jnp.asarray(rng.randn(int(s)).astype(np.float32))
            for i, s in enumerate(sizes)}

print("variant,elements,us_total,us_comm,pct_comm")
for total in [1<<14, 1<<20]:
    tree = workload(total)
    specs = {k: P() for k in tree}

    # pure-comm reference: one pre-fused aligned buffer
    cfg = RingConfig(chunks=2, bidirectional=True)
    pad = cfg.flat_divisor([4, 2])
    L = (total + pad - 1) // pad * pad
    flat = jnp.zeros((L,), jnp.float32)
    comm_only = jax.jit(jax.shard_map(
        lambda x: ring.hierarchical_all_reduce(x, ("data", "pod"), cfg),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
    t_comm = time_call(comm_only, flat)

    for name, kw in [("original", dict(transport="ring", chunks=1,
                                       bidirectional=False, bucket_bytes=1)),
                     ("optimised", dict(transport="ring_hier",
                                        chunks=2, bucket_bytes=32*2**20))]:
        comm = Communicator(mesh, CommConfig(data_axes=("pod","data"), **kw))
        fn = jax.jit(lambda g: comm.reduce(g, specs)[0])
        t_total = time_call(fn, tree)
        pct = 100.0 * min(t_comm / t_total, 1.0)
        print(f"{name},{total},{t_total*1e6:.1f},{t_comm*1e6:.1f},{pct:.0f}")
"""


def run() -> str:
    return run_on_devices(SCRIPT)


if __name__ == "__main__":
    print(run())
