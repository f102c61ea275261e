"""The paper's "Seq vs Concurrent vs Threaded" table, for gradient reduction.

The halo benchmarks already sweep the paper's three endpoint-concurrency
columns; this is the same sweep for the SGD path: issue-schedule policy
{accumulate_then_reduce, stream, scheduled} x virtual channels {1, 2, 4},
executed by ``Communicator.reduce_scheduled`` on a microbatched step whose
grad_fn carries real matmul compute — so streamed schedules have backward
work to hide their collectives under (XLA's latency-hiding scheduler does
the overlapping; the schedule provides the independence).

Rows print as ``policy,channels,microbatches,us_per_step,pct_vs_accum``
(pct > 100 means faster than accumulate_then_reduce at the same channels).

On shared-memory host devices the streamed rows measure the *cost* side of
the paper's trade (more collective launches, finer buckets); the *benefit*
side — reductions hidden under backward compute — needs links that progress
independently of the cores, so it lives in the dry-run roofline's
``t_exposed_collective`` (EXPERIMENTS.md explains the split).
"""

from __future__ import annotations

from benchmarks.common import TIMER_SNIPPET, run_on_devices

SCRIPT = TIMER_SNIPPET + r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.comm import CommConfig, Communicator

mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,) * 1)
MICRO = 4
D, H, LAYERS = 256, 1024, 4       # enough matmul work to overlap against

rng = np.random.RandomState(0)
params = {f"layer{i}": {"wi": jnp.asarray(rng.randn(D, H).astype(np.float32) * 0.02),
                        "wo": jnp.asarray(rng.randn(H, D).astype(np.float32) * 0.02)}
          for i in range(LAYERS)}
batch = jnp.asarray(rng.randn(32 * MICRO, D).astype(np.float32))

def loss_fn(p, x):
    h = x
    for i in range(LAYERS):
        h = jnp.tanh(h @ p[f"layer{i}"]["wi"]) @ p[f"layer{i}"]["wo"]
    return jnp.mean(h ** 2)

def grad_fn(p, mb):
    return jax.value_and_grad(loss_fn)(p, mb)

print("policy,channels,microbatches,us_per_step,pct_vs_accum")
base = {}
for channels in (1, 2, 4):
    comm = Communicator(mesh, CommConfig(transport="ring_hier", chunks=2,
                                         channels=channels,
                                         bucket_bytes=D * H * 4,
                                         data_axes=("data",)))
    for policy in ("accumulate_then_reduce", "stream", "scheduled"):
        sched = comm.schedule(params, policy, MICRO)
        def inner(p, b):
            return comm.reduce_scheduled(grad_fn, p, b, sched,
                                         op="all_reduce")
        fn = jax.jit(jax.shard_map(
            inner, mesh=mesh, in_specs=(P(), P("data")),
            out_specs=(P(), P()), check_vma=False))
        sec = time_call(fn, params, batch)
        if policy == "accumulate_then_reduce":
            base[channels] = sec
        pct = 100.0 * base[channels] / sec
        print(f"{policy},{channels},{MICRO},{sec*1e6:.1f},{pct:.0f}")
"""


def run() -> str:
    return run_on_devices(SCRIPT)


if __name__ == "__main__":
    print(run())
