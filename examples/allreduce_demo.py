"""The paper's headline experiment, live: reduce a gradient-sized pytree
with the original Baidu-style schedule vs the optimised one.

    PYTHONPATH=src python examples/allreduce_demo.py --elements 4194304
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.comm import CommConfig, Communicator


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--elements", type=int, default=1 << 22)
    ap.add_argument("--tensors", type=int, default=64)
    args = ap.parse_args()

    n = len(jax.devices())
    if n == 1:
        print("NOTE: only 1 device visible — rings degenerate to identity, "
              "so this measures pure bucketing overhead.  Run with\n"
              "  XLA_FLAGS=--xla_force_host_platform_device_count=8\n"
              "to see the paper's before/after (as benchmarks/run.py does).")
    mesh = jax.make_mesh((n,), ("data",), axis_types=(AxisType.Auto,) * 1)
    rng = np.random.RandomState(0)
    k = args.tensors
    sizes = np.full(k, args.elements // k)
    sizes[0] += args.elements - sizes.sum()
    tree = {f"g{i}": jnp.asarray(rng.randn(int(s)).astype(np.float32))
            for i, s in enumerate(sizes)}
    specs = {key: P() for key in tree}

    results = {}
    for name, kw in [
        ("original         (per-tensor, uni-ring)",
         dict(transport="ring", chunks=1, bidirectional=False, bucket_bytes=1)),
        ("ring             (buckets + bi + chunks)",
         dict(transport="ring", chunks=2, bucket_bytes=32 * 2**20)),
        ("ring x2 rails    (channel striping)",
         dict(transport="ring", chunks=2, channels=2, bucket_bytes=32 * 2**20)),
        ("psum             (vendor reference)",
         dict(transport="psum", fuse=False)),
    ]:
        comm = Communicator(mesh, CommConfig(data_axes=("data",), **kw))
        fn = jax.jit(lambda g: comm.reduce(g, specs)[0])
        jax.block_until_ready(fn(tree))
        t0 = time.time()
        for _ in range(5):
            jax.block_until_ready(fn(tree))
        dt = (time.time() - t0) / 5
        results[name] = dt
        print(f"{name}: {dt*1e6:10.1f} us/reduction")
    base = results[list(results)[0]]
    for name, dt in list(results.items())[1:]:
        label = name.split("(")[0].strip()
        print(f"speedup vs original — {label}: {base/dt:.1f}x")


if __name__ == "__main__":
    main()
