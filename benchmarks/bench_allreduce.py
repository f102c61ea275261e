"""Paper Figures 1/2/5: gradient-reduction time & bandwidth vs vector length,
original (per-tensor, unidirectional, unfused) vs optimised transports.

Workload mirrors synchronous-SGD gradient reduction: a pytree of K tensors
totalling L fp32 elements (K grows with L like a real model's parameter
list).  The ``original`` row reduces tensor-by-tensor over a one-direction
ring (the published code's behaviour); the optimised rows fuse into aligned
buckets and run the registered ``repro.comm`` transports.  On top of the
transport sweep, the ``ring_hier`` schedule is swept over ``channels`` in
{1, 2, 4} — the paper's multi-rail endpoint count as a config knob.

A second block sweeps the *wire codec* on the ``ring`` transport at fixed
length — fp32 / bf16 rail (``wire_dtype``) / int8+scales (``wire_codec``) —
printing the plan-predicted wire bytes next to the bytes actually lowered
into the HLO's collective-permutes.  ``--dry`` shrinks both blocks to a CI
smoke.
"""

from __future__ import annotations

import argparse

from benchmarks.common import TIMER_SNIPPET, run_on_devices

SCRIPT = TIMER_SNIPPET + r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.comm import CommConfig, Communicator

DRY = %(dry)s
mesh = jax.make_mesh((2, 4), ("pod", "data"), axis_types=(AxisType.Auto,) * 2)
P_WORLD = 8

def workload(total_elems, rng):
    k = int(min(32, max(1, total_elems // 4096)))
    sizes = np.full(k, total_elems // k)
    sizes[0] += total_elems - sizes.sum()
    return {f"g{i}": jnp.asarray(rng.randn(int(s)).astype(np.float32))
            for i, s in enumerate(sizes)}

CONFIGS = [
    # (row label, CommConfig kwargs)
    ("original", dict(transport="ring", chunks=1, bidirectional=False,
                      bucket_bytes=1)),
    ("ring", dict(transport="ring", chunks=2, bucket_bytes=32*2**20)),
    ("ring_hier/ch1", dict(transport="ring_hier", chunks=2, channels=1,
                           bucket_bytes=32*2**20)),
    ("ring_hier/ch2", dict(transport="ring_hier", chunks=2, channels=2,
                           bucket_bytes=32*2**20)),
    ("ring_hier/ch4", dict(transport="ring_hier", chunks=2, channels=4,
                           bucket_bytes=32*2**20)),
    ("ring_hier_int8", dict(transport="ring_hier", chunks=2,
                            wire_codec="int8", bucket_bytes=32*2**20)),
    ("psum", dict(transport="psum", fuse=False)),
    ("psum_fused", dict(transport="psum", bucket_bytes=32*2**20)),
]

rng = np.random.RandomState(0)
print("transport,channels,elements,us_per_call,alg_bw_mb_s,pct_vs_original")
base = {}
for total in ([1<<12] if DRY else [1<<12, 1<<16, 1<<20, 1<<22]):
    tree = workload(total, rng)
    specs = {k: P() for k in tree}
    for name, kw in CONFIGS:
        comm = Communicator(mesh, CommConfig(data_axes=("pod","data"), **kw))
        fn = jax.jit(lambda g: comm.reduce(g, specs)[0])
        sec = time_call(fn, tree)
        # ring algorithm bytes: 2 (p-1)/p * payload, both directions counted once
        alg_bytes = 2 * (P_WORLD - 1) / P_WORLD * total * 4
        bw = alg_bytes / sec / 1e6
        if name == "original":
            base[total] = sec
        pct = 100.0 * base[total] / sec
        ch = kw.get("channels", 0)
        print(f"{name},{ch},{total},{sec*1e6:.1f},{bw:.1f},{pct:.0f}")

# -- wire codec block: what actually crosses the wire per codec -------------
# Single reduce axis (the inner 4-ring): the int8 ring re-encodes per chunk,
# so flat buffers must hold whole codec blocks per chunk and the divisor
# grows as world*chunks*2*block per axis.  bf16 hlo bytes read fp32 on this
# backend (XLA CPU float normalization upcasts bf16 collectives); pred_*
# columns carry the wire format.
from repro.launch.roofline import collective_wire_bytes

CODECS = [
    # (row label, CommConfig wire kwargs)
    ("fp32", dict()),
    ("bf16", dict(wire_dtype="bfloat16")),
    ("int8", dict(wire_codec="int8")),
]
total = 1 << 14 if DRY else 1 << 20
tree = workload(total, rng)
specs = {k: P() for k in tree}
print()
print("# wire codec (ring, fixed length): plan-predicted vs lowered HLO bytes")
print("codec,elements,us_per_call,pred_wire_bytes,hlo_wire_bytes,pred_ratio_vs_fp32")
base_bytes = None
for name, wire_kw in CODECS:
    comm = Communicator(mesh, CommConfig(
        transport="ring", chunks=2, bucket_bytes=32*2**20,
        data_axes=("data",), **wire_kw))
    fn = jax.jit(lambda g: comm.reduce(g, specs)[0])
    hlo = fn.lower(tree).compile().as_text()
    meas = sum(collective_wire_bytes(hlo).op_bytes.values())
    pred = comm.plan(tree).bytes_per_device
    sec = time_call(fn, tree)
    if name == "fp32":
        base_bytes = pred
    ratio = base_bytes / pred if pred else 0.0
    print(f"{name},{total},{sec*1e6:.1f},{pred:.0f},{meas:.0f},{ratio:.2f}")
"""


def run(dry: bool = False) -> str:
    return run_on_devices(SCRIPT % {"dry": dry})


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--dry", action="store_true",
                    help="tiny lengths, single size per block (CI smoke)")
    args = ap.parse_args()
    print(run(dry=args.dry))
