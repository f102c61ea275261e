"""The paper's huge-page/fused-buffer table, for gradient reduction: the
:mod:`repro.mem` CommArena (pack -> fused-span reduce -> unpack, persistent
donated buffer) vs the per-bucket baseline at the same bucket config.

Sweeps page_bytes {4 KiB small-page baseline, 2 MiB huge page} x virtual
channels {1, 2, 4}.  Rows print as::

    page_bytes,channels,n_buckets,n_spans,pad_pct,us_arena,us_buckets,pct

``pct`` > 100 means the arena path is faster.  On shared-memory host
devices this measures the *mechanism* (fewer collective launches, aligned
flat copies, in-place donated buffer) — wire-level byte/page accounting
lives in the dry-run's ``--suite mem`` roofline (EXPERIMENTS.md explains
the split).

A second block sweeps the wire codec on the arena path at page 4096 —
fp32 / bf16 rail / int8+scales (the fused Pallas pack+quantize arena) —
printing predicted vs HLO-lowered collective wire bytes per codec.

``--dry`` runs one tiny combo per page size (plus the codec block) as a
CI smoke.
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
mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,) * 1)
rng = np.random.RandomState(0)
N_LEAVES, LEAF = (6, 4096) if DRY else (24, 65536)
params = {f"g{i}": jnp.asarray(rng.randn(LEAF + 128 * i).astype(np.float32))
          for i in range(N_LEAVES)}
batch = jnp.asarray(rng.randn(16, 8).astype(np.float32))

def loss_fn(p, x):
    return sum(jnp.sum(v) for v in p.values()) * 1e-3 + jnp.mean(x) * 0.0

def grad_fn(p, mb):
    return jax.value_and_grad(loss_fn)(p, mb)

print("page_bytes,channels,n_buckets,n_spans,pad_pct,us_arena,us_buckets,pct")
pages = [4096, 2 * 2**20]
chans = [1] if DRY else [1, 2, 4]
for page_bytes in pages:
    for channels in chans:
        comm = Communicator(mesh, CommConfig(
            transport="ring_hier", chunks=2, channels=channels,
            bucket_bytes=4 * LEAF, page_bytes=page_bytes,
            data_axes=("data",)))
        sched = comm.schedule(params, "scheduled", 1)
        asched = comm.arena_schedule(params, "scheduled", 1)
        arena = comm.arena(params)
        lay = arena.layout

        def bucket_run(p, b):
            return comm.reduce_scheduled(grad_fn, p, b, sched,
                                         op="all_reduce")

        def arena_run(p, b, buf):
            loss, (tree, out) = comm.reduce_scheduled(
                grad_fn, p, b, asched, op="all_reduce", arena=arena,
                arena_buf=buf)
            return loss, tree, out

        fb = jax.jit(jax.shard_map(
            bucket_run, mesh=mesh, in_specs=(P(), P("data")),
            out_specs=(P(), P()), check_vma=False))
        fa = jax.jit(jax.shard_map(
            arena_run, mesh=mesh, in_specs=(P(), P("data"), P(("data",))),
            out_specs=(P(), P(), P(("data",))), check_vma=False),
            donate_argnums=(2,))
        t_bucket = time_call(fb, params, batch)
        # the train-step contract: the returned (donated) arena threads
        # straight back in, so no per-step allocation is paid or timed
        state = {"buf": jnp.zeros((8 * lay.total_elems,), jnp.float32)}
        def arena_call(p, b):
            loss, tree, out = fa(p, b, state["buf"])
            state["buf"] = out
            return loss
        t_arena = time_call(arena_call, params, batch)
        pct = 100.0 * t_bucket / t_arena
        print(f"{page_bytes},{channels},{lay.n_segments},{lay.n_spans},"
              f"{100.0 * lay.padding_fraction:.2f},"
              f"{t_arena*1e6:.1f},{t_bucket*1e6:.1f},{pct:.0f}")

# -- wire codec block: the quantized arena vs the fp32/bf16 wire ------------
# The int8 ring re-encodes per chunk, so flat buffers must hold whole codec
# blocks per chunk: leaves here are multiples of world*chunks*2*block.
# bf16 hlo bytes read fp32 on this backend (XLA CPU float normalization
# upcasts bf16 collectives); pred_* columns carry the wire format.
from repro.launch.roofline import collective_wire_bytes

CODECS = [
    ("fp32", dict()),
    ("bf16", dict(wire_dtype="bfloat16")),
    ("int8", dict(wire_codec="int8")),
]
Q_LEAF = 65536
params_q = {f"q{i}": jnp.asarray(rng.randn(Q_LEAF).astype(np.float32))
            for i in range(4 if DRY else 16)}
N_ELEMS = sum(int(v.size) for v in params_q.values())
print()
print("# wire codec on the arena path (ring, page 4096, ch1): "
      "predicted vs lowered HLO bytes")
print("codec,elements,us_arena,pred_wire_bytes,hlo_wire_bytes,pred_ratio_vs_fp32")
base_bytes = None
for name, wire_kw in CODECS:
    comm = Communicator(mesh, CommConfig(
        transport="ring", chunks=2, channels=1, bucket_bytes=4 * Q_LEAF,
        page_bytes=4096, data_axes=("data",), **wire_kw))
    asched = comm.arena_schedule(params_q, "scheduled", 1)
    arena = comm.arena(params_q)
    lay = arena.layout
    quant = comm.codec is not None
    if quant:
        def arena_run(p, b, buf, ef):
            loss, (tree, out, ef2) = comm.reduce_scheduled(
                grad_fn, p, b, asched, op="all_reduce", arena=arena,
                arena_buf=buf, ef_buf=ef)
            return loss, tree, out, ef2
        donate, flat = (2, 3), P(("data",))
        in_specs = (P(), P("data"), flat, flat)
        out_specs = (P(), P(), flat, flat)
    else:
        def arena_run(p, b, buf):
            loss, (tree, out) = comm.reduce_scheduled(
                grad_fn, p, b, asched, op="all_reduce", arena=arena,
                arena_buf=buf)
            return loss, tree, out
        donate, flat = (2,), P(("data",))
        in_specs = (P(), P("data"), flat)
        out_specs = (P(), P(), flat)
    fa = jax.jit(jax.shard_map(arena_run, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False),
                 donate_argnums=donate)
    bufs = [jnp.zeros((8 * lay.total_elems,), jnp.dtype(lay.dtype))]
    if quant:
        bufs.append(jnp.zeros((8 * lay.payload_elems,), jnp.float32))
    hlo = fa.lower(params_q, batch, *bufs).compile().as_text()
    meas = sum(collective_wire_bytes(hlo).op_bytes.values())
    pred = comm.plan(params_q).arena_bytes_per_device
    state = {"bufs": bufs}
    def arena_call(p, b):
        out = fa(p, b, *state["bufs"])
        state["bufs"] = list(out[2:])
        return out[0]
    t = time_call(arena_call, params_q, batch)
    if name == "fp32":
        base_bytes = pred
    ratio = base_bytes / pred if pred else 0.0
    print(f"{name},{N_ELEMS},{t*1e6:.1f},{pred:.0f},{meas:.0f},{ratio:.2f}")
"""


def run(dry: bool = False) -> str:
    return run_on_devices(SCRIPT % {"dry": dry})


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--dry", action="store_true",
                    help="tiny single-channel combo per page size (CI smoke)")
    args = ap.parse_args()
    print(run(dry=args.dry))
