"""Expert-parallel dispatch A/B: first-class ``all_to_all`` vs the
replicated-psum fallback (repro.comm + repro.models.moe).

One MoE layer's forward + backward under expert parallelism, swept over
the exchange transport x channel rails on a ``(1, R)`` model mesh.  The
``psum`` row *is* the old replicated path (zero-pad the capacity buffer
across the axis, all-reduce, slice), so the A/B is a column away::

    transport,channels,model_parallel,us_per_call,dispatch_B,total_B,msgs,vs_replicated

``dispatch_B`` / ``total_B`` / ``msgs`` come from
:meth:`repro.comm.api.Communicator.a2a_plan` — the same predictions the
dry-run's ``--suite moe`` asserts against lowered HLO at <1% tolerance;
here they annotate measured step times.  ``vs_replicated`` is the
per-device dispatch-bytes ratio against the psum fallback's prediction —
the PR's acceptance bound is <= 1/R for every real transport.

On shared-memory host devices this measures the *mechanism* (exchange
count, rail striping, fallback padding); wire-level effects live in the
dry-run roofline (EXPERIMENTS.md explains the split).

``--dry`` runs one tiny combo per transport as a CI smoke.
"""

from __future__ import annotations

import argparse

from benchmarks.common import TIMER_SNIPPET, run_on_devices

SCRIPT = TIMER_SNIPPET + r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.configs.base import MoEConfig
from repro.models import moe as moe_mod
from repro.runtime.train_step import TrainStepConfig, build_moe_comm, make_ctx

DRY = %(dry)s
if DRY:
    d, E, k, B, S, ff = 32, 4, 2, 4, 16, 64
    grid = [("a2a", 0, 2), ("psum", 0, 2)]
else:
    d, E, k, B, S, ff = 128, 8, 2, 8, 64, 256
    grid = [(t, c, r) for r in (2, 4) for t in ("a2a", "ring", "psum")
            for c in (0, 2)]

cfg = MoEConfig(num_experts=E, top_k=k, expert_ff=ff, capacity_factor=1.5,
                parallelism="ep")
params = moe_mod.moe_init(jax.random.key(0), cfg, d)
x = jnp.asarray(np.random.RandomState(0).randn(B, S, d).astype(np.float32))
pspecs = {"router": {"w": P()}, "w_gate": P("model"), "w_up": P("model"),
          "w_down": P("model")}
cap = moe_mod.capacity(S, cfg)

print("transport,channels,model_parallel,us_per_call,dispatch_B,total_B,"
      "msgs,vs_replicated")
rows = {}
for transport, channels, r in grid:
    mesh = jax.make_mesh((1, r), ("data", "model"),
                         devices=jax.devices()[:r],
                         axis_types=(AxisType.Auto,) * 2)
    tcfg = TrainStepConfig(moe_transport=transport, moe_channels=channels)
    ctx = make_ctx(mesh, tcfg)
    comm = build_moe_comm(mesh, tcfg)
    plan = comm.a2a_plan((B // r, E, cap, d), dtype=jnp.float32)
    rep = build_moe_comm(mesh, TrainStepConfig(moe_transport="psum")) \
        .a2a_plan((B // r, E, cap, d), dtype=jnp.float32)

    def loss(p, xx):
        y, aux, _ = moe_mod.moe_apply(p, xx, cfg, "silu", ctx=ctx,
                                      compute_dtype=jnp.float32)
        return jnp.sum(y * y) + aux

    step = jax.jit(jax.shard_map(jax.grad(loss), mesh=mesh,
                                 in_specs=(pspecs, P()),
                                 out_specs=pspecs, check_vma=False))
    t = time_call(step, params, x, warmup=2, iters=5)
    ratio = plan.dispatch_bytes_per_device / rep.dispatch_bytes_per_device
    rows[(transport, channels, r)] = t
    print(f"{transport},{channels},{r},{t*1e6:.1f},"
          f"{plan.dispatch_bytes_per_device:.0f},"
          f"{plan.bytes_per_device:.0f},{plan.messages_per_device:.0f},"
          f"{ratio:.3f}")
    assert transport == "psum" or ratio <= 1.0 / r + 1e-9, \
        f"{transport} dispatch bytes exceed 1/R of the replicated cost"

for r in sorted({g[2] for g in grid}):
    a, b = rows.get(("a2a", 0, r)), rows.get(("psum", 0, r))
    if a and b:
        print(f"ratio_us_psum_over_a2a_r{r},{b / a:.2f}")
"""


def run(dry: bool = False) -> str:
    return run_on_devices(SCRIPT % {"dry": dry})


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--dry", action="store_true",
                    help="one tiny combo per transport (CI smoke)")
    args = ap.parse_args()
    print(run(dry=args.dry))
