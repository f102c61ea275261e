"""The paper's first workload end-to-end: a Wilson-like stencil operator
driven to convergence by the comm-avoiding CG family — ``solver ∈ {cg,
pipelined, sstep} × precond ∈ {none, eo}`` — with the halo exchange on the
``overlap`` schedule.  The ``reductions`` column counts the latency-bound
inner-product all-reduces each variant pays: classic CG's ``2·iters+1``
drops to ``iters`` (pipelined, reduction hidden under the matvec) to
``ceil(iters/s)`` (s-step, one fused reduction per block), and even-odd
preconditioning roughly halves ``iters`` on top.

    PYTHONPATH=src python examples/halo_stencil.py

Run with more fake devices to see the schedules and variants diverge:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/halo_stencil.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.comm import CommConfig, Communicator
from repro.core.halo import HaloSpec
from repro.stencil import (PRECONDS, SOLVERS, StencilOp,
                           predicted_reduction_collectives, solve)


def main() -> None:
    n = len(jax.devices())
    mesh = jax.make_mesh((n,), ("x",), axis_types=(AxisType.Auto,) * 1)
    L, C = 24, 12                        # local extent, spinor-ish components
    specs = (HaloSpec("x", 0),)
    op = StencilOp(specs=specs, mass=0.2)
    comm = Communicator(mesh, CommConfig(transport="psum", data_axes=("x",),
                                         channels=2))
    rng = np.random.RandomState(0)
    b = jnp.asarray(rng.randn(n * L, L, C).astype(np.float32))

    hplan = comm.halo_plan((L, L, C), specs, schedule="overlap")
    print(f"devices={n}  local={L}x{L}x{C}  halo bytes/exchange="
          f"{hplan.bytes_per_device:.0f}  "
          f"overlap_frac={hplan.overlap_fraction:.2f}\n")
    print(f"{'solver':10s} {'precond':8s} {'iters':>5s} {'reductions':>10s} "
          f"{'rel_resid':>10s} {'ms/solve':>9s}")

    sols = {}
    for solver in SOLVERS:
        for precond in PRECONDS:
            def run(bl, sv=solver, pc=precond):
                r = solve(op, bl, comm, solver=sv, precond=pc, s=4, tol=1e-5,
                          maxiter=300, schedule="overlap", chunks=2,
                          channels=2)
                return r.x, r.iters, r.rel_residual
            fn = jax.jit(jax.shard_map(
                run, mesh=mesh, in_specs=P("x", None, None),
                out_specs=(P("x", None, None), P(), P()), check_vma=False))
            x, iters, rel = jax.block_until_ready(fn(b))
            t0 = time.time()
            for _ in range(3):
                jax.block_until_ready(fn(b))
            dt = (time.time() - t0) / 3
            sols[(solver, precond)] = np.asarray(x)
            red = predicted_reduction_collectives(solver, int(iters), s=4)
            print(f"{solver:10s} {precond:8s} {int(iters):5d} {red:10d} "
                  f"{float(rel):10.2e} {dt*1e3:9.1f}")

    ref = sols[("cg", "none")]
    worst = max(float(np.abs(s - ref).max()) for s in sols.values())
    print(f"\nmax |x_variant - x_cg| across the family: {worst:.2e}")
    ax = op.apply_reference(jnp.asarray(ref))
    print(f"final check ‖A x - b‖/‖b‖ = "
          f"{float(jnp.linalg.norm(ax - b) / jnp.linalg.norm(b)):.2e}")


if __name__ == "__main__":
    main()
