"""End-to-end solver benchmark: the comm-avoiding CG family on the
Wilson-like stencil operator — ``solver ∈ {cg, pipelined, sstep} ×
precond ∈ {none, eo}`` over halo schedules, driven to convergence.  The
``reductions`` column is the predicted inner-product collective count
(:func:`repro.stencil.predicted_reduction_collectives`): the α-latency
budget each variant actually pays, which is the paper's Tables V/VI story
applied to the solver instead of the exchange.

``python -m benchmarks.bench_cg --dry`` runs one tiny lattice over the full
solver × precond grid and asserts convergence (the CI solver smoke job).
"""

from __future__ import annotations

import sys

from benchmarks.common import TIMER_SNIPPET, run_on_devices

_BODY = r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.comm import CommConfig, Communicator
from repro.core.halo import HaloSpec
from repro.stencil import StencilOp, predicted_reduction_collectives, solve

mesh = jax.make_mesh((2, 2, 2), ("x", "y", "z"),
                     axis_types=(AxisType.Auto,) * 3)
SPECS = (HaloSpec("x", 0), HaloSpec("y", 1), HaloSpec("z", 2))
op = StencilOp(specs=SPECS, mass=0.5)

def solver_fn(comm, solver, precond, schedule, channels, tol, maxiter):
    def run(b):
        r = solve(op, b, comm, solver=solver, precond=precond, s=SSTEP_S,
                  tol=tol, maxiter=maxiter, schedule=schedule,
                  chunks=comm.halo_chunks, channels=channels)
        return r.x, r.iters, r.rel_residual
    return jax.jit(jax.shard_map(run, mesh=mesh,
                                 in_specs=P("x", "y", "z", None),
                                 out_specs=(P("x", "y", "z", None), P(), P()),
                                 check_vma=False))

print("solver,precond,schedule,channels,local_vol,iters,reductions,"
      "rel_residual,us_per_solve,us_per_iter")
rng = np.random.RandomState(0)
for L in LATTICES:
    b = jnp.asarray(rng.randn(2*L, 2*L, 2*L, C).astype(np.float32))
    for solver in SOLVERS:
        for precond in PRECONDS:
            for schedule in SCHEDULES:
                for channels in CHANNELS:
                    comm = Communicator(mesh, CommConfig(
                        transport="psum", data_axes=("x", "y", "z"),
                        channels=channels))
                    fn = solver_fn(comm, solver, precond, schedule,
                                   channels, TOL, MAXITER)
                    x, iters, rel = jax.block_until_ready(fn(b))
                    assert float(rel) < TOL, \
                        (solver, precond, schedule, channels, float(rel))
                    sec = time_call(fn, b)
                    it = max(int(iters), 1)
                    red = predicted_reduction_collectives(solver, it, s=SSTEP_S)
                    print(f"{solver},{precond},{schedule},{channels},{L}^3,"
                          f"{int(iters)},{red},{float(rel):.2e},"
                          f"{sec*1e6:.1f},{sec*1e6/it:.1f}")
print("CG_BENCH_OK")
"""

SWEEP_HEADER = """
LATTICES = [8, 12]
C = 12
SOLVERS = ["cg", "pipelined", "sstep"]
PRECONDS = ["none", "eo"]
SCHEDULES = ["concurrent", "overlap"]
CHANNELS = [2]
SSTEP_S = 4
TOL = 1e-5
MAXITER = 200
"""

DRY_HEADER = """
LATTICES = [4]
C = 4
SOLVERS = ["cg", "pipelined", "sstep"]
PRECONDS = ["none", "eo"]
SCHEDULES = ["concurrent"]
CHANNELS = [2]
SSTEP_S = 4
TOL = 1e-5
MAXITER = 100
"""


def run(dry: bool = False) -> str:
    header = DRY_HEADER if dry else SWEEP_HEADER
    return run_on_devices(TIMER_SNIPPET + header + _BODY)


if __name__ == "__main__":
    out = run(dry="--dry" in sys.argv)
    print(out)
    if "CG_BENCH_OK" not in out:
        sys.exit(1)
