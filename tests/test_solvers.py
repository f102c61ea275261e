"""The comm-avoiding solver family: pipelined and s-step CG plus even-odd
preconditioning — reference-mode correctness against dense solves, residual
histories tracking classic CG, NaN-robustness past convergence, the
latency-model collective-count ladder asserted in lowered HLO (classic
``2·iters+1`` → pipelined ``iters`` → s-step ``ceil(iters/s)``), the
pipelined reduction/matvec independence structure, and distributed
cross-transport reproducibility on 2- and 4-proc meshes."""

import math

import numpy as np
import pytest

from conftest import run_distributed

from repro.core.halo import HaloSpec
from repro.stencil import (EvenOddOp, PRECONDS, SOLVERS, StencilOp,
                           leja_chebyshev_shifts, predicted_halo_exchanges,
                           predicted_reduction_collectives, solve)

# see repro/stencil/op.py: bitwise assertions need backend fusion pinned off
NOFUSE = "--xla_disable_hlo_passes=fusion"

SHAPE = (8, 6)
SPECS = tuple(HaloSpec(f"ax{d}", d, 1) for d in range(2))


def _problem(mass=0.2, seed=0, shape=SHAPE, specs=SPECS):
    import jax.numpy as jnp

    op = StencilOp(specs=specs, mass=mass)
    rng = np.random.RandomState(seed)
    b = jnp.asarray(rng.randn(*shape).astype(np.float32))
    return op, b


# ---------------------------------------------------------------------------
# reference-mode correctness: every solver x precond against the dense solve
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("solver", SOLVERS)
@pytest.mark.parametrize("precond", PRECONDS)
def test_solver_family_matches_dense_solve(solver, precond):
    op, b = _problem()
    A = np.asarray(op.dense_matrix(SHAPE)).astype(np.float64)
    xref = np.linalg.solve(A, np.asarray(b).reshape(-1).astype(np.float64))
    res = solve(op, b, None, solver=solver, precond=precond, s=4,
                tol=1e-5, maxiter=200, reference=True)
    assert float(res.rel_residual) < 1e-5
    x = np.asarray(res.x).reshape(-1).astype(np.float64)
    true_rel = (np.linalg.norm(A @ x - np.asarray(b).reshape(-1))
                / np.linalg.norm(np.asarray(b)))
    assert true_rel < 1e-5, (solver, precond, true_rel)
    assert np.abs(x - xref).max() < 1e-4


def test_eo_precond_reduces_iterations_reference():
    """The Schur spectrum is quadratically compressed, so even-odd CG needs
    materially fewer iterations (and with them, reductions)."""
    op, b = _problem(mass=0.2)
    plain = solve(op, b, None, solver="cg", tol=1e-5, maxiter=200,
                  reference=True)
    eo = solve(op, b, None, solver="cg", precond="eo", tol=1e-5,
               maxiter=200, reference=True)
    assert int(plain.iters) >= 1.5 * int(eo.iters), \
        (int(plain.iters), int(eo.iters))


# ---------------------------------------------------------------------------
# residual histories: pipelined per-iteration, s-step per-block boundaries
# ---------------------------------------------------------------------------


def test_pipelined_history_tracks_classic():
    op, b = _problem()
    rc = solve(op, b, None, solver="cg", tol=None, maxiter=14,
               reference=True)
    rp = solve(op, b, None, solver="pipelined", tol=None, maxiter=14,
               reference=True)
    hc, hp = np.asarray(rc.history), np.asarray(rp.history)
    assert hc[0] == hp[0]              # both start at ‖b‖²
    mask = hc[:14] > 1e-6 * hc[0]
    np.testing.assert_allclose(hp[:14][mask], hc[:14][mask], rtol=0.1)


@pytest.mark.parametrize("s", [2, 4])
def test_sstep_history_matches_classic_at_block_boundaries(s):
    """In exact arithmetic each s-step block equals s classic iterations;
    the Newton basis keeps that true to f32 rounding."""
    op, b = _problem()
    rc = solve(op, b, None, solver="cg", tol=None, maxiter=24,
               reference=True)
    rs = solve(op, b, None, solver="sstep", s=s, tol=None, maxiter=24,
               reference=True)
    hc, hs = np.asarray(rc.history), np.asarray(rs.history)
    nblocks = math.ceil(24 / s)
    for i in range(nblocks):
        ref = hc[i * s]
        if ref <= 1e-6 * hc[0]:
            break
        assert abs(hs[i] - ref) <= 0.05 * ref, (s, i, hs[i], ref)


def test_unrolled_past_convergence_is_finite():
    """Fixed-iteration mode far past convergence must stall, not NaN.
    Classic and s-step hold the converged solution; pipelined drifts at the
    f32 floor (the known attainable-accuracy loss of the Ghysels–Vanroose
    recurrence) but stays finite and near the solution."""
    op, b = _problem()
    A = np.asarray(op.dense_matrix(SHAPE)).astype(np.float64)
    xref = np.linalg.solve(A, np.asarray(b).reshape(-1).astype(np.float64))
    tight = {"cg": 1e-4, "sstep": 1e-4, "pipelined": 1e-2}
    for solver in SOLVERS:
        for precond in PRECONDS:
            res = solve(op, b, None, solver=solver, precond=precond,
                        tol=None, maxiter=60, reference=True)
            x = np.asarray(res.x)
            assert np.isfinite(x).all(), (solver, precond)
            err = np.abs(x.reshape(-1) - xref).max()
            assert err < tight[solver], (solver, precond, err)


# ---------------------------------------------------------------------------
# even-odd Schur operator: structure, spectrum, masks
# ---------------------------------------------------------------------------


def test_eo_schur_operator_is_spd_on_even_subspace():
    import jax

    op, _ = _problem(mass=0.4)
    eo = EvenOddOp(op, distributed=False)
    me = np.asarray(eo.parity_mask(SHAPE, even=True)).reshape(-1)
    n = int(np.prod(SHAPE))
    eye = np.eye(n, dtype=np.float32).reshape((n,) + SHAPE)
    S = np.asarray(jax.vmap(eo.apply_reference)(
        np.asarray(eye))).reshape(n, n).T
    Se = S[np.ix_(me > 0, me > 0)]
    np.testing.assert_allclose(Se, Se.T, atol=1e-5)
    assert np.linalg.eigvalsh(Se.astype(np.float64)).min() > 0.0
    lo, hi = eo.eig_bounds()
    ev = np.linalg.eigvalsh(Se.astype(np.float64))
    assert ev.min() >= lo - 1e-5 and ev.max() <= hi + 1e-5


def test_eo_support_and_masks():
    import jax.numpy as jnp

    op, b = _problem()
    eo = EvenOddOp(op, distributed=False)
    me = eo.parity_mask(SHAPE, even=True)
    mo = eo.parity_mask(SHAPE, even=False)
    np.testing.assert_array_equal(np.asarray(me) + np.asarray(mo),
                                  np.ones(SHAPE, np.float32))
    # parity flips between any two neighbouring sites along a stencil dim
    assert np.asarray(me)[0, 0] == 1.0 and np.asarray(me)[0, 1] == 0.0
    # the Schur matvec preserves even support exactly (bitwise zeros)
    rhs = eo.project_rhs_reference(b)
    assert float(jnp.abs(mo * rhs).max()) == 0.0
    out = eo.apply_reference(rhs)
    assert float(jnp.abs(mo * out).max()) == 0.0


def test_eig_bounds_enclose_dense_spectrum():
    op, _ = _problem(mass=0.3)
    A = np.asarray(op.dense_matrix(SHAPE)).astype(np.float64)
    ev = np.linalg.eigvalsh(A)
    lo, hi = op.eig_bounds()
    assert lo - 1e-6 <= ev.min() and ev.max() <= hi + 1e-6
    # halo-2 operator: bounds still enclose (they are not tight there)
    op2 = StencilOp(specs=(HaloSpec("ax0", 0, 2), HaloSpec("ax1", 1, 1)),
                    mass=0.5)
    A2 = np.asarray(op2.dense_matrix((8, 6))).astype(np.float64)
    ev2 = np.linalg.eigvalsh(A2)
    lo2, hi2 = op2.eig_bounds()
    assert lo2 - 1e-6 <= ev2.min() and ev2.max() <= hi2 + 1e-6


def test_leja_chebyshev_shifts_properties():
    lo, hi = 0.2, 1.2
    for s in (1, 2, 4, 7):
        pts = leja_chebyshev_shifts(lo, hi, s)
        assert len(pts) == s
        assert all(lo < p < hi for p in pts)
        assert len(set(pts)) == s
    # Leja ordering starts from the extreme-magnitude point
    pts = leja_chebyshev_shifts(lo, hi, 4)
    assert pts[0] == max(pts, key=abs)
    with pytest.raises(ValueError, match="s must be"):
        leja_chebyshev_shifts(lo, hi, 0)
    with pytest.raises(ValueError, match="hi > lo"):
        leja_chebyshev_shifts(1.0, 1.0, 2)


# ---------------------------------------------------------------------------
# validation and prediction helpers
# ---------------------------------------------------------------------------


def test_solver_validation_errors():
    import jax.numpy as jnp

    op, b = _problem()
    with pytest.raises(ValueError, match="unknown solver"):
        solve(op, b, None, solver="bogus", reference=True)
    with pytest.raises(ValueError, match="unknown precond"):
        solve(op, b, None, precond="bogus", reference=True)
    with pytest.raises(ValueError, match="does not support x0"):
        solve(op, b, None, solver="sstep", x0=jnp.zeros_like(b),
              reference=True)
    with pytest.raises(ValueError, match="does not support x0"):
        solve(op, b, None, precond="eo", x0=jnp.zeros_like(b),
              reference=True)
    # halo-2 coupling connects equal parities: even-odd must refuse
    op2 = StencilOp(specs=(HaloSpec("ax0", 0, 2),), mass=0.5)
    with pytest.raises(ValueError, match="halo == 1"):
        solve(op2, jnp.zeros((8, 3)), None, precond="eo", reference=True)
    # an odd periodic extent breaks the 2-colouring
    op3 = StencilOp(specs=(HaloSpec("ax0", 0, 1),), mass=0.5)
    with pytest.raises(ValueError, match="even global extent"):
        solve(op3, jnp.zeros((7, 3)), None, precond="eo", reference=True)


def test_predicted_collective_counts():
    assert predicted_reduction_collectives("cg", 10) == 21
    assert predicted_reduction_collectives("pipelined", 10) == 10
    assert predicted_reduction_collectives("sstep", 10, s=4) == 3
    assert predicted_reduction_collectives("sstep", 8, s=4) == 2
    assert predicted_halo_exchanges("cg", "none", 10) == 10
    # one residual replacement at k=6 nets three extra matvecs (see helper)
    assert predicted_halo_exchanges("pipelined", "none", 10) == 13
    assert predicted_halo_exchanges("pipelined", "none", 10,
                                    replace_every=0) == 10
    assert predicted_halo_exchanges("pipelined", "none", 6,
                                    replace_every=6) == 6
    assert predicted_halo_exchanges("sstep", "none", 10, s=4) == 12
    assert predicted_halo_exchanges("cg", "eo", 10) == 22
    with pytest.raises(ValueError, match="unknown solver"):
        predicted_reduction_collectives("bogus", 4)
    with pytest.raises(ValueError, match="unknown precond"):
        predicted_halo_exchanges("cg", "bogus", 4)


# ---------------------------------------------------------------------------
# HLO: the collective-count ladder (acceptance: s-step at s=4 lowers to
# <= ceil(iters/4) inner-product reduction collectives) and exact permute
# byte/count predictions for every solver x precond
# ---------------------------------------------------------------------------

COUNTS_SCRIPT = r"""
import math
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.comm import CommConfig, Communicator
from repro.core.halo import HaloSpec
from repro.launch.roofline import collective_wire_bytes
from repro.stencil import (StencilOp, predicted_halo_exchanges,
                           predicted_reduction_collectives, solve)

mesh = jax.make_mesh((2, 2, 2), ("x", "y", "z"),
                     axis_types=(AxisType.Auto,) * 3)
SPECS = (HaloSpec("x", 0), HaloSpec("y", 1), HaloSpec("z", 2))
op = StencilOp(specs=SPECS, mass=0.8)
comm = Communicator(mesh, CommConfig(transport="psum",
                                     data_axes=("x", "y", "z"), channels=2))
local = (6, 6, 6, 4)
gshape = (12, 12, 12, 4)
hplan = comm.halo_plan(local, SPECS, schedule="concurrent")
ITERS, S = 8, 4

for solver in ("cg", "pipelined", "sstep"):
    for precond in ("none", "eo"):
        def run(b, sv=solver, pc=precond):
            r = solve(op, b, comm, solver=sv, precond=pc, s=S, tol=None,
                      maxiter=ITERS, schedule="concurrent",
                      chunks=comm.halo_chunks, channels=2)
            return r.x, r.rel_residual
        fn = jax.jit(jax.shard_map(run, mesh=mesh,
                                   in_specs=P("x", "y", "z", None),
                                   out_specs=(P("x", "y", "z", None), P()),
                                   check_vma=False))
        txt = fn.lower(jax.ShapeDtypeStruct(gshape, jnp.float32)) \
                .compile().as_text()
        stats = collective_wire_bytes(txt)
        ar = stats.op_counts.get("all-reduce", 0)
        cp = stats.op_counts.get("collective-permute", 0)
        pred_red = predicted_reduction_collectives(solver, ITERS, s=S)
        pred_ex = predicted_halo_exchanges(solver, precond, ITERS, s=S)
        assert ar == pred_red, (solver, precond, ar, pred_red)
        assert cp == pred_ex * hplan.n_units, (solver, precond, cp)
        pb = pred_ex * hplan.bytes_per_device
        mb = stats.op_bytes.get("collective-permute", 0.0)
        assert abs(mb - pb) / pb < 0.01, (solver, precond, mb, pb)
        print(solver, precond, "ar", ar, "cp", cp)
        if solver == "sstep":
            # the acceptance bound, verbatim
            assert ar <= math.ceil(ITERS / S), (ar, ITERS, S)

# the ladder itself: each variant strictly cheaper in reductions
assert predicted_reduction_collectives("sstep", ITERS, s=S) \
    < predicted_reduction_collectives("pipelined", ITERS) \
    < predicted_reduction_collectives("cg", ITERS)
print("SOLVER_COUNTS_OK")
"""


def test_solver_reduction_count_ladder_in_hlo():
    out = run_distributed(COUNTS_SCRIPT, n_devices=8)
    assert "SOLVER_COUNTS_OK" in out


# ---------------------------------------------------------------------------
# HLO: pipelined CG's reduction is mutually independent of the same
# iteration's matvec; classic CG's collectives form a chain (modulo the
# initial ‖b‖² batch, which only depends on b)
# ---------------------------------------------------------------------------

OVERLAP_SCRIPT = r"""
import re
import sys
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.comm import CommConfig, Communicator
from repro.core.halo import HaloSpec
from repro.stencil import StencilOp, solve

mesh = jax.make_mesh((2, 2), ("x", "y"), axis_types=(AxisType.Auto,) * 2)
SPECS = (HaloSpec("x", 0), HaloSpec("y", 1))
op = StencilOp(specs=SPECS, mass=0.5)
comm = Communicator(mesh, CommConfig(transport="psum", data_axes=("x", "y"),
                                     channels=0))
gshape = (12, 12, 3)
ITERS = 4
PERMUTES_PER_EXCHANGE = 4        # 2 dims x 2 directions

def compiled_text(solver):
    def run(b):
        r = solve(op, b, comm, solver=solver, tol=None, maxiter=ITERS,
                  schedule="concurrent", chunks=2, channels=0)
        return r.x, r.rel_residual
    fn = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=P("x", "y", None),
                               out_specs=(P("x", "y", None), P()),
                               check_vma=False))
    return fn.lower(jax.ShapeDtypeStruct(gshape, jnp.float32)) \
             .compile().as_text()

VAR = re.compile(r"%[\w.\-]+")
OP = re.compile(r"=\s*(?:\([^)]*\)|\S+)\s+(all-reduce|collective-permute)"
                r"(-start|-done)?\(")

def collective_order(text):
    '''(n_ar, n_cp, mutually-unordered (ar, cp) pairs) in the ENTRY graph.'''
    lines = text.splitlines()
    start = next(i for i, l in enumerate(lines) if l.startswith("ENTRY"))
    defs, ar, cp = {}, [], []
    for line in lines[start:]:
        s = line.strip()
        if not s.startswith("%") or "=" not in s:
            continue
        vs = VAR.findall(s)
        defs[vs[0]] = set(vs[1:])
        m = OP.search(s)
        if m and m.group(2) != "-done":
            (ar if m.group(1) == "all-reduce" else cp).append(vs[0])
    sys.setrecursionlimit(100000)
    reach = {}
    def reachable(v):
        if v in reach:
            return reach[v]
        out = set(); reach[v] = out
        for u in defs.get(v, ()):
            out.add(u); out |= reachable(u)
        return out
    r = {v: reachable(v) for v in ar + cp}
    unordered = [(a, c) for a in ar for c in cp
                 if a not in r[c] and c not in r[a]]
    return len(ar), len(cp), len(unordered)

na, nc, un = collective_order(compiled_text("cg"))
assert na == 2 * ITERS + 1, na
assert nc == ITERS * PERMUTES_PER_EXCHANGE, nc
# classic: a chain — only the initial (rs, bs) batch floats free of the
# first matvec (both consume just b)
assert un == PERMUTES_PER_EXCHANGE, un

na, nc, un = collective_order(compiled_text("pipelined"))
assert na == ITERS, na
# iteration i's reduction is independent of iteration i's matvec: the last
# iteration's matvec is dead in unrolled HLO, so (ITERS-1) iterations
# contribute a full exchange of mutually-unordered permutes each
assert un == (ITERS - 1) * PERMUTES_PER_EXCHANGE, un

na, nc, un = collective_order(compiled_text("sstep"))
assert na == 1 and un == 0, (na, un)   # one reduction, after all matvecs
print("SOLVER_OVERLAP_OK")
"""


def test_pipelined_reduction_independent_of_matvec_in_hlo():
    out = run_distributed(OVERLAP_SCRIPT, n_devices=4)
    assert "SOLVER_OVERLAP_OK" in out


# ---------------------------------------------------------------------------
# distributed: residual histories match classic CG within tolerance; bitwise
# identical across transports on 2 procs (pairwise sums commute), tolerance
# across transports on 4 procs (association differs); fusion pinned off
# ---------------------------------------------------------------------------

HISTORY_SCRIPT = r"""
import math
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.comm import CommConfig, Communicator
from repro.core.halo import HaloSpec
from repro.stencil import StencilOp, solve

MAXITER, S = 16, 4

for mesh_shape, names in [((2,), ("x",)), ((2, 2), ("x", "y"))]:
    nproc = 1
    for p in mesh_shape:
        nproc *= p
    mesh = jax.make_mesh(mesh_shape, names,
                         devices=jax.devices()[:nproc],
                         axis_types=(AxisType.Auto,) * len(mesh_shape))
    specs = tuple(HaloSpec(a, d, 1) for d, a in enumerate(names))
    op = StencilOp(specs=specs, mass=0.3)
    gshape = tuple(6 * p for p in mesh_shape) + (3,)
    rng = np.random.RandomState(5)
    b = jnp.asarray(rng.randn(*gshape).astype(np.float32))
    pspec = P(*names, None)
    results = {}
    for transport in ("psum", "ring_hier"):
        comm = Communicator(mesh, CommConfig(transport=transport,
                                             data_axes=names, channels=2))
        for solver in ("cg", "pipelined", "sstep"):
            def run(bl, sv=solver, c=comm):
                r = solve(op, bl, c, solver=sv, s=S, tol=None,
                          maxiter=MAXITER, schedule="concurrent", chunks=2,
                          channels=2)
                return r.x, r.history
            fn = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=pspec,
                                       out_specs=(pspec, P()),
                                       check_vma=False))
            x, h = fn(b)
            results[(transport, solver)] = (np.asarray(x), np.asarray(h))

    # 1) histories track classic within tolerance (per transport)
    for transport in ("psum", "ring_hier"):
        hc = results[(transport, "cg")][1]
        hp = results[(transport, "pipelined")][1]
        mask = hc[:MAXITER] > 1e-6 * hc[0]
        assert np.allclose(hp[:MAXITER][mask], hc[:MAXITER][mask],
                           rtol=0.1), (mesh_shape, transport, "pipelined")
        hs = results[(transport, "sstep")][1]
        for i in range(math.ceil(MAXITER / S)):
            ref = hc[i * S]
            if ref <= 1e-6 * hc[0]:
                break
            assert abs(hs[i] - ref) <= 0.05 * ref, \
                (mesh_shape, transport, "sstep", i)

    # 2) cross-transport: bitwise on 2 procs, tolerance on 4
    for solver in ("cg", "pipelined", "sstep"):
        xp, hp = results[("psum", solver)]
        xr, hr = results[("ring_hier", solver)]
        if nproc == 2:
            assert np.array_equal(xp, xr), (mesh_shape, solver, "x")
            assert np.array_equal(hp, hr), (mesh_shape, solver, "hist")
        else:
            assert np.allclose(xp, xr, rtol=1e-3, atol=1e-5), \
                (mesh_shape, solver)
            mask = hp > 1e-6 * hp[0]
            assert np.allclose(hp[mask], hr[mask], rtol=0.1), \
                (mesh_shape, solver)
    print(mesh_shape, "ok")

# 3) halo schedules move exact ppermute data: bitwise-identical iterates
#    for the new solvers too (fusion off, psum, 4-proc mesh)
mesh = jax.make_mesh((2, 2), ("x", "y"), axis_types=(AxisType.Auto,) * 2)
specs = (HaloSpec("x", 0, 1), HaloSpec("y", 1, 1))
op = StencilOp(specs=specs, mass=0.3)
rng = np.random.RandomState(7)
b = jnp.asarray(rng.randn(12, 12, 3).astype(np.float32))
comm = Communicator(mesh, CommConfig(transport="psum", data_axes=("x", "y"),
                                     channels=2))
for solver in ("pipelined", "sstep"):
    sols = {}
    for sched in ("sequential", "concurrent", "overlap"):
        def run(bl, sv=solver, sc=sched):
            r = solve(op, bl, comm, solver=sv, s=S, tol=None,
                      maxiter=MAXITER, schedule=sc, chunks=2, channels=2)
            return r.x
        fn = jax.jit(jax.shard_map(run, mesh=mesh,
                                   in_specs=P("x", "y", None),
                                   out_specs=P("x", "y", None),
                                   check_vma=False))
        sols[sched] = np.asarray(fn(b))
    for sched in ("concurrent", "overlap"):
        assert np.array_equal(sols["sequential"], sols[sched]), \
            (solver, sched)
print("SOLVER_HISTORY_OK")
"""


def test_solver_histories_distributed_and_cross_transport():
    out = run_distributed(HISTORY_SCRIPT, n_devices=4, extra_flags=NOFUSE)
    assert "SOLVER_HISTORY_OK" in out


# ---------------------------------------------------------------------------
# slow: even-odd preconditioning on the reference distributed problem —
# >= 1.5x fewer CG iterations, every solver x precond converging below 1e-5
# with the solution verified against the global operator
# ---------------------------------------------------------------------------

EO_SCRIPT = r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.comm import CommConfig, Communicator
from repro.core.halo import HaloSpec
from repro.stencil import StencilOp, solve

mesh = jax.make_mesh((2, 2, 2), ("x", "y", "z"),
                     axis_types=(AxisType.Auto,) * 3)
SPECS = (HaloSpec("x", 0), HaloSpec("y", 1), HaloSpec("z", 2))
op = StencilOp(specs=SPECS, mass=0.2)
rng = np.random.RandomState(3)
b = jnp.asarray(rng.randn(12, 12, 12, 3).astype(np.float32))
comm = Communicator(mesh, CommConfig(transport="psum",
                                     data_axes=("x", "y", "z"), channels=2))

def run_solver(solver, precond):
    def run(bl):
        r = solve(op, bl, comm, solver=solver, precond=precond, s=4,
                  tol=1e-5, maxiter=300, schedule="overlap", chunks=2,
                  channels=2)
        return r.x, r.iters, r.rel_residual
    fn = jax.jit(jax.shard_map(
        run, mesh=mesh, in_specs=P("x", "y", "z", None),
        out_specs=(P("x", "y", "z", None), P(), P()), check_vma=False))
    x, iters, rel = fn(b)
    assert float(rel) < 1e-5, (solver, precond, float(rel))
    # verify against the global operator, not just the recurrence residual
    ax = np.asarray(op.apply_reference(jnp.asarray(np.asarray(x))))
    true_rel = np.linalg.norm(ax - np.asarray(b)) / np.linalg.norm(np.asarray(b))
    assert true_rel < 1e-4, (solver, precond, true_rel)
    return int(iters)

iters = {}
for solver in ("cg", "pipelined", "sstep"):
    for precond in ("none", "eo"):
        iters[(solver, precond)] = run_solver(solver, precond)
        print(solver, precond, "iters", iters[(solver, precond)])

# the acceptance bar: even-odd cuts classic CG's iterations >= 1.5x
assert iters[("cg", "none")] >= 1.5 * iters[("cg", "eo")], iters
assert iters[("pipelined", "none")] >= 1.5 * iters[("pipelined", "eo")], iters
print("SOLVER_EO_OK")
"""


@pytest.mark.slow
def test_eo_reduces_iterations_distributed():
    out = run_distributed(EO_SCRIPT, n_devices=8, extra_flags=NOFUSE)
    assert "SOLVER_EO_OK" in out
