"""Stencil subsystem: halo-schedule construction invariants, the uneven
chunk split, operator/CG correctness against references, bitwise
cross-schedule equivalence on 1-D/2-D/3-D meshes, HLO-level schedule
structure (overlap independence vs sequential chaining), and predicted vs
lowered halo wire bytes for indivisible shapes."""

import numpy as np
import pytest

from conftest import run_distributed

from repro.comm import (CommConfig, Communicator, HALO_SCHEDULES,
                        build_halo_schedule, halo_interior_fraction)
from repro.core.halo import (HaloSpec, _split_chunks, chunk_sizes,
                             halo_bytes)

# backend fusion heuristics may contract FMAs differently per module; the
# bitwise cross-schedule assertions pin the fusion pass off (see
# repro/stencil/op.py docstring), tolerance assertions run under defaults
NOFUSE = "--xla_disable_hlo_passes=fusion"


# ---------------------------------------------------------------------------
# build_halo_schedule invariants (plain-pytest mirror of the hypothesis
# versions in test_properties.py, so they run without the dev extra)
# ---------------------------------------------------------------------------

SHAPE = (6, 7, 5, 3)


@pytest.mark.parametrize("schedule", HALO_SCHEDULES)
@pytest.mark.parametrize("channels", [0, 1, 2, 4])
@pytest.mark.parametrize("halo", [1, 2])
def test_halo_schedule_invariants(schedule, channels, halo):
    specs = [HaloSpec("x", 0, halo), HaloSpec("y", 1, halo),
             HaloSpec("z", 2, halo)]
    s = build_halo_schedule(specs, SHAPE, schedule=schedule,
                            channels=channels, chunks=3)
    # every unit issued exactly once, all in the single phase
    seen = sorted(b for slot in s.slots for b in slot.bucket_ids)
    assert seen == list(range(s.n_buckets))
    assert all(slot.phase == 0 for slot in s.slots)
    # channel assignments within range per schedule semantics
    if schedule == "sequential":
        assert {slot.channel for slot in s.slots} == {0}
    elif schedule == "overlap" and channels >= 1:
        assert all(0 <= slot.channel < channels for slot in s.slots)
    else:
        assert all(0 <= slot.channel < s.n_buckets for slot in s.slots)
    assert 0.0 <= s.overlap_fraction <= 1.0
    # payload bytes conserved: chunk splitting never changes the total
    assert sum(s.bucket_sizes) == halo_bytes(SHAPE, specs, 4)
    if schedule == "overlap":
        assert s.overlap_fraction == pytest.approx(
            halo_interior_fraction(SHAPE, specs))
        assert s.overlap_fraction > 0.0
    else:
        assert s.overlap_fraction == 0.0


def test_chunked_schedule_counts_uneven_pieces():
    specs = [HaloSpec("x", 0)]
    s = build_halo_schedule(specs, (6, 7, 3), schedule="chunked", chunks=3)
    # face (1, 7, 3) splits along the 7-dim into 3+2+2 rows
    assert s.n_buckets == 6
    assert sorted(s.bucket_sizes, reverse=True) == [3 * 3 * 4] * 2 + \
        [2 * 3 * 4] * 4


def test_unknown_halo_schedule_raises():
    import jax.numpy as jnp

    from repro.core.halo import halo_exchange

    with pytest.raises(ValueError, match="unknown halo schedule"):
        build_halo_schedule([HaloSpec("x", 0)], (4, 4), schedule="bogus")
    with pytest.raises(ValueError, match="schedule must be one of"):
        halo_exchange(jnp.zeros((4, 4)), [HaloSpec("x", 0)],
                      schedule="bogus")


# ---------------------------------------------------------------------------
# uneven chunk split (regression: used to silently degrade to 1 chunk)
# ---------------------------------------------------------------------------


def test_chunk_sizes_cover_and_balance():
    for n, k in [(7, 3), (5, 2), (1, 4), (12, 5), (6, 2), (8, 8)]:
        cs = chunk_sizes(n, k)
        assert sum(cs) == n
        assert len(cs) == min(k, n)
        assert max(cs) - min(cs) <= 1


def test_split_chunks_uneven_roundtrip():
    import jax.numpy as jnp

    face = jnp.arange(1 * 7 * 5, dtype=jnp.float32).reshape(1, 7, 5)
    parts = _split_chunks(face, 3, 0)
    assert len(parts) == 3          # regression: was 1 (silent degrade)
    assert [p.shape[1] for p in parts] == [3, 2, 2]
    back = jnp.concatenate(parts, axis=1)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(face))


# ---------------------------------------------------------------------------
# operator + CG against references (single process)
# ---------------------------------------------------------------------------


def test_operator_matches_periodic_reference_all_schedules():
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, PartitionSpec as P
    from repro.stencil import StencilOp

    op = StencilOp(specs=(HaloSpec("x", 0), HaloSpec("y", 1)), mass=0.7)
    x = jnp.asarray(np.random.RandomState(0).randn(6, 5).astype(np.float32))
    ref = np.asarray(op.apply_reference(x))
    mesh = jax.make_mesh((1, 1), ("x", "y"), axis_types=(AxisType.Auto,) * 2)
    outs = {}
    for sched in HALO_SCHEDULES:
        fn = jax.jit(jax.shard_map(
            lambda v, s=sched: op.apply(v, schedule=s, channels=2),
            mesh=mesh, in_specs=P("x", "y"), out_specs=P("x", "y"),
            check_vma=False))
        outs[sched] = np.asarray(fn(x))
        assert np.abs(outs[sched] - ref).max() < 1e-5, sched
    for sched in HALO_SCHEDULES[1:]:
        np.testing.assert_array_equal(outs["sequential"], outs[sched])


def test_operator_spd_and_cg_matches_dense_solve():
    import jax.numpy as jnp

    from repro.stencil import StencilOp, cg_solve

    op = StencilOp(specs=(HaloSpec("x", 0), HaloSpec("y", 1, 2)), mass=0.4)
    A = np.asarray(op.dense_matrix((6, 5)))
    np.testing.assert_allclose(A, A.T, atol=1e-6)
    assert np.linalg.eigvalsh(A).min() > 0.0
    b = jnp.asarray(np.random.RandomState(1).randn(6, 5).astype(np.float32))
    res = cg_solve(op, b, None, tol=1e-7, maxiter=300,
                   matvec=op.apply_reference)
    xref = np.linalg.solve(A, np.asarray(b).reshape(-1)).reshape(6, 5)
    assert float(res.rel_residual) < 1e-6
    assert np.abs(np.asarray(res.x) - xref).max() < 1e-4


def test_cg_fixed_iteration_mode_is_nan_free_past_convergence():
    import jax.numpy as jnp

    from repro.stencil import StencilOp, cg_solve

    op = StencilOp(specs=(HaloSpec("x", 0),), mass=1.0)
    b = jnp.asarray(np.random.RandomState(2).randn(8, 3).astype(np.float32))
    res = cg_solve(op, b, None, tol=None, maxiter=50,
                   matvec=op.apply_reference)
    assert np.isfinite(np.asarray(res.x)).all()
    assert float(res.rel_residual) < 1e-6


def test_halo_plan_bytes_and_describe():
    import jax
    from jax.sharding import AxisType

    mesh = jax.make_mesh((1,), ("x",), axis_types=(AxisType.Auto,) * 1)
    comm = Communicator(mesh, CommConfig(data_axes=("x",), channels=2))
    specs = [HaloSpec("x", 0, 2)]
    plan = comm.halo_plan((6, 5), specs, schedule="concurrent")
    assert plan.bytes_per_device == halo_bytes((6, 5), specs, 4)
    assert plan.n_units == 2 and plan.unit_keys == ("x-", "x+")
    d = plan.describe()
    assert d["schedule"] == "concurrent"
    assert d["bytes_per_device"] == plan.bytes_per_device
    assert d["overlap_fraction"] == 0.0
    # overlap records the interior fraction the roofline can hide under
    ov = comm.halo_plan((6, 5), specs, schedule="overlap")
    assert ov.overlap_fraction == pytest.approx(
        halo_interior_fraction((6, 5), specs))


# ---------------------------------------------------------------------------
# distributed: all four schedules on 1-D / 2-D / 3-D meshes, halo 1-2,
# bitwise-identical operator output (fusion pass pinned off)
# ---------------------------------------------------------------------------

MESH_SCRIPT = r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.comm import HALO_SCHEDULES
from repro.core.halo import HaloSpec
from repro.stencil import StencilOp

rng = np.random.RandomState(3)
CASES = [((8,), ("x",)), ((4, 2), ("x", "y")), ((2, 2, 2), ("x", "y", "z"))]
for mesh_shape, names in CASES:
    mesh = jax.make_mesh(mesh_shape, names,
                         axis_types=(AxisType.Auto,) * len(mesh_shape))
    nd = len(names)
    for halo in (1, 2):
        specs = tuple(HaloSpec(a, d, halo) for d, a in enumerate(names))
        op = StencilOp(specs=specs, mass=0.8)
        gshape = tuple(6 * p for p in mesh_shape) + (3,)
        xg = jnp.asarray(rng.randn(*gshape).astype(np.float32))
        ref = np.asarray(op.apply_reference(xg))
        pspec = P(*names, None)
        outs = {}
        for sched in HALO_SCHEDULES:
            fn = jax.jit(jax.shard_map(
                lambda v, s=sched: op.apply(v, schedule=s, chunks=2,
                                            channels=2),
                mesh=mesh, in_specs=pspec, out_specs=pspec,
                check_vma=False))
            outs[sched] = np.asarray(fn(xg))
            err = np.abs(outs[sched] - ref).max()
            assert err < 1e-5, (mesh_shape, halo, sched, err)
        for sched in HALO_SCHEDULES[1:]:
            assert np.array_equal(outs["sequential"], outs[sched]), \
                (mesh_shape, halo, sched)
        print(mesh_shape, "halo", halo, "ok")
print("STENCIL_MESHES_OK")
"""


def test_operator_bitwise_identical_across_schedules_and_meshes():
    out = run_distributed(MESH_SCRIPT, n_devices=8, extra_flags=NOFUSE)
    assert "STENCIL_MESHES_OK" in out


# ---------------------------------------------------------------------------
# HLO-level schedule structure: the overlap schedule lowers to >= 2*n_dims
# mutually independent collective-permutes; sequential to a data-dependent
# chain (each transfer transitively consumes the previous one's result)
# ---------------------------------------------------------------------------

HLO_SCRIPT = r"""
import re
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.core.halo import HaloSpec, halo_exchange

mesh = jax.make_mesh((2, 2), ("x", "y"), axis_types=(AxisType.Auto,) * 2)
SPECS = (HaloSpec("x", 0), HaloSpec("y", 1))
N_DIMS = 2

def lowered(sched, channels=0):
    def hx(xl):
        h = halo_exchange(xl, SPECS, schedule=sched, chunks=2,
                          channels=channels)
        return sum(v.sum() for v in h.values())
    g = jax.jit(jax.shard_map(hx, mesh=mesh, in_specs=P("x", "y"),
                              out_specs=P(), check_vma=False))
    return g.lower(jnp.zeros((8, 8), jnp.float32)).as_text()

VAR = re.compile(r"%[\w.#]+")

def cp_dependencies(text):
    '''[(cp_def_var, transitively_reachable_earlier_cp_defs)], in order.'''
    defs = {}          # var -> set of operand vars
    cp_vars = []
    for line in text.splitlines():
        if "=" not in line:
            continue
        vs = VAR.findall(line)
        if not vs or not line.lstrip().startswith("%"):
            continue
        head, deps = vs[0], set(vs[1:])
        defs[head] = deps
        if "collective_permute" in line:
            cp_vars.append(head)
    out = []
    for v in cp_vars:
        seen, stack, hits = set(), list(defs.get(v, ())), set()
        while stack:
            u = stack.pop()
            if u in seen:
                continue
            seen.add(u)
            if u in cp_vars and u != v:
                hits.add(u)
            stack.extend(defs.get(u, ()))
        out.append((v, hits))
    return out

seq = cp_dependencies(lowered("sequential"))
assert len(seq) >= 2 * N_DIMS, len(seq)
# a chain: every transfer after the first consumes an earlier one's result
dependent = [v for v, hits in seq if hits]
assert len(dependent) == len(seq) - 1, (len(dependent), len(seq))

ov = cp_dependencies(lowered("overlap", channels=0))
assert len(ov) >= 2 * N_DIMS, len(ov)
# fully independent: no transfer consumes any other transfer's result
assert all(not hits for _, hits in ov), ov

# channels=2 stripes the faces over exactly 2 rails: 2 independent roots,
# everything else chained behind its rail head
ov2 = cp_dependencies(lowered("overlap", channels=2))
roots = [v for v, hits in ov2 if not hits]
assert len(roots) == 2, (len(roots), len(ov2))
print("STENCIL_HLO_OK")
"""


def test_overlap_lowers_independent_permutes_sequential_chains():
    out = run_distributed(HLO_SCRIPT, n_devices=4)
    assert "STENCIL_HLO_OK" in out


# ---------------------------------------------------------------------------
# predicted vs lowered halo wire bytes for odd (chunk-indivisible) shapes
# (regression for the silent 1-chunk degrade)
# ---------------------------------------------------------------------------

BYTES_SCRIPT = r"""
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.comm import CommConfig, Communicator
from repro.core.halo import HaloSpec, halo_exchange
from repro.launch.roofline import collective_wire_bytes

mesh = jax.make_mesh((4, 2), ("x", "y"), axis_types=(AxisType.Auto,) * 2)
SPECS = (HaloSpec("x", 0), HaloSpec("y", 1))
comm = Communicator(mesh, CommConfig(data_axes=("x", "y"), channels=3))
local = (5, 7, 3)                  # odd everywhere: every face splits unevenly
gshape = (4 * 5, 2 * 7, 3)

for sched in ("chunked", "concurrent", "overlap", "sequential"):
    def hx(xl, s=sched):
        h = comm.halo_exchange(xl, SPECS, schedule=s)
        return sum(v.sum() for v in h.values())
    g = jax.jit(jax.shard_map(hx, mesh=mesh, in_specs=P("x", "y", None),
                              out_specs=P(), check_vma=False))
    txt = g.lower(jnp.zeros(gshape, jnp.float32)).compile().as_text()
    stats = collective_wire_bytes(txt)
    plan = comm.halo_plan(local, SPECS, schedule=sched)
    measured = stats.op_bytes.get("collective-permute", 0.0)
    assert plan.bytes_per_device > 0
    rel = abs(measured - plan.bytes_per_device) / plan.bytes_per_device
    assert rel < 0.01, (sched, measured, plan.bytes_per_device)
    n_cp = stats.op_counts.get("collective-permute", 0)
    assert n_cp == plan.n_units, (sched, n_cp, plan.n_units)
    print(sched, "bytes", measured, "units", n_cp)
print("STENCIL_BYTES_OK")
"""


def test_predicted_halo_bytes_match_lowered_hlo_odd_shapes():
    out = run_distributed(BYTES_SCRIPT, n_devices=8)
    assert "STENCIL_BYTES_OK" in out


# ---------------------------------------------------------------------------
# CG end-to-end: converges under every schedule with identical iterates
# (2x2x2 mesh; inner products on the channelized ring and psum transports)
# ---------------------------------------------------------------------------

CG_SCRIPT = r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.comm import CommConfig, Communicator, HALO_SCHEDULES
from repro.core.halo import HaloSpec
from repro.stencil import StencilOp, cg_solve

mesh = jax.make_mesh((2, 2, 2), ("x", "y", "z"),
                     axis_types=(AxisType.Auto,) * 3)
SPECS = (HaloSpec("x", 0), HaloSpec("y", 1), HaloSpec("z", 2))
op = StencilOp(specs=SPECS, mass=0.5)
rng = np.random.RandomState(3)
b = jnp.asarray(rng.randn(12, 12, 12, 3).astype(np.float32))

for transport in ("psum", "ring_hier"):
    comm = Communicator(mesh, CommConfig(transport=transport,
                                         data_axes=("x", "y", "z"),
                                         channels=2))
    sols = {}
    for sched in HALO_SCHEDULES:
        def run(bl, s=sched):
            r = cg_solve(op, bl, comm, tol=1e-6, maxiter=200, schedule=s,
                         chunks=2, channels=2)
            return r.x, r.iters, r.rel_residual
        fn = jax.jit(jax.shard_map(
            run, mesh=mesh, in_specs=P("x", "y", "z", None),
            out_specs=(P("x", "y", "z", None), P(), P()), check_vma=False))
        x, iters, rel = fn(b)
        assert float(rel) < 1e-5, (transport, sched, float(rel))
        sols[sched] = np.asarray(x)
        print(transport, sched, "iters", int(iters), "rel", float(rel))
    for sched in HALO_SCHEDULES[1:]:
        assert np.array_equal(sols["sequential"], sols[sched]), \
            (transport, sched)
    # solution actually solves the global system
    ax = np.asarray(op.apply_reference(jnp.asarray(sols["overlap"])))
    rel = np.linalg.norm(ax - np.asarray(b)) / np.linalg.norm(np.asarray(b))
    assert rel < 1e-4, rel
print("STENCIL_CG_OK")
"""


@pytest.mark.slow
def test_cg_converges_identically_under_all_schedules():
    out = run_distributed(CG_SCRIPT, n_devices=8, extra_flags=NOFUSE)
    assert "STENCIL_CG_OK" in out
