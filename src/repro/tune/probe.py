"""The probe runner: drive the benches as a calibration matrix.

Measured mode (the default) re-uses ``benchmarks/common.py``'s subprocess
harness to run cut-down versions of the existing benches — ``allreduce``
(bucketized gradient reduction on the 2×4 pod/data mesh), ``arena`` (the
fused CommArena path, where the page size actually moves bytes), ``halo``
(the 2×2×2 Cartesian exchange) and ``cg`` (a full solve: reductions +
exchanges) — over the requested transport × channels × page_bytes ×
message-size grid.  Every timed cell prints one ``CELL {json}`` line
carrying the *predicted* message count and wire bytes (straight from
``comm.plan`` / ``comm.halo_plan``, the same numbers the dry-run prices
with) next to the *measured* seconds and dispersion; the fitter then
recovers measured α/bandwidth per (transport, channels, page_bytes) group
and the residuals say how far the model sits from the machine.

``--dry`` mode needs no devices at all: cells are synthesized in pure
Python from the transports' own ``predicted_messages/bytes_per_device``
and a planted :class:`~repro.comm.plan.LatencyModel`, so CI can assert the
whole probe → fit → DB → ``dryrun --tuned`` loop recovers the planted
constants to <1%.

CLI::

    python -m repro.tune.probe --out experiments/tuning.json \
        --benches allreduce arena --transports ring_hier psum \
        --channels 1 2 4 --page-bytes 4096 2097152
    python -m repro.tune.probe --dry --out /tmp/tuning.json   # CI smoke
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
from dataclasses import asdict, dataclass
from typing import Iterable, Mapping, Sequence

from repro.tune.db import GENERIC_ARCH, TuningDB
from repro.tune.fit import FitResult, fit_cells

BENCHES = ("allreduce", "arena", "halo", "cg")


@dataclass(frozen=True)
class ProbeCell:
    """One timed (or synthesized) probe point.

    ``messages``/``nbytes`` are the *model's* per-device predictions for
    this cell (plan-level, the dry-run's own numbers); ``seconds`` is the
    measured median with ``t_min``/``t_max`` the min/max over the timed
    iterations — the dispersion the fitter weights by.
    """

    bench: str
    arch: str
    mesh: str                   # mesh label, e.g. "2x4" or "2x2x2"
    transport: str
    channels: int
    page_bytes: int
    elems: int                  # payload elements (fp32 words)
    messages: float             # predicted discrete sends / device
    nbytes: float               # predicted wire bytes / device
    seconds: float              # measured median seconds per call
    t_min: float
    t_max: float

    @property
    def spread(self) -> float:
        return float(self.t_max) - float(self.t_min)

    def as_dict(self) -> dict:
        return asdict(self)

    @classmethod
    def from_dict(cls, d: Mapping) -> "ProbeCell":
        return cls(**{f: d[f] for f in cls.__dataclass_fields__})


def group_cells(cells: Iterable[ProbeCell]
                ) -> dict[tuple[str, int, int], list[ProbeCell]]:
    """Fit groups: one (transport, channels, page_bytes) per DB record."""
    groups: dict[tuple[str, int, int], list[ProbeCell]] = {}
    for c in cells:
        groups.setdefault((c.transport, c.channels, c.page_bytes),
                          []).append(c)
    return groups


def parse_cells(output: str) -> list[ProbeCell]:
    """Collect the ``CELL {json}`` lines a probe subprocess printed."""
    cells = []
    for line in output.splitlines():
        if line.startswith("CELL "):
            cells.append(ProbeCell.from_dict(json.loads(line[5:])))
    return cells


def _page_padded_elems(elems: int, page_bytes: int) -> int:
    """fp32 payload elements after page-granular arena padding."""
    nbytes = max(int(elems), 1) * 4
    page = max(int(page_bytes), 4)
    return (nbytes + page - 1) // page * page // 4


# ---------------------------------------------------------------------------
# dry mode: pure-python synthesis with planted constants
# ---------------------------------------------------------------------------


def synthesize_cells(*, transports: Sequence[str] = ("psum",),
                     channels: Sequence[int] = (2,),
                     pages: Sequence[int] = (4096,),
                     sizes: Sequence[int] = (1 << 12, 1 << 16),
                     mesh: Sequence[int] = (2, 4),
                     axes: Sequence[str] = ("pod", "data"),
                     arch: str = GENERIC_ARCH,
                     alpha_s: float | None = None,
                     bandwidth: float | None = None) -> list[ProbeCell]:
    """Synthetic probe matrix: message/byte predictions from the real
    transport classes, timings from a planted α/bandwidth model.

    Needs no mesh devices (the transports' ``predicted_*`` methods are pure
    Python), so this runs in-process — it is both the CI smoke for the
    probe → fit → DB loop and the regression oracle that the fitter
    recovers planted constants to <1% (tests/test_tune.py).
    """
    from repro.comm.plan import ALPHA_S, LINK_BANDWIDTH, LatencyModel
    from repro.comm.registry import get_transport
    from repro.core.ring import RingConfig

    model = LatencyModel(alpha_s=ALPHA_S if alpha_s is None else alpha_s,
                         bandwidth=(LINK_BANDWIDTH if bandwidth is None
                                    else bandwidth))
    axis_sizes = tuple(int(d) for d in mesh)
    mesh_label = "x".join(str(d) for d in axis_sizes)
    cells = []
    for tname in transports:
        _, cls = get_transport(tname)
        tr = cls(tuple(axes)[:len(axis_sizes)] or ("data",),
                 RingConfig(chunks=2))
        for ch in channels:
            for page in pages:
                for elems in sizes:
                    padded = _page_padded_elems(elems, page)
                    msgs = tr.predicted_messages_per_device(axis_sizes)
                    nb = tr.predicted_bytes_per_device(padded, axis_sizes)
                    sec = model.collective_seconds(msgs, nb)
                    cells.append(ProbeCell(
                        bench="synthetic", arch=arch, mesh=mesh_label,
                        transport=tname, channels=int(ch),
                        page_bytes=int(page), elems=int(elems),
                        messages=float(msgs), nbytes=float(nb),
                        seconds=float(sec), t_min=float(sec),
                        t_max=float(sec)))
    return cells


# ---------------------------------------------------------------------------
# measured mode: subprocess scripts per bench
# ---------------------------------------------------------------------------

# Each template gets CFG (a dict) injected as JSON and prints one
# ``CELL {json}`` line per timed point.  The predicted messages/bytes come
# from the same plan objects the dry-run prices with, so the fit residual
# really is model-vs-machine.  __CELL_HELPERS__ provides emit()/timing().

_CELL_HELPERS = r"""
import json as _json

def _timing(t):
    lo = float(getattr(t, "t_min", t)); hi = float(getattr(t, "t_max", t))
    return float(t), lo, hi

def emit(**kw):
    sec, lo, hi = _timing(kw.pop("t"))
    kw.update(seconds=sec, t_min=lo, t_max=hi)
    print("CELL " + _json.dumps(kw), flush=True)

CFG = _json.loads('__CFG_JSON__')
"""

_ALLREDUCE_SCRIPT = r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.comm import CommConfig, Communicator

shape = tuple(CFG["mesh"])
axes = ("pod", "data")[:len(shape)] if len(shape) <= 2 else \
    tuple(f"d{i}" for i in range(len(shape)))
mesh = jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))
mesh_label = "x".join(str(d) for d in shape)
rng = np.random.RandomState(0)

def workload(total):
    k = int(min(16, max(1, total // 4096)))
    sizes = np.full(k, total // k); sizes[0] += total - sizes.sum()
    return {f"g{i}": jnp.asarray(rng.randn(int(s)).astype(np.float32))
            for i, s in enumerate(sizes)}

for transport in CFG["transports"]:
    for ch in CFG["channels"]:
        for total in CFG["sizes"]:
            tree = workload(total)
            specs = {k: P() for k in tree}
            comm = Communicator(mesh, CommConfig(
                transport=transport, chunks=2, channels=ch,
                bucket_bytes=CFG["bucket_bytes"],
                page_bytes=CFG["pages"][0], data_axes=axes))
            plan = comm.plan(tree)
            fn = jax.jit(lambda g: comm.reduce(g, specs)[0])
            t = time_call(fn, tree, warmup=CFG["warmup"],
                          iters=CFG["iters"])
            emit(bench="allreduce", arch=CFG["arch"], mesh=mesh_label,
                 transport=transport, channels=ch,
                 page_bytes=CFG["pages"][0], elems=int(total),
                 messages=plan.messages_per_device,
                 nbytes=plan.bytes_per_device, t=t)
"""

_ARENA_SCRIPT = r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.comm import CommConfig, Communicator

n_dev = len(jax.devices())
mesh = jax.make_mesh((n_dev,), ("data",), axis_types=(AxisType.Auto,) * 1)
rng = np.random.RandomState(0)
batch = jnp.asarray(rng.randn(16, 8).astype(np.float32))

def loss_fn(p, x):
    return sum(jnp.sum(v) for v in p.values()) * 1e-3 + jnp.mean(x) * 0.0

def grad_fn(p, mb):
    return jax.value_and_grad(loss_fn)(p, mb)

transport = CFG["transports"][0]
for page_bytes in CFG["pages"]:
    for ch in CFG["channels"]:
        for total in CFG["sizes"]:
            k = max(4, min(16, total // 4096))
            leaf = max(total // k, 64)
            params = {f"g{i}": jnp.asarray(
                rng.randn(leaf).astype(np.float32)) for i in range(k)}
            comm = Communicator(mesh, CommConfig(
                transport=transport, chunks=2, channels=ch,
                bucket_bytes=4 * leaf, page_bytes=page_bytes,
                data_axes=("data",)))
            plan = comm.plan(params)
            asched = comm.arena_schedule(params, "scheduled", 1)
            arena = comm.arena(params)
            lay = arena.layout

            def arena_run(p, b, buf):
                loss, (tree, out) = comm.reduce_scheduled(
                    grad_fn, p, b, asched, op="all_reduce", arena=arena,
                    arena_buf=buf)
                return loss, tree, out

            fa = jax.jit(jax.shard_map(
                arena_run, mesh=mesh,
                in_specs=(P(), P("data"), P(("data",))),
                out_specs=(P(), P(), P(("data",))), check_vma=False),
                donate_argnums=(2,))
            state = {"buf": jnp.zeros((n_dev * lay.total_elems,),
                                      jnp.float32)}
            def arena_call(p, b):
                loss, tree, out = fa(p, b, state["buf"])
                state["buf"] = out
                return loss
            t = time_call(arena_call, params, batch,
                          warmup=CFG["warmup"], iters=CFG["iters"])
            emit(bench="arena", arch=CFG["arch"], mesh=str(n_dev),
                 transport=transport, channels=ch, page_bytes=page_bytes,
                 elems=int(k * leaf),
                 messages=plan.arena_messages_per_device,
                 nbytes=plan.arena_bytes_per_device, t=t)
"""

_HALO_SCRIPT = r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.comm import CommConfig, Communicator
from repro.core.halo import HaloSpec

mesh = jax.make_mesh((2, 2, 2), ("x", "y", "z"),
                     axis_types=(AxisType.Auto,) * 3)
SPECS = [HaloSpec("x", 0), HaloSpec("y", 1), HaloSpec("z", 2)]
transport = CFG["transports"][0]
for ch in CFG["channels"]:
    comm = Communicator(mesh, CommConfig(
        transport=transport, data_axes=("x", "y", "z"), channels=ch))
    for total in CFG["sizes"]:
        L = max(4, int(round((total / 16) ** (1.0 / 3.0))))
        local = (L, L, L, 16)
        x = jnp.ones((2 * L, 2 * L, 2 * L, 16), jnp.float32)
        plan = comm.halo_plan(local, SPECS, schedule="concurrent")
        def fn(xl):
            h = comm.halo_exchange(xl, SPECS, schedule="concurrent")
            return sum(v.sum() for v in h.values())
        g = jax.jit(jax.shard_map(fn, mesh=mesh,
                                  in_specs=P("x", "y", "z", None),
                                  out_specs=P(), check_vma=False))
        t = time_call(g, x, warmup=CFG["warmup"], iters=CFG["iters"])
        emit(bench="halo", arch=CFG["arch"], mesh="2x2x2",
             transport=transport, channels=ch,
             page_bytes=CFG["pages"][0],
             elems=int(np.prod(local)),
             messages=plan.messages_per_device,
             nbytes=plan.bytes_per_device, t=t)
"""

_CG_SCRIPT = r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.comm import CommConfig, Communicator
from repro.core.halo import HaloSpec
from repro.stencil import (StencilOp, predicted_halo_exchanges,
                           predicted_reduction_collectives, solve)

mesh = jax.make_mesh((2, 2, 2), ("x", "y", "z"),
                     axis_types=(AxisType.Auto,) * 3)
WORLD = 8
SPECS = (HaloSpec("x", 0), HaloSpec("y", 1), HaloSpec("z", 2))
op = StencilOp(specs=SPECS, mass=0.5)
rng = np.random.RandomState(0)
transport = CFG["transports"][0]
for ch in CFG["channels"]:
    comm = Communicator(mesh, CommConfig(
        transport=transport, data_axes=("x", "y", "z"), channels=ch))
    for total in CFG["sizes"]:
        L = max(4, int(round((total / 16) ** (1.0 / 3.0))))
        local = (L, L, L, 16)
        b = jnp.asarray(rng.randn(2*L, 2*L, 2*L, 16).astype(np.float32))
        def run(bl):
            r = solve(op, bl, comm, solver="cg", precond="none",
                      tol=1e-5, maxiter=CFG["cg_iters"],
                      schedule="concurrent", chunks=comm.halo_chunks,
                      channels=ch)
            return r.x, r.iters, r.rel_residual
        fn = jax.jit(jax.shard_map(
            run, mesh=mesh, in_specs=P("x", "y", "z", None),
            out_specs=(P("x", "y", "z", None), P(), P()),
            check_vma=False))
        x, iters, rel = jax.block_until_ready(fn(b))
        iters = int(iters)
        hplan = comm.halo_plan(local, SPECS, schedule="concurrent")
        reds = predicted_reduction_collectives("cg", iters)
        exch = predicted_halo_exchanges("cg", "none", iters)
        msgs = (reds * 2 * (WORLD - 1)
                + exch * hplan.messages_per_device)
        nb = (reds * 2 * (WORLD - 1) / WORLD * 8.0
              + exch * hplan.bytes_per_device)
        t = time_call(fn, b, warmup=CFG["warmup"], iters=CFG["iters"])
        emit(bench="cg", arch=CFG["arch"], mesh="2x2x2",
             transport=transport, channels=ch,
             page_bytes=CFG["pages"][0], elems=int(np.prod(local)),
             messages=msgs, nbytes=nb, t=t)
"""

_SCRIPTS = {"allreduce": _ALLREDUCE_SCRIPT, "arena": _ARENA_SCRIPT,
            "halo": _HALO_SCRIPT, "cg": _CG_SCRIPT}


def _bench_harness():
    """Import ``benchmarks.common`` (not an installed package — it lives in
    the repo's ``benchmarks/`` directory next to ``src/``)."""
    try:
        from benchmarks import common  # repo root on sys.path
        return common
    except ImportError:
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
        if repo not in sys.path:
            sys.path.insert(0, repo)
        from benchmarks import common
        return common


def probe_script(bench: str, cfg: Mapping) -> str:
    """The full subprocess source for one bench's probe sweep."""
    common = _bench_harness()
    if bench not in _SCRIPTS:
        raise ValueError(f"unknown bench {bench!r}; one of {BENCHES}")
    helpers = _CELL_HELPERS.replace("__CFG_JSON__", json.dumps(dict(cfg)))
    return common.TIMER_SNIPPET + helpers + _SCRIPTS[bench]


def run_probe(*, benches: Sequence[str] = ("allreduce",),
              transports: Sequence[str] = ("ring_hier", "psum"),
              channels: Sequence[int] = (1, 2),
              pages: Sequence[int] = (4096, 2 * 2**20),
              sizes: Sequence[int] = (1 << 14, 1 << 18),
              mesh: Sequence[int] = (2, 4),
              arch: str = GENERIC_ARCH,
              bucket_bytes: int = 1 << 20,
              warmup: int = 1, iters: int = 5,
              cg_iters: int = 8,
              n_devices: int | None = None) -> list[ProbeCell]:
    """Measured calibration matrix: one subprocess per bench, all cells
    parsed back as :class:`ProbeCell` records."""
    common = _bench_harness()
    n_dev = n_devices or max(int(math.prod(mesh)), 8)
    cfg = {"transports": list(transports), "channels": list(channels),
           "pages": [int(p) for p in pages],
           "sizes": [int(s) for s in sizes], "mesh": list(mesh),
           "arch": arch, "bucket_bytes": int(bucket_bytes),
           "warmup": int(warmup), "iters": int(iters),
           "cg_iters": int(cg_iters)}
    cells: list[ProbeCell] = []
    for bench in benches:
        out = common.run_on_devices(probe_script(bench, cfg),
                                    n_devices=n_dev)
        cells.extend(parse_cells(out))
    return cells


# ---------------------------------------------------------------------------
# fit + persist
# ---------------------------------------------------------------------------


def fit_and_store(cells: Sequence[ProbeCell], db: TuningDB
                  ) -> dict[str, FitResult]:
    """Fit every (transport, channels, page_bytes) group and store the
    records under each group's (arch, mesh) — returns key → fit."""
    fits: dict[str, FitResult] = {}
    for (transport, ch, page), group in sorted(group_cells(cells).items()):
        fit = fit_cells(group)
        key = db.put_fit(arch=group[0].arch, mesh=group[0].mesh,
                         transport=transport, channels=ch, page_bytes=page,
                         fit=fit, cells=group)
        fits[key] = fit
    return fits


def main(argv: Sequence[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="probe the comm substrate and fit measured α/bandwidth")
    ap.add_argument("--dry", action="store_true",
                    help="synthesize cells in pure python (CI smoke; "
                    "plants --plant-alpha/--plant-bandwidth)")
    ap.add_argument("--out", default=None,
                    help="tuning DB path to merge fits into")
    ap.add_argument("--benches", nargs="+", default=["allreduce"],
                    choices=list(BENCHES))
    ap.add_argument("--transports", nargs="+",
                    default=None, help="default: psum (dry) / ring_hier+psum")
    ap.add_argument("--channels", nargs="+", type=int, default=[2])
    ap.add_argument("--page-bytes", nargs="+", type=int, default=[4096])
    ap.add_argument("--sizes", nargs="+", type=int,
                    default=[1 << 12, 1 << 16])
    ap.add_argument("--mesh", default="2x4",
                    help="probe mesh label, e.g. 2x4")
    ap.add_argument("--arch", default=GENERIC_ARCH)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--plant-alpha", type=float, default=None,
                    help="--dry only: planted α seconds")
    ap.add_argument("--plant-bandwidth", type=float, default=None,
                    help="--dry only: planted bandwidth B/s")
    args = ap.parse_args(argv)

    mesh = tuple(int(d) for d in args.mesh.lower().split("x"))
    if args.dry:
        cells = synthesize_cells(
            transports=tuple(args.transports or ("psum",)),
            channels=tuple(args.channels), pages=tuple(args.page_bytes),
            sizes=tuple(args.sizes), mesh=mesh, arch=args.arch,
            alpha_s=args.plant_alpha, bandwidth=args.plant_bandwidth)
    else:
        cells = run_probe(
            benches=tuple(args.benches),
            transports=tuple(args.transports or ("ring_hier", "psum")),
            channels=tuple(args.channels), pages=tuple(args.page_bytes),
            sizes=tuple(args.sizes), mesh=mesh, arch=args.arch,
            warmup=args.warmup, iters=args.iters)

    db = TuningDB.load(args.out) if args.out else TuningDB()
    fits = fit_and_store(cells, db)
    print(f"probed {len(cells)} cells -> {len(fits)} fit group(s)")
    for key, fit in sorted(fits.items()):
        print(f"  {key}: alpha={fit.alpha_s*1e6:.2f}us "
              f"bw={fit.bandwidth/1e9:.2f}GB/s "
              f"mean_rel_err={fit.mean_rel_err:.3%} "
              f"max_rel_err={fit.max_rel_err:.3%} "
              f"(n={fit.n_cells})")
    if args.out:
        db.save(args.out)
        print(f"wrote {args.out} ({len(db)} record(s))")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
