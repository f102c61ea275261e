"""Cartesian halo exchange over mesh axes (the paper's QCD workload).

Mirrors ``Grid``'s ``Benchmark_comms``: every rank sends its faces to the
+/- neighbours along each Cartesian direction.  Four schedules reproduce
the paper's experimental columns:

* ``sequential``  — one direction at a time, each transfer data-dependent on
  the previous (the 'Seq' columns): a token is threaded through the chain so
  XLA cannot overlap them.
* ``concurrent``  — all directions issued as independent ``ppermute`` ops
  (the 'Concurrent' columns): the scheduler may overlap every face transfer.
* ``chunked``     — each face additionally split into ``chunks`` independent
  channels (the 'Threaded' multi-EP columns).  Faces whose split dim is not
  divisible split unevenly (:func:`chunk_sizes`) rather than degrading to a
  single chunk.
* ``overlap``     — whole faces striped across ``channels`` guaranteed rails
  (per-rail FIFO via order tokens, like scheduled bucket reduction); meant
  to be consumed by an interior/boundary-split operator
  (:mod:`repro.stencil.op`) so interior compute hides the transfers.  The
  matching issue slots come from
  :func:`repro.comm.schedule.build_halo_schedule`.

Runs inside ``shard_map`` with the participating axes manual.  Used by the
QCD-style stencil solver and by context/sequence-parallel layers; the
preferred entry point is :meth:`repro.comm.Communicator.halo_exchange`,
which ties the ``chunks``/``channels`` knobs to the communicator's virtual
channels so SGD reduction and QCD halo share one multi-rail configuration.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.topology import order_token, ring_perm

SCHEDULES = ("sequential", "concurrent", "chunked", "overlap")


@dataclass(frozen=True)
class HaloSpec:
    """One exchanged direction: array dim ``dim`` over mesh axis ``axis``."""

    axis: str           # mesh axis name
    dim: int            # array dimension sharded over that axis
    halo: int = 1       # face width


def _face(x: jax.Array, dim: int, lo: bool, width: int) -> jax.Array:
    n = x.shape[dim]
    if lo:
        return lax.slice_in_dim(x, 0, width, axis=dim)
    return lax.slice_in_dim(x, n - width, n, axis=dim)


def face_split_dim(shape: Sequence[int], dim: int) -> int:
    """The dim a face is chunked along: largest non-halo dim, so pieces stay
    contiguous (``dim`` itself only when the face is 1-D)."""
    return max((d for d in range(len(shape)) if d != dim),
               key=lambda d: shape[d], default=dim)


def chunk_sizes(n: int, chunks: int) -> list[int]:
    """Piece lengths splitting ``n`` into ``min(chunks, n)`` near-equal
    parts: the first ``n % k`` pieces are one longer.  Shared by the
    executor (:func:`_split_chunks`) and the prediction layer
    (:func:`repro.comm.schedule.build_halo_schedule`) so predicted and
    lowered payload bytes agree for indivisible shapes."""
    k = max(1, min(int(chunks), int(n)))
    base, extra = divmod(int(n), k)
    return [base + 1] * extra + [base] * (k - extra)


def _split_chunks(face: jax.Array, chunks: int, dim: int) -> list[jax.Array]:
    if chunks <= 1:
        return [face]
    split_dim = face_split_dim(face.shape, dim)
    out, start = [], 0
    for c in chunk_sizes(face.shape[split_dim], chunks):
        out.append(lax.slice_in_dim(face, start, start + c, axis=split_dim))
        start += c
    return out


def _seq_token(dep: jax.Array, arrs: Sequence[jax.Array]) -> list[jax.Array]:
    """Thread a scalar data dependency through ``arrs`` to force ordering."""
    out = []
    for a in arrs:
        a = order_token(dep, a)
        dep = a.reshape(-1)[0]
        out.append(a)
    return out


def halo_exchange(x: jax.Array, specs: Sequence[HaloSpec], *,
                  schedule: str = "concurrent", chunks: int = 4,
                  channels: int = 0) -> dict:
    """Exchange faces along every spec'd direction.

    Returns ``{(axis, '+'): received_hi_face, (axis, '-'): received_lo_face}``
    — the halos a stencil kernel pads with.  '+' is the face received *from*
    the +1 neighbour (i.e. their low face), usable as this rank's high halo.

    ``channels`` only matters to the ``overlap`` schedule: ``>= 1`` stripes
    the faces across that many guaranteed rails, each issuing FIFO through
    an order token (exactly :meth:`Communicator.reduce_scheduled`'s rail
    rule); ``0`` leaves every face an unconstrained independent transfer.
    """
    if schedule not in SCHEDULES:
        raise ValueError(f"schedule must be one of {SCHEDULES}")

    sends = []  # (key, payloads, axis, direction)
    for s in specs:
        p = lax.axis_size(s.axis)
        n_chunks = chunks if (schedule == "chunked" and p > 1) else 1
        hi = _face(x, s.dim, lo=False, width=s.halo)   # travels to +1; recv as lo-halo
        lo = _face(x, s.dim, lo=True, width=s.halo)    # travels to -1; recv as hi-halo
        sends.append(((s.axis, "-"), _split_chunks(hi, n_chunks, s.dim), s.axis, +1))
        sends.append(((s.axis, "+"), _split_chunks(lo, n_chunks, s.dim), s.axis, -1))

    rail_of = None
    if schedule == "overlap" and channels >= 1:
        # core<->comm layering: the striping rule lives with the channel
        # machinery; import lazily to avoid the package-init cycle
        from repro.comm.plan import assign_channels

        sizes = [sum(math.prod(c.shape) for c in payloads)
                 for _, payloads, _, _ in sends]
        rail_of = {}
        for a in assign_channels(sizes, channels):
            for u in a.buckets:
                rail_of[u] = a.channel

    out: dict = {}
    dep = None
    rail_dep: dict[int, jax.Array] = {}
    for idx, (key, payloads, axis, direction) in enumerate(sends):
        p = lax.axis_size(axis)
        perm = ring_perm(p, direction)
        if schedule == "sequential" and dep is not None:
            payloads = _seq_token(dep, payloads)
        if rail_of is not None:
            payloads = [order_token(rail_dep.get(rail_of[idx]), c)
                        for c in payloads]
        received = [lax.ppermute(c, axis, perm) for c in payloads]
        if schedule == "sequential":
            dep = received[-1].reshape(-1)[0]
        if rail_of is not None:
            rail_dep[rail_of[idx]] = received[-1].reshape(-1)[0]
        face = received[0] if len(received) == 1 else _reassemble(received, key, specs, x.shape)
        out[key] = face
    return out


def _reassemble(parts: list[jax.Array], key, specs, x_shape) -> jax.Array:
    spec = next(s for s in specs if s.axis == key[0])
    face_shape = list(x_shape)
    face_shape[spec.dim] = spec.halo
    return jnp.concatenate(parts, axis=face_split_dim(face_shape, spec.dim))


def pad_with_halos(x: jax.Array, halos: dict, spec: HaloSpec) -> jax.Array:
    """Concatenate received halos onto ``x`` along ``spec.dim``."""
    lo = halos[(spec.axis, "-")]
    hi = halos[(spec.axis, "+")]
    return jnp.concatenate([lo, x, hi], axis=spec.dim)


def halo_bytes(x_shape: Sequence[int], specs: Sequence[HaloSpec], itemsize: int) -> int:
    """Bidirectional bytes injected per device per exchange (analysis)."""
    total = 0
    for s in specs:
        face = 1
        for d, n in enumerate(x_shape):
            face *= s.halo if d == s.dim else n
        total += 2 * face * itemsize
    return total
